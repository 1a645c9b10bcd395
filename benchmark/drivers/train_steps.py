"""Traffic kind ``train_steps``: consecutive optimizer steps of one client.

The program's ``Trainer`` on one chip, fed by its own ``StreamingLoader``
over shards the benchmark wrote from the seed. Set-up builds ONE trainer,
drives it through its first three steps (whose losses, first gradient and
parameter change the plain reference then follows), warms the window's call
and hands the same object to the window. The window calls ``Trainer.fit`` in
chunks of ``steps_per_fit`` until ``--seconds`` have passed; each call ends
on the trainer's own fence (the whole state ready and the last loss fetched
to the host), so the rate is whole steps over the whole window. Those
fetched losses, kept in window order, are what ``loss_fell`` reads: the
median over the fits that the traffic file's ``loss_fall_fits`` names.
"""

from __future__ import annotations

import functools
import gc
import importlib

import numpy as np

from benchmark.program import build_config, optimizer_settings

CHECK_STEPS = 3


class Feed:
    """The loader's batches, as the step is given them; keeps the first few
    for the reference to follow."""

    def __init__(self, loader, keep: int) -> None:
        self._loader, self._keep = loader, keep
        self.kept: list[np.ndarray] = []

    def __iter__(self):
        return self

    def __next__(self) -> np.ndarray:
        batch = next(self._loader)
        if len(self.kept) < self._keep:
            self.kept.append(np.array(batch))
        return batch


def reference_family(config: dict):
    return importlib.import_module(f"benchmark.reference.{config['reference']}")


def make_rows(n_rows: int, seq_len: int, vocab: int, zipf_a: float,
              seed: int, salt: int = 0) -> np.ndarray:
    """``n_rows`` rows of Zipf-distributed token ids from the seed (``salt``
    tells one client's rows from another's)."""
    rng = np.random.default_rng([int(seed), salt])
    return (rng.zipf(zipf_a, size=(n_rows, seq_len)) % vocab).astype(np.int32)


def write_rows(path, rows: np.ndarray, vocab: int) -> None:
    """The rows in the program's shard format."""
    from photon_tpu.data.shard_format import ShardWriter

    with ShardWriter(path, rows.shape[1], vocab, samples_per_shard=128) as w:
        w.write(rows)


def second_moment(opt_state):
    """The optimizer's second-moment tree (ADOPT's ``v``, Adam's ``nu``)."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        for name in ("v", "nu"):
            if hasattr(node, name) and hasattr(node, "count"):
                return getattr(node, name)
        if isinstance(node, (tuple, list)):
            stack.extend(node)
    raise RuntimeError("no second moment in the optimizer state")


def make_weights(ref, dims: dict, seed: int):
    """Seeded weights, made on the device in one jitted call. The seed goes
    in as an argument, so that every seed runs the one cached program."""
    import jax

    return _weights_program(ref, tuple(sorted(dims.items())))(ref.seed_key(seed))


@functools.lru_cache(maxsize=None)
def _weights_program(ref, dims_items: tuple):
    import jax

    return jax.jit(lambda key: ref.make_params(dict(dims_items), key))


def build_trainer(run, cfg, params):
    from photon_tpu.data import ShardedDataset, StreamingLoader
    from photon_tpu.parallel.mesh import single_device_mesh
    from photon_tpu.train.trainer import Trainer

    t = run.traffic
    data = run.work_dir / "rows"
    with run.span("setup/rows"):
        write_rows(data, make_rows(t["rows"], cfg.model.max_seq_len,
                                   cfg.model.vocab_size, t["zipf_a"], run.seed),
                   cfg.model.vocab_size)
    with run.span("setup/trainer"):
        trainer = Trainer(cfg, mesh=single_device_mesh(run.devices[0]),
                          params=params)
    loader = StreamingLoader(
        ShardedDataset(data), batch_size=cfg.train.global_batch_size,
        seed=cfg.dataset.shuffle_seed, shuffle=cfg.dataset.shuffle)
    return trainer, Feed(loader, CHECK_STEPS)


def first_steps(run, trainer, feed, ref, dims) -> dict:
    """The program's readings: each of the first steps' losses, the per-leaf
    norm of the first gradient as the optimizer got it (ADOPT's first call
    stores its square), and of the parameters' change over the steps."""
    import jax
    import jax.numpy as jnp

    losses = []
    grad_norms = None
    for i in range(CHECK_STEPS):
        with run.span("check/step"):
            losses.append(trainer.fit(feed, 1)["loss"])
        if i == 0:
            squares = second_moment(trainer.state.opt_state)
            # v = g**2 elementwise, so the norm of sqrt(v) is the norm of g
            grad_norms = ref.leaf_norms(jax.tree.map(jnp.sqrt, squares))
    change = jax.tree.map(jnp.subtract, trainer.state.params,
                          make_weights(ref, dims, run.seed))
    rows = np.concatenate(feed.kept)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": ref.leaf_norms(change),
            "distinct_rows": len({r.tobytes() for r in rows}) / len(rows)}


def reference_steps(run, ref, dims, opt: dict, batches, matmul: str) -> dict:
    """The same readings from the plain reference at ``matmul`` precision,
    on weights it makes itself from the seed."""
    import jax
    import jax.numpy as jnp

    params0 = make_weights(ref, dims, run.seed)
    grad = ref.Grad(dims, matmul, rows=run.traffic["reference_rows"])
    step = jax.jit(lambda p, s, g: ref.adopt_step(p, s, g, opt), donate_argnums=(1,))
    params, state = params0, ref.adopt_init(params0)
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        loss, g = grad(params, batch)
        if i == 0:
            grad_norms = ref.leaf_norms(
                ref.clip_by_global_norm(g, opt["grad_clip_norm"]))
        params, state = step(params, state, g)
        losses.append(float(loss))
    change = jax.tree.map(jnp.subtract, params, params0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": ref.leaf_norms(change)}


def gaps(ref, got: dict, want: dict) -> dict[str, float]:
    """Every number the comparison makes, by the name of its limit."""
    out = {f"loss_gap_step{i + 1}": abs(g - w)
           for i, (g, w) in enumerate(zip(got["losses"], want["losses"]))}
    out["first_grad_norm_gap"] = ref.worst_leaf_gap(got["grad_norms"],
                                                    want["grad_norms"])
    out["param_change_norm_gap"] = ref.worst_leaf_gap(got["change_norms"],
                                                      want["change_norms"])
    return out


def fit_chunk(run, trainer, feed) -> dict:
    with run.span("trainer/fit"):
        return trainer.fit(feed, run.traffic["steps_per_fit"])


def start(run):
    """Set-up up to the first steps: the trainer on seeded weights, its feed,
    and the program's readings of those steps."""
    ref = reference_family(run.config)
    dims = ref.dims_of(run.config["model"])
    cfg = build_config(run.config, run.traffic, run.work_dir / "save", run.seed)
    with run.span("setup/weights"):
        params = make_weights(ref, dims, run.seed)
    trainer, feed = build_trainer(run, cfg, params)
    del params  # the step donates its state
    got = first_steps(run, trainer, feed, ref, dims)
    return ref, dims, cfg, trainer, feed, got


def run(run) -> None:
    ref, dims, cfg, trainer, feed, got = start(run)
    for _ in range(run.traffic["warm_fits"]):
        fit_chunk(run, trainer, feed)

    k = run.traffic["steps_per_fit"]
    tokens_per_step = cfg.train.global_batch_size * cfg.model.max_seq_len
    steps, fit_losses = 0, []
    with run.timed_window() as t0:
        while True:
            fit_losses.append(fit_chunk(run, trainer, feed)["loss"])
            steps += k
            run.stop_trace_if_due()
            if run.window_over(t0):
                break
    window_s = run.window[1] - run.window[0]
    run.attempted, run.failed = steps, 0
    run.end_to_end["train_tokens_per_s"] = steps * tokens_per_step / window_s
    run.counters.update(steps=steps, tokens_per_step=tokens_per_step,
                        device_microbatch_size=trainer.device_microbatch_size)

    # the reference runs once the program's state is freed, so that it fits
    # and the peak reported is the program's
    del trainer
    gc.collect()
    want = reference_steps(run, ref, dims, optimizer_settings(cfg), feed.kept, "float32")
    limits = run.traffic["limits"]
    for name, value in gaps(ref, got, want).items():
        run.check(name, value, limits[name])
    run.check("distinct_rows_share", got["distinct_rows"], 1.0, at_least=True)
    run.check_loss_fell(got["losses"][0], fit_losses, "fits")


def readings(run) -> dict:
    """For setting limits (see PERF.md): the program's gaps to the reference
    and the control's, at the cell's own size, with no measured window."""
    ref, dims, cfg, trainer, feed, got = start(run)
    del trainer
    gc.collect()
    opt = optimizer_settings(cfg)
    want = reference_steps(run, ref, dims, opt, feed.kept, "float32")
    control = reference_steps(run, ref, dims, opt, feed.kept,
                              run.traffic["control_matmul"])
    return {"program": gaps(ref, got, want), "control": gaps(ref, control, want)}
