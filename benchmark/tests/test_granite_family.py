"""The ``granite_hybrid`` family's benchmark files: its cost functions against
numbers worked by hand, its configuration file against the published one, its
plain reference's sequential recurrence against a per-position Python loop
(numpy float64), the new readers against a hand-written trace with the new
scopes, and a toy cell of the family through the ``train_steps`` driver."""

from __future__ import annotations

import json
import math
import time
import types

import jax
import numpy as np
import pytest

from benchmark.costs import granite_hybrid_train as granite_cost
from benchmark.costs import ssd_scan as scan_cost
from benchmark.reference import granite_hybrid as ref
from benchmark.tests import toy
from benchmark.tests.test_host_spans import reader, write_trace

GRANITE = json.loads(
    (toy.ROOT / "benchmark/configs/granite-4.0-h-micro-stage1.json").read_text())
NEW_METRICS = ("mamba_proj_ms_train", "mamba_conv_ms_train", "mamba_scan_ms_train",
               "ssd_scan_roofline", "mfu_train_granite4h")


def test_ssd_scan_costs_by_hand():
    shape = dict(seq=8192, heads=64, d_head=64, d_state=128)
    # a chunk of 256: 32,896 causal pairs; a pair costs 2 x 128 once and 2 x 64
    # for each of 64 heads; a position 4 x 64 x 128 a head for the two state products
    per_chunk = 32_896 * 256 + 64 * 32_896 * 128 + 256 * 64 * 4 * 64 * 128
    assert per_chunk == 814_776_320
    assert scan_cost.forward_flops(chunk=256, **shape) == 32 * per_chunk
    assert scan_cost.training_flops(chunk=256, **shape) == 3 * 32 * per_chunk
    # a whole row as one chunk has more pairs; two rows are twice one
    assert scan_cost.forward_flops(chunk=8192, **shape) > 10 * 32 * per_chunk
    assert scan_cost.forward_flops(chunk=256, batch=2, **shape) == 64 * per_chunk
    # bf16 x, B, C and y, float32 dt: 2 x (2 x 4,096 + 2 x 128) + 4 x 64 a position
    assert scan_cost.forward_bytes(**shape) == 8192 * 17_152
    # backward: x, B, C, dt, dy in; dx, dB, dC, ddt out
    assert scan_cost.training_bytes(**shape) == 8192 * (17_152 + 2 * (3 * 4096 + 4 * 128)
                                                        + 8 * 64)


def test_granite_training_flops_per_token_by_hand():
    model = GRANITE["model"]
    assert granite_cost.layer_counts(model) == (9, 1)
    parts = granite_cost.parts_per_token(model)
    # a Mamba-2 mixer's two matrices: 2,048 x 8,512 + 4,096 x 2,048
    assert parts["mamba_projections"] == 6 * 9 * (17_432_576 + 8_388_608)
    assert parts["ssd_scan"] == 9 * 3 * 814_776_320 / 256
    # q and out 2,048 x 2,048 each, k and v 2,048 x 512 each
    assert parts["attention_projections"] == 6 * (2 * 4_194_304 + 2 * 1_048_576)
    # causal pairs a token (8,193 / 2) x 4 x 64 a pair x 32 heads x 3
    assert parts["flash_core"] == pytest.approx(3 * 32 * 8193 / 2 * 4 * 64)
    assert parts["mlp"] == 6 * 10 * 3 * 2048 * 8192
    assert parts["head"] == 6 * 2048 * 12544
    total = granite_cost.flops_per_token(model)
    assert total == pytest.approx(4.82e9, rel=2e-3)  # ISSUE 33 reckoned 4.85
    shares = {k: round(100 * v / total) for k, v in parts.items()}
    assert shares == {"mamba_projections": 29, "mamba_conv": 0, "ssd_scan": 2,
                      "attention_projections": 1, "flash_core": 2, "mlp": 63, "head": 3}
    # the program's own estimate counts the attention layer's full square
    from photon_tpu.config import load_preset
    from photon_tpu.utils.profiling import model_flops_per_token

    own = model_flops_per_token(load_preset("granite-4.0-h-micro-stage1").model)
    assert own == pytest.approx(total + parts["flash_core"], rel=1e-3)


def test_the_configuration_file_states_the_published_widths():
    """Every number of the catalog's ``config`` under the same key, the three
    reduced keys apart, the published value of each of those beside it, and
    the parameter count the cut's arithmetic gives."""
    published = {
        "attention_multiplier": 0.015625, "embedding_multiplier": 12, "hidden_size": 2048,
        "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "max_position_embeddings": 131072,
        "num_attention_heads": 32, "num_experts_per_tok": 0, "num_key_value_heads": 8,
        "num_local_experts": 0, "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "shared_intermediate_size": 8192}
    assert {k: GRANITE[k] for k in published} == published
    assert (GRANITE["position_embedding_type"], GRANITE["tie_word_embeddings"],
            GRANITE["mamba_conv_bias"], GRANITE["mamba_proj_bias"]) == ("nope", True, True, False)
    assert sorted(GRANITE["reduced"]) == ["layer_types", "num_hidden_layers", "vocab_size"]
    assert (GRANITE["published_num_hidden_layers"], GRANITE["num_hidden_layers"]) == (40, 10)
    assert (GRANITE["published_vocab_size"], GRANITE["vocab_size"]) == (100352, 12544)
    assert GRANITE["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    m = GRANITE["model"]
    assert m["layer_types"] == ",".join(GRANITE["layer_types"])
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"], m["mlp_hidden_size"],
            m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"], m["mamba_d_conv"],
            m["mamba_chunk_size"], m["n_layers"], m["vocab_size"]) == (
        2048, 32, 8, 64, 8192, 64, 64, 128, 4, 256, 10, 12544)
    # ISSUE 33's table: a Mamba layer, the attention layer, the slice and ln_f
    mamba = (2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
             + 3 * 2048 * 8192 + 2 * 2048)
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192 + 2 * 2048
    assert (mamba, attention) == (76_182_976, 60_821_504)
    assert 9 * mamba + attention + 12544 * 2048 + 2048 == GRANITE["parameters"] == 772_160_448
    shapes = jax.eval_shape(lambda: ref.make_params(ref.dims_of(m), 0))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == 772_160_448


# ---------------------------------------------------------------------------
# the reference against a second formulation
# ---------------------------------------------------------------------------


def test_sequential_recurrence_matches_a_per_position_loop():
    """``H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T``, ``y_t = H_t C_t + D x_t``
    one row, head and position at a time in float64, against the reference's
    ``lax.scan``, whole and cut into kept segments."""
    rng = np.random.default_rng(5)
    b, s, h, p, n = 2, 12, 3, 4, 5
    x = rng.normal(size=(b, s, h, p))
    dt = rng.uniform(0.01, 0.5, size=(b, s, h))
    a = -rng.uniform(1.0, 16.0, size=h)
    bm, cm = rng.normal(size=(b, s, n)), rng.normal(size=(b, s, n))
    skip = rng.normal(size=h)
    want = np.zeros((b, s, h, p))
    for row in range(b):
        for head in range(h):
            state = np.zeros((p, n))
            for t in range(s):
                state = math.exp(dt[row, t, head] * a[head]) * state + np.outer(
                    dt[row, t, head] * x[row, t, head], bm[row, t])
                want[row, t, head] = state @ cm[row, t] + skip[head] * x[row, t, head]
    f32 = lambda t: np.asarray(t, np.float32)  # noqa: E731
    args = [f32(t) for t in (x, dt, a, bm, cm, skip)]
    assert float(np.max(np.abs(want))) > 1.0
    np.testing.assert_allclose(ref.recurrence(*args), want, atol=2e-5, rtol=1e-5)
    seg, ref.SEGMENT = ref.SEGMENT, 4  # three kept segments of four positions
    try:
        np.testing.assert_allclose(ref.recurrence(*args, remat=True), want,
                                   atol=2e-5, rtol=1e-5)
    finally:
        ref.SEGMENT = seg


def test_convolution_is_four_shifted_products():
    rng = np.random.default_rng(6)
    u = f = rng.normal(size=(1, 7, 3)).astype(np.float32)
    kernel, bias = rng.normal(size=(4, 3)).astype(np.float32), np.float32([0.5, -1.0, 2.0])
    want = np.zeros_like(f)
    for t in range(7):
        for k in range(4):
            if t - 3 + k >= 0:
                want[0, t] += kernel[k] * u[0, t - 3 + k]
    np.testing.assert_allclose(ref.causal_conv(u, kernel, bias), want + bias, atol=1e-6)


TOY_MODEL = {
    "d_model": 32, "n_layers": 4, "layer_types": "mamba,mamba,attention,mamba",
    "n_heads": 4, "n_kv_heads": 2, "d_head": 8, "max_seq_len": 32, "vocab_size": 128,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 8,
    "mamba_d_conv": 4, "mamba_chunk_size": 8, "mlp_hidden_size": 48,
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22, "logits_scaling": 8.0,
    "attention_multiplier": 0.125, "norm_eps": 1e-5, "param_dtype": "float32",
    "compute_dtype": "float32", "attn_impl": "xla"}


def test_lower_precision_moves_the_reference():
    dims = ref.dims_of(TOY_MODEL)
    params = ref.make_params(dims, seed=3)
    tokens = np.random.default_rng(1).integers(0, 128, size=(2, 32)).astype(np.int32)
    exact = ref.forward(params, tokens, dims)
    gaps = {mm: float(np.max(np.abs(ref.forward(params, tokens, dims, mm) - exact)))
            for mm in ("bf16_state", "bfloat16", "int8")}
    # the family's own control rounds nothing but the recurrence's carried state
    assert 0 < gaps["bf16_state"] < gaps["bfloat16"] < gaps["int8"]


# ---------------------------------------------------------------------------
# the new readers against a hand-written trace with the new scopes
# ---------------------------------------------------------------------------


@pytest.fixture()
def mamba_trace(tmp_path):
    from benchmark.trace.reduce import reduce_trace

    trace_dir = write_trace(tmp_path, "mamba_scopes.xplane.txt")
    run = types.SimpleNamespace(
        trace_dir=trace_dir, config=GRANITE, peaks=toy.TOY_PEAKS, devices=[None],
        traffic={"steps_per_fit": 2}, counters={"tokens_per_step": 8192},
        span_seconds=lambda name: [0.2] if name == "trainer/fit" else [])
    return run, reduce_trace(trace_dir, [0])


@pytest.mark.parametrize("metric,ms_per_step", [
    ("mamba_proj_ms_train", 0.042),  # in-projection 30 us + out-projection 12 us
    ("mamba_conv_ms_train", 0.006),
    ("mamba_scan_ms_train", 0.030),  # the forward body 20 us + its transpose 10 us
])
def test_scope_reader_against_known_answers(mamba_trace, metric, ms_per_step):
    run, reduction = mamba_trace
    assert reader(metric).read(run, reduction) == pytest.approx(ms_per_step)


def test_scan_roofline_and_mfu_against_known_answers(mamba_trace):
    run, reduction = mamba_trace
    shape = dict(seq=8192, heads=64, d_head=64, d_state=128)
    # at the toy peaks the scan is bound by its operations: 78 ms of them
    # against 3.5 ms of bytes (on a v5e the two are 0.40 and 0.43 ms)
    least = max(scan_cost.training_flops(chunk=256, **shape) / 1.0e12,
                scan_cost.training_bytes(**shape) / 1.0e11)
    assert least == 3 * 32 * 814_776_320 / 1.0e12
    # nine layers (the span's own count) of one row, over 30 us a step
    assert reader("ssd_scan_roofline").read(run, reduction) == pytest.approx(
        100.0 * 9 * least / 30e-6)
    # 8,192 tokens in 0.1 s a step at 4.82 GFLOP a token over 1e12 FLOP/s
    assert reader("mfu_train_granite4h").read(run, reduction) == pytest.approx(
        100.0 * 8192 / 0.1 * granite_cost.flops_per_token(GRANITE["model"]) / 1.0e12)


@pytest.mark.parametrize("fixture", ["train_scopes.xplane.txt", "small_trace.xplane.txt", None])
def test_readers_find_nothing_on_a_program_without_the_scopes(tmp_path, fixture):
    """What another model's or a parent commit's traced run gives the new
    readers: no ``mamba/*`` scope, no ``mamba_layers`` on ``trainer/steps``
    (or no trace at all). Each returns ``None`` and raises nothing."""
    from benchmark.trace.reduce import reduce_trace

    trace_dir = write_trace(tmp_path, fixture) if fixture else None
    reduction = (reduce_trace(trace_dir, [0]) if fixture else
                 {"ops": [], "busy_s": 0.0, "window_s": 1.0, "idle_gaps": []})
    run = types.SimpleNamespace(
        trace_dir=trace_dir, config=GRANITE, peaks=toy.TOY_PEAKS, devices=[None],
        traffic={"steps_per_fit": 4}, counters={"tokens_per_step": 8192},
        span_seconds=lambda name: [1.0])
    for name in NEW_METRICS:
        assert reader(name).read(run, reduction) is None, name


# ---------------------------------------------------------------------------
# a toy cell of the family through the driver
# ---------------------------------------------------------------------------

TOY_TRAFFIC = {
    "kind": "train_steps", "why": "toy",
    "overrides": {"train.global_batch_size": 2, "train.device_microbatch_size": 2,
                  "dataset.synthetic": True},
    "rows": 64, "zipf_a": 1.3, "steps_per_fit": 2, "loss_fall_fits": [0, 3],
    "warm_fits": 1,
    "trace_seconds": 1, "reference_rows": 1, "control_matmul": "bfloat16",
    # the float32 program reads 1e-6 or less on the losses and 1e-5 on the
    # norms (the order of summation alone differs); the bfloat16 control 1e-3
    # or more on a norm
    "limits": {"loss_fall_min": -1.0, "loss_gap_step1": 1e-5, "loss_gap_step2": 1e-5,
               "loss_gap_step3": 1e-5, "first_grad_norm_gap": 1e-4,
               "param_change_norm_gap": 1e-4},
}


@pytest.fixture()
def checkout(tmp_path):
    root = toy.copy_benchmark(tmp_path)
    toy.add_files(root, {
        "benchmark/configs/toy-granite.json": {
            "name": "toy-granite", "source": "benchmark/tests (a test, not a model)",
            "preset": "granite-4.0-h-micro-stage1", "reference": "granite_hybrid",
            "model": TOY_MODEL,
            "overrides": {f"model.{k}": v for k, v in TOY_MODEL.items() if k != "d_head"},
            "reduced": [], "assumed": {}, "deployment": "a test"},
        "benchmark/traffic/toy-granite-train.json": TOY_TRAFFIC,
    })
    toy.add_entries(root, configs=[toy.config_entry("toy-granite")], workloads=[
        {"name": "toy-granite-train", "config": "toy-granite",
         "traffic": "toy-granite-train", "chips": 1, "why": "toy"}])
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "step_ms_train") + NEW_METRICS:
            m["workloads"].append("toy-granite-train")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _prepare(root, seed, seconds, trace):
    from benchmark.harness import prepare
    from benchmark.spec import Spec

    return prepare(Spec(root), "toy-granite-train", seed, seconds, trace,
                   t_process=time.monotonic(),
                   devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS))


def test_toy_cell_of_the_family_is_correct(checkout):
    from benchmark.harness import execute
    from benchmark.spec import Spec

    lines = []
    result = execute(Spec(checkout), "toy-granite-train", 2**31 + 13, 0.5, False,
                     t_process=time.monotonic(),
                     devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS),
                     log=lines.append)
    assert result["correct"], [json.loads(ln) for ln in lines]
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_traced_toy_cell_reads_the_layer_count_from_the_programs_span(checkout):
    """On the CPU a trace has the host plane only: the readers of device time
    find nothing and return ``None``; the layer count rides the program's
    ``trainer/steps`` span, so the utilisation is read."""
    parts, run = _prepare(checkout, 2**31 + 13, 0.5, True)
    try:
        parts["driver"].run(run)
    finally:
        run.clock.close()
    assert run.correct, run.checks
    from benchmark.trace.mamba_attrs import mamba_layers
    from benchmark.trace.span_attrs import mean_attr

    assert mamba_layers(run) == 3
    assert mean_attr(run, "trainer/steps", "ssd_chunks") == 4  # 32 positions in chunks of 8
    reduction = {"ops": [], "busy_s": 0.0, "window_s": 1.0, "idle_gaps": []}
    values = {name: parts["per_layer"][name].read(run, reduction) for name in NEW_METRICS}
    assert values["mfu_train_granite4h"] > 0
    for name in NEW_METRICS[:4]:
        assert values[name] is None, name


def test_the_control_one_precision_down_is_not_correct(checkout):
    parts, run = _prepare(checkout, 13, 0.0, False)
    try:
        out = parts["driver"].readings(run)
    finally:
        run.clock.close()
    limits = run.traffic["limits"]
    numbers = [k for k in limits if k in out["program"]]
    assert numbers and all(out["program"][k] <= limits[k] for k in numbers), out
    assert any(out["control"][k] > limits[k] for k in numbers), out
