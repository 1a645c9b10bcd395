"""The 1B/3B/7B recipes compile under real multi-chip sharding — abstractly.

BASELINE.md's north star includes "scale to 1.3B across 8 TPU-slice
clients". Hardware for that doesn't exist here, but the whole sharded
program is validated without materializing a single parameter:
``jax.eval_shape`` builds the abstract TrainState for the ACTUAL preset
(reference ``conf/llm_config/mpt-1b.yaml`` etc.), GSPMD shardings are
derived for the mesh, and the full train step (microbatch scan + chunked CE
+ optimizer) is lowered and compiled AOT. XLA's memory analysis then bounds
the per-device footprint — the "does it fit a 16 GiB v5e" question — with
zero FLOPs executed. The fitting meshes and the widen-tensor-not-fsdp rule
they expose are recorded in PERF.md ("1B per-device memory").
"""

import jax
import numpy as np
import pytest

from photon_tpu.config import load_preset
from photon_tpu.config.schema import MeshConfig


@pytest.mark.parametrize(
    "preset,mesh_kw,micro,params_range",
    [
        # reference recipe micro=4 measures 12.6 GiB/device on 8 chips
        ("mpt-1b", dict(fsdp=4, tensor=2), 4, (1.2e9, 1.5e9)),
        # 3B fits ONE 8-chip v5e slice at micro 2
        ("mpt-3b", dict(fsdp=4, tensor=2), 2, (2.4e9, 2.9e9)),
        # (7B needs 32 chips, more than the 8 virtual devices here: its case
        # compiles against a described topology in tests/test_tpu_compile.py)
        # llama family at 1B scale: RoPE/RMSNorm/SwiGLU/GQA params shard
        # under the same rules (separate q/k/v + gate/up projections)
        ("llama-1b", dict(fsdp=4, tensor=2), 2, (1.0e9, 1.2e9)),
    ],
    ids=["1b-8dev", "3b-8dev", "llama1b-8dev"],
)
def test_preset_train_step_compiles_sharded(preset, mesh_kw, micro, params_range):
    from jax.sharding import NamedSharding

    from photon_tpu.models.mpt import MPTModel, init_params
    from photon_tpu.optim import build_optimizer
    from photon_tpu.parallel.mesh import make_mesh
    from photon_tpu.parallel.sharding import batch_spec, state_shardings
    from photon_tpu.train.train_step import init_train_state, make_train_step

    cfg = load_preset(preset)
    cfg.mesh = MeshConfig(**mesh_kw)
    cfg.model.attn_impl = "xla"  # sharding identical; keeps the 8-dev cases fast
    cfg.train.device_microbatch_size = micro
    cfg.validate()

    mesh = make_mesh(cfg.mesh)
    model = MPTModel(cfg.model)
    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)

    abstract_state = jax.eval_shape(
        lambda: init_train_state(model, tx, init_params(cfg.model, seed=0))
    )
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree.leaves(abstract_state.params)
    )
    lo, hi = params_range
    assert lo < n_params < hi, f"{n_params:,} params is not the {preset} recipe"

    dp = cfg.mesh.data * cfg.mesh.fsdp
    n_micro = max(cfg.train.global_batch_size // (micro * dp), 1)
    step = make_train_step(model, tx, n_microbatches=n_micro,
                           loss_chunk_tokens=cfg.train.loss_chunk_tokens)

    shardings = state_shardings(abstract_state, mesh)
    batch_sh = NamedSharding(mesh, batch_spec(mesh))
    tokens = jax.ShapeDtypeStruct(
        (cfg.train.global_batch_size, cfg.model.max_seq_len), np.int32,
        sharding=batch_sh,
    )
    jitted = jax.jit(
        step, in_shardings=(shardings, batch_sh), out_shardings=(shardings, None),
        donate_argnums=0,
    )
    compiled = jitted.lower(abstract_state, tokens).compile()

    mem = compiled.memory_analysis()
    if mem is not None:  # backend-dependent availability
        # donated state aliases into the output (alias_size covers it), so
        # live bytes = args + temps + any non-aliased output
        per_dev_gb = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                      + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2**30
        assert per_dev_gb < 14.0, f"{per_dev_gb:.1f} GiB/device exceeds v5e headroom"
