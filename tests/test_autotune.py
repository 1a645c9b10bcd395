"""Layout auto-tuner (ISSUE 14b): enumeration legality, cost-model
monotonicity, ranking sanity on real presets and the federated DCN term (the
memory-analysis cross-check against the TPU compiler lives in
``tests/test_tpu_compile.py``). The top pick against a step measured on
the chip: not measured."""

import dataclasses

import pytest

from photon_tpu.config.schema import MeshConfig, ModelConfig
from photon_tpu.parallel.autotune import (
    autotune_layout,
    autotune_mesh,
    enumerate_layouts,
    estimate_layout,
    model_param_count,
    rank_layouts,
)

TINY = ModelConfig(
    d_model=64, n_layers=2, n_heads=4, max_seq_len=32, vocab_size=256,
    attn_impl="xla", compute_dtype="float32",
)


# ---------------------------------------------------------------------------
# enumeration legality
# ---------------------------------------------------------------------------


def test_enumerate_covers_exact_factorizations():
    layouts = enumerate_layouts(TINY, 8, global_batch_size=8)
    for m in layouts:
        assert m.data * m.fsdp * m.tensor * m.pipe == 8
        assert m.sequence == 1 and m.expert == 1
    # pure data-parallel is always among the legal layouts
    assert any(m.data == 8 for m in layouts)


def test_enumerate_respects_divisibility():
    # tensor must divide n_heads (4) AND d_model: tensor=8 is illegal
    assert not any(
        m.tensor == 8 for m in enumerate_layouts(TINY, 8, 8)
    )
    # pipe must divide n_layers (2): pipe=4 and pipe=8 are illegal
    assert not any(
        m.pipe in (4, 8) for m in enumerate_layouts(TINY, 8, 8)
    )
    # GQA: kv heads constrain tensor too
    gqa = dataclasses.replace(TINY, n_kv_heads=2, rope=True,
                              learned_pos_emb=False)
    assert not any(m.tensor == 4 for m in enumerate_layouts(gqa, 8, 8))
    assert any(m.tensor == 2 for m in enumerate_layouts(gqa, 8, 8))


def test_enumerate_pipeline_single_batch_axis():
    # the schema allows at most ONE batch-sharded axis with pipe > 1
    deep = dataclasses.replace(TINY, n_layers=8)
    for m in enumerate_layouts(deep, 8, 8):
        if m.pipe > 1:
            assert not (m.data > 1 and m.fsdp > 1)


def test_enumerate_max_pipe_cap():
    deep = dataclasses.replace(TINY, n_layers=8)
    assert any(m.pipe > 1 for m in enumerate_layouts(deep, 8, 8))
    capped = enumerate_layouts(deep, 8, 8, max_pipe=1)
    assert capped and all(m.pipe == 1 for m in capped)


def test_enumerate_batch_divisibility_and_errors():
    # global batch 4 cannot shard over data*fsdp = 8
    assert not any(
        m.data * m.fsdp == 8 for m in enumerate_layouts(TINY, 8, 4)
    )
    with pytest.raises(ValueError, match="n_devices"):
        enumerate_layouts(TINY, 0, 8)
    # 7 devices: tensor=7 (64 % 7), pipe=7 (2 % 7) and dp=7 (batch 8 % 7)
    # are all illegal -> ranking raises loudly instead of silently 1x1x1x1
    with pytest.raises(ValueError, match="no legal"):
        rank_layouts(TINY, 7, global_batch_size=8)


# ---------------------------------------------------------------------------
# cost model shape
# ---------------------------------------------------------------------------


def test_param_count_tracks_presets():
    from photon_tpu.config import load_preset

    n125 = model_param_count(ModelConfig())
    assert 1.1e8 < n125 < 1.4e8  # the 125M recipe
    n1b = model_param_count(load_preset("mpt-1b").model)
    assert 1.2e9 < n1b < 1.5e9


def test_comm_grows_with_tensor_and_hbm_shrinks_with_fsdp():
    cfg = ModelConfig()  # 125M
    t1 = estimate_layout(cfg, MeshConfig(data=8), 256, microbatch=8)
    t2 = estimate_layout(cfg, MeshConfig(data=4, tensor=2), 256, microbatch=8)
    assert t2.breakdown["tensor_s"] > t1.breakdown["tensor_s"] == 0.0
    f1 = estimate_layout(cfg, MeshConfig(data=8), 256, microbatch=8)
    f8 = estimate_layout(cfg, MeshConfig(fsdp=8), 256, microbatch=8)
    assert f8.hbm_bytes_per_device < f1.hbm_bytes_per_device
    # pipeline bubble inflates compute
    deep = estimate_layout(cfg, MeshConfig(data=4, pipe=2), 256, microbatch=8)
    assert deep.bubble_frac > 0.0
    assert deep.compute_s > t1.compute_s


def test_ranking_small_model_prefers_data_parallel():
    best = rank_layouts(ModelConfig(), 8, 256, microbatch=8)[0]
    assert best.axes == (8, 1, 1, 1)
    assert best.fits


def test_ranking_big_model_shards_state_to_fit():
    """A 1.3B server state cannot live replicated on a 16 GB chip — the
    tuner must pick a layout that shards params/optimizer state (fsdp or
    tensor), exactly the heterogeneity story: the same model config gets
    a different layout on a different slice."""
    from photon_tpu.config import load_preset

    big = load_preset("mpt-1b").model
    ranked = rank_layouts(big, 8, 256, microbatch=4)
    best = ranked[0]
    assert best.fits
    assert best.mesh.fsdp * best.mesh.tensor * best.mesh.pipe > 1
    # pure dp8 is enumerated but cannot fit 1.3B x 16 bytes/param
    dp8 = next(e for e in ranked if e.axes == (8, 1, 1, 1))
    assert not dp8.fits


def test_federated_term_priced_with_pr7_machinery():
    cfg = ModelConfig()
    base = estimate_layout(cfg, MeshConfig(data=4), 256, microbatch=8)
    fed = estimate_layout(
        cfg, MeshConfig(data=4), 256, microbatch=8,
        n_clients=8, local_steps=10,
    )
    assert "federated_dcn_s" not in base.breakdown
    dcn = fed.breakdown["federated_dcn_s"]
    assert dcn > 0.0
    # q8 on the DCN leg shrinks the exchange term ~4x (the PR 7 model)
    fed_q8 = estimate_layout(
        cfg, MeshConfig(data=4), 256, microbatch=8,
        n_clients=8, local_steps=10, quantization="q8",
    )
    ratio = dcn / fed_q8.breakdown["federated_dcn_s"]
    assert 3.0 < ratio < 4.0
    # more local steps amortize the exchange
    fed_more = estimate_layout(
        cfg, MeshConfig(data=4), 256, microbatch=8,
        n_clients=8, local_steps=100,
    )
    assert fed_more.breakdown["federated_dcn_s"] < dcn


def test_entry_points():
    import jax

    mesh_cfg = autotune_mesh(TINY, n_devices=4, global_batch_size=8)
    assert isinstance(mesh_cfg, MeshConfig)
    assert mesh_cfg.size == 4
    best = autotune_layout(TINY, devices=jax.devices()[:4],
                           global_batch_size=8)
    assert best.mesh.size == 4
    with pytest.raises(ValueError, match="devices"):
        autotune_layout(TINY)


def test_trainer_autotunes_mesh_when_enabled():
    """The per-client entry point end to end: a Trainer built without an
    explicit mesh under photon.mesh_autotune derives its layout from the
    tuner over the local devices, and records the search for the
    server/layout_* KPIs."""
    from photon_tpu.config.schema import (
        Config, OptimizerConfig, SchedulerConfig, TrainConfig,
    )
    from photon_tpu.train.trainer import Trainer

    cfg = Config(
        model=TINY,
        optimizer=OptimizerConfig(name="adamw", lr=1e-3),
        scheduler=SchedulerConfig(t_warmup=2, t_max=100),
        train=TrainConfig(global_batch_size=8, device_microbatch_size=1),
    )
    cfg.photon.mesh_autotune = True
    trainer = Trainer(cfg, init_seed=0)
    tuned = trainer.layout_autotune
    assert tuned is not None
    assert tuned["search_s"] >= 0.0 and tuned["est_step_s"] > 0.0
    # 8 local CPU devices, tiny model -> pure data parallel
    assert trainer.mesh.shape["data"] == 8
    # an explicit mesh still wins (the collective runner's contract)
    from photon_tpu.parallel.mesh import single_device_mesh

    pinned = Trainer(cfg, mesh=single_device_mesh(), init_seed=0)
    assert pinned.layout_autotune is None
    assert pinned.mesh.devices.size == 1


def test_autotune_probe_never_kills_collective_runner_config():
    """The CollectiveFedRunner's layout probe is observability-only: a
    slice shape with no legal layout must degrade to a warning, not kill
    server construction (the loud error belongs to the Trainer path,
    which consumes the layout). Unit-covers the guarded call shape."""
    # heads=3/d_model=63-style indivisibility with an odd batch: nothing
    # legal at n_devices=7
    odd = dataclasses.replace(TINY, n_layers=3)
    with pytest.raises(ValueError, match="no legal"):
        rank_layouts(odd, 7, global_batch_size=9)
    # the runner wraps exactly this call in try/except ValueError — pin
    # that the exception type stays ValueError so the guard keeps working
    try:
        autotune_layout(odd, n_devices=7, global_batch_size=9)
    except ValueError:
        pass
    else:  # pragma: no cover
        pytest.fail("expected ValueError for an un-layoutable slice")
