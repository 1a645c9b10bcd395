"""Multi-device sharding tests on the virtual 8-device CPU mesh
(SURVEY.md §4: fake multi-chip via xla_force_host_platform_device_count)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from photon_tpu.config.schema import Config, MeshConfig, ModelConfig, OptimizerConfig, SchedulerConfig, TrainConfig
from photon_tpu.models.mpt import MPTModel, init_params
from photon_tpu.optim import build_optimizer
from photon_tpu.parallel import make_mesh, param_specs
from photon_tpu.train import init_train_state
from photon_tpu.train.trainer import Trainer

TINY = ModelConfig(
    d_model=64, n_layers=2, n_heads=4, max_seq_len=32, vocab_size=256,
    attn_impl="xla", compute_dtype="float32",
)


def _cfg(mesh: MeshConfig) -> Config:
    return Config(
        model=TINY,
        mesh=mesh,
        optimizer=OptimizerConfig(name="adamw", lr=1e-3),
        scheduler=SchedulerConfig(t_warmup=2, t_max=100),
        train=TrainConfig(global_batch_size=8, device_microbatch_size=8),
    )


def test_mesh_axes_and_size():
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, tensor=2, sequence=1))
    assert mesh.shape == {"data": 2, "fsdp": 2, "tensor": 2, "sequence": 1,
                          "pipe": 1, "expert": 1}
    with pytest.raises(ValueError):
        make_mesh(MeshConfig(data=16))


def test_mesh_surplus_devices_knob():
    """ISSUE 14 satellite: ``devices[:size]`` used to truncate silently;
    a surplus that is NOT a whole multiple of the mesh size now warns by
    default, raises under ``surplus_devices='error'``, and stays silent
    for exact multiples (several same-size gangs from one list is a
    deliberate layout) or under 'ignore'."""
    import warnings

    devices = jax.devices()  # 8 virtual CPU devices

    # 8 % 3 != 0: warn by default, mentioning the idle count
    with pytest.warns(UserWarning, match="2 device"):
        mesh = make_mesh(MeshConfig(data=3), devices=devices)
    assert mesh.devices.size == 3

    with pytest.raises(ValueError, match="not a whole multiple"):
        make_mesh(MeshConfig(data=3, surplus_devices="error"),
                  devices=devices)

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        # 'ignore' restores the old silence
        make_mesh(MeshConfig(data=3, surplus_devices="ignore"),
                  devices=devices)
        # exact multiples never warn (8 % 4 == 0, 8 % 8 == 0)
        make_mesh(MeshConfig(data=4), devices=devices)
        make_mesh(MeshConfig(data=8), devices=devices)


def test_param_specs_rules():
    mesh = make_mesh(MeshConfig(fsdp=4, tensor=2))
    params = init_params(TINY, seed=0)
    specs = param_specs(params, mesh)
    blk = specs["blocks"]["block"]
    # leading layer axis carries the pipe-stage sharding (a no-op at pipe=1)
    assert blk["wqkv"]["kernel"] == P("pipe", "fsdp", "tensor")
    assert blk["out_proj"]["kernel"] == P("pipe", "tensor", "fsdp")
    assert specs["wte"]["embedding"] == P("fsdp", "tensor")
    assert blk["ln_1"]["scale"] == P("pipe", None)  # per-layer scales ride the slab
    assert all(a is None for a in specs["ln_f"]["scale"])  # replicated


def test_spec_drops_indivisible_axes():
    mesh = make_mesh(MeshConfig(fsdp=8))
    # 64 % 8 == 0 → sharded on fsdp
    assert param_specs({"wpe": np.zeros((2, 64))}, mesh)["wpe"] == P(None, "fsdp")
    # 60 % 8 != 0 → axis dropped, replicated
    assert param_specs({"wpe": np.zeros((2, 60))}, mesh)["wpe"] == P(None, None)


@pytest.mark.parametrize(
    "mesh_cfg",
    [
        MeshConfig(data=8),
        MeshConfig(fsdp=8),
        MeshConfig(data=2, fsdp=4),
        MeshConfig(data=2, fsdp=2, tensor=2),
        # the sequence axis beside each of the others on FOUR of the eight
        # virtual devices: on all eight (fsdp=2, tensor=2, sequence=2) the
        # ring's in-process collectives starve each other of XLA:CPU's one
        # 8-thread pool and ``rendezvous.cc`` aborts the process after 40 s
        MeshConfig(fsdp=2, sequence=2),
        MeshConfig(tensor=2, sequence=2),
    ],
    ids=["dp8", "fsdp8", "dp2xfsdp4", "dp2fsdp2tp2", "fsdp2sp2", "tp2sp2"],
)
def test_sharded_training_matches_single_device(mesh_cfg):
    """The same batch must produce the same loss trajectory on any mesh."""
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(0), (8, 32), 0, TINY.vocab_size)
    )

    def run(mesh):
        t = Trainer(_cfg(mesh), init_seed=0)
        losses = []
        for _ in range(3):
            _ = t.fit([tokens], duration_steps=1)
            losses.append(_["client/final_loss"])
        return losses

    ref = run(MeshConfig())  # single device
    got = run(mesh_cfg)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_get_set_parameters_roundtrip_sharded():
    t = Trainer(_cfg(MeshConfig(data=2, fsdp=2, tensor=2)), init_seed=0)
    meta, arrays = t.get_parameters()
    mutated = [a + 1.0 for a in arrays]
    t.set_parameters(meta, mutated)
    meta2, arrays2 = t.get_parameters()
    assert meta2 == meta
    for a, b in zip(mutated, arrays2):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_set_step_and_reset_optimizer():
    t = Trainer(_cfg(MeshConfig(data=2)), init_seed=0)
    tokens = np.zeros((8, 32), np.int64)
    t.fit([tokens], duration_steps=1)
    assert t.step == 1
    t.set_step(100)
    assert t.step == 100
    t.reset_optimizer()
    # optimizer state zeroed: one more step still works
    t.fit([tokens], duration_steps=1)
    assert t.step == 101
