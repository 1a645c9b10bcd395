"""Test harness: fake an 8-device TPU-like mesh on CPU.

SURVEY.md §4: the reference ships no tests; we build the pyramid ourselves.
Multi-chip behavior is tested on a virtual 8-CPU-device mesh
(``jax.config jax_num_cpu_devices``), per the driver's contract.
"""

import jax

# Force CPU via jax.config, which works any time before backend init — also
# when something imported jax before this file and read the env already.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_threefry_partitionable", True)

# Persistent compile cache: jit compiles dominate suite wall time (a whole
# tier-1 run without it takes 1.4 x as long: the same tiny programs are
# compiled again and again across tests and workers). The dir is gitignored —
# first run per environment pays once. A user-set JAX_COMPILATION_CACHE_DIR is
# honored everywhere (in-process, spawned children via env inheritance, and
# tests/_helpers.subprocess_env).
#
# The xdist workers and their children share the one directory, so a read and
# a write of an entry must exclude each other: jax's ``LRUCache.put`` writes
# an entry with a bare ``write_bytes`` and takes its file lock around ``get``
# and ``put`` only where eviction is on. A size the cache never reaches turns
# the lock on and evicts nothing. (It does not end tier-1's lost workers:
# XLA:CPU still dies now and then compiling, serializing or loading one of
# ``tests/test_xing_mhc.py``'s programs, lock or no lock: ROADMAP D1.)
import os as _os  # noqa: E402

from tests._helpers import TEST_JAX_CACHE as _TEST_JAX_CACHE  # noqa: E402
from tests._helpers import TEST_JAX_CACHE_MAX_SIZE as _MAX_SIZE  # noqa: E402

jax.config.update("jax_compilation_cache_dir", _TEST_JAX_CACHE)
jax.config.update("jax_compilation_cache_max_size", _MAX_SIZE)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
_os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _TEST_JAX_CACHE)
_os.environ.setdefault("JAX_COMPILATION_CACHE_MAX_SIZE", str(_MAX_SIZE))
_os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Deselect `slow` tests by default, keeping two escape hatches: an
    explicit ``-m`` expression, or naming a test by node id
    (``pytest tests/test_federation.py::test_failure_budget`` must never
    report 'no tests ran' because of a hidden default filter)."""
    if config.option.markexpr:
        return  # user chose, e.g. -m "slow or not slow" (make test-all)
    if getattr(config.option, "keyword", ""):
        return  # -k filtered runs pick their own tests, incl. slow ones
    if any("::" in arg for arg in config.args):
        return  # explicit node ids run regardless of markers
    selected, deselected = [], []
    for item in items:
        (deselected if item.get_closest_marker("slow") else selected).append(item)
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        items[:] = selected


@pytest.fixture(scope="module")
def tiny_trainer():
    """A single-device Trainer on a tiny model + one synthetic batch."""
    from photon_tpu.config.schema import (
        Config, MeshConfig, ModelConfig, OptimizerConfig, SchedulerConfig, TrainConfig,
    )
    from photon_tpu.train.trainer import Trainer

    cfg = Config(
        model=ModelConfig(
            d_model=32, n_layers=2, n_heads=2, max_seq_len=16, vocab_size=64,
            attn_impl="xla", compute_dtype="float32",
        ),
        mesh=MeshConfig(),
        optimizer=OptimizerConfig(name="adopt", lr=1e-3),
        scheduler=SchedulerConfig(t_warmup=2, t_max=50),
        train=TrainConfig(global_batch_size=4, device_microbatch_size=4),
    )
    trainer = Trainer(cfg, init_seed=0)
    batch = np.random.default_rng(0).integers(0, 64, (4, 16), dtype=np.int64)
    return trainer, batch
