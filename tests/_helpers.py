"""Shared helpers: subprocess environments, the tiny llama config, the
tolerance at which two serving programs' logits are held to each other, and
what the shm plane's views look like from outside.

A plain module (NOT conftest) so test files can import it without
re-executing conftest's module-level jax.config setup under a second module
name (`tests.conftest` vs pytest's top-level `conftest`).
"""

from __future__ import annotations

import os
import pathlib
import socket

# honor a user-set cache dir; default to the suite's persistent cache
TEST_JAX_CACHE = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
    pathlib.Path(__file__).parent / ".jax_cache"
)


#: How far the fp32 logits of two serving programs that compute the same
#: function (chunked vs one-shot prefill, cached vs cold admission, paged vs
#: contiguous decode) may sit apart. Read under jax 0.9.0 on the CPU backend:
#: <= 9e-8 absolute on logits of order 1, i.e. a last-place difference from
#: the order XLA sums a dot in, which it may choose per program. Bits are
#: promised on no backend (the TPU's fp32 dots go through bf16 passes and sit
#: ~1e-2 from an fp64 oracle), so the pin is this tolerance AND the served
#: token: the argmax must not move.
SERVE_LOGITS_ATOL = 1e-6


def assert_logits_match(got, want, err_msg: str = "") -> None:
    """``got`` and ``want`` within :data:`SERVE_LOGITS_ATOL` and picking the
    same token."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=0, atol=SERVE_LOGITS_ATOL,
                               err_msg=err_msg)
    assert int(got.argmax(-1)) == int(want.argmax(-1)), err_msg


def shm_mappings(name: str = "") -> list[str]:
    """This process's mappings of shm-plane segments whose name starts with
    ``name`` (all of them by default), as ``/proc/self/maps`` lines: an
    unlinked segment keeps its path there, with `` (deleted)`` after it."""
    with open("/proc/self/maps") as f:
        return [line.rstrip() for line in f if f"/photon-{name}" in line]


def is_readonly_view(a) -> bool:
    """What the shm plane hands a reader: the mapping's memory, unwritable."""
    return not a.flags.writeable and not a.flags.owndata


def free_port() -> int:
    """Bind-port-0 trick for subprocess tests (TCP driver, jax.distributed)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def subprocess_env() -> dict:
    """Env for spawned children: repo prepended to PYTHONPATH, CPU backend
    forced (a child must never reach for a chip its parent may hold), suite
    compile cache shared."""
    env = dict(os.environ)
    repo = str(pathlib.Path(__file__).parent.parent)
    env["PYTHONPATH"] = repo + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = TEST_JAX_CACHE
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.5"
    return env


def tiny_llama_config(n_kv_heads: int = 0):
    """Shared tiny llama-family config for the checkpoint-interop tests
    (kept in one place so export/import tests can't drift apart)."""
    from photon_tpu.config.schema import Config

    cfg = Config()
    cfg.model.d_model = 32
    cfg.model.n_layers = 2
    cfg.model.n_heads = 4
    cfg.model.n_kv_heads = n_kv_heads
    cfg.model.max_seq_len = 16
    cfg.model.vocab_size = 96
    cfg.model.attn_impl = "xla"
    cfg.model.compute_dtype = "float32"
    cfg.model.logits_dtype = "float32"
    cfg.model.rope = True
    cfg.model.learned_pos_emb = False
    cfg.model.norm = "rmsnorm"
    cfg.model.mlp = "swiglu"
    cfg.model.mlp_hidden_size = 48
    cfg.model.tie_embeddings = False
    return cfg.validate()
