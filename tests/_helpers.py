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

# honor a user-set cache dir; default to the suite's persistent cache (a
# directory of its own under the ignored one: entries written before the
# cache was locked carry no access time, and jax's eviction scan, which every
# locked write runs, fails on the first of them)
TEST_JAX_CACHE = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
    pathlib.Path(__file__).parent / ".jax_cache" / "locked"
)
#: eviction "on" is what makes jax lock an entry's read and write
#: (``tests/conftest.py``); 8 GiB is never reached (a whole run writes ~0.1), so nothing is evicted
TEST_JAX_CACHE_MAX_SIZE = 8 << 30


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
    env["JAX_COMPILATION_CACHE_MAX_SIZE"] = str(TEST_JAX_CACHE_MAX_SIZE)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.5"
    return env


def run_in_fresh_process(module: str, function: str, *args, timeout: int = 900) -> None:
    """``module.function(*args)`` (JSON-able ``args``) in a child interpreter
    with this suite's JAX settings, for a test whose body compiles whole train
    steps: tier-1 loses about one long-lived xdist worker a run to a native
    crash inside XLA:CPU (compile, ``serialize()`` or ``deserialize_executable``;
    ROADMAP D1), and the test that worker was running is counted as failed
    though nothing it asserts is wrong. A child has run nothing before; one
    that a signal kills all the same is run once more. A child that exits
    non-zero by itself (an assertion) fails the test with its output."""
    import json
    import subprocess
    import sys

    env = subprocess_env()
    env["JAX_THREEFRY_PARTITIONABLE"] = "1"  # conftest's: the seeded weights depend on it
    code = ("import importlib, json, sys; m, f, a = sys.argv[1:4]; "
            "getattr(importlib.import_module(m), f)(*json.loads(a))")
    for attempt in range(2):
        done = subprocess.run([sys.executable, "-c", code, module, function, json.dumps(args)],
                              env=env, capture_output=True, text=True, timeout=timeout)
        if done.returncode == 0:
            return
        if done.returncode > 0 or attempt:  # its own failure, or killed twice
            raise AssertionError(
                f"{module}.{function}{args} exited {done.returncode}:\n"
                + done.stdout[-2000:] + done.stderr[-6000:])


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


#: each benchmark preset at a tiny size (its own family's test's sizes), for
#: tests that hold every preset to one thing
TINY_PRESETS = {
    "mpt-125m": dict(d_model=32, n_layers=2, n_heads=2, max_seq_len=32, vocab_size=96),
    "glm-4.7-flash-ep8": dict(
        d_model=64, n_layers=3, n_heads=4, max_seq_len=32, vocab_size=96, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
        dense_mlp_hidden_size=160, mlp_hidden_size=48, moe_num_experts=8, moe_top_k=2,
        moe_experts_held=4),
    "granite-4.0-h-micro-stage1": dict(
        d_model=32, n_layers=4, layer_types="mamba,mamba,attention,mamba", n_heads=4,
        n_kv_heads=2, max_seq_len=32, vocab_size=96, mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=8, mamba_chunk_size=8, mlp_hidden_size=48,
        attention_multiplier=0.125),
    "keye-vl-2.0-30b-a3b-ep8": dict(
        d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=32, max_seq_len=64,
        vocab_size=96, dsa_topk=16, dsa_index_heads=4, dsa_index_head_dim=16, dsa_chunk=16,
        mlp_hidden_size=32, moe_num_experts=8, moe_top_k=2, moe_experts_held=2),
    "lfm2-8b-a1b-ep4": dict(
        d_model=32, n_heads=4, n_kv_heads=2, max_seq_len=32, vocab_size=96,
        dense_mlp_hidden_size=48, mlp_hidden_size=24, moe_num_experts=8, moe_top_k=2,
        moe_experts_held=4),
    "xing4.0-29b-a4b-ep8": dict(
        d_model=32, n_layers=3, n_heads=2, max_seq_len=32, vocab_size=96,
        q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6,
        rope_scaling_original_max_position=16, dense_mlp_hidden_size=48, mlp_hidden_size=24,
        moe_num_experts=8, moe_top_k=2, moe_experts_held=4),
    "laguna-xs.2-ep8": dict(
        d_model=32, n_heads=4, swa_n_heads=6, n_kv_heads=2, head_dim=8, sliding_window=8,
        max_seq_len=32, vocab_size=96, rope_scaling_original_max_position=8,
        dense_mlp_hidden_size=48, mlp_hidden_size=16, moe_num_experts=16, moe_top_k=4,
        moe_experts_held=4),
    "nemotron-3-nano-30b-a3b-ep16": dict(
        d_model=32, n_heads=4, n_kv_heads=2, head_dim=8, max_seq_len=32, vocab_size=96,
        mamba_n_heads=8, mamba_n_groups=2, mamba_d_head=8, mamba_d_state=8,
        mamba_chunk_size=8, mlp_hidden_size=24, moe_shared_hidden_size=40,
        moe_num_experts=16, moe_top_k=3, moe_experts_held=4),
}


def tiny_preset(preset: str, batch: int = 2, microbatch: int = 2, **model):
    """``preset`` at its :data:`TINY_PRESETS` size, float32 on the XLA
    attention, ``batch`` rows a step in microbatches of ``microbatch``."""
    from photon_tpu.config import load_preset

    cfg = load_preset(preset)
    for key, value in {**TINY_PRESETS[preset], "attn_impl": "xla",
                       "compute_dtype": "float32", **model}.items():
        setattr(cfg.model, key, value)
    cfg.train.global_batch_size, cfg.train.device_microbatch_size = batch, microbatch
    return cfg.validate()


def leaf_names(tree) -> list[str]:
    """Every leaf's path, ``/``-joined, in the tree's own order."""
    import jax

    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


def recorded_spans(monkeypatch) -> list:
    """``(name, attrs)`` of every ``telemetry.span`` the trainer opens from here on."""
    import contextlib

    from photon_tpu.train import trainer

    spans: list = []

    @contextlib.contextmanager
    def span(name, **attrs):
        spans.append((name, attrs))
        yield

    monkeypatch.setattr(trainer.telemetry, "span", span)
    return spans
