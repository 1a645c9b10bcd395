"""The main path's programs compile for a described TPU v5e — no chip attached.

The TPU compiler is installed here and compiles for a topology that is only
described (``jax.experimental.topologies``): what Mosaic or XLA:TPU would
refuse on the chip — a tile that overflows VMEM, a misaligned slice, a step
that does not fit 16 GiB — it refuses here, at no chip time. Nothing runs, so
these say nothing about results or speed.

This is the ONE test file that loads the TPU library, and it does so inside a
module-scoped fixture: only one process may hold the library, every xdist
worker imports every test file, and a second file would land on another worker
and skip there in silence. The persistent compile cache is off around these
tests (a described-device executable can be written to it but never read
back).
"""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

import chip_smoke

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo_devices():
    """``abstract_devices(shape)`` for v5e topologies, or skip the file."""
    from jax.experimental.compilation_cache import compilation_cache

    from photon_tpu.parallel.topo import abstract_tpu_devices

    try:
        first = abstract_tpu_devices("v5e:2x2x1")
    except RuntimeError as e:
        pytest.skip(f"no v5e topology can be described here: {e}")
    described = {"2x2": first}

    def get(shape: str = "2x2"):
        if shape not in described:
            described[shape] = abstract_tpu_devices(f"v5e:{shape}x1")
        return described[shape]

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield get
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo_devices):
    return SingleDeviceSharding(topo_devices()[0])


def _abstract(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# flash attention (training)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize(
    "h,h_kv,d,block,alibi",
    [
        (12, 12, 64, 256, True),    # mpt-125m, the tile every run had before PR 28
        (12, 12, 64, 1024, True),   # mpt-125m, the tile the July record used
        (16, 16, 128, 512, True),   # mpt-1b widths
        (32, 8, 128, 512, True),    # grouped-query, 4 q heads per kv head
        # block None is what the models pass: the tiles pick_tiles derives,
        # with the vmem_limit_bytes its estimate asks for, are what ships
        (12, 12, 64, None, False),  # mpt-125m as the preset runs it
        (12, 12, 64, None, True),
        (16, 16, 128, None, True),
        (32, 8, 128, None, True),
    ],
    ids=["125m-default", "125m-b1024", "1b-b512", "gqa32x8-b512",
         "125m-derived", "125m-alibi-derived", "1b-derived", "gqa32x8-derived"],
)
def test_flash_attention_compiles(one_chip, h, h_kv, d, block, alibi, grad):
    from photon_tpu.ops.flash_attention import flash_attention

    b, s = 2, 2048
    q = _abstract((b, s, h, d), jnp.bfloat16, one_chip)
    kv = _abstract((b, s, h_kv, d), jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, alibi=alibi,
                               block_q=block, block_k=block)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    hlo = _hlo(fn, q, kv, kv)
    # fwd is one kernel; bwd re-runs it and adds the dq and dk/dv kernels
    assert hlo.count(KERNEL) >= (3 if grad else 1)
    # each launch keeps the instruction name the benchmark's
    # flash_attention_roofline anchors on, and carries its own kernel's name
    # in the op_name (what flash_fwd_ms_train / flash_bwd_ms_train find)
    launches = [ln.strip() for ln in hlo.splitlines() if KERNEL in ln]
    assert all(ln.startswith("%multihead_attention") or
               ln.startswith("ROOT %multihead_attention") for ln in launches)
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv") if grad else ("flash_fwd",):
        assert any(re.search(rf"\b{kernel}/multihead_attention\b", ln)
                   for ln in launches), kernel


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "fp32"])
@pytest.mark.parametrize(
    "d,block_q,block_k",
    [(64, 512, 512), (128, 1024, 1024), (128, 2048, 1024), (128, 1024, 2048),
     (256, 2048, 512), (256, 1024, 1024),
     # a whole-sequence tile: all three launches run it as eight strips
     (64, 2048, 2048), (128, 2048, 2048)],
    ids=lambda v: str(v),
)
def test_flash_vmem_estimate_is_enough(one_chip, monkeypatch, d, block_q, block_k, dtype):
    """``launch_vmem_bytes`` is what ``pick_tiles`` fits tiles by and what a
    launch asks the compiler for, so it may never be under what the compiler
    needs: with the unasked-for 16 MiB taken away, every launch compiles
    inside its own estimate (tall, wide and square tiles; the widths and
    dtypes whose buffers differ). A square tile's launches hold the strip
    bodies beside the whole-tile one, a tall or wide tile's the masked body
    alone. The launches read the layout the widths give (``flash_layout``):
    the pair body at 64, in place at 128 and 256."""
    _vmem_estimate_holds(one_chip, monkeypatch, (1, 2048, 4, d), dtype, block_q, block_k,
                         {64: "head_pairs", 128: "in_place", 256: "in_place"}[d])


def _vmem_estimate_holds(one_chip, monkeypatch, shape, dtype, block_q, block_k, layout,
                         forced=None, kv_heads=None, d_v=None):
    """Compiles forward and backward of ``shape``'s attention with every
    launch held to its own estimate, and says the ``layout`` its launches took."""
    from photon_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "VMEM_SCOPED_DEFAULT", 0)
    if forced:
        monkeypatch.setattr(fa, "flash_layout", lambda *a: forced)
    for launch in ("fwd", "dq", "dkv"):
        if block_q is not None:
            strips = fa.strip_rows(launch, block_q, block_k, causal=True, offset=0)
            assert bool(strips) == (block_q == block_k)
    b, s, h, d = shape
    q = _abstract(shape, dtype, one_chip)
    k = _abstract((b, s, kv_heads or h, d), dtype, one_chip)
    v = _abstract((b, s, kv_heads or h, d_v or d), dtype, one_chip)
    assert fa.flash_layout(h, kv_heads or h, d, d_v or d) == layout

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, alibi=True, block_q=block_q,
                                  block_k=block_k).astype(jnp.float32).sum()

    hlo = _hlo(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert hlo.count(KERNEL) >= 3
    # to_bh's copies are head-major arrays; read in place there is none
    head_major = re.search(rf"\[{b * h},{s},{fa.lane_padded(d)}\]", hlo) is not None
    assert head_major == (layout == "head_major")


@pytest.mark.parametrize(
    "d,block_q,block_k",
    [(64, 512, 512), (128, 1024, 1024), (256, 2048, 512), (64, 2048, 2048)],
    ids=lambda v: str(v),
)
def test_flash_vmem_estimate_is_enough_for_head_major_copies(one_chip, monkeypatch, d,
                                                             block_q, block_k):
    """The same launches over ``to_bh``'s transposed and padded copies, the
    layout every shape had before PR 45 and grouped heads at 64, a 192-wide
    head and the ring's kernel still have."""
    _vmem_estimate_holds(one_chip, monkeypatch, (1, 2048, 4, d), jnp.bfloat16, block_q,
                         block_k, "head_major", forced="head_major")


@pytest.mark.parametrize("shape,kv_heads,d_v,layout", [
    pytest.param((4, 2048, 12, 64), None, None, "head_pairs", id="mpt125m-train"),
    pytest.param((2, 4096, 20, 256), None, None, "in_place", id="glm47flash-train"),
    pytest.param((1, 4096, 32, 192), None, 128, "head_major", id="xing4-train-4k"),
    pytest.param((1, 8192, 32, 64), 8, None, "head_major", id="lfm2moe-train-8k"),
    pytest.param((1, 8192, 40, 64), 8, None, "head_major", id="granite4hmicro-train"),
])
def test_flash_vmem_estimate_is_enough_at_the_cells_tiles(one_chip, monkeypatch, shape,
                                                          kv_heads, d_v, layout):
    """Each cell's heads at the tiles ``pick_tiles`` derives for them, in the
    layout their widths give: two heads of 64 side by side (the pair body's
    second set of score temporaries is in its estimate), 20 heads of 256 in
    place, and the three that keep ``to_bh``."""
    _vmem_estimate_holds(one_chip, monkeypatch, shape, jnp.bfloat16, None, None, layout,
                         kv_heads=kv_heads, d_v=d_v)


def test_flash_attention_with_lse_compiles(one_chip):
    """The (o, lse) variant ring attention merges chunks with: forward is the
    kernel, backward recomputes through XLA."""
    from photon_tpu.ops.flash_attention import flash_attention_with_lse

    q = _abstract((2, 1024, 12, 64), jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return flash_attention_with_lse(q, k, v, causal=True, q_start=1024,
                                        k_start=0)

    assert KERNEL in _hlo(fwd, q, q, q)

    def loss(q, k, v):
        o, lse = fwd(q, k, v)
        return o.astype(jnp.float32).sum() + lse.sum()

    _hlo(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


# ---------------------------------------------------------------------------
# ragged paged attention (serving) at the shapes the engine passes
# ---------------------------------------------------------------------------


def _serve_shapes():
    """(mpt-125m model config, photon.serve defaults, max_blocks)."""
    from photon_tpu.config import load_preset

    cfg = load_preset("mpt-125m")
    sc = cfg.photon.serve
    return cfg.model, sc, -(-cfg.model.max_seq_len // sc.block_size)


def _buckets(prompt_len: int, max_new: int = chip_smoke.MAX_NEW) -> tuple[int, int]:
    """(chunk width Tq, live context width in blocks) ``engine._bucket`` and
    ``engine._ctx_width`` give a lone request of this size."""
    from photon_tpu.serve.engine import _pow2_bucket

    _, sc, max_blocks = _serve_shapes()
    bs = sc.block_size
    tq = min(_pow2_bucket(-(-prompt_len // bs)), max_blocks) * bs
    n_ctx = min(_pow2_bucket(-(-(prompt_len + max_new) // bs)), max_blocks)
    return tq, n_ctx


# chip_smoke.py's prompts, and the decode step at every width they reach
SMOKE_PROMPTS = chip_smoke.PROMPT_LENS


@pytest.mark.parametrize(
    "rows,prompt_len",
    [("decode", n) for n in SMOKE_PROMPTS] + [("chunk", n) for n in SMOKE_PROMPTS],
)
def test_ragged_paged_attention_compiles_at_engine_shapes(one_chip, rows, prompt_len):
    from photon_tpu.ops.attention import alibi_slopes
    from photon_tpu.ops.ragged_paged_attention import ragged_paged_attention

    mc, sc, max_blocks = _serve_shapes()
    tq, n_ctx = _buckets(prompt_len)
    # decode: every slot, one token; chunk: the one prefilling slot, Tq tokens
    b, t = (sc.n_slots, 1) if rows == "decode" else (1, tq)
    n_blocks = sc.n_slots * max_blocks
    dtype = jnp.dtype(mc.compute_dtype)  # the pool's dtype
    assert dtype == jnp.bfloat16
    q = _abstract((b, t, mc.n_heads, mc.d_head), dtype, one_chip)
    pool = _abstract((n_blocks + 1, sc.block_size, mc.n_heads, mc.d_head),
                     dtype, one_chip)
    table = _abstract((b, n_ctx), jnp.int32, one_chip)
    pos = _abstract((b, t), jnp.int32, one_chip)

    def fn(q, k, v, table, pos):
        return ragged_paged_attention(
            q, k, v, table, pos, scale=1.0 / math.sqrt(mc.d_head),
            slopes=alibi_slopes(mc.n_heads),
        )

    assert KERNEL in _hlo(fn, q, pool, pool, table, pos)


def test_engine_mixed_step_compiles(one_chip):
    """The whole serving step — decode rows riding the widest prefill chunk
    the smoke sends — as ``PagedEngine`` traces it for the ragged kernel."""
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.cache import init_paged_state, mixed_chunk_step

    mc, sc, max_blocks = _serve_shapes()
    tq, n_ctx = _buckets(max(SMOKE_PROMPTS))

    def on_chip(tree):
        return jax.tree.map(lambda x: _abstract(x.shape, x.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: init_params(mc, seed=0)))
    state = on_chip(jax.eval_shape(lambda: init_paged_state(
        mc, sc.n_slots, sc.n_slots * max_blocks, sc.block_size, max_blocks)))
    grid = (sc.n_slots, tq)
    slots = (sc.n_slots,)

    def step(params, state, tokens, positions, q_valid, emit_off, lengths, slot):
        return mixed_chunk_step(params, state, tokens, positions, q_valid,
                                emit_off, lengths, slot, mc, n_ctx=n_ctx,
                                has_chunk=True, impl="ragged")

    hlo = _hlo(
        step, params, state, _abstract(grid, jnp.int32, one_chip),
        _abstract(grid, jnp.int32, one_chip), _abstract(grid, jnp.bool_, one_chip),
        _abstract(slots, jnp.int32, one_chip), _abstract(slots, jnp.int32, one_chip),
        _abstract((), jnp.int32, one_chip),
    )
    assert KERNEL in hlo


# ---------------------------------------------------------------------------
# glm-4.7-flash-ep8: latent attention's head shapes, the grouped products
# ---------------------------------------------------------------------------


def test_flash_attention_compiles_at_latent_attention_shapes(one_chip):
    """20 heads of 256 at 4,096 tokens, 4 rows (the ``glm47flash-train``
    cell), forward and backward at the tiles ``pick_tiles`` derives."""
    from photon_tpu.ops.flash_attention import flash_attention

    qkv = _abstract((4, 4096, 20, 256), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    assert _hlo(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv).count(KERNEL) >= 3


def test_grouped_expert_products_compile(one_chip, monkeypatch):
    """The dropless layer's three grouped products at the cell's widths
    (65,536 static rows of 2,048, 8 experts of 1,536), forward and backward:
    megablox's kernel, its transposed form and the weight-gradient kernel at
    ``ops/moe.GMM_TILING``."""
    import photon_tpu.ops.flash_attention as fa
    from photon_tpu.ops import moe

    monkeypatch.setattr(fa, "pallas_supported", lambda x: True)
    rows = _abstract((65536, 2048), jnp.bfloat16, one_chip)
    w_in = _abstract((8, 2048, 1536), jnp.bfloat16, one_chip)
    w_out = _abstract((8, 1536, 2048), jnp.bfloat16, one_chip)
    sizes = _abstract((9,), jnp.int32, one_chip)

    def loss(x, w_gate, w_up, w_down, sizes):
        mm = lambda a, b: moe.grouped_matmul(a, b, sizes)  # noqa: E731
        out = mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)
        return out.astype(jnp.float32).sum()

    hlo = _hlo(jax.grad(loss, argnums=(0, 1, 2, 3)), rows, w_in, w_in, w_out, sizes)
    # gate and up forward (the down product's output is dead under a sum),
    # and for each of the three a transposed product and a weight gradient
    assert hlo.count(KERNEL) >= 8


@pytest.mark.parametrize("k, experts, held, hidden, router", [
    (4, 32, 8, 1792, "sigmoid"), (8, 128, 16, 768, "softmax_topk")],
    ids=["lfm2moe-train-8k", "keyevl2-train-16k"])
def test_the_dispatchs_gathers_read_from_vmem(one_chip, monkeypatch, k, experts, held,
                                              hidden, router):
    """One dropless layer under ``jax.checkpoint``, forward and backward, at
    the cells' shapes (16,384 tokens: 65,536 and 131,072 static rows of 2,048):
    each of the two un-permutes is a conditional whose common branch gathers
    from a chunk of ``ops/moe.DISPATCH_CHUNK_BYTES`` of the expert-ordered
    rows, which the compiler keeps in VMEM (memory space 1), and whose other
    branch gathers from all ``N k``; every other row gather's operand is the
    ``N`` tokens; nothing un-permutes under ``remat``; inside a minute and a
    half."""
    import functools
    import time

    import photon_tpu.ops.flash_attention as fa
    from photon_tpu.ops import moe

    monkeypatch.setattr(fa, "pallas_supported", lambda x: True)
    tokens, d = 16384, 2048
    w_in = _abstract((held, d, hidden), jnp.float32, one_chip)

    def loss(h32, router_w, w_gate, w_up, w_down):
        bias = jnp.zeros((experts,)) if router == "sigmoid" else None
        out, _ = jax.checkpoint(functools.partial(
            moe.dropless_moe_mlp, top_k=k, first_expert=0, router=router))(
                h32, router_w, bias, w_gate, w_up, w_down)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    t0 = time.monotonic()
    text = _hlo(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                _abstract((tokens, d), jnp.float32, one_chip),
                _abstract((d, experts), jnp.float32, one_chip), w_in, w_in,
                _abstract((held, hidden, d), jnp.float32, one_chip))
    assert time.monotonic() - t0 < 90
    chunk, m = moe.DISPATCH_CHUNK_BYTES // (d * 2), tokens * k
    gathers = re.findall(r"fusion\((%[\w.\-]+), [^\n]*(moe/dispatch\)?/[^\"]*gather)\"", text)
    shapes = [(re.findall(rf"^\s*(?:ROOT )?{re.escape(operand)} = (\S+) ", text, flags=re.M)[0], op)
              for operand, op in gathers]
    rows = [(shape, op) for shape, op in shapes if shape.startswith("bf16[")]
    assert not any("rematted_computation" in op and "cond" in op for _, op in rows)
    chunks = [shape for shape, _ in rows if shape.startswith(f"bf16[{chunk},{d}]")]
    assert len(chunks) == 2 and all(shape.endswith("S(1)}") for shape in chunks), chunks
    assert len([1 for shape, op in rows if shape.startswith(f"bf16[{m},{d}]")]) == 2
    assert {shape.split("{")[0] for shape, op in rows if "cond" not in op} == {
        f"bf16[{tokens},{d}]"}


# ---------------------------------------------------------------------------
# granite-4.0-h-micro-stage1: grouped heads with the published scale at 8,192
# positions, and the chunked state-space scan
# ---------------------------------------------------------------------------


def test_flash_attention_compiles_at_the_hybrid_stages_shapes(one_chip):
    """32 query / 8 key-value heads of 64 at 8,192 positions, one row (the
    ``granite4hmicro-train`` cell), softmax scale 1/64, forward and backward
    at the tiles ``pick_tiles`` derives."""
    from photon_tpu.ops.flash_attention import flash_attention

    q = _abstract((1, 8192, 32, 64), jnp.bfloat16, one_chip)
    kv = _abstract((1, 8192, 8, 64), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=0.015625).astype(
            jnp.float32).sum()

    assert _hlo(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv).count(KERNEL) >= 3


def test_chunked_state_space_scan_compiles_and_keeps_one_chunk_of_decays(one_chip, monkeypatch):
    """``ops/ssd.ssd_scan`` at the cell's widths (one row of 8,192, 64 heads
    of 64, state 128, chunks of 256), forward and backward: the two launches
    compile for the chip (a head's ``[256, 256]`` decays live in their VMEM),
    XLA writes no float32 ``[..., 256, 256]`` array beside them (the walk
    wrote three a chunk, 17 MB each), and what the program holds beside its
    arguments and results stays under the 0.4 GiB line the walk was held to
    (the chunks' start states are 67 MB, ``y`` 134)."""
    import photon_tpu.ops.flash_attention as fa
    from photon_tpu.ops import ssd

    monkeypatch.setattr(fa, "pallas_supported", lambda x: True)
    x = _abstract((1, 8192, 64, 64), jnp.bfloat16, one_chip)
    dt = _abstract((1, 8192, 64), jnp.float32, one_chip)
    bc = _abstract((1, 8192, 128), jnp.bfloat16, one_chip)
    head = _abstract((64,), jnp.float32, one_chip)
    assert ssd.uses_kernel("pallas", False, 8192, 256, 64, 64, 128)

    def loss(x, dt, a_log, b, c, d):
        return ssd.ssd_scan(x, dt, a_log, b, c, d, chunk=256, impl="pallas").sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        x, dt, head, bc, bc, head).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 2
    launches = [ln for ln in text.splitlines() if KERNEL in ln]
    for name in ("ssd_scan_fwd", "ssd_scan_bwd"):  # each carries its name in its op_name
        assert sum(bool(re.search(rf"\b{name}\)*/pallas_call", ln)) for ln in launches) == 1, name
    assert not re.search(r"f32\[[\d,]*256,256\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4 * 2**30


def test_the_hybrid_cells_step_compiles_with_the_scans_launches(topo_devices, monkeypatch):
    """``granite-4.0-h-micro-stage1`` at its cell's size (1 row x 8,192
    tokens, one microbatch, ``remat``, 772 M parameters): the whole train step
    for a described v5e; each stack of Mamba-2 layers holds the scan's forward
    launch twice (the second under ``remat``, keeping the chunks' start
    states) and its backward launch once, all under ``mamba/scan``; no fusion
    writes a float32 ``[..., 256, 256]`` array; and the donated state + its
    temporaries by ``memory_analysis()`` stand where the walk's step stood
    (16.446 GiB then, 16.500 now: the scheduler's, 16.250 at 64 heads a block
    and 16.500 again at 32; the compiler's own report totals 14.27 GiB where
    the walk's step took 14.26: ``XLA_FLAGS=--xla_dump_to``,
    ``*memory-usage-report.txt``) (~25 s)."""
    from photon_tpu.config import load_preset

    cfg = load_preset("granite-4.0-h-micro-stage1")
    compiled, state = _compile_train_step(cfg, topo_devices()[:1], monkeypatch)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(state.params)) == 772_160_448
    text = compiled.as_text()
    launches = [ln for ln in text.splitlines() if KERNEL in ln and "ssd_scan_" in ln]
    assert len(launches) == 6 and all("mamba/scan/" in ln for ln in launches)
    assert sum("ssd_scan_bwd" in ln for ln in launches) == 2
    written = re.findall(r"^\s*(?:ROOT )?%\S+ = (.*?) fusion\(", text, re.M)
    assert len(written) > 300 and not [
        shape for shape in written if re.search(r"f32\[[\d,]*256,256\]", shape)]
    print(f"live GiB {_live_gib(compiled):.3f}")
    assert 11.0 < _live_gib(compiled) < 16.446 + 0.1  # the parent's reading, and a hundredth


# ---------------------------------------------------------------------------
# lfm2-8b-a1b-ep4: the grouped products at a width that is 7 x 256, and the
# gated short convolution's mix
# ---------------------------------------------------------------------------


def test_grouped_expert_products_compile_at_a_width_of_seven_tiles(one_chip, monkeypatch):
    """The dropless layer's three grouped products at the ``lfm2moe-train-8k``
    cell's widths (65,536 static rows of 2,048, 8 experts of 1,792 = 7 x 256),
    forward and backward: under ``ops/moe.GMM_TILING`` the 1,792 dimension
    takes an 896 tile (it divides; a 1,024 tile would pad and mask an eighth),
    and megablox's three kernels accept it."""
    import photon_tpu.ops.flash_attention as fa
    from photon_tpu.ops import moe

    assert moe._tiles(moe.GMM_TILING, 65536, 2048, 1792) == (512, 1024, 896)
    assert moe._tiles(moe.GMM_TILING, 65536, 1792, 2048) == (512, 896, 1024)
    monkeypatch.setattr(fa, "pallas_supported", lambda x: True)
    rows = _abstract((65536, 2048), jnp.bfloat16, one_chip)
    w_in = _abstract((8, 2048, 1792), jnp.bfloat16, one_chip)
    w_out = _abstract((8, 1792, 2048), jnp.bfloat16, one_chip)
    sizes = _abstract((9,), jnp.int32, one_chip)

    def loss(x, w_gate, w_up, w_down, sizes):
        mm = lambda a, b: moe.grouped_matmul(a, b, sizes)  # noqa: E731
        out = mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)
        return out.astype(jnp.float32).sum()

    hlo = _hlo(jax.grad(loss, argnums=(0, 1, 2, 3)), rows, w_in, w_in, w_out, sizes)
    assert hlo.count(KERNEL) >= 8


def test_the_short_convolutions_mix_compiles_to_two_forward_passes(one_chip):
    """``B | C | u`` -> ``C * taps(B * u)`` at the cell's widths (2 rows of
    8,192, 2,048 channels, 3 taps): the forward is TWO passes over the rows
    (``B * u`` written once in float32, 134 MB, which is all it holds beside
    its argument and result; then the three shifted products and the second
    gate in one fusion), and the backward's temporaries stay under four
    float32 copies of the output. (A pass fewer is a kernel's work: PERF.md
    section 7.)"""
    from photon_tpu.ops import ssd

    bcu = _abstract((2, 8192, 6144), jnp.bfloat16, one_chip)
    dy = _abstract((2, 8192, 2048), jnp.bfloat16, one_chip)
    kernel = _abstract((3, 2048), jnp.float32, one_chip)

    def mix(bcu, kernel):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        return (c * ssd.causal_conv1d(b * u, kernel)).astype(jnp.bfloat16)

    forward = jax.jit(mix).lower(bcu, kernel).compile()
    passes = re.findall(r"^\s*(?:ROOT )?%\S*fusion\S* = \w+\[2,8192,2048\]", forward.as_text(), re.M)
    assert len(passes) == 2, passes
    assert forward.memory_analysis().temp_size_in_bytes <= 2 * 8192 * 2048 * 4 * 1.01
    backward = jax.jit(jax.grad(
        lambda bcu, kernel, dy: jnp.vdot(mix(bcu, kernel).astype(jnp.float32),
                                         dy.astype(jnp.float32)), argnums=(0, 1))).lower(
        bcu, kernel, dy).compile()
    assert backward.memory_analysis().temp_size_in_bytes < 4 * 2 * 8192 * 2048 * 4


# ---------------------------------------------------------------------------
# keye-vl-2.0-30b-a3b-ep8: the flash kernel under a (query, key) mask, and the
# exact selection
# ---------------------------------------------------------------------------


def test_masked_flash_attention_compiles_at_the_sparse_cells_widths(one_chip):
    """32 query / 4 key-value heads of 128 under an int8 mask for all heads,
    forward and both backward launches at the tiles of the cell
    (``keyevl2-train-16k``; 4,096 positions here, the same 1,024- and
    512-square tiles): Mosaic takes the int8 tile, the two prefetched tile
    tables and the data-dependent block indices, and the launches keep the
    names the benchmark's ``flash_*_ms_train`` readers find."""
    from photon_tpu.ops.masked_flash_attention import masked_flash_attention, plan_tiles

    s = 4096
    assert plan_tiles(s, s) == plan_tiles(16384, 16384) == (
        (1024, 1024), (1024, 1024), (512, 512))
    q = _abstract((1, s, 32, 128), jnp.bfloat16, one_chip)
    kv = _abstract((1, s, 4, 128), jnp.bfloat16, one_chip)
    mask = _abstract((1, s, s), jnp.int8, one_chip)

    def loss(q, k, v, mask):
        out, lse = masked_flash_attention(q, k, v, mask)
        return out.astype(jnp.float32).sum() + jax.lax.stop_gradient(lse).sum()

    hlo = _hlo(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, mask)
    launches = [ln.strip() for ln in hlo.splitlines() if KERNEL in ln]
    assert len(launches) >= 3
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert any(re.search(rf"\b{kernel}/multihead_attention\b", ln)
                   for ln in launches), kernel


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_the_exact_selection_compiles_without_a_sort_or_a_whole_square(one_chip, monkeypatch,
                                                                       impl):
    """``ops/dsa.select_keys`` at the cell's indexer widths (16 heads of 64,
    one key head, 2,048 keys a query, chunks of 512; 8,192 positions here):
    no sort in the program (the threshold is a search over the float's bits),
    and beside the int8 mask it returns it holds a few chunks' worth, far
    under the 4.3 GB the 16 heads' ``[S, S]`` products would take. On the
    kernel path the search is one launch a band's chunk loop
    (``ops/index_select.py``), under ``dsa/select`` and a scope of its own,
    not the names by which the attention's launches are found; at the cell's
    widest band, ``[512, 16,384]``, the launch asks Mosaic for its row block
    three times over and compiles."""
    import photon_tpu.ops.flash_attention as fa
    from photon_tpu.ops import dsa, index_select
    from photon_tpu.utils.profiling import DSA_SELECT_SCOPE

    monkeypatch.setattr(fa, "pallas_supported", lambda x: True)  # steered, in the test
    s = 8192
    q_idx = _abstract((1, s, 16, 64), jnp.bfloat16, one_chip)
    k_idx = _abstract((1, s, 64), jnp.bfloat16, one_chip)
    w = _abstract((1, s, 16), jnp.float32, one_chip)

    def select(q, k, w):
        with jax.named_scope(DSA_SELECT_SCOPE):
            return dsa.select_keys(q, k, w, topk=2048, chunk=512, impl=impl)

    compiled = jax.jit(select).lower(q_idx, k_idx, w).compile()
    assert not re.search(r"\bsort\(", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0 * 2**30
    launches = [ln.strip() for ln in compiled.as_text().splitlines() if KERNEL in ln]
    if impl == "xla":
        assert not launches
        return
    assert len(launches) == 4  # a launch a band, inside its chunk loop
    for ln in launches:
        name = re.search(r'op_name="([^"]*)"', ln).group(1)
        assert re.search(
            rf"\b{DSA_SELECT_SCOPE}\b.*\b{index_select.INDEX_SELECT_SCOPE}/.*pallas_call", name)
        assert not re.search(r"multihead_attention", name)
    widest = _abstract((512, 16384), jnp.float32, one_chip)
    text = jax.jit(lambda x: index_select.kth_largest(x, 2048)).lower(widest).compile().as_text()
    assert KERNEL in text and index_select.row_block(512, 16384) == index_select.ROW_BLOCK


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_the_index_loss_keeps_the_heads_scores_in_vmem(one_chip, monkeypatch, impl):
    """``ops/dsa.index_loss`` with its gradient at the cell's widths (32 query
    / 4 key-value heads of 128, an indexer of 16 heads of 64, chunks of 512;
    4,096 positions here, the same 1,024-key tiles). On the kernel path
    Mosaic takes a group's eight heads as row slices of one block and an
    output block that stays put over the inner grid dimension; the launch
    carries ``dsa/index_loss`` and a scope of its own, not the names by which
    ``flash_*_ms_train`` and ``sparse_attention_roofline`` find the
    attention's launches; and all the program's temporaries together (149 MB:
    the indexer heads' ``[512, 16, S]`` float32 products are most of it) are
    smaller than a chunk's ``[32, 512, S]`` float32 scores (268 MB here),
    which the ``xla`` path holds (427 MB)."""
    import photon_tpu.ops.flash_attention as fa
    from photon_tpu.ops import dsa
    from photon_tpu.ops.index_pbar import INDEX_PBAR_SCOPE, key_block
    from photon_tpu.utils.profiling import DSA_INDEX_LOSS_SCOPE

    # tracing here sees the CPU as the default backend, where the dispatch
    # steps down to ``jax.numpy``: steer it, in the test
    monkeypatch.setattr(fa, "pallas_supported", lambda x: True)
    s = 4096
    assert key_block(s) == key_block(16384) == 1024
    q_idx = _abstract((1, s, 16, 64), jnp.bfloat16, one_chip)
    k_idx = _abstract((1, s, 64), jnp.bfloat16, one_chip)
    w = _abstract((1, s, 16), jnp.float32, one_chip)
    q = _abstract((1, s, 32, 128), jnp.bfloat16, one_chip)
    k = _abstract((1, s, 4, 128), jnp.bfloat16, one_chip)
    lse = _abstract((1, 32, s), jnp.float32, one_chip)
    mask = _abstract((1, s, s), jnp.int8, one_chip)

    def loss(q_idx, k_idx, w, q, k, lse, mask):
        with jax.named_scope(DSA_INDEX_LOSS_SCOPE):
            return dsa.index_loss(q_idx, k_idx, w, q, k, lse, mask, chunk=512, impl=impl)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q_idx, k_idx, w, q, k, lse, mask).compile()
    launches = [ln.strip() for ln in compiled.as_text().splitlines() if KERNEL in ln]
    scores = 32 * 512 * s * 4
    if impl == "xla":
        assert not launches
        assert compiled.memory_analysis().temp_size_in_bytes > scores
        return
    assert len(launches) == 4  # a launch a band, inside its chunk loop
    for ln in launches:
        name = re.search(r'op_name="([^"]*)"', ln).group(1)
        assert re.search(rf"\b{DSA_INDEX_LOSS_SCOPE}\b.*\b{INDEX_PBAR_SCOPE}/pallas_call", name)
        assert not re.search(r"\bflash_(fwd|dq|dkv)/multihead_attention\b", name)
    assert compiled.memory_analysis().temp_size_in_bytes < scores


# ---------------------------------------------------------------------------
# whole train steps
# ---------------------------------------------------------------------------


def _compile_train_step(cfg, devices, monkeypatch=None):
    """The jitted train step the Trainer would build for ``cfg``, compiled
    against described devices from shapes alone."""
    lowered, state = _lower_train_step(cfg, devices, monkeypatch)
    return lowered.compile(), state


def _lower_train_step(cfg, devices, monkeypatch=None):
    """That step lowered for the described devices, not yet compiled."""
    from photon_tpu.config.schema import effective_model_config
    from photon_tpu.models.mpt import MPTModel, init_params
    from photon_tpu.optim import build_optimizer
    from photon_tpu.parallel.context import use_mesh
    from photon_tpu.parallel.mesh import make_mesh
    from photon_tpu.parallel.sharding import batch_spec, state_shardings
    from photon_tpu.train.train_step import init_train_state, make_train_step

    if monkeypatch is not None:
        # tracing here sees the CPU as the default backend, where the
        # attention dispatcher steps down to XLA; steer it to the kernel the
        # chip would run (in the test, not through an option of the program)
        import photon_tpu.ops.flash_attention as fa

        monkeypatch.setattr(fa, "pallas_supported", lambda x: True)
    cfg.validate()
    mesh = make_mesh(cfg.mesh, devices=devices)
    model_cfg = effective_model_config(cfg.model, cfg.mesh)
    model = MPTModel(model_cfg)
    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)
    state = jax.eval_shape(
        lambda: init_train_state(model, tx, init_params(model_cfg, seed=0))
    )
    dp = cfg.mesh.data * cfg.mesh.fsdp * cfg.mesh.expert
    n_micro = max(
        cfg.train.global_batch_size // (cfg.train.device_microbatch_size * dp), 1
    )
    step = make_train_step(model, tx, n_microbatches=n_micro,
                           loss_chunk_tokens=cfg.train.loss_chunk_tokens)
    shardings = state_shardings(state, mesh)
    batch_sh = NamedSharding(mesh, batch_spec(mesh))
    tokens = jax.ShapeDtypeStruct(
        (cfg.train.global_batch_size, cfg.model.max_seq_len), np.int32,
        sharding=batch_sh,
    )
    jitted = jax.jit(step, in_shardings=(shardings, batch_sh),
                     out_shardings=(shardings, None), donate_argnums=0)
    with use_mesh(mesh):
        return jitted.lower(state, tokens), state


def _live_gib(compiled) -> float:
    mem = compiled.memory_analysis()
    # donated state aliases into the output (alias_size covers it), so live
    # bytes = args + temps + any non-aliased output
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2**30


def _smoke_cfg():
    """mpt-125m under ``chip_smoke.TRAIN_SETS`` (full width, small job)."""
    import pathlib

    return chip_smoke.load_config("mpt-125m", chip_smoke.TRAIN_SETS,
                                  pathlib.Path("unused"))


@pytest.mark.parametrize("mesh_kw", [{}, {"fsdp": 2, "tensor": 2}],
                         ids=["one-chip", "fsdp2xtensor2"])
def test_chip_smoke_train_step_compiles(topo_devices, monkeypatch, mesh_kw):
    """The mpt-125m train step ``chip_smoke.py`` runs: on one chip, and under
    the fsdp=2 x tensor=2 mesh of its ``--chips 4`` phase (the flash kernel
    under ``shard_map``)."""
    cfg = _smoke_cfg()
    cfg.mesh = dataclasses.replace(cfg.mesh, **mesh_kw)
    n_dev = cfg.mesh.size
    compiled, _ = _compile_train_step(cfg, topo_devices()[:n_dev], monkeypatch)
    assert compiled.as_text().count(KERNEL) >= 3
    assert _live_gib(compiled) < 14.0


def test_flash_attention_compiles_at_a_v_width_of_its_own(one_chip):
    """32 heads at 4,096 tokens with q / k 192 wide and v 128 (the
    ``xing4-train-4k`` cell): v, o, dO and dv blocks of 128 lanes beside q / k
    blocks of 256, forward and backward at the tiles ``pick_tiles`` derives."""
    from photon_tpu.ops.flash_attention import flash_attention

    qk = _abstract((1, 4096, 32, 192), jnp.bfloat16, one_chip)
    v = _abstract((1, 4096, 32, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=0.1447).astype(jnp.float32).sum()

    text = _hlo(jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v)
    assert text.count(KERNEL) >= 3
    assert "bf16[32,4096,128]" in text and "bf16[32,4096,256]" in text  # v is not padded to 256


def test_the_hyper_connected_cells_step_compiles_and_fits_the_chip(topo_devices, monkeypatch):
    """``xing4.0-29b-a4b-ep8`` at its cell's size (1 row x 4,096 tokens, one
    microbatch, ``remat``, 759 M parameters): the whole train step for a
    described v5e, the flash kernel's four launches a layer in it, and the
    compiler's memory report (the donated state + its temporaries) under the
    chip's 16 GiB (~95 s)."""
    from photon_tpu.config import load_preset

    cfg = load_preset("xing4.0-29b-a4b-ep8")
    compiled, state = _compile_train_step(cfg, topo_devices()[:1], monkeypatch)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(state.params)) == 759_346_446
    assert compiled.as_text().count(KERNEL) >= 4
    assert 12.0 < _live_gib(compiled) < 16.0


@pytest.mark.slow  # real-TPU-compiler compile of a 32-device program, ~2 min
def test_mpt_7b_train_step_compiles_on_32_chips(topo_devices):
    """7B needs 32 chips; fsdp8 x tensor4 fits where fsdp16 x tensor2
    (36 GiB) won't (the 8-device cases live in tests/test_1b_compile.py)."""
    from photon_tpu.config import load_preset
    from photon_tpu.config.schema import MeshConfig

    cfg = load_preset("mpt-7b")
    cfg.mesh = MeshConfig(fsdp=8, tensor=4)
    cfg.model.attn_impl = "xla"
    cfg.train.device_microbatch_size = 2
    compiled, state = _compile_train_step(cfg, topo_devices("4x8"))
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(state.params))
    assert 6.2e9 < n_params < 7.2e9, f"{n_params:,} params is not the mpt-7b recipe"
    assert _live_gib(compiled) < 14.0


def test_autotune_hbm_estimate_brackets_tpu_memory_analysis(topo_devices):
    """The layout tuner's HBM estimate and the TPU compiler's memory analysis
    must agree within a loose factor for the 1B recipe at a layout the tuner
    marks as fitting — the estimate is a ranking device, not an allocator, but
    it must not be fantasy."""
    from photon_tpu.config import load_preset
    from photon_tpu.config.schema import MeshConfig
    from photon_tpu.parallel.autotune import HardwareModel, estimate_layout

    cfg = load_preset("mpt-1b")
    micro = 2
    best = estimate_layout(cfg.model, MeshConfig(fsdp=4),
                           cfg.train.global_batch_size, microbatch=micro)
    assert best.fits
    cfg.mesh = dataclasses.replace(best.mesh)
    cfg.model.attn_impl = "xla"
    cfg.train.device_microbatch_size = micro
    compiled, _ = _compile_train_step(cfg, topo_devices())
    live = _live_gib(compiled) * 2**30
    est = best.hbm_bytes_per_device
    assert est / 4 < live < est * 4, (
        f"estimate {est / 2**30:.2f} GiB vs AOT {live / 2**30:.2f} GiB"
    )
    # and both respect the chip the tuner said it fits
    assert live < HardwareModel().hbm_bytes


# ---------------------------------------------------------------------------
# which layout a preset's train step hands the flash launches (PR 45)
# ---------------------------------------------------------------------------

# each preset cut to a toy in depth, sequence, vocabulary and experts, its
# HEAD widths (and its grouping of them) the published ones
LAYOUT_PRESETS = {
    "mpt-125m": (dict(n_layers=2, n_heads=4, d_model=256, max_seq_len=256, vocab_size=256),
                 "head_pairs"),
    "glm-4.7-flash-ep8": (dict(
        n_layers=2, n_heads=2, d_model=128, max_seq_len=256, vocab_size=256, q_lora_rank=32,
        kv_lora_rank=128, dense_mlp_hidden_size=128, mlp_hidden_size=128, moe_num_experts=8,
        moe_top_k=2, moe_experts_held=4), "in_place"),
    "xing4.0-29b-a4b-ep8": (dict(
        n_layers=2, n_heads=2, d_model=128, max_seq_len=256, vocab_size=256, q_lora_rank=32,
        kv_lora_rank=128, rope_scaling_original_max_position=64, dense_mlp_hidden_size=128,
        mlp_hidden_size=128, moe_num_experts=8, moe_top_k=2, moe_experts_held=4),
        "head_major"),
    "lfm2-8b-a1b-ep4": (dict(
        d_model=256, n_heads=4, n_kv_heads=2, max_seq_len=256, vocab_size=256,
        dense_mlp_hidden_size=128, mlp_hidden_size=128, moe_num_experts=8, moe_top_k=2,
        moe_experts_held=4), "head_major"),
    "granite-4.0-h-micro-stage1": (dict(
        d_model=256, n_layers=4, layer_types="mamba,mamba,attention,mamba", n_heads=4,
        n_kv_heads=2, max_seq_len=256, vocab_size=256, mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=8, mamba_chunk_size=64, mlp_hidden_size=128), "head_major"),
}
TO_BH = re.compile(r"stablehlo\.transpose.*dims = \[0, 2, 1, 3\]")


@pytest.mark.parametrize("preset", list(LAYOUT_PRESETS))
def test_a_presets_train_step_takes_its_layout(topo_devices, monkeypatch, preset):
    """The rule by head widths, seen in a whole lowered train step: ``xing4``
    (q / k 192, v 128), ``lfm2`` and ``granite`` (grouped heads of 64) tell
    ``head_major`` on ``trainer/steps`` and still lower ``to_bh``'s
    transposes to head-major around their launches; ``mpt-125m`` (pairs of
    64-wide heads) and ``glm`` (256 / 256) hand the launches ``[B, S, H·D]``
    and lower no such transpose."""
    from photon_tpu.config import load_preset
    from photon_tpu.models.step import step_attrs

    overrides, layout = LAYOUT_PRESETS[preset]
    cfg = load_preset(preset)
    for key, value in overrides.items():
        setattr(cfg.model, key, value)
    cfg.train.global_batch_size = cfg.train.device_microbatch_size = 2
    lowered, _ = _lower_train_step(cfg, topo_devices()[:1], monkeypatch)
    text = lowered.as_text()
    assert text.count(KERNEL) >= 3
    assert step_attrs(cfg.model, batch_rows=2).steps["flash_layout"] == layout
    assert bool(TO_BH.search(text)) == (layout == "head_major")
    heads, d = cfg.model.n_heads, cfg.model.d_head
    copies = f"tensor<{2 * heads}x256x{-(-d // 128) * 128}x"  # to_bh's [B·H, S, d_pad]
    assert (copies in text) == (layout == "head_major")


def test_the_banded_flash_launches_compile_at_the_sliding_layers_shapes(one_chip):
    """64 query / 8 key-value heads of 128 at 16,384 tokens under a window of
    512 (a sliding layer of ``lagunaxs2-train-16k``): forward, dq and dk/dv at
    the tiles ``pick_tiles`` derives for the band, read in place, under their
    own names; the band's grid and not the square's."""
    from photon_tpu.ops import flash_attention as fa

    q = _abstract((1, 16384, 64, 128), jnp.bfloat16, one_chip)
    kv = _abstract((1, 16384, 8, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=512).astype(jnp.float32).sum()

    text = _hlo(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count(KERNEL) == 3
    for name in ("flash_swa_fwd", "flash_swa_dq", "flash_swa_dkv"):
        assert f"{name}/multihead_attention" in text, name
    assert "flash_fwd/" not in text and "flash_dq/" not in text
    assert "bf16[1,16384,8192]" in text and "bf16[1,16384,1024]" in text  # in place


def test_the_one_branch_cells_step_compiles_and_fits_the_chip(topo_devices, monkeypatch):
    """``nemotron-3-nano-30b-a3b-ep16`` at its cell's size (1 row x 8,192
    tokens, one microbatch, ``remat``, 667 M parameters): the whole train step
    for a described v5e. Each of the four Mamba-2 layers (a stack of its own)
    holds the grouped scan's forward launch ONCE and its backward launch once,
    all under ``mamba/scan``: a stack of one layer has no loop, and XLA merges
    the block's recomputation under ``remat`` with its forward (granite's
    stacks of four and five hold the forward launch twice); each of the four
    expert layers its two grouped products and their transposes with the
    1,856-wide dimension walked in tiles of 640 (as ONE tile it does not
    compile: 16.61 MB of a 16 MB scoped VMEM in the down product's transpose:
    PERF.md section 6, PR 52); the attention layer the causal flash launches.
    The donated state + its temporaries by ``memory_analysis()`` count
    temporaries that are never live together; the compiler's own report
    (``XLA_FLAGS=--xla_dump_to``, ``*memory-usage-report.txt``) totals 15.55
    GiB and the program fits the chip's 15.75 (~60 s)."""
    from photon_tpu.config import load_preset

    cfg = load_preset("nemotron-3-nano-30b-a3b-ep16")
    compiled, state = _compile_train_step(cfg, topo_devices()[:1], monkeypatch)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(state.params)) == 666_963_456
    text = compiled.as_text()
    launches = [ln for ln in text.splitlines() if KERNEL in ln and "ssd_scan_" in ln]
    assert len(launches) == 8 and all("mamba/scan/" in ln for ln in launches)
    assert sum("ssd_scan_bwd" in ln for ln in launches) == 4
    # a launch's block is one group's 8 heads: B and C arrive as [1, 8192, 8 x 128]
    assert all("bf16[1,8192,1024]" in ln for ln in launches)
    experts = [ln for ln in text.splitlines() if KERNEL in ln and "moe/experts/" in ln]
    assert len(experts) >= 4 * 6  # up, down, and two transposes each, a layer
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert f"{name}/multihead_attention" in text, name
    print(f"live GiB {_live_gib(compiled):.3f}")
    assert 10.0 < _live_gib(compiled) < 17.5


def test_the_windowed_cells_step_compiles_and_fits_the_chip(topo_devices, monkeypatch):
    """``laguna-xs.2-ep8`` at its cell's size (1 row x 16,384 tokens, one
    microbatch, ``remat``, 692 M parameters): the whole train step for a
    described v5e, the full layers' causal launches and the sliding layers'
    banded ones in it, and the donated state + its temporaries by
    ``memory_analysis()``, which counts temporaries that are never live
    together: 16.21 GiB where the compiler's own report
    (``XLA_FLAGS=--xla_dump_to``, ``*memory-usage-report.txt``) totals 14.77
    and the program fits the chip's 15.75 (~70 s). Those are the step's
    before the headwise gate had a pull-back of its own (``ops/head_gate.py``,
    PR 50); with it no fusion writes a float32 array of tokens x heads x 128
    (three did: the gate's multiply forward, recomputed and back), the gate's
    two launches are in the text, and the step asks for no more."""
    from photon_tpu.config import load_preset

    cfg = load_preset("laguna-xs.2-ep8")
    compiled, state = _compile_train_step(cfg, topo_devices()[:1], monkeypatch)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(state.params)) == 691_624_960
    text = compiled.as_text()
    assert text.count(KERNEL) >= 8  # four launches a layer kind, the experts' besides
    for name in ("flash_fwd", "flash_dq", "flash_dkv", "flash_swa_fwd", "flash_swa_dq",
                 "flash_swa_dkv"):
        assert f"{name}/multihead_attention" in text, name
    wide = re.compile(r"f32\[(1,)?16384,(64,128|48,128|8192|6144)\]")
    written = re.findall(r"^\s*(?:ROOT )?%\S+ = (.*?) fusion\(", text, re.M)
    assert len(written) > 500 and not [shape for shape in written if wide.search(shape)]
    for name in ("head_gate_fwd", "head_gate_bwd"):
        assert f"attn/gate/{name}/pallas_call" in text, name
    print(f"live GiB {_live_gib(compiled):.3f}")
    assert 11.0 < _live_gib(compiled) <= 16.21  # the parent's reading
