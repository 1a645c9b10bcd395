"""``pick_tiles``: the flash kernel's tile is a function of the shapes.

For every shape the program hands the kernel (training at 2,048, the serve
prefill buckets 16 * 2^k, a short query block against a long key sequence,
grouped-query attention) the rule returns tiles that divide both sequences,
sit inside the rule's own VMEM estimate, grow with the budget and never
shrink, and report live / grid tile counts equal to a brute-force count of
the kernels' ``live`` predicate. CPU only: nothing here runs a kernel.
"""

import jax.numpy as jnp
import pytest

from photon_tpu.ops import flash_attention as fa
from photon_tpu.ops.flash_attention import (
    VMEM_BUDGET,
    _kv_block,
    _q_block,
    executed_pairs,
    launch_vmem_bytes,
    live_tiles,
    pick_tiles,
    strip_rows,
    visible_pairs,
)

LAUNCHES = ("fwd", "dq", "dkv")

# (s_q, s_k, d_pad, itemsize, n_kv_group)
SHAPES = [
    pytest.param(2048, 2048, 128, 2, 1, id="125m-d64-padded"),
    pytest.param(2048, 2048, 128, 4, 1, id="1b-d128-fp32"),
    pytest.param(2048, 2048, 256, 2, 1, id="d256"),
    pytest.param(512, 512, 128, 2, 1, id="s512"),
    pytest.param(48, 48, 128, 2, 1, id="s48"),
    *[pytest.param(16 * 2**k, 16 * 2**k, 128, 2, 1, id=f"bucket{16 * 2**k}")
      for k in range(8)],
    pytest.param(1, 2048, 128, 2, 1, id="1x2048"),
    pytest.param(256, 2048, 128, 2, 1, id="256x2048"),
    pytest.param(2048, 2048, 128, 2, 4, id="gqa4"),
    pytest.param(1536, 1536, 128, 2, 1, id="s1536"),
    pytest.param(8192, 8192, 128, 2, 1, id="s8192"),
]


def _brute_live(s_q, s_k, bq, bk, offset):
    """The kernels' predicate, tile by tile."""
    return sum(j * bk <= i * bq + (bq - 1) + offset
               for i in range(s_q // bq) for j in range(s_k // bk))


@pytest.mark.parametrize("s_q,s_k,d_pad,itemsize,group", SHAPES)
def test_pick_tiles_divides_fits_and_counts(s_q, s_k, d_pad, itemsize, group):
    plan = pick_tiles(s_q, s_k, d_pad, itemsize, group)
    assert plan._fields == LAUNCHES
    for launch, t in zip(LAUNCHES, plan):
        assert s_q % t.block_q == 0 and s_k % t.block_k == 0, (launch, t)
        # a block is whole lane widths, or the whole axis
        assert t.block_q % fa.LANE == 0 or t.block_q == s_q
        assert t.block_k % fa.LANE == 0 or t.block_k == s_k
        assert t.vmem_bytes == launch_vmem_bytes(launch, t.block_q, t.block_k,
                                                 d_pad, itemsize)
        assert t.vmem_bytes <= VMEM_BUDGET, (launch, t)
        mult = group if launch == "dkv" else 1
        assert t.grid_tiles == mult * (s_q // t.block_q) * (s_k // t.block_k)
        assert t.live_tiles == mult * _brute_live(s_q, s_k, t.block_q, t.block_k,
                                                  s_k - s_q)
        assert 0 < t.live_tiles <= t.grid_tiles
    assert plan.blocks == tuple((t.block_q, t.block_k) for t in plan)
    attrs = plan.attrs()
    assert set(attrs) == {"flash_layout", "flash_tiles", "flash_live_tiles",
                          "flash_executed_share"}
    assert attrs["flash_layout"] == fa.HEAD_MAJOR
    assert plan.attrs(fa.IN_PLACE) == {**attrs, "flash_layout": "in_place"}
    assert f"fwd={plan.fwd.block_q}x{plan.fwd.block_k}" in attrs["flash_tiles"]
    assert f"fwd={plan.fwd.executed_share:.3f}" in attrs["flash_executed_share"]


def _brute_visible(s_q, s_k, offset):
    return sum(min(max(r + offset + 1, 0), s_k) for r in range(s_q))


def _brute_executed(s_q, s_k, bq, bk, offset, sub):
    """What the bodies multiply, counted block by block: with strips, every
    ``sub x sub`` block of a live tile but those wholly above the diagonal;
    without, every live tile whole."""
    if not sub:
        return _brute_live(s_q, s_k, bq, bk, offset) * bq * bk
    return sub * sub * sum(
        a * sub + (sub - 1) + offset >= b * sub  # the block's last query sees its first key
        for a in range(s_q // sub) for b in range(s_k // sub))


@pytest.mark.parametrize("s_q,s_k,d_pad,itemsize,group", SHAPES)
def test_executed_share_is_the_bodies_count(s_q, s_k, d_pad, itemsize, group):
    """``flash_executed_share``'s closed form against a count of the blocks the
    bodies multiply, at the tiles the rule picks and at pinned ones, with the
    shipped strip heights and with others."""
    offset = s_k - s_q
    assert visible_pairs(s_q, s_k) == _brute_visible(s_q, s_k, offset)
    assert visible_pairs(s_q, s_k, causal=False) == s_q * s_k
    plan = pick_tiles(s_q, s_k, d_pad, itemsize, group)
    tiles = {(t.block_q, t.block_k) for t in plan}
    tiles |= {(b, b) for b in (128, 256, 512) if s_q % b == 0 and s_k % b == 0}
    for launch, t in zip(LAUNCHES, plan):
        for bq, bk in sorted(tiles):
            sub = strip_rows(launch, bq, bk, causal=True, offset=offset)
            assert sub == 0 or (bq == bk and bq % sub == 0)
            assert executed_pairs(launch, s_q, s_k, bq, bk) == _brute_executed(
                s_q, s_k, bq, bk, offset, sub), (launch, bq, bk, sub)
        assert executed_pairs(launch, s_q, s_k, t.block_q, t.block_k, causal=False) == s_q * s_k
        assert t.executed_share == pytest.approx(
            executed_pairs(launch, s_q, s_k, t.block_q, t.block_k) / visible_pairs(s_q, s_k))
        assert t.executed_share >= 1.0


@pytest.mark.parametrize("offset", [-256, 0, 128, 192, 256, 1024])
def test_executed_share_at_an_offset(offset, monkeypatch):
    """Ring attention's chunks: an offset of whole tiles keeps the strips (a
    chunk wholly in the past has no tile on the diagonal), another one does
    not, and the closed forms follow."""
    monkeypatch.setattr(fa, "STRIP_ROWS", dict.fromkeys(LAUNCHES, 128))
    for tile in (128, 256):
        sub = strip_rows("fwd", tile, tile, causal=True, offset=offset)
        assert sub == (128 if offset % tile == 0 else 0)
        assert visible_pairs(512, 512, offset=offset) == _brute_visible(512, 512, offset)
        assert executed_pairs("fwd", 512, 512, tile, tile, offset=offset) == _brute_executed(
            512, 512, tile, tile, offset, sub)


@pytest.mark.parametrize("cell,shape", [
    ("mpt125m-train", (2048, 2048, 128, 2, 1)),        # and -gbs256, mpt125m-fedround
    ("glm47flash-train", (4096, 4096, 256, 2, 1)),
    ("granite4hmicro-train", (8192, 8192, 128, 2, 4)),
    ("mpt-1b", (2048, 2048, 128, 2, 1)),
])
def test_the_cells_forward_executes_little_dead_work(cell, shape):
    """Every dense-kernel cell's shapes engage the strips in all three
    launches: no launch multiplies more than 1.25 pairs a visible pair."""
    plan = pick_tiles(*shape)
    for launch, t in zip(LAUNCHES, plan):
        assert strip_rows(launch, t.block_q, t.block_k, causal=True, offset=0), (cell, launch)
        assert 1.0 <= t.executed_share <= 1.25, (cell, launch, t)


def test_executed_share_before_the_strips(monkeypatch):
    """What ISSUE 39 read off the parent: whole tiles at mpt-125m's plan."""
    monkeypatch.setattr(fa, "STRIP_ROWS", dict.fromkeys(LAUNCHES, 0))
    half = 2048 * 2049 // 2
    shares = [executed_pairs(launch, 2048, 2048, b, b) / half
              for launch, b in zip(LAUNCHES, (2048, 1024, 512))]
    assert [round(s, 2) for s in shares] == [2.0, 1.5, 1.25]


@pytest.mark.parametrize("s_q,s_k,d_pad,itemsize,group", SHAPES)
def test_pick_tiles_monotone_in_budget(s_q, s_k, d_pad, itemsize, group):
    """More VMEM never buys a smaller tile, and an answer always comes, also
    from a budget nothing fits."""
    budgets = [2**18, 2**20, 2**21, 2**22, 2**23, 2**24, 2**25, 2**26, 2**27]
    area = {launch: 0 for launch in LAUNCHES}
    for budget in budgets:
        plan = pick_tiles(s_q, s_k, d_pad, itemsize, group, vmem_budget=budget)
        for launch, t in zip(LAUNCHES, plan):
            assert s_q % t.block_q == 0 and s_k % t.block_k == 0
            fits = t.vmem_bytes <= budget
            if fits:
                assert t.block_q * t.block_k >= area[launch], (launch, budget)
                area[launch] = t.block_q * t.block_k


def test_pick_tiles_leaves_the_old_default():
    """The training shape of both presets: no launch stays at 256 x 256."""
    for t in pick_tiles(2048, 2048, 128, 2):
        assert t.block_q * t.block_k > 256 * 256
        assert t.live_tiles / t.grid_tiles > 36 / 64


@pytest.mark.parametrize("pin_q,pin_k", [(256, None), (None, 512), (128, 128), (4096, 4096)])
def test_explicit_tile_wins(pin_q, pin_k):
    plan = pick_tiles(2048, 2048, 128, 2, block_q=pin_q, block_k=pin_k)
    free = pick_tiles(2048, 2048, 128, 2)
    for t, f in zip(plan, free):
        assert t.block_q == (min(pin_q, 2048) if pin_q else f.block_q)
        assert t.block_k == (min(pin_k, 2048) if pin_k else f.block_k)


def test_explicit_tile_must_divide():
    with pytest.raises(ValueError, match="must divide"):
        pick_tiles(2048, 2048, 128, 2, block_q=768)
    with pytest.raises(ValueError, match="must divide"):
        pick_tiles(512, 2048, 128, 2, block_k=384)


@pytest.mark.parametrize("launch", LAUNCHES)
def test_vmem_estimate_grows_with_the_tile(launch):
    sizes = [128, 256, 512, 1024, 2048]
    for d, itemsize in ((128, 2), (128, 4), (256, 2)):
        square = [launch_vmem_bytes(launch, b, b, d, itemsize) for b in sizes]
        assert square == sorted(square) and len(set(square)) == len(square)
        assert launch_vmem_bytes(launch, 512, 1024, d, itemsize) > square[2]
        assert launch_vmem_bytes(launch, 1024, 512, d, itemsize) > square[2]
    with pytest.raises(ValueError):
        launch_vmem_bytes("dv", 128, 128, 128, 2)


@pytest.mark.parametrize("s_q,s_k,bq,bk", [
    (2048, 2048, 256, 256), (2048, 2048, 512, 512), (2048, 2048, 1024, 1024),
    (2048, 2048, 1024, 512), (2048, 2048, 512, 1024), (512, 512, 128, 128),
    (256, 2048, 128, 256), (2048, 512, 256, 128),
])
def test_clamped_maps_fetch_live_blocks_only(s_q, s_k, bq, bk):
    """Every live step maps to its own block; every dead step repeats the
    block of the step before it in the sweep (so no copy is issued); and the
    old defaults' counts are what the issue quotes."""
    offset = s_k - s_q
    n_q, n_k = s_q // bq, s_k // bk
    kw = dict(causal=True, block_q=bq, block_k=bk, offset=offset)
    live = lambda i, j: j * bk <= i * bq + (bq - 1) + offset  # noqa: E731
    for i in range(n_q):
        row = [int(_kv_block(i, j, n_k=n_k, **kw)) for j in range(n_k)]
        for j in range(n_k):
            if live(i, j):
                assert row[j] == j
            elif j:
                assert row[j] == row[j - 1]
            assert 0 <= row[j] < n_k
    for j in range(n_k):
        col = [int(_q_block(i, j, n_q=n_q, **kw)) for i in range(n_q)]
        for i in range(n_q):
            if live(i, j):
                assert col[i] == i
            elif i + 1 < n_q:
                assert col[i] == col[i + 1]
            assert 0 <= col[i] < n_q
    assert live_tiles(s_q, s_k, bq, bk) == (
        sum(live(i, j) for i in range(n_q) for j in range(n_k)), n_q * n_k)


def test_live_tiles_of_the_old_default():
    assert live_tiles(2048, 2048, 256, 256) == (36, 64)
    assert live_tiles(2048, 2048, 512, 512) == (10, 16)
    assert live_tiles(2048, 2048, 1024, 1024) == (3, 4)
    assert live_tiles(2048, 2048, 256, 256, causal=False) == (64, 64)
    # a chunk wholly in the past (ring attention's off-diagonal): all live
    assert live_tiles(512, 512, 128, 128, offset=512) == (16, 16)


def test_maps_are_the_identity_without_a_mask():
    for i in range(4):
        for j in range(4):
            assert _kv_block(i, j, causal=False, block_q=128, block_k=128,
                             offset=0, n_k=4) == j
            assert _q_block(i, j, causal=False, block_q=128, block_k=128,
                            offset=0, n_q=4) == i
    # and traced indices come back untouched, not as new values
    i, j = jnp.int32(1), jnp.int32(3)
    assert _kv_block(i, j, causal=False, block_q=128, block_k=128, offset=0, n_k=4) is j
    assert _q_block(i, j, causal=False, block_q=128, block_k=128, offset=0, n_q=4) is i


# ---------------------------------------------------------------------------
# the program says which tiles its compiled step took
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl,interpret,told,d_model,layout", [
    # the kernel is in the step (the interpreter runs it): d_head 32, padded copies
    ("pallas", True, True, 64, "head_major"),
    ("pallas", True, True, 128, "head_pairs"),  # two heads of 64: one column block
    ("pallas", True, True, 256, "in_place"),  # d_head 128: a head is a column block
    # the CPU backend steps down to XLA: nothing to tell
    ("pallas", False, False, 64, None),
    ("xla", False, False, 64, None),
])
def test_trainer_steps_span_carries_the_tile_plan(impl, interpret, told, d_model, layout,
                                                  monkeypatch):
    import numpy as np

    from photon_tpu import telemetry
    from photon_tpu.config.schema import (
        Config, MeshConfig, ModelConfig, OptimizerConfig, SchedulerConfig, TrainConfig,
    )
    from photon_tpu.train import trainer as trainer_mod
    from photon_tpu.utils.profiling import TRAINER_STEPS_SPAN

    cfg = Config(
        model=ModelConfig(d_model=d_model, n_layers=1, n_heads=2, max_seq_len=128,
                          vocab_size=64, attn_impl=impl, attn_interpret=interpret,
                          compute_dtype="float32"),
        mesh=MeshConfig(),
        optimizer=OptimizerConfig(name="adopt", lr=1e-3),
        scheduler=SchedulerConfig(t_warmup=2, t_max=50),
        train=TrainConfig(global_batch_size=2, device_microbatch_size=2),
    )
    seen = {}
    real_span = telemetry.span

    def spy(name, *a, **attrs):
        seen.setdefault(name, attrs)
        return real_span(name, *a, **attrs)

    monkeypatch.setattr(trainer_mod.telemetry, "span", spy)
    t = trainer_mod.Trainer(cfg, init_seed=0)
    t.fit([np.zeros((2, 128), np.int64)], duration_steps=1)
    attrs = seen[TRAINER_STEPS_SPAN]
    assert attrs["steps"] == 1
    if told:
        plan = pick_tiles(128, 128, fa.LANE, 4)
        assert plan.blocks == ((128, 128),) * 3
        assert {k: attrs[k] for k in plan.attrs()} == plan.attrs(layout)
        assert attrs["flash_layout"] == layout
        assert attrs["flash_live_tiles"] == "fwd=1/1 dq=1/1 dkv=1/1"
    else:
        assert set(attrs) == {"steps"}


# ---------------------------------------------------------------------------
# a window: the band's tiles, pairs and pick
# ---------------------------------------------------------------------------


def _brute_band(s_q, s_k, bq, bk, offset, window, sub):
    """``(live tiles, executed pairs, visible pairs)`` of a windowed launch,
    tile by tile and pair by pair: a tile is live iff it holds a visible pair;
    it runs whole, but for the tile on the diagonal that the window's edge
    does not cut, which runs as strips where ``sub`` says so."""
    import numpy as np

    q = np.arange(s_q)[:, None] + offset
    k = np.arange(s_k)[None, :]
    seen = (k <= q) & (k > q - window)
    live = executed = 0
    for i in range(s_q // bq):
        for j in range(s_k // bk):
            tile = seen[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            if not tile.any():
                continue
            live += 1
            on_c = j * bk + bk - 1 > i * bq + offset
            on_w = i * bq + offset + bq - 1 - j * bk >= window
            n = bq // sub if sub else 0
            edge = sub and window % bq == 0  # the lower-edge tile's mirrored strips
            stripped = sub and ((on_c and not on_w) or (on_w and not on_c and edge))
            executed += sub * sub * n * (n + 1) // 2 if stripped else bq * bk
    return live, executed, int(seen.sum())


@pytest.mark.parametrize("s, window, bq, bk", [
    (2048, 512, 512, 512), (2048, 512, 256, 256), (2048, 512, 128, 128),
    (2048, 512, 1024, 1024), (2048, 512, 256, 512), (2048, 512, 512, 256),
    (2048, 200, 128, 128), (1536, 512, 512, 512), (2048, 64, 128, 128),
    (2048, 2047, 512, 512), (640, 256, 128, 128),
])
def test_a_windows_counts_are_the_bands(s, window, bq, bk):
    """``live_tiles``, ``executed_pairs`` and ``visible_pairs`` with a window
    against a brute count, for each launch; the grid paid is the band's steps
    and never the square's."""
    for launch in LAUNCHES:
        sub = strip_rows(launch, bq, bk, causal=True, offset=0, window=window)
        live, executed, visible = _brute_band(s, s, bq, bk, 0, window, sub)
        got_live, grid = live_tiles(s, s, bq, bk, window=window, launch=launch)
        assert got_live == live and live <= grid
        assert executed_pairs(launch, s, s, bq, bk, window=window) == executed
        assert visible_pairs(s, s, window=window) == visible
        n_q, n_k = s // bq, s // bk
        if launch == "dkv":
            assert grid <= n_k * min(n_q, -(-(window - 1 + bk) // bq) + 1)
        else:
            assert grid <= n_q * min(n_k, -(-(window - 1 + bq) // bk) + 1)
    assert visible_pairs(s, s, window=window) == sum(min(t + 1, window) for t in range(s))


def test_the_bands_index_maps_fetch_live_blocks_only():
    """Step ``j`` of q tile ``i`` holds the band's first tile plus ``j``, held
    at the diagonal's (a repeated index: no copy); dk/dv likewise over q."""
    band = fa._Band(256, 256, 0, 512, 8, 8)
    assert [band.k_span(i) for i in range(4)] == [(0, 0), (0, 1), (0, 2), (1, 3)]
    assert [band.q_span(j) for j in (0, 5, 7)] == [(0, 2), (5, 7), (7, 7)]
    assert (band.steps("q"), band.steps("k")) == (3, 3)
    assert [int(band.kv_block(3, j)) for j in range(3)] == [1, 2, 3]
    assert [int(band.kv_block(0, j)) for j in range(3)] == [0, 0, 0]
    assert [int(band.q_block(7, j)) for j in range(3)] == [7, 7, 7]
    # the kinds of tile a launch meets are its kernel's bodies
    assert band.cases("q") == {(True, False), (False, False), (False, True)}
    assert fa._Band(512, 512, 0, 512, 4, 4).cases("q") == {(True, False), (False, True)}
    assert fa._Band(1024, 1024, 0, 512, 2, 2).cases("k") == {(True, True), (False, True)}


def test_pick_tiles_weighs_a_bands_executed_pairs_against_its_steps(monkeypatch):
    """At the cell's sliding layers (64 / 8 heads of 128, window 512, 16,384
    positions) the causal square's 2,048 tile is four windows wide and
    multiplies 4.5 pairs a visible one: the rule takes the tile with the least
    executed pairs + steps x ``BAND_STEP_PAIRS``; with free steps the smallest
    tile wins, with dear ones a larger one, and without a window nothing moves."""
    args = (16384, 16384, 128, 2, 8)
    causal = pick_tiles(*args, layout=fa.IN_PLACE)
    assert causal.blocks == ((2048, 2048),) * 3
    assert executed_pairs("fwd", 16384, 16384, 2048, 2048, window=512) / visible_pairs(
        16384, 16384, window=512) > 4.0
    plan = pick_tiles(*args, layout=fa.IN_PLACE, window=512)
    for launch, t in zip(LAUNCHES, plan):
        assert t.block_q == t.block_k and 128 <= t.block_q <= 1024, (launch, t)
        assert 1.0 <= t.executed_share < 2.0
        assert t.live_tiles <= t.grid_tiles
        assert t.vmem_bytes == launch_vmem_bytes(
            launch, t.block_q, t.block_k, 128, 2, None, fa.IN_PLACE, 512) <= VMEM_BUDGET
    assert plan.fwd.grid_tiles < 16384 // plan.fwd.block_q * 4  # not the square's
    monkeypatch.setattr(fa, "BAND_STEP_PAIRS", dict.fromkeys(LAUNCHES, 0))
    free = pick_tiles(*args, layout=fa.IN_PLACE, window=512)
    for launch, t in zip(LAUNCHES, free):  # the least executed pairs; of equals the larger
        pairs = {b: executed_pairs(launch, 16384, 16384, b, b, window=512)
                 for b in (128, 256, 512, 1024, 2048)}
        assert pairs[t.block_q] == min(pairs.values()), (launch, t, pairs)
        assert t.block_q == max(b for b, n in pairs.items() if n == min(pairs.values()))
    monkeypatch.setattr(fa, "BAND_STEP_PAIRS", dict.fromkeys(LAUNCHES, 10**7))
    assert pick_tiles(*args, layout=fa.IN_PLACE, window=512).fwd.block_q >= 1024
    assert pick_tiles(*args, layout=fa.IN_PLACE).blocks == causal.blocks
    # a pinned tile is taken as given, and its counts are the band's
    pinned = pick_tiles(*args, layout=fa.IN_PLACE, window=512, block_q=256, block_k=256)
    assert pinned.blocks == ((256, 256),) * 3 and pinned.fwd.executed_share == pytest.approx(
        executed_pairs("fwd", 16384, 16384, 256, 256, window=512)
        / visible_pairs(16384, 16384, window=512))
    told = plan.attrs(fa.IN_PLACE, banded=True)
    assert set(told) == {"swa_tiles", "swa_live_tiles", "swa_executed_share"}
