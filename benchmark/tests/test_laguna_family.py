"""The ``laguna`` family's benchmark files: its cost functions against numbers
worked by hand, its configuration file against the published one, its plain
reference's exports, band and rotation against second formulations, the new
readers against a hand-written trace with the new kernel names and scope, and
a toy cell of the family through the ``train_steps`` driver."""

from __future__ import annotations

import json
import math
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.costs import flash_attention as full_cost
from benchmark.costs import flash_attention_window as band_cost
from benchmark.costs import laguna_swa_moe_train as laguna_cost
from benchmark.reference import laguna_swa_moe as ref
from benchmark.tests import toy
from benchmark.tests.test_host_spans import reader, write_trace

LAGUNA = json.loads((toy.ROOT / "benchmark/configs/laguna-xs.2-ep8.json").read_text())
NEW_METRICS = ("swa_flash_ms_train", "swa_flash_roofline", "attn_gate_ms_train",
               "mfu_train_laguna")
CELL = "lagunaxs2-train-16k"
FULL, SLIDING = "full_attention", "sliding_attention"


def test_band_costs_by_hand():
    shape = dict(batch=1, heads=64, seq=16384, d_head=128, window=512)
    # query t sees min(t + 1, 512) keys: a triangle of 512 and 15,872 whole windows
    assert band_cost.band_pairs(16384, 512) == 512 * 513 / 2 + 15872 * 512 == 8_257_792
    assert band_cost.band_pairs(300, 512) == 300 * 301 / 2  # a row shorter than the window
    assert band_cost.band_pairs(16384, 16384) == 16384 * 16385 / 2  # the causal half
    assert band_cost.forward_flops(**shape) == 64 * 8_257_792 * 4 * 128
    assert band_cost.training_flops(**shape) == 3 * band_cost.forward_flops(**shape)
    assert band_cost.backward_flops(**shape) == 2 * band_cost.forward_flops(**shape)
    # a window as wide as the row is the causal kernel's count
    assert band_cost.forward_flops(batch=1, heads=48, seq=4096, d_head=128, window=4096) == \
        full_cost.forward_flops(batch=1, heads=48, seq=4096, d_head=128)
    # bf16 q and o a query head, k and v a key-value head, the float32 log-sum-exp
    rows = dict(batch=1, heads=64, kv_heads=8, seq=16384, d_head=128)
    assert band_cost.forward_bytes(**rows) == 16384 * ((2 * 64 + 2 * 8) * 128 * 2 + 4 * 64)
    assert band_cost.backward_bytes(**rows) == 16384 * ((4 * 64 + 4 * 8) * 128 * 2 + 4 * 64)
    assert band_cost.training_bytes(**rows) == band_cost.forward_bytes(
        **rows) + band_cost.backward_bytes(**rows)
    # on a v5e the operations bind a sliding layer: 4.1 ms of them, 2.2 of bytes
    assert band_cost.training_flops(**shape) / 197e12 == pytest.approx(4.12e-3, rel=1e-2)
    assert band_cost.training_bytes(**rows) / 819e9 == pytest.approx(2.22e-3, rel=1e-2)


def test_laguna_training_flops_per_token_by_hand():
    model = LAGUNA["model"]
    assert laguna_cost.layer_counts(model) == (2, 3)
    assert laguna_cost.expected_routed_rows_per_token(model) == 4 * 8 * 32 / 256 == 4.0
    parts = laguna_cost.parts_per_token(model, 4.0)
    heads = 2 * 48 + 3 * 64  # query heads over the five layers
    # q and out a query head (2,048 x 128 each), k and v 8 heads a layer
    assert parts["attention_projections"] == 6 * (heads * 2 + 5 * 16) * 2048 * 128
    assert parts["attention_gate"] == 6 * heads * 2048
    assert parts["flash_full"] == pytest.approx(2 * 3 * 48 * 16385 / 2 * 4 * 128)
    assert parts["flash_band"] == pytest.approx(3 * 3 * 64 * 8_257_792 / 16384 * 4 * 128)
    assert parts["dense_mlp"] == 6 * 3 * 2048 * 8192
    assert parts["router"] == 6 * 4 * 2048 * 256
    assert parts["shared_expert"] == 6 * 4 * 3 * 2048 * 512
    # 4 rows a token over the four expert layers, three 2,048 x 512 products each
    assert parts["routed_experts"] == 4.0 * 3 * 3 * 2 * 2048 * 512
    assert parts["head"] == 6 * 2048 * 12544
    total = laguna_cost.flops_per_token(model, 4.0)
    assert total == pytest.approx(3.012e9, rel=1e-3)
    shares = {k: round(100 * v / total) for k, v in parts.items()}
    assert shares == {"attention_projections": 34, "attention_gate": 0, "flash_full": 40,
                      "flash_band": 5, "dense_mlp": 10, "router": 0, "shared_expert": 3,
                      "routed_experts": 3, "head": 5}
    # a step is 16,384 tokens x 3.01 GFLOP = 49 TFLOP (ISSUE 49 guessed 65)
    assert 16384 * total == pytest.approx(49.3e12, rel=1e-2)
    # the routed term follows the counted rows and nothing else does
    more = laguna_cost.parts_per_token(model, 6.0)
    assert more["routed_experts"] == 1.5 * parts["routed_experts"]
    assert {k: v for k, v in more.items() if k != "routed_experts"} == {
        k: v for k, v in parts.items() if k != "routed_experts"}


def test_the_configuration_file_states_the_published_widths():
    """Every number of the catalog's ``config`` under the same key, the six
    reduced keys apart, the published value of each of those beside it, the
    rotary group whole, and the parameter count the cut's arithmetic gives."""
    published = {
        "hidden_size": 2048, "intermediate_size": 8192, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 262144,
        "rms_norm_eps": 1e-06, "num_experts_per_tok": 8, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "sliding_window": 512,
        "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5}
    assert {k: LAGUNA[k] for k in published} == published
    assert (LAGUNA["model_type"], LAGUNA["gating"], LAGUNA["tie_word_embeddings"],
            LAGUNA["attention_bias"], LAGUNA["moe_apply_router_weight_on_input"]) == (
        "laguna", True, False, False, False)
    assert LAGUNA["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
            "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}
    assert sorted(LAGUNA["reduced"]) == [
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer", "num_experts",
        "num_hidden_layers", "vocab_size"]
    assert sorted(LAGUNA["reduced_why"]) == sorted(LAGUNA["reduced"])
    assert all(f"published_{k}" in LAGUNA for k in LAGUNA["reduced"])
    assert (LAGUNA["published_num_hidden_layers"], LAGUNA["num_hidden_layers"]) == (40, 5)
    assert (LAGUNA["published_num_experts"], LAGUNA["num_experts"]) == (256, 32)
    assert (LAGUNA["published_vocab_size"], LAGUNA["vocab_size"]) == (100352, 12544)
    assert LAGUNA["layer_types"] == [FULL, SLIDING, SLIDING, SLIDING, FULL]
    assert LAGUNA["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert LAGUNA["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    m = LAGUNA["model"]
    assert m["layer_types"] == ",".join(LAGUNA["layer_types"])
    assert (m["d_model"], m["n_heads"], m["swa_n_heads"], m["n_kv_heads"], m["d_head"],
            m["sliding_window"], m["dense_mlp_hidden_size"], m["mlp_hidden_size"],
            m["moe_num_experts"], m["moe_top_k"], m["moe_experts_held"], m["moe_shared_experts"],
            m["moe_routed_scale"], m["n_layers"], m["first_k_dense"], m["vocab_size"],
            m["attn_gate"], m["partial_rotary_factor"], m["rope_theta"], m["swa_rope_theta"],
            m["rope_scaling_factor"], m["rope_scaling_beta_fast"]) == (
        2048, 48, 64, 8, 128, 512, 8192, 512, 256, 8, 32, 1, 2.5, 5, 1, 12544, "headwise",
        0.5, 5e5, 1e4, 64.0, 64.0)
    assert m["rope_scaling_attention_factor"] == pytest.approx(0.1 * math.log(64) + 1)
    # ISSUE 49's table
    full = 2 * 2048 * 48 * 128 + 2 * 2048 * 8 * 128 + 2048 * 48
    sliding = 2 * 2048 * 64 * 128 + 2 * 2048 * 8 * 128 + 2048 * 64
    experts = 2048 * 256 + 256 + 32 * 3 * 2048 * 512 + 3 * 2048 * 512
    layer0 = full + 4096 + 3 * 2048 * 8192
    assert layer0 == 79_794_176
    total = (layer0 + 3 * (sliding + 4096 + experts) + (full + 4096 + experts)
             + 2 * 12544 * 2048 + 2048)
    assert total == LAGUNA["parameters"] == 691_624_960
    shapes = jax.eval_shape(lambda: ref.make_params(ref.dims_of(m), 0))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == 691_624_960
    # the published 33.4 B adds up with a gate a head (assumed.gating) ...
    uncut = lambda attention: attention + 4096 + experts + 224 * 3 * 2048 * 512  # noqa: E731
    whole = 2 * 100352 * 2048 + 2048 + layer0 + 30 * uncut(sliding) + 9 * uncut(full)
    assert whole == pytest.approx(33.44e9, rel=1e-3)
    # ... and misses it with a gate an element
    elementwise = whole + (30 * 64 + 10 * 48) * 127 * 2048
    assert elementwise - whole == pytest.approx(0.62e9, rel=2e-2)


def test_the_reference_exports_what_the_driver_takes():
    for name in ("dims_of", "seed_key", "make_params", "forward", "Grad", "adopt_init",
                 "adopt_step", "clip_by_global_norm", "leaf_norms", "worst_leaf_gap", "MATMULS"):
        assert hasattr(ref, name), name
    assert {"float32", "bfloat16", "int8"} <= set(ref.MATMULS)
    source = (toy.ROOT / "benchmark/reference/laguna_swa_moe.py").read_text()
    assert "photon_tpu" not in source.split('"""', 2)[2]  # nothing of the program


# ---------------------------------------------------------------------------
# the reference against second formulations
# ---------------------------------------------------------------------------

TOY_MODEL = {
    "d_model": 32, "n_layers": 5,
    "layer_types": ",".join([FULL, SLIDING, SLIDING, SLIDING, FULL]),
    "n_heads": 4, "swa_n_heads": 6, "n_kv_heads": 2, "head_dim": 8, "d_head": 8,
    "sliding_window": 8, "attn_gate": "headwise", "max_seq_len": 32, "vocab_size": 128,
    "rope": True, "rope_theta": 500000.0, "swa_rope_theta": 10000.0,
    "partial_rotary_factor": 0.5, "rope_scaling_type": "yarn", "rope_scaling_factor": 64.0,
    "rope_scaling_original_max_position": 8, "rope_scaling_beta_fast": 64.0,
    "rope_scaling_beta_slow": 1.0, "rope_scaling_attention_factor": 1.4158883083359672,
    "norm_eps": 1e-6, "first_k_dense": 1, "dense_mlp_hidden_size": 48, "mlp_hidden_size": 16,
    "moe_num_experts": 16, "moe_top_k": 4, "moe_experts_held": 4, "moe_first_expert": 0,
    "moe_shared_experts": 1, "moe_routed_scale": 2.5, "moe_bias_update_speed": 0.1,
    "param_dtype": "float32", "compute_dtype": "float32", "attn_impl": "xla"}


@pytest.mark.parametrize("kind", [FULL, SLIDING])
def test_attention_in_query_blocks_is_attention_position_by_position(kind, monkeypatch):
    """The reference's blocks of queries (each against the keys its queries can
    see) against a loop over single queries with the equations written out:
    the grouped heads, the band, the turned dims, the factor and the gate."""
    monkeypatch.setattr(ref, "QUERY_BLOCK", 5)  # blocks that do not divide the row
    dims = ref.dims_of(TOY_MODEL)
    params = ref.make_params(dims, seed=4)
    stack = {FULL: "blocks_2", SLIDING: "blocks_1"}[kind]
    p = jax.tree.map(lambda a: np.asarray(a[0], np.float64), params[stack]["block"])
    rng = np.random.default_rng(8)
    h = rng.normal(size=(1, 32, 32))
    got = np.asarray(ref.attention(jnp.asarray(h, jnp.float32), jax.tree.map(
        lambda a: jnp.asarray(a, jnp.float32), p), kind, dims, ref.MATMULS["float32"]))

    heads = 6 if kind == SLIDING else 4
    window = 8 if kind == SLIDING else 32
    turned, cos, sin = ref.rope_tables(dims, kind, 32)
    cos, sin = np.asarray(cos, np.float64), np.asarray(sin, np.float64)
    assert turned == (8 if kind == SLIDING else 4)
    if kind == FULL:  # the factor rides cos and sin
        assert cos[0, 0] == pytest.approx(1.4158883083359672)

    def turn(x, t):  # x [heads, 8] at position t
        half = turned // 2
        x1, x2 = x[:, :half], x[:, half:turned]
        return np.concatenate([x1 * cos[t] - x2 * sin[t], x2 * cos[t] + x1 * sin[t],
                               x[:, turned:]], axis=-1)

    q = (h[0] @ p["q_proj"]["kernel"]).reshape(32, heads, 8)
    k = (h[0] @ p["k_proj"]["kernel"]).reshape(32, 2, 8)
    v = (h[0] @ p["v_proj"]["kernel"]).reshape(32, 2, 8)
    gate = 1.0 / (1.0 + np.exp(-(h[0] @ p["attn_gate"]["kernel"])))  # [32, heads]
    out = np.zeros((32, heads, 8))
    for i in range(32):
        qi = turn(q[i], i)
        for head in range(heads):
            kv = head // (heads // 2)
            js = [j for j in range(32) if j <= i and j > i - window]
            scores = np.array([qi[head] @ turn(k[j], j)[kv] for j in js]) / math.sqrt(8)
            prob = np.exp(scores - scores.max())
            prob /= prob.sum()
            out[i, head] = gate[i, head] * sum(pj * v[j, kv] for pj, j in zip(prob, js))
    want = out.reshape(32, heads * 8) @ p["out_proj"]["kernel"]
    np.testing.assert_allclose(got[0], want, atol=2e-6)


def test_yarn_over_the_turned_half_slows_the_long_waves_alone():
    dims = ref.dims_of(LAGUNA["model"])
    inv = ref.yarn_inv_freq(dims, 64)
    plain = 5e5 ** (-2.0 * np.arange(32) / 64)
    assert inv.shape == (32,) and inv[0] == 1.0
    # c(64) = 64 ln(4096 / (128 pi)) / (2 ln 5e5) = 5.66, c(1) = 15.8: dims up to
    # 5 keep their frequency, dims from 16 on turn 64 times slower, a ramp between
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-6)
    assert np.all(np.diff(inv) < 0) and plain[10] / 64 < inv[10] < plain[10]
    turned, cos, sin = ref.rope_tables(dims, FULL, 4)
    assert turned == 64 and cos.shape == (4, 32)
    assert float(cos[0, 0]) == pytest.approx(0.1 * math.log(64) + 1)
    turned, cos, _ = ref.rope_tables(dims, SLIDING, 4)
    assert turned == 128 and cos.shape == (4, 64) and float(cos[0, 0]) == 1.0


def test_lower_precision_moves_the_reference():
    dims = ref.dims_of(TOY_MODEL)
    params = ref.make_params(dims, seed=3)
    tokens = np.random.default_rng(1).integers(0, 128, size=(2, 32)).astype(np.int32)
    exact = ref.forward(params, tokens, dims)
    gaps = {mm: float(np.max(np.abs(ref.forward(params, tokens, dims, mm) - exact)))
            for mm in ("bfloat16", "int8")}
    assert 0 < gaps["bfloat16"] < gaps["int8"]


def test_the_balancing_step_rides_each_stacks_own_bias_leaf():
    """``Grad`` puts every expert stack's balancing step (of that stack's rows)
    where its ``b``'s zero gradient would be in the host tree; ``adopt_step``
    takes each out again and moves that stack's ``b`` by it."""
    dims = ref.dims_of(TOY_MODEL)
    assert [name for name, _, dense, _ in ref.stacks(dims) if not dense] == [
        "blocks_1", "blocks_2"]
    params = ref.make_params(dims, seed=5)
    tokens = np.random.default_rng(2).integers(0, 128, size=(2, 32)).astype(np.int32)
    _, host = ref.Grad(dims, rows=1)(params, tokens)
    grads = host.tree()
    _, rows = ref.forward_and_rows(params, tokens, dims)
    for stack in ("blocks_1", "blocks_2"):
        np.testing.assert_allclose(grads[stack]["block"]["router_bias"],
                                   ref.bias_step(rows[stack], 0.1), atol=1e-7)
    assert grads["blocks_1"]["block"]["router_bias"].shape == (3, 16)
    clipped = ref.clip_by_global_norm(host, 1.0).tree()
    assert not np.any(clipped["blocks_1"]["block"]["router_bias"])
    assert not np.any(clipped["blocks_2"]["block"]["router_bias"])
    opt = {"name": "adopt", "lr": 1e-3, "betas": (0.9, 0.9999), "eps": 1e-6,
           "grad_clip_norm": 1.0, "schedule": "cosine_with_warmup", "t_warmup": 1,
           "t_max": 10, "alpha_f": 0.1}
    stepped, _ = ref.adopt_step(params, ref.adopt_init(params), host, opt)
    for stack in ("blocks_1", "blocks_2"):
        np.testing.assert_allclose(
            stepped[stack]["block"]["router_bias"],
            params[stack]["block"]["router_bias"] - grads[stack]["block"]["router_bias"],
            atol=1e-7)


# ---------------------------------------------------------------------------
# the new readers against a hand-written trace with the new names
# ---------------------------------------------------------------------------


@pytest.fixture()
def swa_trace(tmp_path):
    from benchmark.trace.reduce import reduce_trace

    trace_dir = write_trace(tmp_path, "swa_scopes.xplane.txt")
    run = types.SimpleNamespace(
        trace_dir=trace_dir, config=LAGUNA, peaks=toy.TOY_PEAKS, devices=[None],
        traffic={"steps_per_fit": 2},
        counters={"tokens_per_step": 16384, "device_microbatch_size": 1},
        span_seconds=lambda name: [0.2] if name == "trainer/fit" else [])
    return run, reduce_trace(trace_dir, [0])


@pytest.mark.parametrize("metric,ms_per_step", [
    ("swa_flash_ms_train", 0.050),  # forward 12 + recomputation 9 + dq 14 + dk/dv 15
    ("flash_fwd_ms_train", 0.020),  # the full layer's forward launch alone
    ("flash_bwd_ms_train", 0.010),  # the full layer's dq alone
    ("attn_gate_ms_train", 0.010),  # the multiply 4 + its product's transpose 6
    ("moe_experts_ms_train", 0.010),
])
def test_scope_reader_against_known_answers(swa_trace, metric, ms_per_step):
    run, reduction = swa_trace
    assert reader(metric).read(run, reduction) == pytest.approx(ms_per_step)


def test_band_roofline_and_mfu_against_known_answers(swa_trace):
    run, reduction = swa_trace
    shape = dict(batch=1, heads=64, seq=16384, d_head=128, window=512)
    # at the toy peaks the operations bind: 0.81 s of them against 0.18 of bytes
    least = max(band_cost.training_flops(**shape) / 1.0e12,
                band_cost.training_bytes(batch=1, heads=64, kv_heads=8, seq=16384,
                                         d_head=128) / 1.0e11)
    assert least == band_cost.training_flops(**shape) / 1.0e12
    # three layers (the span's own count, not n_layers' five) of one row, over 50 us
    assert reader("swa_flash_roofline").read(run, reduction) == pytest.approx(
        100.0 * 3 * least / 50e-6)
    # 16,384 tokens in 0.1 s a step, 20,000 rows held over both stacks
    flops = laguna_cost.flops_per_token(LAGUNA["model"], 20000 / 16384)
    assert reader("mfu_train_laguna").read(run, reduction) == pytest.approx(
        100.0 * 16384 / 0.1 * flops / 1.0e12)
    assert reader("moe_max_expert_load").read(run, reduction) == 2.5


def test_the_step_partition_leaves_the_new_names_to_its_remainder(swa_trace):
    """``trace/step_parts.PARTS`` has no row for the banded launches or for
    ``attn/gate``: they fall to ``fwd_bwd_rest``, which is why the cell is not
    on that reader's list (PERF.md section 7); the full layers' launches keep
    their parts."""
    from benchmark.trace import step_parts

    band = "jit(train_step)/train_step/forward_backward/x/flash_swa_dq/multihead_attention/pallas_call:"
    full = "jit(train_step)/train_step/forward_backward/x/flash_dq/multihead_attention/pallas_call:"
    gate = "jit(train_step)/train_step/forward_backward/x/block/attn/gate/mul:"
    assert step_parts.part_of([band]) == step_parts.part_of([gate]) == "fwd_bwd_rest"
    assert step_parts.part_of([full]) == "flash_bwd"
    run, reduction = swa_trace
    table = step_parts.parts_table(run, reduction)
    by_part = {p["part"]: p["ms_per_step"] for p in table["parts"]}
    assert by_part["fwd_bwd_rest"] == pytest.approx(0.060)  # the band 50 + the gate 10
    assert by_part["flash_fwd"] == pytest.approx(0.020)
    assert sum(by_part.values()) == pytest.approx(0.100)


@pytest.mark.parametrize("fixture", ["train_scopes.xplane.txt", "shortconv_scopes.xplane.txt",
                                     "mhc_scopes.xplane.txt", "small_trace.xplane.txt", None])
def test_readers_find_nothing_on_a_program_without_the_names(tmp_path, fixture):
    """What another model's or a parent commit's traced run gives the new
    readers: no ``flash_swa_*`` launch, no ``attn/gate`` scope, no
    ``swa_layers`` on ``trainer/steps`` (or no trace at all). Each returns
    ``None`` and raises nothing."""
    from benchmark.trace.reduce import reduce_trace

    trace_dir = write_trace(tmp_path, fixture) if fixture else None
    reduction = (reduce_trace(trace_dir, [0]) if fixture else
                 {"ops": [], "busy_s": 0.0, "window_s": 1.0, "idle_gaps": []})
    run = types.SimpleNamespace(
        trace_dir=trace_dir, config=LAGUNA, peaks=toy.TOY_PEAKS, devices=[None],
        traffic={"steps_per_fit": 4},
        counters={"tokens_per_step": 16384, "device_microbatch_size": 1},
        span_seconds=lambda name: [1.0])
    for name in NEW_METRICS:
        assert reader(name).read(run, reduction) is None, name


def test_the_cell_lists_what_it_reports_and_not_what_it_cannot():
    from benchmark.spec import Spec

    spec = Spec(toy.ROOT)
    cell = spec.cell(CELL)
    assert (cell.config, cell.traffic, cell.chips) == (
        "laguna-xs.2-ep8", "ep8-share-swa-1x16384", 1)
    names = {m.name for m in spec.cell_per_layer(cell)}
    assert set(NEW_METRICS) <= names
    assert {"moe_grouped_matmul_roofline", "moe_max_expert_load", "flash_fwd_ms_train",
            "flash_bwd_ms_train", "mlp_ms_train", "attn_proj_ms_train", "norm_ms_train",
            "moe_dispatch_ms_train", "moe_experts_ms_train", "loss_head_ms_train",
            "optimizer_ms_train", "grad_norm_ms_train", "loader_wait_ms_train",
            "step_ms_train", "step_unscoped_ms_train"} <= names
    # the remainder would hold the band and the gate, and the flash share counts
    # `n_layers` attention layers of one kind (PERF.md section 7)
    assert not {"fwd_bwd_rest_ms_train", "flash_attention_step_roofline"} & names
    assert {m.name for m in spec.cell_end_to_end(cell)} == {"train_tokens_per_s", "setup_s"}
    for m in spec.per_layer:
        if m.name in NEW_METRICS:
            assert m.workloads == (CELL,), m.name
    traffic = spec.traffic_file(cell)
    assert traffic["kind"] == "train_steps" and traffic["control_matmul"] == "int8"
    assert (traffic["rows"], traffic["zipf_a"], traffic["steps_per_fit"], traffic["warm_fits"],
            traffic["reference_rows"]) == (512, 1.01, 4, 0, 1)
    assert traffic["overrides"] == {"train.global_batch_size": 1,
                                    "train.device_microbatch_size": 1,
                                    "dataset.synthetic": True}


# ---------------------------------------------------------------------------
# a toy cell of the family through the driver
# ---------------------------------------------------------------------------

TOY_TRAFFIC = {
    "kind": "train_steps", "why": "toy",
    "overrides": {"train.global_batch_size": 2, "train.device_microbatch_size": 2,
                  "dataset.synthetic": True},
    "rows": 64, "zipf_a": 1.01, "steps_per_fit": 2, "loss_fall_fits": [0, 3],
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
        "benchmark/configs/toy-laguna.json": {
            "name": "toy-laguna", "source": "benchmark/tests (a test, not a model)",
            "preset": "laguna-xs.2-ep8", "reference": "laguna_swa_moe", "model": TOY_MODEL,
            "overrides": {f"model.{k}": v for k, v in TOY_MODEL.items() if k != "d_head"},
            "reduced": [], "assumed": {}, "deployment": "a test"},
        "benchmark/traffic/toy-laguna-train.json": TOY_TRAFFIC,
    })
    toy.add_entries(root, configs=[toy.config_entry("toy-laguna")], workloads=[
        {"name": "toy-laguna-train", "config": "toy-laguna",
         "traffic": "toy-laguna-train", "chips": 1, "why": "toy"}])
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "step_ms_train", "moe_max_expert_load") \
                + NEW_METRICS:
            m["workloads"].append("toy-laguna-train")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _prepare(root, seed, seconds, trace):
    from benchmark.harness import prepare
    from benchmark.spec import Spec

    return prepare(Spec(root), "toy-laguna-train", seed, seconds, trace,
                   t_process=time.monotonic(),
                   devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS))


def test_toy_cell_of_the_family_is_correct(checkout):
    from benchmark.harness import execute
    from benchmark.spec import Spec

    lines = []
    result = execute(Spec(checkout), "toy-laguna-train", 2**31 + 13, 0.5, False,
                     t_process=time.monotonic(),
                     devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS),
                     log=lines.append)
    assert result["correct"], [json.loads(ln) for ln in lines]
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_traced_toy_cell_reads_the_programs_counts_from_its_spans(checkout):
    """On the CPU a trace has the host plane only: the readers of device time
    find nothing and return ``None``; the sliding layers' count and window and
    the rows of both expert stacks ride the program's spans, so the
    utilisation is read."""
    parts, run = _prepare(checkout, 2**31 + 13, 0.5, True)
    try:
        parts["driver"].run(run)
    finally:
        run.clock.close()
    assert run.correct, run.checks
    from benchmark.trace.span_attrs import MOE_LOAD_SPAN, mean_attr
    from benchmark.trace.swa_attrs import sliding_window, swa_layers

    assert (swa_layers(run), sliding_window(run)) == (3, 8)
    # 2 rows x 32 tokens x top-4 x 4 expert layers = 1,024 assignments, about a quarter held
    assert 64 <= mean_attr(run, MOE_LOAD_SPAN, "rows_held") <= 512
    reduction = {"ops": [], "busy_s": 0.0, "window_s": 1.0, "idle_gaps": []}
    values = {name: parts["per_layer"][name].read(run, reduction) for name in NEW_METRICS}
    assert values["mfu_train_laguna"] > 0
    for name in NEW_METRICS[:3]:
        assert values[name] is None, name
    assert parts["per_layer"]["moe_max_expert_load"].read(run, reduction) >= 1.0


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
