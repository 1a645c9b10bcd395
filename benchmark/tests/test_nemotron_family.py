"""The ``nemotron_h`` family's benchmark files: its cost functions against
numbers worked by hand, its configuration file against the published one, its
plain reference's exports, grouped recurrence and grouped norm against second
formulations, the new readers against a hand-written trace with the family's
scopes, and a toy cell of the family through the ``train_steps`` driver."""

from __future__ import annotations

import json
import math
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.costs import moe_grouped_matmul_ungated as ungated_cost
from benchmark.costs import nemotron_h_moe_train as nemotron_cost
from benchmark.costs import ssd_scan as one_group_cost
from benchmark.costs import ssd_scan_grouped as scan_cost
from benchmark.reference import nemotron_h_moe as ref
from benchmark.tests import toy
from benchmark.tests.test_host_spans import reader, write_trace

NEMOTRON = json.loads(
    (toy.ROOT / "benchmark/configs/nemotron-3-nano-30b-a3b-ep16.json").read_text())
NEW_METRICS = ("ssd_scan_grouped_roofline", "moe_ungated_matmul_roofline",
               "mfu_train_nemotron3n")
CELL = "nemotron3nano-train-8k"
PATTERN = "mamba,moe,mamba,moe,mamba,attention,moe,mamba,moe"


def test_grouped_scan_and_ungated_costs_by_hand():
    shape = dict(seq=8192, heads=64, d_head=64, d_state=128)
    # one group is costs/ssd_scan.py's count, operations and bytes
    assert scan_cost.training_flops(groups=1, chunk=256, **shape) == \
        one_group_cost.training_flops(chunk=256, **shape)
    assert scan_cost.training_bytes(groups=1, **shape) == one_group_cost.training_bytes(**shape)
    # a chunk of 128: 8,256 causal pairs; C B^T once a group of 8, every head's
    # masked square, state in and out
    per_chunk = 8 * 8256 * 2 * 128 + 64 * 8256 * 2 * 64 + 2 * 64 * 128 * 2 * 64 * 128
    assert scan_cost.forward_flops(groups=8, chunk=128, **shape) == 64 * per_chunk
    assert scan_cost.training_flops(groups=8, chunk=128, **shape) == 3 * 64 * per_chunk
    # bf16 x and y a head, B and C a group, dt in float32
    assert scan_cost.forward_bytes(groups=8, **shape) == 8192 * (
        2 * (2 * 4096 + 2 * 1024) + 4 * 64)
    assert scan_cost.training_bytes(groups=8, **shape) == 8192 * (
        2 * (5 * 4096 + 6 * 1024) + 12 * 64)
    # on a v5e the bytes bind a layer: 0.34 ms of operations, 0.54 of bytes
    assert scan_cost.training_flops(groups=8, chunk=128, **shape) / 197e12 == pytest.approx(
        3.44e-4, rel=2e-2)
    assert scan_cost.training_bytes(groups=8, **shape) / 819e9 == pytest.approx(5.4e-4, rel=2e-2)
    # two products a row, not three
    assert ungated_cost.forward_flops(384, 2688, 1856) == 384 * 2 * 2 * 2688 * 1856
    assert ungated_cost.training_flops(384, 2688, 1856) == 3 * 384 * 4 * 2688 * 1856
    assert ungated_cost.forward_bytes(384, 2688, 1856, experts=8) == 2 * (
        2 * 384 * 2688 + 8 * 2 * 2688 * 1856)
    assert ungated_cost.training_bytes(384, 2688, 1856, experts=8) == 2 * (
        5 * 384 * 2688 + 3 * 8 * 2 * 2688 * 1856)


def test_nemotron_training_flops_per_token_by_hand():
    model = NEMOTRON["model"]
    assert nemotron_cost.layer_counts(model) == (4, 1, 4)
    assert nemotron_cost.expected_routed_rows_per_token(model) == 4 * 6 * 8 / 128 == 1.5
    parts = nemotron_cost.parts_per_token(model, 1.5)
    assert parts["mamba_projections"] == 6 * 4 * (2688 * 10304 + 4096 * 2688)
    assert parts["mamba_conv"] == 3 * 4 * 2 * 4 * 6144
    assert parts["ssd_scan"] == 4 * scan_cost.training_flops(8192, 64, 8, 64, 128, 128) / 8192
    assert parts["attention_projections"] == 6 * (2688 * (32 + 4) * 128 + 4096 * 2688)
    assert parts["flash_core"] == pytest.approx(3 * 32 * 8193 / 2 * 4 * 128)
    assert parts["router"] == 6 * 4 * 2688 * 128
    assert parts["shared_expert"] == 6 * 4 * 2 * 2688 * 3712
    # 1.5 rows a token over the four expert layers, two 2,688 x 1,856 products each
    assert parts["routed_experts"] == 1.5 * 3 * 2 * 2 * 2688 * 1856
    assert parts["head"] == 6 * 2688 * 16384
    total = nemotron_cost.flops_per_token(model, 1.5)
    assert total == pytest.approx(2.1456e9, rel=1e-4)
    shares = {k: round(100 * v / total) for k, v in parts.items()}
    assert shares == {"mamba_projections": 43, "mamba_conv": 0, "ssd_scan": 2,
                      "attention_projections": 7, "flash_core": 9, "router": 0,
                      "shared_expert": 22, "routed_experts": 4, "head": 12}


def test_the_configuration_file_states_the_published_widths():
    """Every number of the catalog's ``config`` under the same key, the four
    reduced keys (three cuts: the depth with its pattern, the experts held, the
    vocabulary) apart, the published value of each of those beside it, and the
    parameter count the cut's arithmetic gives."""
    published = {
        "hidden_size": 2688, "intermediate_size": 1856, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128, "max_position_embeddings": 262144,
        "chunk_size": 128, "conv_kernel": 4, "expand": 2, "mamba_head_dim": 64,
        "mamba_num_heads": 64, "n_groups": 8, "ssm_state_size": 128, "n_group": 1,
        "topk_group": 1, "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_shared_experts": 1,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5, "norm_eps": 1e-05,
        "layer_norm_epsilon": 1e-05, "rope_theta": 10000, "partial_rotary_factor": 1,
        "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
        "num_logits_to_keep": 1}
    assert {k: NEMOTRON[k] for k in published} == published
    assert (NEMOTRON["model_type"], NEMOTRON["mlp_hidden_act"], NEMOTRON["mamba_hidden_act"],
            NEMOTRON["tie_word_embeddings"], NEMOTRON["use_conv_bias"], NEMOTRON["use_bias"],
            NEMOTRON["norm_topk_prob"], NEMOTRON["rescale_prenorm_residual"]) == (
        "nemotron_h", "relu2", "silu", False, True, False, True, True)
    assert sorted(NEMOTRON["reduced"]) == [
        "hybrid_override_pattern", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(NEMOTRON["reduced_why"]) == sorted(NEMOTRON["reduced"])
    assert all(f"published_{k}" in NEMOTRON for k in NEMOTRON["reduced"])
    assert (NEMOTRON["published_num_hidden_layers"], NEMOTRON["num_hidden_layers"]) == (52, 9)
    assert (NEMOTRON["published_n_routed_experts"], NEMOTRON["n_routed_experts"]) == (128, 8)
    assert (NEMOTRON["published_vocab_size"], NEMOTRON["vocab_size"]) == (131072, 16384)
    whole = NEMOTRON["published_hybrid_override_pattern"]
    assert (len(whole), whole.count("M"), whole.count("E"), whole.count("*")) == (52, 23, 23, 6)
    assert NEMOTRON["hybrid_override_pattern"] == whole[:9] == "MEMEM*EME"
    m = NEMOTRON["model"]
    letters = {"M": "mamba", "E": "moe", "*": "attention"}
    assert m["layer_types"] == ",".join(letters[c] for c in whole[:9]) == PATTERN
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"], m["mamba_n_heads"],
            m["mamba_n_groups"], m["mamba_d_head"], m["mamba_d_state"], m["mamba_d_conv"],
            m["mamba_chunk_size"], m["mlp_hidden_size"], m["moe_shared_hidden_size"],
            m["moe_num_experts"], m["moe_top_k"], m["moe_experts_held"], m["moe_routed_scale"],
            m["n_layers"], m["vocab_size"], m["moe_mlp_act"], m["rope"], m["max_seq_len"]) == (
        2688, 32, 2, 128, 64, 8, 64, 128, 4, 128, 1856, 3712, 128, 6, 8, 2.5, 9, 16384,
        "relu2", False, 8192)
    # ISSUE 52's table
    mamba = 2688 * 10304 + 4 * 6144 + 6144 + 192 + 4096 + 4096 * 2688 + 2688
    attention = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688 + 2688
    experts = lambda held: (  # noqa: E731
        2688 * 128 + 128 + 2 * 2688 * 3712 + held * 2 * 2688 * 1856 + 2688)
    assert (mamba, attention, experts(8)) == (38_744_896, 23_399_040, 100_125_440)
    total = 4 * mamba + attention + 4 * experts(8) + 2 * 16384 * 2688 + 2688
    assert total == NEMOTRON["parameters"] == 666_963_456
    shapes = jax.eval_shape(lambda: ref.make_params(ref.dims_of(m), 0))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == 666_963_456
    assert 16 * total / 17.18e9 > 0.25  # the state alone is over the floor
    # the published 31.6 B adds up with d_inner 4,096 and two matrices an expert
    # (assumed.d_inner) ...
    whole_model = 23 * mamba + 6 * attention + 23 * experts(128) + 2 * 131072 * 2688 + 2688
    assert whole_model == pytest.approx(31.58e9, rel=1e-3)
    # ... and misses it with expand x hidden_size = 5,376 inner channels
    wide = mamba + 2688 * 2 * 1280 + 1280 * 2688 + 5 * 1280 + 1280
    assert 23 * (wide - mamba) == pytest.approx(0.24e9, rel=5e-2)


def test_the_reference_exports_what_the_driver_takes():
    for name in ("dims_of", "seed_key", "make_params", "forward", "Grad", "adopt_init",
                 "adopt_step", "clip_by_global_norm", "leaf_norms", "worst_leaf_gap", "MATMULS"):
        assert hasattr(ref, name), name
    assert {"float32", "bfloat16", "int8"} <= set(ref.MATMULS)
    source = (toy.ROOT / "benchmark/reference/nemotron_h_moe.py").read_text()
    assert "photon_tpu" not in source.split('"""', 2)[2]  # nothing of the program


# ---------------------------------------------------------------------------
# the reference against second formulations
# ---------------------------------------------------------------------------

TOY_MODEL = {
    "d_model": 32, "n_layers": 9, "single_branch_layers": True, "layer_types": PATTERN,
    "n_heads": 4, "n_kv_heads": 2, "head_dim": 8, "d_head": 8, "max_seq_len": 32,
    "vocab_size": 128, "mamba_n_heads": 8, "mamba_n_groups": 2, "mamba_d_head": 8,
    "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_chunk_size": 8, "rope": False,
    "learned_pos_emb": False, "norm_eps": 1e-5, "mlp": "moe", "mlp_hidden_size": 24,
    "moe_mlp_act": "relu2", "moe_router": "sigmoid", "moe_num_experts": 16, "moe_top_k": 3,
    "moe_experts_held": 4, "moe_first_expert": 0, "moe_shared_experts": 1,
    "moe_shared_hidden_size": 40, "moe_routed_scale": 2.5, "moe_bias_update_speed": 0.1,
    "param_dtype": "float32", "compute_dtype": "float32", "attn_impl": "xla"}


def test_the_grouped_recurrence_is_each_heads_own_walk_with_its_groups_b_and_c():
    """A head's output is the one-group recurrence of that head ALONE against
    its group's ``b`` and ``c``; with group 0's for every head it is not."""
    rng = np.random.default_rng(0)
    bsz, s, h, p, g, n = 2, 12, 8, 4, 4, 6
    x = jnp.asarray(rng.normal(size=(bsz, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(bsz, s, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 4.0, size=(h,)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(bsz, s, g, n)), jnp.float32) for _ in range(2))
    d = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    y = np.asarray(ref.grouped_recurrence(x, dt, a, b, c, d))
    # position by position in numpy, one head at a time
    want = np.zeros((bsz, s, h, p))
    for head in range(h):
        grp = head // (h // g)
        state = np.zeros((bsz, p, n))
        for t in range(s):
            decay = np.exp(np.asarray(dt[:, t, head]) * float(a[head]))[:, None, None]
            grow = np.asarray(dt[:, t, head])[:, None, None] * np.einsum(
                "bp,bn->bpn", np.asarray(x[:, t, head]), np.asarray(b[:, t, grp]))
            state = decay * state + grow
            want[:, t, head] = np.einsum("bpn,bn->bp", state, np.asarray(c[:, t, grp])) \
                + float(d[head]) * np.asarray(x[:, t, head])
    np.testing.assert_allclose(y, want, atol=2e-5)
    shared = np.asarray(ref.grouped_recurrence(
        x, dt, a, jnp.repeat(b[:, :, :1], g, axis=2), jnp.repeat(c[:, :, :1], g, axis=2), d))
    np.testing.assert_allclose(shared[:, :, :h // g], y[:, :, :h // g], atol=1e-6)
    assert np.max(np.abs(shared[:, :, h // g:] - y[:, :, h // g:])) > 0.1


def test_the_grouped_norm_is_a_norm_of_each_groups_channels():
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.normal(size=(2, 5, 24)) * np.repeat([1.0, 10.0, 0.1], 8), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(24,)), jnp.float32)
    got = np.asarray(ref.grouped_rms_norm(u, scale, 3, 1e-5))
    for i in range(3):
        cols = slice(8 * i, 8 * i + 8)
        part = np.asarray(u[..., cols], np.float64)
        want = part / np.sqrt(np.mean(part ** 2, axis=-1, keepdims=True) + 1e-5)
        np.testing.assert_allclose(got[..., cols], want * np.asarray(scale[cols]), rtol=2e-5)
    # over all 24 channels at once the loud group drowns the quiet ones
    whole = np.asarray(ref.grouped_rms_norm(u, scale, 1, 1e-5))
    assert np.max(np.abs(whole - got)) > 0.5


def test_attention_in_query_blocks_is_attention_position_by_position(monkeypatch):
    dims = ref.dims_of(TOY_MODEL)
    p = jax.tree.map(lambda a: a[0], ref.make_params(dims, 2)["blocks_5"]["block"])
    h = jnp.asarray(np.random.default_rng(2).normal(size=(2, 32, 32)), jnp.float32)
    mm = ref.MATMULS["float32"]
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    got = np.asarray(ref.attention(h, p, dims, mm))
    q = np.asarray(mm(h, p["q_proj"]["kernel"])).reshape(2, 32, 4, 8)
    k = np.asarray(mm(h, p["k_proj"]["kernel"])).reshape(2, 32, 2, 8)
    v = np.asarray(mm(h, p["v_proj"]["kernel"])).reshape(2, 32, 2, 8)
    out = np.zeros((2, 32, 4, 8))
    for head in range(4):
        for i in range(32):
            scores = np.einsum("bd,bjd->bj", q[:, i, head], k[:, :i + 1, head // 2]) / math.sqrt(8)
            w = np.exp(scores - scores.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            out[:, i, head] = np.einsum("bj,bjd->bd", w, v[:, :i + 1, head // 2])
    want = out.reshape(2, 32, 32) @ np.asarray(p["out_proj"]["kernel"], np.float64)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_lower_precision_moves_the_reference():
    dims = ref.dims_of(TOY_MODEL)
    params = ref.make_params(dims, seed=3)
    tokens = np.random.default_rng(1).integers(0, 128, size=(2, 32)).astype(np.int32)
    exact = ref.forward(params, tokens, dims)
    gaps = {mm: float(np.max(np.abs(ref.forward(params, tokens, dims, mm) - exact)))
            for mm in ("bfloat16", "int8")}
    assert 0 < gaps["bfloat16"] < gaps["int8"]


def test_the_balancing_step_rides_each_expert_layers_own_bias_leaf():
    """``Grad`` puts every expert layer's balancing step (of that layer's rows)
    where its ``b``'s zero gradient would be in the host tree; ``adopt_step``
    takes each out again and moves that layer's ``b`` by it."""
    dims = ref.dims_of(TOY_MODEL)
    stacks = [name for name, kind, _ in ref.layer_runs(dims) if kind == "moe"]
    assert stacks == ["blocks_1", "blocks_3", "blocks_6", "blocks_8"]
    params = ref.make_params(dims, seed=5)
    tokens = np.random.default_rng(2).integers(0, 128, size=(2, 32)).astype(np.int32)
    _, host = ref.Grad(dims, rows=1)(params, tokens)
    grads = host.tree()
    _, rows = ref.forward_and_rows(params, tokens, dims)
    for stack in stacks:
        assert grads[stack]["block"]["router_bias"].shape == (1, 16)
        np.testing.assert_allclose(grads[stack]["block"]["router_bias"],
                                   ref.bias_step(rows[stack], 0.1), atol=1e-7)
    clipped = ref.clip_by_global_norm(host, 1.0).tree()
    assert not any(np.any(clipped[s]["block"]["router_bias"]) for s in stacks)
    opt = {"name": "adopt", "lr": 1e-3, "betas": (0.9, 0.9999), "eps": 1e-6,
           "grad_clip_norm": 1.0, "schedule": "cosine_with_warmup", "t_warmup": 1,
           "t_max": 10, "alpha_f": 0.1}
    stepped, _ = ref.adopt_step(params, ref.adopt_init(params), host, opt)
    for stack in stacks:
        np.testing.assert_allclose(
            stepped[stack]["block"]["router_bias"],
            params[stack]["block"]["router_bias"] - grads[stack]["block"]["router_bias"],
            atol=1e-7)


# ---------------------------------------------------------------------------
# the new readers against a hand-written trace with the family's scopes
# ---------------------------------------------------------------------------


@pytest.fixture()
def nemotron_trace(tmp_path):
    from benchmark.trace.reduce import reduce_trace

    trace_dir = write_trace(tmp_path, "nemotron_scopes.xplane.txt")
    run = types.SimpleNamespace(
        trace_dir=trace_dir, config=NEMOTRON, peaks=toy.TOY_PEAKS, devices=[None],
        traffic={"steps_per_fit": 2},
        counters={"tokens_per_step": 8192, "device_microbatch_size": 1},
        span_seconds=lambda name: [0.2] if name == "trainer/fit" else [])
    return run, reduce_trace(trace_dir, [0])


@pytest.mark.parametrize("metric,ms_per_step", [
    ("mamba_proj_ms_train", 0.030),
    ("mamba_conv_ms_train", 0.004),
    ("mamba_scan_ms_train", 0.024),  # the forward launch 10 + the backward launch 14
    ("moe_experts_ms_train", 0.020),  # the grouped product 8 + the shared expert 12
    ("moe_dispatch_ms_train", 0.004),
    ("attn_proj_ms_train", 0.006),
    ("norm_ms_train", 0.006),  # the pre-norm 4 + the gated norm 2
    ("fwd_bwd_rest_ms_train", 0.006),  # the residual add alone
])
def test_scope_reader_against_known_answers(nemotron_trace, metric, ms_per_step):
    run, reduction = nemotron_trace
    assert reader(metric).read(run, reduction) == pytest.approx(ms_per_step)


def test_rooflines_and_mfu_against_known_answers(nemotron_trace):
    run, reduction = nemotron_trace
    shape = dict(seq=8192, heads=64, groups=8, d_head=64, d_state=128)
    # at the toy peaks the scan is bound by its operations
    least = max(scan_cost.training_flops(chunk=128, **shape) / 1.0e12,
                scan_cost.training_bytes(**shape) / 1.0e11)
    assert least == scan_cost.training_flops(chunk=128, **shape) / 1.0e12
    # four layers and eight groups (the span's own counts) of one row, over 24 us
    assert reader("ssd_scan_grouped_roofline").read(run, reduction) == pytest.approx(
        100.0 * 4 * least / 24e-6)
    # 12,000 rows over the four expert layers' 32 held experts; the scope
    # ``moe/experts`` alone (8 us), not the shared expert's
    least = max(ungated_cost.training_flops(12000, 2688, 1856) / 1.0e12,
                ungated_cost.training_bytes(12000, 2688, 1856, experts=32) / 1.0e11)
    assert reader("moe_ungated_matmul_roofline").read(run, reduction) == pytest.approx(
        100.0 * least / 8e-6)
    # 8,192 tokens in 0.1 s a step, 12,000 rows held over the four layers
    flops = nemotron_cost.flops_per_token(NEMOTRON["model"], 12000 / 8192)
    assert reader("mfu_train_nemotron3n").read(run, reduction) == pytest.approx(
        100.0 * 8192 / 0.1 * flops / 1.0e12)
    assert reader("moe_max_expert_load").read(run, reduction) == 1.5


def test_every_operation_of_the_family_has_a_named_part(nemotron_trace):
    """``trace/step_parts.PARTS`` has a row for every scope of the family: only
    the residual add falls to ``fwd_bwd_rest``, nothing to ``step_unscoped``,
    and the parts sum to the step."""
    from benchmark.trace import step_parts

    run, reduction = nemotron_trace
    table = step_parts.parts_table(run, reduction)
    by_part = {p["part"]: p["ms_per_step"] for p in table["parts"]}
    assert by_part["mamba_scan"] == pytest.approx(0.024)
    assert by_part["moe_experts"] == pytest.approx(0.020)
    assert by_part["fwd_bwd_rest"] == pytest.approx(0.006)
    assert by_part["step_unscoped"] == 0.0
    assert sum(by_part.values()) == pytest.approx(0.100)


@pytest.mark.parametrize("fixture", ["train_scopes.xplane.txt", "mamba_scopes.xplane.txt",
                                     "swa_scopes.xplane.txt", "small_trace.xplane.txt", None])
def test_readers_find_nothing_on_a_program_without_the_names(tmp_path, fixture):
    """What another model's or a parent commit's traced run gives the new
    readers: no ``mamba_groups`` and no ``moe_layers`` on ``trainer/steps`` (a
    granite step has ``mamba_layers`` and a laguna step a ``trainer/moe_load``
    span, and neither is enough), or no trace at all. Each returns ``None`` and
    raises nothing."""
    from benchmark.trace.reduce import reduce_trace

    trace_dir = write_trace(tmp_path, fixture) if fixture else None
    reduction = (reduce_trace(trace_dir, [0]) if fixture else
                 {"ops": [], "busy_s": 0.0, "window_s": 1.0, "idle_gaps": []})
    run = types.SimpleNamespace(
        trace_dir=trace_dir, config=NEMOTRON, peaks=toy.TOY_PEAKS, devices=[None],
        traffic={"steps_per_fit": 4},
        counters={"tokens_per_step": 8192, "device_microbatch_size": 1},
        span_seconds=lambda name: [1.0])
    for name in NEW_METRICS:
        assert reader(name).read(run, reduction) is None, name


def test_the_cell_lists_what_it_reports_and_not_what_it_cannot():
    from benchmark.spec import Spec

    spec = Spec(toy.ROOT)
    cell = spec.cell(CELL)
    assert (cell.config, cell.traffic, cell.chips) == (
        "nemotron-3-nano-30b-a3b-ep16", "ep16-share-1x8192", 1)
    names = {m.name for m in spec.cell_per_layer(cell)}
    assert set(NEW_METRICS) <= names
    assert {"moe_max_expert_load", "flash_fwd_ms_train", "flash_bwd_ms_train",
            "mamba_proj_ms_train", "mamba_conv_ms_train", "mamba_scan_ms_train",
            "attn_proj_ms_train", "norm_ms_train", "moe_dispatch_ms_train",
            "moe_experts_ms_train", "loss_head_ms_train", "optimizer_ms_train",
            "grad_norm_ms_train", "loader_wait_ms_train", "step_ms_train",
            "step_unscoped_ms_train", "fwd_bwd_rest_ms_train"} <= names
    # no operation under ``block/mlp``; the one-group scan cost and the
    # three-product expert cost would misread this model (PERF.md section 3)
    assert not {"mlp_ms_train", "ssd_scan_roofline", "moe_grouped_matmul_roofline",
                "flash_attention_step_roofline", "mfu_train_granite4h"} & names
    assert {m.name for m in spec.cell_end_to_end(cell)} == {"train_tokens_per_s", "setup_s"}
    for m in spec.per_layer:
        if m.name in NEW_METRICS:
            assert m.workloads == (CELL,), m.name
    traffic = spec.traffic_file(cell)
    assert traffic["kind"] == "train_steps" and traffic["control_matmul"] == "int8"
    assert (traffic["rows"], traffic["zipf_a"], traffic["steps_per_fit"], traffic["warm_fits"],
            traffic["trace_seconds"], traffic["reference_rows"]) == (512, 1.01, 4, 0, 6, 1)
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
        "benchmark/configs/toy-nemotron.json": {
            "name": "toy-nemotron", "source": "benchmark/tests (a test, not a model)",
            "preset": "nemotron-3-nano-30b-a3b-ep16", "reference": "nemotron_h_moe",
            "model": TOY_MODEL,
            "overrides": {f"model.{k}": v for k, v in TOY_MODEL.items() if k != "d_head"},
            "reduced": [], "assumed": {}, "deployment": "a test"},
        "benchmark/traffic/toy-nemotron-train.json": TOY_TRAFFIC,
    })
    toy.add_entries(root, configs=[toy.config_entry("toy-nemotron")], workloads=[
        {"name": "toy-nemotron-train", "config": "toy-nemotron",
         "traffic": "toy-nemotron-train", "chips": 1, "why": "toy"}])
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "step_ms_train", "moe_max_expert_load") \
                + NEW_METRICS:
            m["workloads"].append("toy-nemotron-train")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _prepare(root, seed, seconds, trace):
    from benchmark.harness import prepare
    from benchmark.spec import Spec

    return prepare(Spec(root), "toy-nemotron-train", seed, seconds, trace,
                   t_process=time.monotonic(),
                   devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS))


def test_toy_cell_of_the_family_is_correct(checkout):
    from benchmark.harness import execute
    from benchmark.spec import Spec

    lines = []
    result = execute(Spec(checkout), "toy-nemotron-train", 2**31 + 13, 0.5, False,
                     t_process=time.monotonic(),
                     devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS),
                     log=lines.append)
    assert result["correct"], [json.loads(ln) for ln in lines]
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_traced_toy_cell_reads_the_programs_counts_from_its_spans(checkout):
    """On the CPU a trace has the host plane only: the readers of device time
    find nothing and return ``None``; the layer counts, the groups and the rows
    of all four expert layers ride the program's spans, so the utilisation is
    read."""
    parts, run = _prepare(checkout, 2**31 + 13, 0.5, True)
    try:
        parts["driver"].run(run)
    finally:
        run.clock.close()
    assert run.correct, run.checks
    from benchmark.trace.nemotron_attrs import static_count
    from benchmark.trace.span_attrs import MOE_LOAD_SPAN, mean_attr

    assert [static_count(run, a) for a in (
        "mamba_layers", "mamba_groups", "moe_layers", "attention_layers")] == [4, 2, 4, 1]
    assert static_count(run, "ssd_kernel_layers") is None  # 0 on the CPU backend
    # 2 rows x 32 tokens x top-3 x 4 expert layers = 768 assignments, about a quarter held
    assert 48 <= mean_attr(run, MOE_LOAD_SPAN, "rows_held") <= 384
    reduction = {"ops": [], "busy_s": 0.0, "window_s": 1.0, "idle_gaps": []}
    values = {name: parts["per_layer"][name].read(run, reduction) for name in NEW_METRICS}
    assert values["mfu_train_nemotron3n"] > 0
    for name in NEW_METRICS[:2]:
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
