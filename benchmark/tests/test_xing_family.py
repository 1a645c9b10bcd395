"""The ``xing_mhc_moe`` family's benchmark files: its configuration file
against the catalog's row, its traffic file's shape, its two new cost files
and the step's operations against hand counts, its plain reference's exports
and its Sinkhorn against a per-token numpy loop, the new readers against a
hand-written trace with the new scopes (a value with them, ``None`` without),
and a toy cell of the family through the ``train_steps`` driver."""

from __future__ import annotations

import json
import math
import time
import types

import jax
import numpy as np
import pytest

from benchmark.costs import flash_attention_dv as dv_cost
from benchmark.costs import mhc_mix as mix_cost
from benchmark.costs import xing_mhc_moe_train as xing_cost
from benchmark.reference import xing_mhc_moe as ref
from benchmark.tests import toy
from benchmark.tests.test_host_spans import reader, write_trace

XING = json.loads((toy.ROOT / "benchmark/configs/xing4.0-29b-a4b-ep8.json").read_text())
TRAFFIC = json.loads((toy.ROOT / "benchmark/traffic/ep8-share-4096.json").read_text())
NEW_METRICS = ("mhc_maps_ms_train", "mhc_mix_ms_train", "flash_dv_step_roofline",
               "mfu_train_xing4")
CELL = "xing4-train-4k"
#: the catalog row's ``config`` (model-configs guide, ``architectures.jsonl``),
#: every key; the five of ``reduced`` are compared through ``published_*``
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu",
    "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
    "max_position_embeddings": 262144, "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
CUT = {"num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 8,
       "vocab_size": 16384, "num_nextn_predict_layers": 0}


def test_the_configuration_file_states_the_published_widths():
    """Every key of the catalog's ``config`` under the same name, the five
    reduced ones at their cut value with the published one beside it, and the
    parameter count of ISSUE 44's table."""
    assert sorted(XING["reduced"]) == sorted(CUT) == sorted(XING["reduced_why"])
    for key, published in CATALOG.items():
        if key in CUT:
            assert (XING[f"published_{key}"], XING[key]) == (published, CUT[key]), key
        else:
            assert XING[key] == published, key
    m = XING["model"]
    scaling = CATALOG["rope_scaling"]
    assert (m["d_model"], m["n_heads"], m["d_head"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"], m["q_lora_rank"], m["kv_lora_rank"],
            m["dense_mlp_hidden_size"], m["mlp_hidden_size"], m["moe_num_experts"],
            m["moe_top_k"], m["moe_shared_experts"], m["moe_routed_scale"], m["hc_mult"],
            m["hc_sinkhorn_iters"], m["hc_eps"], m["hc_res_clamp"], m["norm_eps"],
            m["rope_theta"]) == (
        3584, 32, 192, 128, 64, 128, 768, 512, 9216, 1024, 64, 4, 1, 2.0, 4, 20, 1e-6, 30.0,
        1e-6, 10000.0)
    assert (m["rope_scaling_type"], m["rope_scaling_factor"],
            m["rope_scaling_original_max_position"], m["rope_scaling_beta_fast"],
            m["rope_scaling_beta_slow"], m["rope_scaling_mscale"],
            m["rope_scaling_mscale_all_dim"]) == tuple(scaling[k] for k in (
        "type", "factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
        "mscale", "mscale_all_dim"))
    assert (m["n_layers"], m["first_k_dense"], m["moe_experts_held"], m["vocab_size"],
            m["max_seq_len"]) == (5, 1, 8, 16384, 4096)
    # ISSUE 44's table, row by row
    attention = (3584 * 768 + 768 + 768 * 6144 + 3584 * 576 + 512 + 512 * 8192
                 + 4096 * 3584)
    maps = 14336 * 24 + 24 + 3
    layer0 = attention + 7168 + 2 * maps + 3 * 3584 * 9216
    expert = (attention + 7168 + 2 * maps + 3584 * 64 + 64 + 3 * 3584 * 1024
              + 8 * 3 * 3584 * 1024)
    ends = 2 * 16384 * 3584 + 3584
    assert (attention, maps, layer0, expert, ends) == (
        28_411_136, 344_091, 128_196_918, 128_426_358, 117_444_096)
    assert layer0 + 4 * expert + ends == 759_346_446
    shapes = jax.eval_shape(lambda: ref.make_params(ref.dims_of(m), 0))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == 759_346_446
    assert "759,346,446" in XING["deployment"]
    for topic in ("streams' entry and exit", "flattened norm", "Sinkhorn", "init",
                  "rope pairing", "e_score_correction_bias", "optimizer", "max_seq_len",
                  "remat"):
        assert topic in XING["assumed"], topic


def test_the_traffic_file_is_one_row_of_4096_in_one_microbatch():
    assert TRAFFIC["kind"] == "train_steps"
    assert TRAFFIC["overrides"] == {"train.global_batch_size": 1,
                                    "train.device_microbatch_size": 1,
                                    "dataset.synthetic": True, "optimizer.lr": 1.5e-4}
    assert "optimizer.lr" in TRAFFIC["assumed"]  # not the recipe's 6e-4, and why
    assert (TRAFFIC["zipf_a"], TRAFFIC["steps_per_fit"], TRAFFIC["reference_rows"],
            TRAFFIC["control_matmul"], TRAFFIC["trace_seconds"]) == (1.01, 4, 1, "int8", 8)
    assert sorted(TRAFFIC["limits"]) == [
        "first_grad_norm_gap", "loss_fall_min", "loss_gap_step1", "loss_gap_step2",
        "loss_gap_step3", "param_change_norm_gap"]
    assert "PERF.md" in TRAFFIC["limits_from"] and len(TRAFFIC["why"]) > 200


def test_the_mix_costs_by_hand():
    assert (mix_cost.forward_units(4), mix_cost.backward_units(4)) == (14, 15)
    # 29 passes over one stream's [4,096, 3,584] bf16 array: 851 MB a sublayer
    assert mix_cost.training_bytes(4096, 3584, 4) == 29 * 4096 * 3584 * 2 == 851_443_712
    # ten sublayers: 8.5 GB a step, 10.4 ms at 819 GB/s (ISSUE 44's 6n + 3: 7.9 GB)
    assert 10 * mix_cost.training_bytes(4096, 3584, 4) / 819e9 == pytest.approx(
        10.4e-3, rel=1e-2)
    # a multiply and an add for each of n + n^2 + n weights a channel
    assert mix_cost.forward_flops(4096, 3584, 4) == 4096 * 3584 * 2 * 24
    assert mix_cost.training_flops(1, 3584, 4) == 3 * 2 * 24 * 3584
    # on a v5e the bytes bind, by two orders
    assert mix_cost.training_bytes(4096, 3584, 4) / 819e9 > 50 * mix_cost.training_flops(
        4096, 3584, 4) / 197e12


def test_the_two_width_flash_costs_by_hand():
    shape = dict(batch=1, heads=32, seq=4096, d_qk=192, d_v=128)
    pairs = 4096 * 4097 / 2
    assert dv_cost.forward_flops(**shape) == 32 * pairs * (2 * 192 + 2 * 128)
    assert dv_cost.training_flops(**shape) == 3 * dv_cost.forward_flops(**shape)
    assert dv_cost.forward_bytes(**shape) == 32 * 4096 * ((2 * 192 + 2 * 128) * 2 + 4)
    assert dv_cost.backward_bytes(**shape) == 32 * 4096 * ((4 * 192 + 4 * 128) * 2 + 4)
    # with one width it is the one-width file's count
    from benchmark.costs import flash_attention as one

    same = dict(batch=2, heads=20, seq=4096)
    assert dv_cost.training_flops(d_qk=256, d_v=256, **same) == one.training_flops(
        d_head=256, **same)
    assert dv_cost.training_bytes(d_qk=256, d_v=256, **same) == one.training_bytes(
        d_head=256, **same)
    # what the one-width reader would credit this model: a fifth too much
    assert one.training_flops(d_head=192, batch=1, heads=32, seq=4096) / dv_cost.training_flops(
        **shape) == pytest.approx(1.2)


def test_xing_training_flops_per_token_by_hand():
    model = XING["model"]
    assert xing_cost.expected_routed_rows_per_token(model) == 4 * 4 * 8 / 64 == 2.0
    parts = xing_cost.parts_per_token(model, 2.0)
    low_rank = (3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584)
    assert parts["low_rank_projections"] == 6 * 5 * low_rank
    assert parts["flash_core"] == pytest.approx(5 * 3 * 32 * 4097 / 2 * (2 * 192 + 2 * 128))
    assert parts["dense_mlp"] == 6 * 3 * 3584 * 9216
    assert parts["router"] == 6 * 4 * 3584 * 64
    assert parts["shared_expert"] == 6 * 4 * 3 * 3584 * 1024
    assert parts["routed_experts"] == 2.0 * 3 * 3 * 2 * 3584 * 1024
    assert parts["hyper_connection_maps"] == 6 * 10 * 14336 * 24
    assert parts["hyper_connection_mix"] == 10 * 3 * 2 * 24 * 3584
    assert parts["head"] == 6 * 3584 * 16384
    total = xing_cost.flops_per_token(model, 2.0)
    assert total == pytest.approx(2.856e9, rel=1e-3)
    shares = {k: round(100 * v / total) for k, v in parts.items()}
    assert shares == {"low_rank_projections": 30, "flash_core": 22, "dense_mlp": 21,
                      "router": 0, "shared_expert": 9, "routed_experts": 5,
                      "hyper_connection_maps": 1, "hyper_connection_mix": 0, "head": 12}
    more = xing_cost.parts_per_token(model, 3.0)
    assert more["routed_experts"] == 1.5 * parts["routed_experts"]
    assert {k: v for k, v in more.items() if k != "routed_experts"} == {
        k: v for k, v in parts.items() if k != "routed_experts"}


def test_the_reference_exports_what_the_driver_takes():
    for name in ("dims_of", "seed_key", "make_params", "forward", "Grad", "adopt_init",
                 "adopt_step", "clip_by_global_norm", "leaf_norms", "worst_leaf_gap", "MATMULS"):
        assert hasattr(ref, name), name
    assert {"float32", "bfloat16", "int8"} <= set(ref.MATMULS)
    source = (toy.ROOT / "benchmark/reference/xing_mhc_moe.py").read_text()
    assert "photon_tpu" not in source.split('"""', 2)[2]  # nothing of the program


# ---------------------------------------------------------------------------
# the reference against a second formulation
# ---------------------------------------------------------------------------

TOY_MODEL = {
    "d_model": 32, "n_layers": 3, "n_heads": 2, "d_head": 12, "q_lora_rank": 12,
    "kv_lora_rank": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 6,
    "max_seq_len": 32, "vocab_size": 128, "rope": True, "rope_theta": 10000.0,
    "rope_scaling_type": "yarn", "rope_scaling_factor": 64.0,
    "rope_scaling_original_max_position": 16, "rope_scaling_beta_fast": 32.0,
    "rope_scaling_beta_slow": 1.0, "rope_scaling_mscale": 1.0,
    "rope_scaling_mscale_all_dim": 1.0, "norm_eps": 1e-6, "first_k_dense": 1,
    "dense_mlp_hidden_size": 48, "mlp_hidden_size": 24, "moe_num_experts": 8,
    "moe_top_k": 2, "moe_experts_held": 4, "moe_first_expert": 0, "moe_shared_experts": 1,
    "moe_routed_scale": 2.0, "moe_bias_update_speed": 0.1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "hc_res_clamp": 30.0,
    "param_dtype": "float32", "compute_dtype": "float32", "attn_impl": "xla"}


def test_the_maps_are_the_equations_token_by_token():
    """``hyper_maps`` against a numpy float64 loop over tokens written from
    the equations: the flattened norm, ``r Phi^T``, the squashes, 20 rounds of
    columns then rows. ``H_res`` comes out doubly stochastic."""
    dims = ref.dims_of(TOY_MODEL)
    n, c = 4, 32
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2, 3, n, c)).astype(np.float32)
    p = {"hc_1_phi": rng.normal(size=(24, n * c)).astype(np.float32) * 0.3,
         "hc_1_b": rng.normal(size=(24,)).astype(np.float32),
         "hc_1_alpha": np.array([0.5, -0.7, 0.2], np.float32)}
    pre, post, res = (np.asarray(a, np.float64) for a in ref.hyper_maps(
        x, p, "hc_1", dims, ref.MATMULS["float32"]))
    for b in range(2):
        for t in range(3):
            v = x[b, t].reshape(-1).astype(np.float64)
            r = v / math.sqrt(np.mean(v * v) + 1e-6)
            out = p["hc_1_phi"].astype(np.float64) @ r
            bias = p["hc_1_b"].astype(np.float64)
            want_pre = 1 / (1 + np.exp(-(0.5 * out[:4] + bias[:4])))
            want_post = 2 / (1 + np.exp(-(-0.7 * out[4:8] + bias[4:8])))
            m = np.exp(np.clip((0.2 * out[8:] + bias[8:]).reshape(4, 4), -30, 30))
            for _ in range(20):
                m = m / (m.sum(axis=0, keepdims=True) + 1e-6)
                m = m / (m.sum(axis=1, keepdims=True) + 1e-6)
            np.testing.assert_allclose(pre[b, t], want_pre, rtol=2e-5)
            np.testing.assert_allclose(post[b, t], want_post, rtol=2e-5)
            np.testing.assert_allclose(res[b, t], m, rtol=2e-4, atol=1e-7)
    # rows were divided last; the columns are at the iteration's precision
    np.testing.assert_allclose(res.sum(axis=-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(axis=-2), 1.0, atol=1e-3)


def test_the_frequencies_are_yarns_by_hand():
    """64 rotary dims, theta 10,000, factor 64 over 4,096: the finder gives
    10.47 and 22.51, so dims 0-10 keep their frequency, 23-31 turn 64 times
    slower, a ramp of thirteenths between."""
    dims = ref.dims_of(XING["model"])
    inv = ref.yarn_inv_freq(dims)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    np.testing.assert_allclose(inv[15], plain[15] * ((1 - 5 / 13) + (5 / 13) / 64), rtol=1e-6)
    assert ref.softmax_scale(dims) == pytest.approx(192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert ref.softmax_scale(dims) == pytest.approx(0.14468, rel=1e-4)


def test_lower_precision_moves_the_reference():
    dims = ref.dims_of(TOY_MODEL)
    params = ref.make_params(dims, seed=3)
    tokens = np.random.default_rng(1).integers(0, 128, size=(2, 32)).astype(np.int32)
    exact = ref.forward(params, tokens, dims)
    gaps = {mm: float(np.max(np.abs(ref.forward(params, tokens, dims, mm) - exact)))
            for mm in ("bfloat16", "int8")}
    assert 0 < gaps["bfloat16"] < gaps["int8"], gaps


def test_the_balancing_step_rides_the_bias_leaf_through_the_host_tree():
    """``Grad`` hands the balancing step to ``adopt_step`` in ``router_bias``'s
    place; the clipped gradient has a zero there, and the step moves ``b`` by
    exactly that much and by nothing else."""
    dims = ref.dims_of(TOY_MODEL)
    params = ref.make_params(dims, seed=4)
    tokens = np.random.default_rng(2).integers(0, 128, size=(2, 32)).astype(np.int32)
    loss, grads = ref.Grad(dims, rows=1)(params, tokens)
    k = ref._bias_leaf(grads.treedef)
    step = np.array(grads.leaves[k])
    assert step.shape == (2, 8) and np.abs(step).max() <= 0.1 + 1e-7 and np.abs(step).max() > 0
    clipped = ref.clip_by_global_norm(grads, 1.0)
    assert not clipped.leaves[k].any()
    norms = ref.leaf_norms(clipped)
    assert norms["blocks/block/router_bias"].tolist() == [0.0, 0.0]
    assert norms["blocks/block/hc_1_phi"].shape == (2,) and norms["lm_head/kernel"].shape == (1,)
    opt = {"name": "adopt", "lr": 1e-3, "betas": (0.9, 0.9999), "eps": 1e-6,
           "grad_clip_norm": 1.0, "schedule": "cosine_with_warmup", "t_warmup": 0,
           "t_max": 10, "alpha_f": 0.1}
    stepped, _ = ref.adopt_step(params, ref.adopt_init(params), grads, opt)
    moved = np.asarray(params["blocks"]["block"]["router_bias"]) - np.asarray(
        stepped["blocks"]["block"]["router_bias"])
    np.testing.assert_allclose(moved, step, atol=1e-7)
    # ADOPT's first call moves no weight
    np.testing.assert_array_equal(np.asarray(stepped["lm_head"]["kernel"]),
                                  np.asarray(params["lm_head"]["kernel"]))
    assert float(loss) > 0


# ---------------------------------------------------------------------------
# the new readers against a hand-written trace with the new scopes
# ---------------------------------------------------------------------------


@pytest.fixture()
def mhc_trace(tmp_path):
    from benchmark.trace.reduce import reduce_trace

    trace_dir = write_trace(tmp_path, "mhc_scopes.xplane.txt")
    run = types.SimpleNamespace(
        trace_dir=trace_dir, config=XING, peaks=toy.TOY_PEAKS, devices=[None],
        traffic={"steps_per_fit": 2},
        counters={"tokens_per_step": 4096, "device_microbatch_size": 1},
        span_seconds=lambda name: [0.2] if name == "trainer/fit" else [])
    return run, reduce_trace(trace_dir, [0])


@pytest.mark.parametrize("metric,ms_per_step", [
    ("mhc_maps_ms_train", 0.010),  # the projection 6 us + the Sinkhorn fusion 4
    ("mhc_mix_ms_train", 0.037),  # read-in 5 + 7, write-back 11 + 14
    ("mla_proj_ms_train", 0.010),
    ("moe_experts_ms_train", 0.015),
    ("flash_fwd_ms_train", 0.009),
    ("flash_bwd_ms_train", 0.014),
])
def test_scope_reader_against_known_answers(mhc_trace, metric, ms_per_step):
    run, reduction = mhc_trace
    assert reader(metric).read(run, reduction) == pytest.approx(ms_per_step)


def test_the_roofline_and_mfu_against_known_answers(mhc_trace):
    run, reduction = mhc_trace
    flash = dict(batch=1, heads=32, seq=4096, d_qk=192, d_v=128)
    least = max(dv_cost.training_flops(**flash) / 1.0e12,
                dv_cost.training_bytes(**flash) / 1.0e11)
    # five layers of one row, over the three launches' 23 us a step
    assert reader("flash_dv_step_roofline").read(run, reduction) == pytest.approx(
        100.0 * 5 * least / 23e-6)
    # 4,096 tokens in 0.1 s a step, 2,000 rows held
    flops = xing_cost.flops_per_token(XING["model"], 2000 / 4096)
    assert reader("mfu_train_xing4").read(run, reduction) == pytest.approx(
        100.0 * 4096 / 0.1 * flops / 1.0e12)


@pytest.mark.parametrize("fixture", ["train_scopes.xplane.txt", "shortconv_scopes.xplane.txt",
                                     "small_trace.xplane.txt", None])
def test_readers_find_nothing_on_a_program_without_the_scopes(tmp_path, fixture):
    """What another model's or a parent commit's traced run gives the readers
    of the new scopes and attrs: no ``mhc/*`` scope, no ``mhc_sublayers`` on
    ``trainer/steps`` (or no trace at all). Each returns ``None`` and raises
    nothing."""
    from benchmark.trace.reduce import reduce_trace

    trace_dir = write_trace(tmp_path, fixture) if fixture else None
    reduction = (reduce_trace(trace_dir, [0]) if fixture else
                 {"ops": [], "busy_s": 0.0, "window_s": 1.0, "idle_gaps": []})
    lfm2 = json.loads((toy.ROOT / "benchmark/configs/lfm2-8b-a1b-ep4.json").read_text())
    run = types.SimpleNamespace(
        trace_dir=trace_dir, config=lfm2, peaks=toy.TOY_PEAKS, devices=[None],
        traffic={"steps_per_fit": 4},
        counters={"tokens_per_step": 16384, "device_microbatch_size": 2},
        span_seconds=lambda name: [1.0])
    for name in NEW_METRICS:
        assert reader(name).read(run, reduction) is None, name


def test_the_cell_lists_what_it_reports_and_not_what_it_cannot():
    from benchmark.spec import Spec

    spec = Spec(toy.ROOT)
    cell = spec.cell(CELL)
    assert (cell.config, cell.traffic, cell.chips) == (
        "xing4.0-29b-a4b-ep8", "ep8-share-4096", 1)
    names = {m.name for m in spec.cell_per_layer(cell)}
    assert set(NEW_METRICS) <= names
    assert {"step_ms_train", "loader_wait_ms_train", "optimizer_ms_train",
            "loss_head_ms_train", "flash_fwd_ms_train", "flash_bwd_ms_train",
            "mla_proj_ms_train", "moe_dispatch_ms_train", "moe_experts_ms_train",
            "moe_grouped_matmul_roofline", "moe_max_expert_load", "mlp_ms_train",
            "norm_ms_train", "grad_norm_ms_train", "step_unscoped_ms_train",
            "compile_s"} <= names
    # `trace/step_parts.PARTS` has no row for the `mhc/` scopes, so the
    # remainder would hold the residual path; the one-width flash share would
    # read a fifth high; the latent branch has no `attn/proj` (PERF.md section 7)
    assert not {"fwd_bwd_rest_ms_train", "flash_attention_step_roofline",
                "attn_proj_ms_train"} & names
    for m in spec.per_layer:
        if m.name in NEW_METRICS:
            assert m.workloads == (CELL,), m.name
    assert CELL in next(m for m in spec.end_to_end if m.name == "train_tokens_per_s").workloads


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
        "benchmark/configs/toy-xing.json": {
            "name": "toy-xing", "source": "benchmark/tests (a test, not a model)",
            "preset": "xing4.0-29b-a4b-ep8", "reference": "xing_mhc_moe", "model": TOY_MODEL,
            "overrides": {f"model.{k}": v for k, v in TOY_MODEL.items() if k != "d_head"},
            "reduced": [], "assumed": {}, "deployment": "a test"},
        "benchmark/traffic/toy-xing-train.json": TOY_TRAFFIC,
    })
    toy.add_entries(root, configs=[toy.config_entry("toy-xing")], workloads=[
        {"name": "toy-xing-train", "config": "toy-xing",
         "traffic": "toy-xing-train", "chips": 1, "why": "toy"}])
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "step_ms_train", "moe_max_expert_load") \
                + NEW_METRICS:
            m["workloads"].append("toy-xing-train")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _prepare(root, seed, seconds, trace):
    from benchmark.harness import prepare
    from benchmark.spec import Spec

    return prepare(Spec(root), "toy-xing-train", seed, seconds, trace,
                   t_process=time.monotonic(),
                   devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS))


def test_toy_cell_of_the_family_is_correct(checkout):
    from benchmark.harness import execute
    from benchmark.spec import Spec

    lines = []
    result = execute(Spec(checkout), "toy-xing-train", 2**31 + 17, 0.5, False,
                     t_process=time.monotonic(),
                     devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS),
                     log=lines.append)
    assert result["correct"], [json.loads(ln) for ln in lines]
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_traced_toy_cell_reads_the_programs_counts_from_its_spans(checkout):
    """On the CPU a trace has the host plane only: the readers of device time
    find nothing and return ``None``; the sublayers and the rows
    held ride the program's spans, so the utilisation is read."""
    parts, run = _prepare(checkout, 2**31 + 17, 0.5, True)
    try:
        parts["driver"].run(run)
    finally:
        run.clock.close()
    assert run.correct, run.checks
    from benchmark.trace import host_spans as hs
    from benchmark.trace.mhc_attrs import mhc_sublayers
    from benchmark.trace.span_attrs import MOE_LOAD_SPAN, mean_attr

    assert mhc_sublayers(run) == 6
    # 2 rows x 32 tokens x top-2 x 2 expert layers = 256 assignments, about half held
    assert 64 <= mean_attr(run, MOE_LOAD_SPAN, "rows_held") <= 192
    gap = mean_attr(run, "trainer/mhc", "sinkhorn_gap")
    assert gap is not None and 0 <= gap < 1e-3
    assert hs.named(hs.host_spans(run.trace_dir), "trainer/mhc")
    reduction = {"ops": [], "busy_s": 0.0, "window_s": 1.0, "idle_gaps": []}
    values = {name: parts["per_layer"][name].read(run, reduction) for name in NEW_METRICS}
    assert values["mfu_train_xing4"] > 0
    for name in NEW_METRICS[:3]:
        assert values[name] is None, name


def test_the_control_one_precision_down_is_not_correct(checkout):
    parts, run = _prepare(checkout, 17, 0.0, False)
    try:
        out = parts["driver"].readings(run)
    finally:
        run.clock.close()
    limits = run.traffic["limits"]
    numbers = [k for k in limits if k in out["program"]]
    assert numbers and all(out["program"][k] <= limits[k] for k in numbers), out
    assert any(out["control"][k] > limits[k] for k in numbers), out
