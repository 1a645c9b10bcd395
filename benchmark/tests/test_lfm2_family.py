"""The ``lfm2_moe`` family's benchmark files: its cost functions against
numbers worked by hand, its configuration file against the published one, its
plain reference's exports and its taps against a per-position Python loop, the
new readers against a hand-written trace with the new scopes, and a toy cell
of the family through the ``train_steps`` driver."""

from __future__ import annotations

import json
import math
import time
import types

import jax
import numpy as np
import pytest

from benchmark.costs import lfm2_moe_train as lfm2_cost
from benchmark.costs import short_conv as conv_cost
from benchmark.reference import lfm2_moe as ref
from benchmark.tests import toy
from benchmark.tests.test_host_spans import reader, write_trace

LFM2 = json.loads((toy.ROOT / "benchmark/configs/lfm2-8b-a1b-ep4.json").read_text())
NEW_METRICS = ("shortconv_proj_ms_train", "shortconv_mix_ms_train",
               "shortconv_mix_roofline", "mfu_train_lfm2moe")
CELL = "lfm2moe-train-8k"


def test_short_conv_costs_by_hand():
    shape = dict(seq=8192, d_model=2048, taps=3)
    # a token and channel: B * u, a multiply and an add a tap, C *
    assert conv_cost.forward_flops(**shape) == 8192 * 2048 * 8
    assert conv_cost.training_flops(**shape) == 3 * 8192 * 2048 * 8
    assert conv_cost.forward_flops(batch=2, **shape) == 2 * 8192 * 2048 * 8
    # bf16 B | C | u in and the gated output out; the taps in float32
    assert conv_cost.forward_bytes(**shape) == 8192 * 2 * 4 * 2048 + 4 * 3 * 2048
    # backward: B | C | u and dy in, d(B | C | u) out, the taps' gradient
    assert conv_cost.training_bytes(**shape) == 8192 * 2 * 11 * 2048 + 2 * 4 * 3 * 2048
    # ISSUE 41's floor: four layers and two rows a step are 2.95 GB, 3.6 ms at 819 GB/s
    step = 4 * 2 * conv_cost.training_bytes(**shape)
    assert step == pytest.approx(2.953e9, rel=1e-3)
    assert step / 819e9 == pytest.approx(3.6e-3, rel=2e-2)
    # on a v5e the bytes bind, by two orders
    assert conv_cost.training_bytes(**shape) / 819e9 > 50 * conv_cost.training_flops(
        **shape) / 197e12


def test_lfm2_training_flops_per_token_by_hand():
    model = LFM2["model"]
    assert lfm2_cost.layer_counts(model) == (4, 1)
    assert lfm2_cost.expected_routed_rows_per_token(model) == 4 * 4 * 8 / 32 == 4.0
    parts = lfm2_cost.parts_per_token(model, 4.0)
    # a conv mixer's two matrices: 2,048 x 6,144 + 2,048 x 2,048
    assert parts["conv_projections"] == 6 * 4 * (12_582_912 + 4_194_304)
    assert parts["conv_mix"] == 4 * 3 * 8 * 2048
    # q and out 2,048 x 2,048 each, k and v 2,048 x 512 each, ONE attention layer
    assert parts["attention_projections"] == 6 * (2 * 4_194_304 + 2 * 1_048_576)
    assert parts["flash_core"] == pytest.approx(3 * 32 * 8193 / 2 * 4 * 64)
    assert parts["dense_mlp"] == 6 * 3 * 2048 * 7168
    assert parts["router"] == 6 * 4 * 2048 * 32
    # 4 rows a token over the four expert layers, three 2,048 x 1,792 products each
    assert parts["routed_experts"] == 4.0 * 3 * 3 * 2 * 2048 * 1792
    assert parts["head"] == 6 * 2048 * 16384
    total = lfm2_cost.flops_per_token(model, 4.0)
    assert total == pytest.approx(1.298e9, rel=1e-3)
    shares = {k: round(100 * v / total) for k, v in parts.items()}
    assert shares == {"conv_projections": 31, "conv_mix": 0, "attention_projections": 5,
                      "flash_core": 8, "dense_mlp": 20, "router": 0, "routed_experts": 20,
                      "head": 16}
    # the routed term follows the counted rows and nothing else does
    more = lfm2_cost.parts_per_token(model, 6.0)
    assert more["routed_experts"] == 1.5 * parts["routed_experts"]
    assert {k: v for k, v in more.items() if k != "routed_experts"} == {
        k: v for k, v in parts.items() if k != "routed_experts"}


def test_the_configuration_file_states_the_published_widths():
    """Every number of the catalog's ``config`` under the same key, the five
    reduced keys apart, the published value of each of those beside it, and
    the parameter count the cut's arithmetic gives."""
    published = {
        "conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 7168,
        "max_position_embeddings": 128000, "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "num_attention_heads": 32, "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1}
    assert {k: LFM2[k] for k in published} == published
    assert (LFM2["model_type"], LFM2["conv_bias"], LFM2["norm_topk_prob"],
            LFM2["use_expert_bias"]) == ("lfm2_moe", False, True, True)
    assert sorted(LFM2["reduced"]) == ["layer_types", "num_dense_layers", "num_experts",
                                       "num_hidden_layers", "vocab_size"]
    assert sorted(LFM2["reduced_why"]) == sorted(LFM2["reduced"])
    assert (LFM2["published_num_hidden_layers"], LFM2["num_hidden_layers"]) == (24, 5)
    assert (LFM2["published_num_dense_layers"], LFM2["num_dense_layers"]) == (2, 1)
    assert (LFM2["published_num_experts"], LFM2["num_experts"]) == (32, 8)
    assert (LFM2["published_vocab_size"], LFM2["vocab_size"]) == (65536, 16384)
    assert LFM2["layer_types"] == ["conv", "full_attention", "conv", "conv", "conv"]
    m = LFM2["model"]
    assert m["layer_types"] == ",".join(LFM2["layer_types"]).replace("full_attention",
                                                                     "attention")
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"], m["conv_kernel_size"],
            m["dense_mlp_hidden_size"], m["mlp_hidden_size"], m["moe_num_experts"],
            m["moe_top_k"], m["moe_experts_held"], m["n_layers"], m["first_k_dense"],
            m["vocab_size"], m["moe_gate_eps"], m["moe_routed_scale"]) == (
        2048, 32, 8, 64, 3, 7168, 1792, 32, 4, 8, 5, 1, 16384, 1e-6, 1.0)
    # ISSUE 41's table
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    experts = 2048 * 32 + 32 + 8 * 3 * 2048 * 1792
    assert (conv, attention, experts) == (16_783_360, 10_485_888, 88_145_952)
    layer0 = conv + 4096 + 3 * 2048 * 7168
    assert layer0 == 60_827_648
    total = layer0 + (attention + 4096 + experts) + 3 * (conv + 4096 + experts) + (
        16384 * 2048 + 2048)
    assert total == LFM2["parameters"] == 507_820_288
    shapes = jax.eval_shape(lambda: ref.make_params(ref.dims_of(m), 0))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == 507_820_288
    # the published total only adds up with the head tied (assumed.tie_word_embeddings)
    whole = (22 * (2048 * 32 + 32 + 32 * 3 * 2048 * 1792) + 2 * 3 * 2048 * 7168
             + 18 * conv + 6 * attention + 65536 * 2048)
    assert whole == pytest.approx(8.34e9, rel=2e-3)


def test_the_reference_exports_what_the_driver_takes():
    for name in ("dims_of", "seed_key", "make_params", "forward", "Grad", "adopt_init",
                 "adopt_step", "clip_by_global_norm", "leaf_norms", "worst_leaf_gap", "MATMULS"):
        assert hasattr(ref, name), name
    assert {"float32", "bfloat16", "int8"} <= set(ref.MATMULS)
    source = (toy.ROOT / "benchmark/reference/lfm2_moe.py").read_text()
    assert "photon_tpu" not in source.split('"""', 2)[2]  # nothing of the program


# ---------------------------------------------------------------------------
# the reference against a second formulation
# ---------------------------------------------------------------------------


def test_the_taps_are_three_shifted_products_without_a_bias():
    rng = np.random.default_rng(6)
    v = rng.normal(size=(1, 7, 3)).astype(np.float32)
    kernel = rng.normal(size=(3, 3)).astype(np.float32)
    want = np.zeros_like(v)
    for t in range(7):
        for k in range(3):
            if t - 2 + k >= 0:
                want[0, t] += kernel[k] * v[0, t - 2 + k]
    np.testing.assert_allclose(ref.short_conv(v, kernel), want, atol=1e-6)
    assert not np.any(ref.short_conv(np.zeros_like(v), kernel))  # no bias


TOY_MODEL = {
    "d_model": 32, "n_layers": 5, "layer_types": "conv,attention,conv,conv,conv",
    "conv_kernel_size": 3, "n_heads": 4, "n_kv_heads": 2, "d_head": 8, "qk_norm": True,
    "max_seq_len": 32, "vocab_size": 128, "rope": True, "rope_theta": 1000000.0,
    "norm_eps": 1e-5, "first_k_dense": 1, "dense_mlp_hidden_size": 48,
    "mlp_hidden_size": 24, "moe_num_experts": 8, "moe_top_k": 2, "moe_experts_held": 4,
    "moe_first_expert": 0, "moe_routed_scale": 1.0, "moe_gate_eps": 1e-6,
    "moe_bias_update_speed": 0.1, "param_dtype": "float32", "compute_dtype": "float32",
    "attn_impl": "xla"}


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
    where its ``b``'s zero gradient would be; ``adopt_step`` takes each out
    again and moves that stack's ``b`` by it."""
    dims = ref.dims_of(TOY_MODEL)
    assert [name for name, _, dense, _ in ref.stacks(dims) if not dense] == [
        "blocks_1", "blocks_2"]
    params = ref.make_params(dims, seed=5)
    tokens = np.random.default_rng(2).integers(0, 128, size=(2, 32)).astype(np.int32)
    _, grads = ref.Grad(dims, rows=1)(params, tokens)
    _, rows = ref.forward_and_rows(params, tokens, dims)
    for stack in ("blocks_1", "blocks_2"):
        np.testing.assert_allclose(grads[stack]["block"]["router_bias"],
                                   ref.bias_step(rows[stack], 0.1), atol=1e-7)
    assert not np.array_equal(grads["blocks_1"]["block"]["router_bias"][0],
                              grads["blocks_2"]["block"]["router_bias"][0])
    clipped = ref.clip_by_global_norm(grads, 1.0)
    assert not np.any(clipped["blocks_1"]["block"]["router_bias"])
    assert not np.any(clipped["blocks_2"]["block"]["router_bias"])
    opt = {"name": "adopt", "lr": 1e-3, "betas": (0.9, 0.9999), "eps": 1e-6,
           "grad_clip_norm": 1.0, "schedule": "cosine_with_warmup", "t_warmup": 1,
           "t_max": 10, "alpha_f": 0.1}
    stepped, _ = ref.adopt_step(params, ref.adopt_init(params), grads, opt)
    for stack in ("blocks_1", "blocks_2"):
        np.testing.assert_allclose(
            stepped[stack]["block"]["router_bias"],
            params[stack]["block"]["router_bias"] - grads[stack]["block"]["router_bias"],
            atol=1e-7)


# ---------------------------------------------------------------------------
# the new readers against a hand-written trace with the new scopes
# ---------------------------------------------------------------------------


@pytest.fixture()
def conv_trace(tmp_path):
    from benchmark.trace.reduce import reduce_trace

    trace_dir = write_trace(tmp_path, "shortconv_scopes.xplane.txt")
    run = types.SimpleNamespace(
        trace_dir=trace_dir, config=LFM2, peaks=toy.TOY_PEAKS, devices=[None],
        traffic={"steps_per_fit": 2}, counters={"tokens_per_step": 16384},
        span_seconds=lambda name: [0.2] if name == "trainer/fit" else [])
    return run, reduce_trace(trace_dir, [0])


@pytest.mark.parametrize("metric,ms_per_step", [
    ("shortconv_proj_ms_train", 0.032),  # in-projection 24 us + out-projection 8 us
    ("shortconv_mix_ms_train", 0.020),  # forward 5 + recomputation 5 + transpose 10
    ("moe_experts_ms_train", 0.030),  # both expert stacks: 12 + 18
    ("mlp_ms_train", 0.013),  # the leading dense layer
])
def test_scope_reader_against_known_answers(conv_trace, metric, ms_per_step):
    run, reduction = conv_trace
    assert reader(metric).read(run, reduction) == pytest.approx(ms_per_step)


def test_mix_roofline_and_mfu_against_known_answers(conv_trace):
    run, reduction = conv_trace
    shape = dict(seq=8192, d_model=2048, taps=3)
    # at the toy peaks too the bytes bind: 3.7 ms of them against 0.4 of operations
    least = max(conv_cost.training_flops(**shape) / 1.0e12,
                conv_cost.training_bytes(**shape) / 1.0e11)
    assert least == conv_cost.training_bytes(**shape) / 1.0e11
    # four layers (the span's own count) of two rows, over 20 us a step
    assert reader("shortconv_mix_roofline").read(run, reduction) == pytest.approx(
        100.0 * 4 * 2 * least / 20e-6)
    # 16,384 tokens in 0.1 s a step, 60,000 rows held over both stacks
    flops = lfm2_cost.flops_per_token(LFM2["model"], 60000 / 16384)
    assert reader("mfu_train_lfm2moe").read(run, reduction) == pytest.approx(
        100.0 * 16384 / 0.1 * flops / 1.0e12)


def test_the_grouped_products_roofline_counts_both_expert_stacks(conv_trace):
    """``moe_grouped_matmul_roofline`` in this family: the time under
    ``moe/experts`` of ``blocks_1`` and ``blocks_2`` together, the rows the
    span sums over both, four expert layers' held experts (``n_layers -
    first_k_dense``)."""
    from benchmark.costs import moe_grouped_matmul as gmm

    run, reduction = conv_trace
    least = max(gmm.training_flops(60000, d_model=2048, hidden=1792) / 1.0e12,
                gmm.training_bytes(60000, d_model=2048, hidden=1792, experts=4 * 8) / 1.0e11)
    assert reader("moe_grouped_matmul_roofline").read(run, reduction) == pytest.approx(
        100.0 * least / 30e-6)
    assert reader("moe_max_expert_load").read(run, reduction) == 1.5


@pytest.mark.parametrize("fixture", ["train_scopes.xplane.txt", "mamba_scopes.xplane.txt",
                                     "small_trace.xplane.txt", None])
def test_readers_find_nothing_on_a_program_without_the_scopes(tmp_path, fixture):
    """What another model's or a parent commit's traced run gives the new
    readers: no ``shortconv/*`` scope, no ``conv_layers`` on ``trainer/steps``
    (or no trace at all). Each returns ``None`` and raises nothing; a Mamba-2
    step's ``mamba/conv`` is not the mix."""
    from benchmark.trace.reduce import reduce_trace

    trace_dir = write_trace(tmp_path, fixture) if fixture else None
    reduction = (reduce_trace(trace_dir, [0]) if fixture else
                 {"ops": [], "busy_s": 0.0, "window_s": 1.0, "idle_gaps": []})
    run = types.SimpleNamespace(
        trace_dir=trace_dir, config=LFM2, peaks=toy.TOY_PEAKS, devices=[None],
        traffic={"steps_per_fit": 4}, counters={"tokens_per_step": 16384},
        span_seconds=lambda name: [1.0])
    for name in NEW_METRICS:
        assert reader(name).read(run, reduction) is None, name


def test_the_mamba_readers_find_nothing_in_a_conv_step(conv_trace):
    run, reduction = conv_trace
    for name in ("mamba_conv_ms_train", "mamba_proj_ms_train", "mamba_scan_ms_train"):
        assert reader(name).read(run, reduction) is None, name


def test_the_cell_lists_what_it_reports_and_not_the_remainder_it_cannot():
    from benchmark.spec import Spec

    spec = Spec(toy.ROOT)
    cell = spec.cell(CELL)
    assert (cell.config, cell.traffic, cell.chips) == ("lfm2-8b-a1b-ep4", "ep4-share-2x8192", 1)
    names = {m.name for m in spec.cell_per_layer(cell)}
    assert set(NEW_METRICS) <= names
    assert {"moe_grouped_matmul_roofline", "moe_max_expert_load", "flash_fwd_ms_train",
            "mlp_ms_train", "attn_proj_ms_train", "step_unscoped_ms_train"} <= names
    # `trace/step_parts.PARTS` has no row for the two new scopes: the remainder
    # would hold the conv mixer, so the cell is not on its list; nor on the
    # flash share's, which counts `n_layers` attention layers (PERF.md section 7)
    assert not {"fwd_bwd_rest_ms_train", "flash_attention_step_roofline"} & names
    for m in spec.per_layer:
        if m.name in NEW_METRICS:
            assert m.workloads == (CELL,), m.name


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
        "benchmark/configs/toy-lfm2.json": {
            "name": "toy-lfm2", "source": "benchmark/tests (a test, not a model)",
            "preset": "lfm2-8b-a1b-ep4", "reference": "lfm2_moe", "model": TOY_MODEL,
            "overrides": {f"model.{k}": v for k, v in TOY_MODEL.items() if k != "d_head"},
            "reduced": [], "assumed": {}, "deployment": "a test"},
        "benchmark/traffic/toy-lfm2-train.json": TOY_TRAFFIC,
    })
    toy.add_entries(root, configs=[toy.config_entry("toy-lfm2")], workloads=[
        {"name": "toy-lfm2-train", "config": "toy-lfm2",
         "traffic": "toy-lfm2-train", "chips": 1, "why": "toy"}])
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "step_ms_train", "moe_max_expert_load") \
                + NEW_METRICS:
            m["workloads"].append("toy-lfm2-train")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _prepare(root, seed, seconds, trace):
    from benchmark.harness import prepare
    from benchmark.spec import Spec

    return prepare(Spec(root), "toy-lfm2-train", seed, seconds, trace,
                   t_process=time.monotonic(),
                   devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS))


def test_toy_cell_of_the_family_is_correct(checkout):
    from benchmark.harness import execute
    from benchmark.spec import Spec

    lines = []
    result = execute(Spec(checkout), "toy-lfm2-train", 2**31 + 13, 0.5, False,
                     t_process=time.monotonic(),
                     devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS),
                     log=lines.append)
    assert result["correct"], [json.loads(ln) for ln in lines]
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_traced_toy_cell_reads_the_programs_counts_from_its_spans(checkout):
    """On the CPU a trace has the host plane only: the readers of device time
    find nothing and return ``None``; the layer count and the rows of both
    expert stacks ride the program's spans, so the utilisation is read."""
    parts, run = _prepare(checkout, 2**31 + 13, 0.5, True)
    try:
        parts["driver"].run(run)
    finally:
        run.clock.close()
    assert run.correct, run.checks
    from benchmark.trace.conv_attrs import conv_layers
    from benchmark.trace.span_attrs import MOE_LOAD_SPAN, mean_attr

    assert conv_layers(run) == 4
    # 2 rows x 32 tokens x top-2 x 4 expert layers = 512 assignments, about half held
    assert 128 <= mean_attr(run, MOE_LOAD_SPAN, "rows_held") <= 384
    reduction = {"ops": [], "busy_s": 0.0, "window_s": 1.0, "idle_gaps": []}
    values = {name: parts["per_layer"][name].read(run, reduction) for name in NEW_METRICS}
    assert values["mfu_train_lfm2moe"] > 0
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
