"""The ``glm_moe_lite`` family's benchmark files: its cost functions against
numbers worked by hand, its plain reference against a second, slower
formulation (per token, per head, per expert, numpy float64), and a toy cell
of the family through the ``train_steps`` driver and the new readers."""

from __future__ import annotations

import json
import math
import time

import jax
import numpy as np
import pytest

from benchmark.tests import toy
from benchmark.costs import glm_moe_lite_train as glm_cost
from benchmark.costs import moe_grouped_matmul as gmm_cost
from benchmark.reference import glm_moe_lite as ref

GLM = json.loads((toy.ROOT / "benchmark/configs/glm-4.7-flash-ep8.json").read_text())


def test_grouped_matmul_costs_by_hand():
    # a row of an expert: gate, up, down at 2 * 2,048 * 1,536 = 6,291,456 each
    assert gmm_cost.forward_flops(1, 2048, 1536) == 3 * 6_291_456 == 18_874_368
    # backward is two products for each forward one; nothing recomputed
    assert gmm_cost.training_flops(8192, 2048, 1536) == 3 * 8192 * 18_874_368
    # bf16: rows in and out once, 8 experts' three matrices once
    assert gmm_cost.forward_bytes(8192, 2048, 1536, 8) == 2 * (
        2 * 8192 * 2048 + 8 * 3 * 2048 * 1536)
    # backward reads rows, their output's gradient and the matrices, writes
    # the rows' gradient and the matrices'
    assert gmm_cost.training_bytes(8192, 2048, 1536, 8) == 2 * (
        5 * 8192 * 2048 + 3 * 8 * 3 * 2048 * 1536)


def test_glm47_training_flops_per_token_by_hand():
    model = GLM["model"]
    rows = glm_cost.expected_routed_rows_per_token(model)
    assert rows == 4 * 4 * 8 / 64 == 2.0  # 4 expert layers x top-4 x 8 of 64
    parts = glm_cost.parts_per_token(model, rows)
    # attention's weights a layer: 2,048x768 + 768x5,120 + 2,048x576 + 512x8,960
    # + 5,120x2,048 = 21,757,952 (ISSUE 29's 21.76 M less the two norm scales)
    assert parts["low_rank_projections"] == 6 * 5 * 21_757_952
    # causal pairs a token (4,097 / 2) x 4 x 256 a pair x 20 heads x 3 (forward
    # + backward) x 5 layers
    assert parts["flash_core"] == pytest.approx(5 * 3 * 20 * 4097 / 2 * 4 * 256)
    assert parts["dense_mlp"] == 6 * 3 * 2048 * 10240
    assert parts["shared_expert"] == 6 * 4 * 3 * 2048 * 1536
    assert parts["routed_experts"] == 2 * 3 * 18_874_368
    assert parts["head"] == 6 * 2048 * 19360
    total = glm_cost.flops_per_token(model, rows)
    assert total == pytest.approx(2.24e9, rel=2e-3)  # ISSUE 29: 2.24 GFLOP a token
    shares = {k: round(100 * v / total) for k, v in parts.items()}
    assert shares == {"low_rank_projections": 29, "flash_core": 28, "dense_mlp": 17,
                      "router": 0, "shared_expert": 10, "routed_experts": 5, "head": 11}
    # the program's own estimate (utils/profiling.model_flops_per_token)
    # takes the expected share and agrees
    from photon_tpu.config import load_preset
    from photon_tpu.utils.profiling import model_flops_per_token

    assert model_flops_per_token(load_preset("glm-4.7-flash-ep8").model) == pytest.approx(
        total, rel=1e-3)


def test_the_configuration_file_states_the_published_widths():
    """Every number of the catalog's ``config`` under the same key, the four
    reduced keys apart, and the published value of each of those beside it."""
    published = {
        "hidden_size": 2048, "intermediate_size": 10240, "max_position_embeddings": 202752,
        "moe_intermediate_size": 1536, "num_attention_heads": 20, "n_group": 1,
        "topk_group": 1, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
        "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_key_value_heads": 20,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_theta": 1000000,
        "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256}
    assert {k: GLM[k] for k in published} == published
    cut = {"num_hidden_layers": (47, 5), "n_routed_experts": (64, 8),
           "vocab_size": (154880, 19360), "num_nextn_predict_layers": (1, 0)}
    assert sorted(GLM["reduced"]) == sorted(cut)
    for key, (was, now) in cut.items():
        assert (GLM[f"published_{key}"], GLM[key]) == (was, now)
    m = GLM["model"]
    assert (m["d_model"], m["n_heads"], m["d_head"], m["dense_mlp_hidden_size"],
            m["mlp_hidden_size"], m["moe_num_experts"], m["moe_top_k"],
            m["moe_experts_held"], m["n_layers"], m["vocab_size"]) == (
        2048, 20, 256, 10240, 1536, 64, 4, 8, 5, 19360)


# ---------------------------------------------------------------------------
# the reference against a second formulation
# ---------------------------------------------------------------------------

TOY_GLM = {
    "d_model": 16, "n_layers": 2, "first_k_dense": 1, "n_heads": 2, "q_lora_rank": 6,
    "kv_lora_rank": 5, "qk_nope_head_dim": 4, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rope_theta": 100.0, "norm_eps": 1e-5, "max_seq_len": 6, "vocab_size": 11,
    "dense_mlp_hidden_size": 12, "mlp_hidden_size": 6, "moe_num_experts": 4,
    "moe_top_k": 2, "moe_experts_held": 2, "moe_first_expert": 2,
    "moe_shared_experts": 1, "moe_routed_scale": 1.8}


def _slow_forward(params, tokens, dims):
    """One token, one head, one expert at a time, float64."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    eps, theta = dims["norm_eps"], dims["rope_theta"]
    nope, rope, dv, rkv = dims["d_nope"], dims["d_rope"], dims["d_v"], dims["kv_rank"]

    def rms(x, scale):
        return x / math.sqrt(float(np.mean(x * x)) + eps) * scale

    def turn(x, pos):
        half = len(x) // 2
        out = np.empty_like(x)
        for i in range(half):
            angle = pos * theta ** (-i / half)
            out[i] = x[i] * math.cos(angle) - x[i + half] * math.sin(angle)
            out[i + half] = x[i + half] * math.cos(angle) + x[i] * math.sin(angle)
        return out

    def silu(x):
        return x / (1.0 + np.exp(-x))

    def ffn(h, wg, wu, wd):
        return (silu(h @ wg) * (h @ wu)) @ wd

    def attention(xs, lp):
        hs = [rms(x, lp["ln_1"]["scale"]) for x in xs]
        qs, ks, vs = [], [], []
        for pos, h in enumerate(hs):
            q = (rms(h @ lp["q_a_proj"]["kernel"], lp["q_a_norm"]["scale"])
                 @ lp["q_b_proj"]["kernel"]).reshape(dims["n_heads"], nope + rope)
            kv_a = h @ lp["kv_a_proj"]["kernel"]
            kv = (rms(kv_a[:rkv], lp["kv_a_norm"]["scale"])
                  @ lp["kv_b_proj"]["kernel"]).reshape(dims["n_heads"], nope + dv)
            k_rope = turn(kv_a[rkv:], pos)
            qs.append([np.concatenate([qh[:nope], turn(qh[nope:], pos)]) for qh in q])
            ks.append([np.concatenate([kvh[:nope], k_rope]) for kvh in kv])
            vs.append([kvh[nope:] for kvh in kv])
        out = []
        for t, x in enumerate(xs):
            heads = []
            for head in range(dims["n_heads"]):
                scores = np.array([qs[t][head] @ ks[u][head] for u in range(t + 1)])
                scores = np.exp(scores / math.sqrt(nope + rope) - scores.max())
                heads.append(sum(w * vs[u][head] for u, w in enumerate(scores / scores.sum())))
            out.append(x + np.concatenate(heads) @ lp["out_proj"]["kernel"])
        return out

    logits = []
    for row in np.asarray(tokens):
        xs = [p["wte"]["embedding"][t] for t in row]
        for stack in ("dense_blocks", "blocks"):
            layers = p[stack]["block"]
            for layer in range(layers["ln_1"]["scale"].shape[0]):
                lp = jax.tree.map(lambda a: a[layer], layers)
                xs = attention(xs, lp)
                for t, x in enumerate(xs):
                    h = rms(x, lp["ln_2"]["scale"])
                    if stack == "dense_blocks":
                        xs[t] = x + ffn(h, lp["gate_proj"]["kernel"], lp["up_proj"]["kernel"],
                                        lp["down_proj"]["kernel"])
                        continue
                    scores = 1.0 / (1.0 + np.exp(-(h @ lp["router"])))
                    chosen = np.argsort(-(scores + lp["router_bias"]), kind="stable")[
                        :dims["top_k"]]
                    norm = sum(scores[e] for e in chosen) + 1e-20
                    out = ffn(h, lp["shared_gate_proj"]["kernel"],
                              lp["shared_up_proj"]["kernel"], lp["shared_down_proj"]["kernel"])
                    for e in chosen:
                        held = e - dims["first_expert"]
                        if 0 <= held < dims["experts_held"]:  # the others are absent
                            out = out + dims["routed_scale"] * scores[e] / norm * ffn(
                                h, lp["moe_gate"][held], lp["moe_up"][held],
                                lp["moe_down"][held])
                    xs[t] = x + out
        logits.append([rms(x, p["ln_f"]["scale"]) @ p["lm_head"]["kernel"] for x in xs])
    return np.array(logits)


def test_reference_matches_a_per_token_formulation():
    dims = ref.dims_of(TOY_GLM)
    params = ref.make_params(dims, seed=2**31 + 7)
    # weights large enough that routing, softmax and norms all matter
    params = jax.tree.map(lambda a: a * 12.0 if a.ndim > 1 else a, params)
    tokens = np.random.default_rng(0).integers(0, 11, size=(2, 6)).astype(np.int32)
    got = ref.forward(params, tokens, dims)
    want = _slow_forward(params, tokens, dims)
    assert float(np.max(np.abs(want))) > 0.5
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_lower_precision_moves_the_reference():
    dims = ref.dims_of(TOY_GLM)
    params = jax.tree.map(lambda a: a * 12.0 if a.ndim > 1 else a,
                          ref.make_params(dims, seed=3))
    tokens = np.random.default_rng(1).integers(0, 11, size=(2, 6)).astype(np.int32)
    exact = ref.forward(params, tokens, dims)
    gaps = {mm: float(np.max(np.abs(ref.forward(params, tokens, dims, mm) - exact)))
            for mm in ("bfloat16", "int8")}
    assert 0 < gaps["bfloat16"] < gaps["int8"]


# ---------------------------------------------------------------------------
# a toy cell of the family through the driver and the new readers
# ---------------------------------------------------------------------------

TOY_CELL_MODEL = {
    "d_model": 32, "n_layers": 3, "n_heads": 2, "d_head": 16, "max_seq_len": 32,
    "vocab_size": 128, "q_lora_rank": 12, "kv_lora_rank": 8, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 4, "v_head_dim": 16, "rope_theta": 1000000.0, "norm_eps": 1e-5,
    "first_k_dense": 1, "dense_mlp_hidden_size": 64, "mlp_hidden_size": 24,
    "moe_num_experts": 8, "moe_top_k": 2, "moe_experts_held": 4, "moe_first_expert": 0,
    "moe_shared_experts": 1, "moe_routed_scale": 1.8, "moe_bias_update_speed": 0.05,
    "param_dtype": "float32",
    "compute_dtype": "float32", "attn_impl": "xla"}
TOY_TRAFFIC = {
    "kind": "train_steps", "why": "toy",
    "overrides": {"train.global_batch_size": 4, "train.device_microbatch_size": 4,
                  "dataset.synthetic": True},
    "rows": 64, "zipf_a": 1.01, "steps_per_fit": 2, "loss_fall_fits": [0, 3],
    "warm_fits": 1,
    "trace_seconds": 1, "reference_rows": 1, "control_matmul": "bfloat16",
    # the float32 program reads 1e-6 or less on the losses and 1e-5 on the norms;
    # the bfloat16 control 1e-3 or more on a norm
    "limits": {"loss_fall_min": -1.0, "loss_gap_step1": 1e-5, "loss_gap_step2": 1e-5,
               "loss_gap_step3": 1e-5, "first_grad_norm_gap": 1e-4,
               "param_change_norm_gap": 1e-4},
}
NEW_METRICS = ("mla_proj_ms_train", "moe_dispatch_ms_train", "moe_experts_ms_train",
               "moe_grouped_matmul_roofline", "moe_max_expert_load", "mfu_train_glm47",
               "flash_attention_step_roofline")


@pytest.fixture()
def checkout(tmp_path):
    root = toy.copy_benchmark(tmp_path)
    toy.add_files(root, {
        "benchmark/configs/toy-glm.json": {
            "name": "toy-glm", "source": "benchmark/tests (a test, not a model)",
            "preset": "glm-4.7-flash-ep8", "reference": "glm_moe_lite",
            "model": TOY_CELL_MODEL,
            "overrides": {f"model.{k}": v for k, v in TOY_CELL_MODEL.items() if k != "d_head"},
            "reduced": [], "assumed": {}, "deployment": "a test"},
        "benchmark/traffic/toy-glm-train.json": TOY_TRAFFIC,
    })
    toy.add_entries(root, configs=[toy.config_entry("toy-glm")], workloads=[
        {"name": "toy-glm-train", "config": "toy-glm", "traffic": "toy-glm-train",
         "chips": 1, "why": "toy"}])
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "step_ms_train") + NEW_METRICS:
            m["workloads"].append("toy-glm-train")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _execute(root, trace):
    from benchmark.harness import execute
    from benchmark.spec import Spec

    lines = []
    result = execute(Spec(root), "toy-glm-train", 2**31 + 13, 0.5, trace,
                     t_process=time.monotonic(),
                     devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS),
                     log=lines.append)
    return result, [json.loads(ln) for ln in lines]


def test_toy_cell_of_the_family_is_correct(checkout):
    result, checks = _execute(checkout, trace=False)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_traced_toy_cell_reads_the_counters_from_the_programs_span(checkout):
    """On the CPU a trace has the host plane only (``reduce_trace`` refuses
    it, so the driver is run and the readers are called by hand): the readers
    of device time find nothing and return ``None``, as on a parent commit;
    the counters ride a host span and are read."""
    from benchmark.harness import prepare
    from benchmark.spec import Spec

    parts, run = prepare(Spec(checkout), "toy-glm-train", 2**31 + 13, 0.5, True,
                         t_process=time.monotonic(),
                         devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS))
    try:
        parts["driver"].run(run)
    finally:
        run.clock.close()
    assert run.correct, run.checks
    reduction = {"ops": [], "busy_s": 0.0, "window_s": 1.0, "idle_gaps": []}
    values = {name: parts["per_layer"][name].read(run, reduction) for name in NEW_METRICS}
    # 4 rows x 32 tokens x top-2 x 2 expert layers, half the experts held
    assert 1.0 <= values["moe_max_expert_load"] <= 4.0
    assert values["mfu_train_glm47"] > 0
    for name in ("mla_proj_ms_train", "moe_dispatch_ms_train", "moe_experts_ms_train",
                 "moe_grouped_matmul_roofline", "flash_attention_step_roofline"):
        assert values[name] is None, name


def test_the_control_one_precision_down_is_not_correct(checkout):
    from benchmark.harness import prepare
    from benchmark.spec import Spec

    parts, run = prepare(Spec(checkout), "toy-glm-train", 13, 0.0, False,
                         t_process=time.monotonic(),
                         devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS))
    try:
        out = parts["driver"].readings(run)
    finally:
        run.clock.close()
    limits = run.traffic["limits"]
    numbers = [k for k in limits if k in out["program"]]
    assert numbers and all(out["program"][k] <= limits[k] for k in numbers), out
    assert any(out["control"][k] > limits[k] for k in numbers), out


def test_readers_find_nothing_on_a_program_without_the_spans():
    """What the parent commit's traced run gives the new readers: no
    ``trainer/moe_load`` span, no ``moe/*`` or ``mla/proj`` scope. Each
    returns ``None`` and raises nothing."""
    from benchmark.spec import Spec

    spec = Spec(toy.ROOT)

    class Run:
        trace_dir = None
        config = GLM
        traffic = {"steps_per_fit": 4}
        counters = {"tokens_per_step": 16384}
        peaks = toy.TOY_PEAKS
        devices = [None]

        def span_seconds(self, name):
            return [1.0]

    reduction = {"ops": [], "busy_s": 0.0, "window_s": 1.0, "idle_gaps": []}
    for name in NEW_METRICS:
        assert spec.layer_metric(name).read(Run(), reduction) is None, name


def test_flash_step_roofline_counts_required_work_over_all_the_launches(tmp_path):
    """The hand-written train trace holds two steps of one layer and one
    microbatch: 26 us of forward and 36 us of backward launches a step. Without
    recomputation the step's share is ``flash_attention_roofline``'s; a second
    forward launch a layer (``remat``) would add to the time and not to the
    work, where the launch-counting reader would credit a third more work."""
    import types

    from benchmark.costs import flash_attention as cost
    from benchmark.tests.test_host_spans import reader, write_trace
    from benchmark.trace.reduce import reduce_trace

    trace_dir = write_trace(tmp_path, "train_scopes.xplane.txt")
    model = {"n_layers": 1, "n_heads": 12, "max_seq_len": 2048, "d_head": 64}
    run = types.SimpleNamespace(
        trace_dir=trace_dir, config={"model": model}, peaks=toy.TOY_PEAKS,
        counters={"device_microbatch_size": 4, "tokens_per_step": 4 * 2048})
    reduction = reduce_trace(trace_dir, [0])
    shape = dict(batch=4, heads=12, seq=2048, d_head=64)
    least = max(cost.training_flops(**shape) / toy.TOY_PEAKS["flops_per_s_bf16"],
                cost.training_bytes(**shape) / toy.TOY_PEAKS["hbm_bytes_per_s"])
    got = reader("flash_attention_step_roofline").read(run, reduction)
    assert got == pytest.approx(100.0 * least / 62e-6)
    assert got == pytest.approx(reader("flash_attention_roofline").read(run, reduction))
    # five layers and two microbatches a step: ten times the work in that time
    model["n_layers"], run.counters["tokens_per_step"] = 5, 8 * 2048
    assert reader("flash_attention_step_roofline").read(run, reduction) == pytest.approx(10 * got)
