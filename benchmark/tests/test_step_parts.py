"""The one partition of a training step (``benchmark/trace/step_parts.py``)
and the readers on it, against compiled toy steps of every family written
out as traces (one event an instruction, its ``op_name`` in the event's
metadata where a TPU's trace has it), against the hand-written trace of two
steps with known answers, and against traces of a program without the scopes.
"""

from __future__ import annotations

import re
import types

import pytest
from jax.profiler import ProfileData

from benchmark.tests.test_host_spans import reader, write_trace
from benchmark.trace import step_parts
from benchmark.trace.reduce import reduce_trace

US_IN_MS = 1e-3
#: the readers that were there, each with the part that takes its pattern
OLDER = {
    "flash_fwd_ms_train": "flash_fwd", "flash_bwd_ms_train": "flash_bwd",
    "loss_head_ms_train": "loss_head", "optimizer_ms_train": "optimizer",
    "mla_proj_ms_train": "mla_proj", "moe_dispatch_ms_train": "moe_dispatch",
    "moe_experts_ms_train": "moe_experts", "mamba_proj_ms_train": "mamba_proj",
    "mamba_conv_ms_train": "mamba_conv", "mamba_scan_ms_train": "mamba_scan",
    "dsa_indexer_ms_train": "dsa_indexer", "dsa_select_ms_train": "dsa_select",
    "dsa_index_loss_ms_train": "dsa_index_loss",
}
NEW = {
    "mlp_ms_train": "mlp", "attn_proj_ms_train": "attn_proj", "norm_ms_train": "norm",
    "grad_norm_ms_train": "grad_norm", "fwd_bwd_rest_ms_train": "fwd_bwd_rest",
    "step_unscoped_ms_train": "step_unscoped",
}
#: the parts a family's compiled step must hold beside the step's own stages
FAMILY_PARTS = {
    "dense": {"mlp", "attn_proj", "norm"},
    "glm": {"mla_proj", "moe_dispatch", "moe_experts", "mlp", "norm"},
    "granite": {"mamba_proj", "mamba_conv", "mamba_scan", "mlp", "attn_proj", "norm"},
    "keye": {"dsa_indexer", "dsa_select", "dsa_index_loss", "moe_dispatch",
             "moe_experts", "attn_proj", "norm"},
}
STAGES = {"loss_head", "optimizer", "grad_norm", "fwd_bwd_rest"}


def _model_cfg(family: str):
    if family == "dense":
        from photon_tpu.config.schema import ModelConfig

        return ModelConfig(d_model=32, n_layers=2, n_heads=2, max_seq_len=32,
                           vocab_size=64, attn_impl="xla", compute_dtype="float32")
    module = {"glm": "test_glm_moe_lite", "granite": "test_granite_hybrid",
              "keye": "test_keye_sparse"}[family]
    return __import__(f"tests.{module}", fromlist=["tiny_cfg"]).tiny_cfg().model


def compiled_instructions(family: str) -> list[tuple[str, str | None]]:
    """``(instruction name, op_name or None)`` of every instruction of a toy
    model's compiled train step."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.config.schema import OptimizerConfig, SchedulerConfig
    from photon_tpu.models.mpt import MPTModel, init_params
    from photon_tpu.optim import build_optimizer
    from photon_tpu.train import init_train_state, make_train_step

    cfg = _model_cfg(family)
    tx, _ = build_optimizer(OptimizerConfig(name="adopt", lr=1e-3),
                            SchedulerConfig(t_warmup=2, t_max=50))
    model = MPTModel(cfg)
    state = init_train_state(model, tx, init_params(cfg, seed=0))
    tokens = jnp.zeros((2, cfg.max_seq_len), jnp.int32)
    text = jax.jit(make_train_step(model, tx, loss_chunk_tokens=16)).lower(
        state, tokens).compile().as_text()
    out = []
    for line in text.splitlines():
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = ", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), op.group(1) if op else None))
    return out


def write_xspace(tmp_path, text: str):
    """``text`` (an XSpace in protobuf text format) as the trace under
    ``tmp_path``."""
    out = tmp_path / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return tmp_path


def write_step_trace(tmp_path, instructions, steps: int = 2, host: str = ""):
    """A trace of one device line on which every instruction runs once, a
    microsecond each, and a host line with ``steps`` ``trainer/next_batch``
    spans (and ``host``, more lines of the host plane, verbatim)."""
    events, metadata = [], []
    for i, (name, op_name) in enumerate(instructions, start=1):
        events.append(f"events {{ metadata_id: {i} offset_ps: {i * 1000000} "
                      "duration_ps: 1000000 }")
        stat = (f' stats {{ metadata_id: 1 str_value: "{op_name}" }}'
                if op_name is not None else "")
        metadata.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "%{name} = f32[] op()"{stat} }} }}')
    batches = "\n".join(f"events {{ metadata_id: 1 offset_ps: {i * 1000000} "
                        "duration_ps: 500000 }" for i in range(steps))
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000
    {' '.join(events)} }}
  {' '.join(metadata)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 1000 {batches} }}
  {host}
  event_metadata {{ key: 1 value {{ id: 1 name: "trainer/next_batch" }} }} }}
"""
    return write_xspace(tmp_path, text)


@pytest.fixture(scope="module", params=sorted(FAMILY_PARTS))
def step_trace(request, tmp_path_factory):
    """``(family, run, reduction, instructions)`` of a compiled toy step."""
    instructions = compiled_instructions(request.param)
    trace_dir = write_step_trace(tmp_path_factory.mktemp(request.param), instructions)
    run = types.SimpleNamespace(trace_dir=trace_dir)
    return request.param, run, reduce_trace(trace_dir, [0]), instructions


def test_every_operation_is_in_exactly_one_part(step_trace):
    family, run, reduction, instructions = step_trace
    table = step_parts.parts_table(run, reduction)
    assert table["steps"] == 2
    ops = [o["op"] for o in table["ops"]]
    assert sorted(ops) == sorted(name for name, _ in instructions)  # none twice, none lost
    assert sum(p["ops"] for p in table["parts"]) == len(ops)
    per_step = len(ops) * US_IN_MS / 2
    assert table["total_ms_per_step"] == pytest.approx(per_step)
    assert sum(p["ms_per_step"] for p in table["parts"]) == pytest.approx(per_step)
    assert 1000.0 * reduction["busy_s"] / 2 == pytest.approx(per_step)
    assert sum(p["share"] for p in table["parts"]) == pytest.approx(1.0)
    assert [p["part"] for p in table["parts"]] == [part for part, _ in step_parts.PARTS]
    held = {p["part"] for p in table["parts"] if p["ops"]}
    assert FAMILY_PARTS[family] | STAGES <= held, (FAMILY_PARTS[family] | STAGES) - held
    # an instruction without an op_name (a parameter, a reducer's own) is unscoped
    unnamed = {name for name, op_name in instructions if op_name is None}
    assert unnamed <= {o["op"] for o in table["ops"] if o["part"] == "step_unscoped"}


@pytest.mark.parametrize("metric", sorted(OLDER))
def test_part_holds_the_operations_its_older_reader_counts(step_trace, metric):
    """The table's pattern for the part and the reader's own find the same
    operations: no earlier row of the table takes one the reader counts, and
    a program without the scope reads ``None`` from both."""
    _, run, reduction, _ = step_trace
    own = reader(metric).read(run, reduction)
    part = step_parts.part_ms_per_step(run, reduction, OLDER[metric])
    assert (own is None) == (part is None)
    if own is not None:
        assert part == pytest.approx(own)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_reader_reads_its_part(step_trace, metric):
    family, run, reduction, _ = step_trace
    value = reader(metric).read(run, reduction)
    assert value == step_parts.part_ms_per_step(run, reduction, NEW[metric])
    expected = NEW[metric] in FAMILY_PARTS[family] | STAGES | {"step_unscoped"}
    assert (value is not None and value > 0) == expected, (family, metric, value)


def test_all_readers_together_are_the_busy_time(step_trace):
    """What the record asks of a traced cell: every part has a reader, so the
    readers sum to the device's busy time a step."""
    _, run, reduction, _ = step_trace
    total = sum(reader(m).read(run, reduction) or 0.0 for m in {**OLDER, **NEW})
    assert total == pytest.approx(1000.0 * reduction["busy_s"] / 2)


@pytest.fixture()
def known(tmp_path):
    trace_dir = write_trace(tmp_path, "train_scopes.xplane.txt")
    return types.SimpleNamespace(trace_dir=trace_dir), reduce_trace(trace_dir, [0])


@pytest.mark.parametrize("part,ms_per_step", [
    ("flash_fwd", 0.026), ("flash_bwd", 0.036), ("loss_head", 0.010),
    ("optimizer", 0.002),
    ("fwd_bwd_rest", 0.030),  # PR 27's program: its MLP fusion has no scope of a block
    ("step_unscoped", 0.006),  # the norm outside every scope 5 us, the copy without a name 1
])
def test_parts_of_the_hand_written_trace(known, part, ms_per_step):
    run, reduction = known
    assert step_parts.part_ms_per_step(run, reduction, part) == pytest.approx(ms_per_step)
    table = step_parts.parts_table(run, reduction)
    assert table["total_ms_per_step"] == pytest.approx(0.110)  # 5 us idle a step apart


@pytest.mark.parametrize("part", ["mlp", "attn_proj", "norm", "grad_norm", "mla_proj"])
def test_a_scopes_part_is_left_out_where_the_program_has_no_such_scope(known, part):
    run, reduction = known
    assert step_parts.part_ms_per_step(run, reduction, part) is None
    with pytest.raises(KeyError):
        step_parts.part_ms_per_step(run, reduction, "no such part")


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_reader_finds_nothing_without_steps_or_without_a_trace(tmp_path, metric):
    """A trace whose program writes no span (no step to divide by), and an
    untraced run: the metric is left out and nothing raises."""
    trace_dir = write_trace(tmp_path, "small_trace.xplane.txt")
    run = types.SimpleNamespace(trace_dir=trace_dir)
    assert reader(metric).read(run, reduce_trace(trace_dir, [0])) is None
    assert reader(metric).read(types.SimpleNamespace(trace_dir=None), {"ops": []}) is None


def test_finest_scope_wins():
    under = "jit(train_step)/train_step/forward_backward/jvp(MPTModel)/blocks/block/"
    assert step_parts.part_of([under + "block/mlp/up_proj/dot_general"]) == "mlp"
    assert step_parts.part_of([under + "multihead_attention/flash_fwd/multihead_attention/"
                               "pallas_call"]) == "flash_fwd"
    assert step_parts.part_of([under + "multihead_attention/transpose"]) == "fwd_bwd_rest"
    assert step_parts.part_of([under + "dsa/index_loss/index_pbar/pallas_call"]) == "dsa_index_loss"
    assert step_parts.part_of([under + "attn/qk_norm/q_norm/mul"]) == "norm"
    assert step_parts.part_of(["jit(train_step)/blocks/block/attn/proj/cos"]) == "attn_proj"
    assert step_parts.part_of(["jit(train_step)/train_step/grad_norm/reduce_sum"]) == "grad_norm"
    assert step_parts.part_of(["jit(train_step)/reduce_sum"]) == "step_unscoped"
    assert step_parts.part_of([]) == "step_unscoped"
    # an instruction name two programs share: the first part any of its names is in
    assert step_parts.part_of(["jit(f)/add", under + "block/norm/ln_1/mul"]) == "norm"


def test_scope_table_report(step_trace):
    from benchmark.tools import scope_table

    _, run, reduction, instructions = step_trace
    report = scope_table.report_of(run, reduction)
    assert report["steps"] == 2 and len(report["ops"]) == len(instructions)
    assert report["parts_ms_per_step"] == pytest.approx(report["busy_ms_per_step"])
    assert {"op", "part", "ms_per_step", "op_name", "hlo"} <= set(report["ops"][0])
    assert report["ops"][0]["hlo"].startswith("%" + report["ops"][0]["op"] + " = ")
    assert "dsa_index_loss_attrs" not in report  # no ``trainer/dsa`` span in this trace
    loose = report["step_unscoped_ms_per_step"]
    unnamed = sum(1 for _, op_name in instructions if op_name is None)
    assert loose["without_op_name"] == pytest.approx(unnamed * US_IN_MS / 2)
    assert sum(loose.values()) == pytest.approx(next(
        p["ms_per_step"] for p in report["parts"] if p["part"] == "step_unscoped"))


def test_scope_table_prints_the_index_loss_attrs(tmp_path):
    from benchmark.tools import scope_table

    host = """lines { id: 2 name: "fence" timestamp_ns: 1000
      events { metadata_id: 2 offset_ps: 9000000 duration_ps: 1000
        stats { metadata_id: 1 int64_value: 1 } stats { metadata_id: 2 int64_value: 2176 }
        stats { metadata_id: 3 int64_value: 384 } } }
    event_metadata { key: 2 value { id: 2 name: "trainer/dsa" } }
    stat_metadata { key: 1 value { id: 1 name: "index_loss_kernel" } }
    stat_metadata { key: 2 value { id: 2 name: "index_loss_tiles" } }
    stat_metadata { key: 3 value { id: 3 name: "index_loss_tiles_skipped" } }"""
    name = "jit(train_step)/train_step/forward_backward/dsa/index_loss/index_pbar/pallas_call"
    trace_dir = write_step_trace(tmp_path, [("index_pbar.1", name)], steps=1, host=host)
    run = types.SimpleNamespace(trace_dir=trace_dir)
    report = scope_table.report_of(run, reduce_trace(trace_dir, [0]))
    assert report["dsa_index_loss_attrs"] == {
        "index_loss_kernel": 1, "index_loss_tiles": 2176, "index_loss_tiles_skipped": 384}
    assert report["ops"][0]["part"] == "dsa_index_loss"


# -- the round's un-map ------------------------------------------------------
ROUND = """
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 10000000000000 }}
    events {{ metadata_id: 2 offset_ps: 1000000000000 duration_ps: 1000000000000 }}
    events {{ metadata_id: 3 offset_ps: 1000000000000 duration_ps: 200000000000 }}
    {unmap}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "server/round" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "node/set_broadcast" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "transport/get" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "transport/unmap" }} }} }}
"""
UNMAP = "events { metadata_id: 4 offset_ps: 1200000000000 duration_ps: 700000000000 }"


def _round_trace(tmp_path, unmap: str):
    return types.SimpleNamespace(
        trace_dir=write_xspace(tmp_path, ROUND.format(unmap=unmap)))


def test_round_unmap_takes_its_seconds_out_of_the_unattributed(tmp_path):
    """A 10 s round whose ``node/set_broadcast`` (1 s) holds a 0.2 s ``get``:
    the 0.7 s un-map after it is unattributed in the parent's trace and
    ``round_unmap_s`` in the change's; the two sum to the same."""
    parent = _round_trace(tmp_path / "parent", "")
    change = _round_trace(tmp_path / "change", UNMAP)
    unmap, rest = reader("round_unmap_s"), reader("round_unattributed_s")
    assert unmap.read(parent, None) is None
    assert unmap.read(change, None) == pytest.approx(0.7)
    assert rest.read(parent, None) == pytest.approx(9.8)
    assert rest.read(change, None) + unmap.read(change, None) == pytest.approx(9.8)
    assert unmap.read(types.SimpleNamespace(trace_dir=None), None) is None
