"""One partition of a training step's device time.

``PARTS`` is an ordered table ``part -> pattern`` over an operation's
``op_name`` (``op_scopes.op_names``: the program's ``jax.named_scope``s and
the kernels' own names, as the trace's event metadata hold them). An
operation falls in the FIRST part whose pattern one of its ``op_name``s
matches, so every operation of a traced step is in exactly one part and the
parts sum to the device's busy time: the finest scope wins (a kernel's launch
before the scope around it, a block's scope before the step's stage), and the
last two rows are remainders: under ``train_step/forward_backward`` with
nothing finer (the layer scan's stacking copies and loop carries, residual
adds, the embedding, what surrounds a kernel's launch), and under no
``train_step/`` scope at all (an operation without an ``op_name`` too).

A part's name is its reader's without ``_ms_train``. For the parts an older
reader covers, the pattern is that reader's own, and no earlier row matches
an operation it matches (``benchmark/tests/test_step_parts.py`` holds both on
compiled toy steps of every family); those readers stay as they are. A
fusion has ONE ``op_name``, its root's: a part holds the operations that kept
its name, not every instruction traced under it.
"""

from __future__ import annotations

import re

from benchmark.trace import host_spans
from benchmark.trace.op_scopes import op_names

PARTS: tuple[tuple[str, str], ...] = (
    # the kernels' launches, by their own names (``flash_*_ms_train``);
    # ``index_pbar``'s launch stays inside ``dsa/index_loss``
    ("flash_fwd", r"\bflash_fwd/multihead_attention\b.*pallas_call"),
    ("flash_bwd", r"\bflash_d(q|kv)/multihead_attention\b.*pallas_call"),
    # a family's scopes
    ("mla_proj", r"\bmla/proj\b"),
    ("moe_dispatch", r"\bmoe/(router|dispatch)\b"),
    ("moe_experts", r"\bmoe/(experts|shared_expert)\b"),
    ("mamba_proj", r"\bmamba/proj\b"),
    ("mamba_conv", r"\bmamba/conv\b"),
    ("mamba_scan", r"\bmamba/scan\b"),
    ("dsa_indexer", r"\bdsa/indexer\b"),
    ("dsa_select", r"\bdsa/select\b"),
    ("dsa_index_loss", r"\bdsa/index_loss\b"),
    # every block's
    ("mlp", r"\bblock/mlp\b"),
    ("attn_proj", r"\battn/proj\b"),
    ("norm", r"\b(block/norm|attn/qk_norm|mamba/gate_norm)\b"),
    # the step's stages
    ("loss_head", r"train_step/loss_head"),
    ("optimizer", r"train_step/optimizer"),
    ("grad_norm", r"train_step/grad_norm"),
    # the two remainders
    ("fwd_bwd_rest", r"train_step/forward_backward"),
    ("step_unscoped", r""),
)
#: parts that are read even at 0: a remainder of 0 is a reading, a scope's 0
#: is a program without the scope (a parent commit, another family)
REMAINDERS = ("fwd_bwd_rest", "step_unscoped")
HLO_CHARS = 240
_COMPILED = tuple((part, re.compile(pattern)) for part, pattern in PARTS)


def part_of(names) -> str:
    """The part of an operation with these ``op_name``s (none: the last)."""
    names = tuple(names)
    for part, rx in _COMPILED[:-1]:
        if any(rx.search(n) for n in names):
            return part
    return _COMPILED[-1][0]


def steps_of(run) -> int:
    """The optimizer steps a trace holds: one ``trainer/next_batch`` span of
    the program each, as ``op_scopes.device_ms_per_step`` counts them."""
    return len(host_spans.named(host_spans.host_spans(run.trace_dir),
                                "trainer/next_batch"))


def parts_table(run, reduction) -> dict | None:
    """The whole partition of a reduced trace: ``steps``; ``parts``, a row
    for every part of the table in its order (``ms_per_step``, ``share`` of
    all parts, ``ops``, the count of distinct operations); ``ops``, every
    operation with its part, ``ms_per_step``, one ``op_name`` and the head of
    its instruction text, the largest first. ``None`` where the trace holds no
    step."""
    steps = steps_of(run)
    if not steps:
        return None
    names = op_names(run.trace_dir)
    ops = []
    for e in reduction["ops"]:
        mine = sorted(names.get(e["name"], ()))
        ops.append({"op": e["name"], "part": part_of(mine),
                    "ms_per_step": 1000.0 * e["seconds"] / steps,
                    "op_name": mine[0] if mine else "",
                    # shapes and operands: what a name like ``reshape.3956`` is
                    "hlo": e["scope"][:HLO_CHARS]})
    total = sum(o["ms_per_step"] for o in ops)
    parts = []
    for part, _ in PARTS:
        mine = [o["ms_per_step"] for o in ops if o["part"] == part]
        parts.append({"part": part, "ms_per_step": sum(mine),
                      "share": sum(mine) / total if total else 0.0,
                      "ops": len(mine)})
    return {"steps": steps, "total_ms_per_step": total, "parts": parts, "ops": ops}


def part_ms_per_step(run, reduction, part: str) -> float | None:
    """Device milliseconds per optimizer step of the operations in ``part``.
    ``None`` where the trace holds no step, and for a scope's part that holds
    nothing (the program has no such scope)."""
    if part not in dict(PARTS):
        raise KeyError(f"no part {part!r} in step_parts.PARTS")
    table = parts_table(run, reduction)
    if table is None:
        return None
    row = next(p for p in table["parts"] if p["part"] == part)
    if not row["ops"] and part not in REMAINDERS:
        return None
    return row["ms_per_step"]
