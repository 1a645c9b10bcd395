"""The static counts ``Trainer.fit`` writes on its ``trainer/steps`` span when
the compiled step's layers are one branch each and its Mamba-2 mixers have
groups of B and C: ``mamba_groups`` beside ``mamba_layers``, and the other two
kinds' counts, ``moe_layers`` and ``attention_layers``. A program without them
(a parent commit, another model) writes none, and a reader gets ``None``."""

from __future__ import annotations

from benchmark.trace.span_attrs import mean_attr

STEPS_SPAN = "trainer/steps"


def static_count(run, attr: str) -> int | None:
    """The whole number the traced step's ``trainer/steps`` span carries as
    ``attr``, by the program's word."""
    if run.trace_dir is None:
        return None
    value = mean_attr(run, STEPS_SPAN, attr)
    return int(value) if value else None
