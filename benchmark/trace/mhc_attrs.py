"""The static count ``Trainer.fit`` writes on its ``trainer/steps`` span when
the compiled step's blocks are hyper-connected: ``mhc_sublayers`` (the
sublayers whose maps, read-in and write-back a step runs; ``mhc_streams``, the
residual streams, rides beside it and has no reader yet). A program with one
residual stream (a parent commit, another model) does not write it, and a
reader gets ``None``."""

from __future__ import annotations

from benchmark.trace.span_attrs import mean_attr

STEPS_SPAN = "trainer/steps"


def mhc_sublayers(run) -> int | None:
    """How many hyper-connected sublayers the traced step held."""
    if run.trace_dir is None:
        return None
    value = mean_attr(run, STEPS_SPAN, "mhc_sublayers")
    return int(value) if value else None
