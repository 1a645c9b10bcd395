"""The static counts ``Trainer.fit`` writes on its ``trainer/steps`` span when
the compiled step holds sliding-window attention layers: ``swa_layers`` and
``sliding_window`` (``swa_executed_share``, the banded launches' pairs
multiplied over the pairs visible, rides beside them and has no reader yet).
A program without such layers (a parent commit, another model) does not write
them, and a reader gets ``None``."""

from __future__ import annotations

from benchmark.trace.span_attrs import mean_attr

STEPS_SPAN = "trainer/steps"


def _static_count(run, attr: str) -> int | None:
    if run.trace_dir is None:
        return None
    value = mean_attr(run, STEPS_SPAN, attr)
    return int(value) if value else None


def swa_layers(run) -> int | None:
    """How many sliding-window layers the traced step held, by the program's word."""
    return _static_count(run, "swa_layers")


def sliding_window(run) -> int | None:
    """The keys a query of such a layer saw, by the program's word."""
    return _static_count(run, "sliding_window")
