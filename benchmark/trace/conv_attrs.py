"""The static count ``Trainer.fit`` writes on its ``trainer/steps`` span when
the compiled step holds gated short-convolution layers: ``conv_layers``. A
program without such layers (a parent commit, another model) does not write
it, and a reader gets ``None``."""

from __future__ import annotations

from benchmark.trace.span_attrs import mean_attr

STEPS_SPAN = "trainer/steps"


def conv_layers(run) -> int | None:
    """How many conv layers the traced step held, by the program's word."""
    if run.trace_dir is None:
        return None
    layers = mean_attr(run, STEPS_SPAN, "conv_layers")
    return int(layers) if layers else None
