"""The static counts ``Trainer.fit`` writes on its ``trainer/steps`` span when
the compiled step holds Mamba-2 layers: ``mamba_layers`` and ``ssd_chunks``
(the chunks each layer's scan walks a row in). A program without such layers
(a parent commit, another model) writes neither, and a reader gets ``None``."""

from __future__ import annotations

from benchmark.trace.span_attrs import mean_attr

STEPS_SPAN = "trainer/steps"


def mamba_layers(run) -> int | None:
    """How many Mamba-2 layers the traced step held, by the program's word."""
    if run.trace_dir is None:
        return None
    layers = mean_attr(run, STEPS_SPAN, "mamba_layers")
    return int(layers) if layers else None
