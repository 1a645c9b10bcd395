"""Counters the program writes as attributes of its host spans, read from a
trace: ``Trainer.fit`` fetches the dropless expert layers' routing counters
with the loss at its fence and writes them on a ``trainer/moe_load`` span
(``rows_held``, ``max_expert_load``), as it writes the flash kernel's tiles
on ``trainer/steps``. A program without the span (a parent commit, a model
without such layers) gives ``None``."""

from __future__ import annotations

from benchmark.trace import host_spans as hs

MOE_LOAD_SPAN = "trainer/moe_load"


def mean_attr(run, span: str, attr: str) -> float | None:
    """The mean, over the trace's spans named ``span``, of their numeric
    attribute ``attr``; ``None`` where no span carries it."""
    values = [float(s.stats[attr])
              for s in hs.named(hs.host_spans(run.trace_dir), span)
              if attr in s.stats]
    return sum(values) / len(values) if values else None
