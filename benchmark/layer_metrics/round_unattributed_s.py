"""Layer: round loop. Seconds of a round that no span names: the harness's
``server/round`` event minus the union of the program's leaf spans inside it,
on every line (the checkpoint writer's background span left out: nothing
waits for it). What is left is host work that still has no name; the median
over the trace's rounds. Moves ``round_s``."""

from benchmark.trace import host_spans as hs


def read(run, reduction):
    return hs.per_unit(hs.host_spans(run.trace_dir), "server/round",
                       hs.unattributed_seconds)
