"""Layer: round loop (``federation/client_runtime.py``). Seconds of a round in
which a node lets the previous broadcast go once the new one is read: the
program's ``transport/unmap`` spans (on the shm plane the last reference to
the old arrays un-maps the server's previous segment) on every thread,
summed inside each ``server/round`` of the trace; the median over the
trace's rounds. A leaf span: ``round_unattributed_s`` no longer holds this
time. Moves ``round_s``."""

from benchmark.trace import host_spans as hs


def read(run, reduction):
    return hs.per_unit(hs.host_spans(run.trace_dir), "server/round",
                       hs.named_self_seconds("transport/unmap"))
