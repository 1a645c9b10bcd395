"""Layer: round loop (``federation/transport.py``). Seconds of a round inside
the parameter plane: the program's ``transport/put``, ``transport/get`` and
``transport/free`` spans (the plane's write, read and release alone, whoever
calls: server broadcast, node copy, client upload, server fetch) on every
thread, summed inside each ``server/round`` of the trace; the median over
the trace's rounds. Moves ``round_s``."""

from benchmark.trace import host_spans as hs


def read(run, reduction):
    return hs.per_unit(hs.host_spans(run.trace_dir), "server/round",
                       hs.named_self_seconds("transport/put", "transport/get",
                                             "transport/free"))
