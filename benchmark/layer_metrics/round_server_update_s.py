"""Layer: round loop (``strategy/``). Seconds of a round the server spends on
the clients' results: ``server/agg_decode_time`` and ``server/agg_fold_time``
(the streaming average, pool workers included) and ``server/update``
(pseudo-gradient, server rule, norms), summed inside each ``server/round`` of
the trace; the median over the trace's rounds. Moves ``round_s``."""

from benchmark.trace import host_spans as hs


def read(run, reduction):
    return hs.per_unit(hs.host_spans(run.trace_dir), "server/round",
                       hs.named_self_seconds("server/agg_decode_time",
                                             "server/agg_fold_time",
                                             "server/update"))
