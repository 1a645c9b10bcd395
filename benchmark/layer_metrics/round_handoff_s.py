"""Layer: client trainer, inside a round. Seconds of a round between the
parameter plane and the train loop: ``trainer/set_parameters`` (flat host
arrays onto the device), ``trainer/get_parameters`` (back) and
``client/pseudo_grad_norm_time`` (the difference and its two norms on the
host), summed over a round's clients inside each ``server/round`` of the
trace; the median over the trace's rounds. Moves ``round_s``."""

from benchmark.trace import host_spans as hs


def read(run, reduction):
    return hs.per_unit(hs.host_spans(run.trace_dir), "server/round",
                       hs.named_self_seconds("trainer/set_parameters",
                                             "trainer/get_parameters",
                                             "client/pseudo_grad_norm_time"))
