"""Layer: client trainer (``ops/moe.py``, the dropless expert layer). The
busiest held expert's rows over the held experts' mean, worst layer: the
program's counter ``moe/max_expert_load``, which ``Trainer.fit`` fetches with
the loss at its fence and writes as the ``max_expert_load`` attribute of a
``trainer/moe_load`` span; the mean over the trace's spans (one a fit, of
its last step). 1 is perfect balance; the grouped products' tiles are padded
per expert, so imbalance costs ``train_tokens_per_s`` little, but it is what
an expert-parallel deployment waits for."""

from benchmark.trace.span_attrs import MOE_LOAD_SPAN, mean_attr


def read(run, reduction):
    return mean_attr(run, MOE_LOAD_SPAN, "max_expert_load")
