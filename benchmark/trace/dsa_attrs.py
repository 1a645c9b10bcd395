"""What ``Trainer.fit`` writes into a trace when its step holds learned sparse
attention: the static ``dsa_layers`` and ``dsa_topk`` on its ``trainer/steps``
span, and at its fence a ``trainer/dsa`` span with the last step's
``picked_pairs``, ``causal_pairs``, ``tiles_visited``, ``tiles_causal`` (each
summed over the layers) and ``index_loss``. A program without them (a parent
commit, another model) writes none, and a reader gets ``None``."""

from __future__ import annotations

from benchmark.trace.span_attrs import mean_attr

STEPS_SPAN = "trainer/steps"
DSA_SPAN = "trainer/dsa"


def dsa_layers(run) -> int | None:
    """How many sparse-attention layers the traced step held, by the
    program's word."""
    if run.trace_dir is None:
        return None
    layers = mean_attr(run, STEPS_SPAN, "dsa_layers")
    return int(layers) if layers else None


def picked_pairs(run) -> float | None:
    """The (query, key) pairs a step's selections picked, summed over the
    layers: the mean over the trace's fits (of each one's last step)."""
    if run.trace_dir is None:
        return None
    return mean_attr(run, DSA_SPAN, "picked_pairs")
