"""Layer: client trainer (``data/``). Milliseconds a step waits for its
batch: the mean ``trainer/next_batch`` span (``Trainer.fit`` taking the next
batch from its prefetcher) over the trace's steps. Moves
``train_tokens_per_s``."""

from benchmark.trace import host_spans as hs


def read(run, reduction):
    waits = hs.named(hs.host_spans(run.trace_dir), "trainer/next_batch")
    if not waits:
        return None
    return 1000.0 * sum(s.seconds for s in waits) / len(waits)
