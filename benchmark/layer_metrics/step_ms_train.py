"""Layer: client trainer. Median milliseconds of one optimizer step: each
``trainer/fit`` span of the window (``steps_per_fit`` steps closed by the
trainer's fence) divided by its steps. Moves ``train_tokens_per_s``."""

from benchmark.harness import median


def read(run, reduction):
    spans = run.span_seconds("trainer/fit")
    if not spans:
        return None
    return 1000.0 * median(spans) / run.traffic["steps_per_fit"]
