"""Layer: serve engine (``serve/engine.py``, ``cache.py``). Mean share of the
engine's slots that held a request, sampled from ``ContinuousBatcher.stats()``
through the window. Moves ``itl_p95_ms``: more rows in a step, longer steps."""


def read(run, reduction):
    samples = run.samples.get("slot_occupancy")
    return 100.0 * sum(samples) / len(samples) if samples else None
