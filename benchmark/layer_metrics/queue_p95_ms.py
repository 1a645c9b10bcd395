"""Layer: serve scheduler (``serve/scheduler.py``). 95th percentile, over the
window's requests, of the time between ``submit`` and admission to a slot
(``ServeRequest.t_admit - t_submit``, the scheduler's own clock, read from
the requests the driver saw go into ``ContinuousBatcher.submit``).
Moves ``ttft_p95_ms``."""

from benchmark.harness import percentile


def read(run, reduction):
    waits = run.samples.get("queue_s")
    return 1000.0 * percentile(waits, 95) if waits else None
