"""Layer: round loop (``federation/server.py``, ``driver.py``,
``transport.py``, ``strategy/``). Seconds of a round that are not a client's
training loop: the window's mean ``server/round`` span minus the clients'
summed ``client/fit_time`` (History) of the same rounds. It holds broadcast,
parameter transport to and from the device, aggregation, the server update
and the checkpoint. Moves ``round_s``."""

from benchmark.harness import median


def read(run, reduction):
    rounds = run.span_seconds("server/round")
    fits = run.samples.get("client/fit_time")
    if not rounds or not fits:
        return None
    # History holds the sample-weighted mean over a round's clients
    return median(rounds) - run.counters["clients_per_round"] * median(fits)
