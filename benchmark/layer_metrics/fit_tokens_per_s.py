"""Layer: client trainer, inside a round. Tokens per second of the clients'
training loops alone (``client/tokens_per_sec`` from History, median over the
window's rounds): what ``mpt125m-train`` measures, seen from inside the round.
Moves ``round_s``."""

from benchmark.harness import median


def read(run, reduction):
    return median(run.samples.get("client/tokens_per_sec", []))
