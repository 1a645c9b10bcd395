"""Request traffic from a traffic file and a seed: one general generator.

Every seed gets the SAME set of prompt lengths, answer lengths and gaps
between arrivals (the quantiles of the mix's distributions), in another
order and with other token ids: a run's work does not depend on its seed, so
runs of different seeds are as alike as two runs of one.
"""

from __future__ import annotations

import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int, hi: int) -> np.ndarray:
    """``n`` whole numbers at the mid-quantiles of a log-normal, clipped."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in q])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def poisson_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` gaps at the mid-quantiles of the exponential distribution of
    rate ``rate``, scaled so that they span exactly ``n / rate`` seconds."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (n / rate) / gaps.sum()


def open_loop_requests(traffic: dict, seed: int, seconds: float, vocab: int,
                       rate: float | None = None) -> list[dict]:
    """Requests due over ``seconds`` at the mix's fixed rate."""
    rate = float(traffic["rate_per_s"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(seed), 7])
    p, a = traffic["prompt_tokens"], traffic["answer_tokens"]
    prompts = rng.permutation(lognormal_quantiles(n, p["median"], p["sigma"], p["min"], p["max"]))
    answers = rng.permutation(lognormal_quantiles(n, a["median"], a["sigma"], a["min"], a["max"]))
    due = np.cumsum(rng.permutation(poisson_gaps(n, rate)))
    due -= due[0]
    return [{"id": i, "due_s": float(due[i]), "max_new": int(answers[i]),
             "prompt": rng.integers(0, vocab, int(prompts[i])).tolist()}
            for i in range(n)]
