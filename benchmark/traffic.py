"""The one general traffic generator: a mix file → a schedule.

A traffic mix is a data file ``benchmark/traffic/<traffic>.json`` of
parameters; this module turns it, a rate, a length and a seed into due
times and users. It imports neither jax nor the program, so the load
generator's process can use it.

Arrivals. ``{"process": "poisson"}`` is a Poisson process conditioned on
its count: every period of ``period_s`` seconds gets the same number of
arrivals (rate × period, the remainder carried so that the total is
rate × length to the request), at uniform instants. A share
``burst_share`` of each period's arrivals falls inside one window of
``burst_s`` seconds whose offset within the period comes from the seed,
the rest outside it; ``burst_share`` 0 is the plain process. So every
seed offers the same amount of work with the same shape, in another
order — seeds do not change the load.

Users. ``zipf``: known users drawn Zipf(s) over a seeded permutation of
the user rows.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def arrival_times(arrivals: dict, rate: float, length_s: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Sorted due times in [0, length_s), exactly round(rate × length_s)
    of them."""
    period = float(arrivals.get("period_s", 1.0))
    share = float(arrivals.get("burst_share", 0.0))
    burst = float(arrivals.get("burst_s", 0.0))
    if arrivals.get("process", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {arrivals!r}")
    if share > 0 and not 0 < burst < period:
        raise ValueError("burst_s must lie inside the period")
    total = int(round(rate * length_s))
    n_periods = int(np.ceil(length_s / period - 1e-9))
    out = []
    placed = 0
    for p in range(n_periods):
        start = p * period
        span = min(period, length_s - start)
        # the count up to the end of this period, to the request
        upto = int(round(total * (start + span) / length_s))
        n = upto - placed
        placed = upto
        n_in = int(round(n * share)) if span >= period - 1e-9 else 0
        t = rng.uniform(0.0, 1.0, n)
        if n_in:
            off = rng.uniform(0.0, period - burst)
            inside = off + t[:n_in] * burst
            # the rest falls on the period with the burst window cut out
            rest = t[n_in:] * (period - burst)
            rest = np.where(rest >= off, rest + burst, rest)
            t_abs = np.concatenate([inside, rest])
        else:
            t_abs = t * span
        out.append(start + t_abs)
    times = np.sort(np.concatenate(out)) if out else np.zeros(0)
    return times


def draw_users(query: dict, n_users: int, count: int,
               rng: np.random.Generator) -> np.ndarray:
    """``count`` user rows by the mix's popularity law."""
    pop = query.get("user_popularity", {"dist": "zipf", "s": 1.0})
    if pop.get("dist") != "zipf":
        raise ValueError(f"unknown user popularity {pop!r}")
    s = float(pop.get("s", 1.0))
    weights = np.arange(1, n_users + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    ranks = np.searchsorted(cdf, rng.uniform(0.0, 1.0, count))
    perm = rng.permutation(n_users)
    return perm[np.minimum(ranks, n_users - 1)]


def schedule(mix: dict, rate: float, length_s: float, n_users: int,
             seed: int):
    """(due times [n], user rows [n]) for ``length_s`` seconds of the
    mix at ``rate`` requests a second."""
    rng = np.random.default_rng([int(seed), 0x7261])
    times = arrival_times(mix["arrivals"], rate, length_s, rng)
    users = draw_users(mix["query"], n_users, len(times), rng)
    return times, users
