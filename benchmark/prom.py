"""Reading the server's ``/metrics`` (Prometheus text) as window deltas."""

from __future__ import annotations

import urllib.request


def parse(text: str) -> dict:
    """{(name, frozenset(label pairs)): value} of an exposition text."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name, brace, rest = head.partition("{")
        labels = []
        if brace:
            for part in rest.rstrip("}").split(","):
                k, _, v = part.partition("=")
                if k:
                    labels.append((k.strip(), v.strip().strip('"')))
        try:
            out[(name, frozenset(labels))] = float(value)
        except ValueError:
            continue
    return out


def scrape(base: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(base + "/metrics", timeout=timeout) as r:
        return parse(r.read().decode())


def value(samples: dict, name: str, **labels) -> float:
    """Sum of the series of ``name`` whose labels include ``labels``."""
    want = set(labels.items())
    return sum(v for (n, ls), v in samples.items()
               if n == name and want <= set(ls))


def delta(before: dict, after: dict, name: str, **labels) -> float:
    return value(after, name, **labels) - value(before, name, **labels)


def histogram_delta(before: dict, after: dict, name: str) -> list:
    """[(upper bound, count in the window)] per bucket, not cumulative,
    summed over the label children."""
    bounds = {}
    for samples, sign in ((after, 1.0), (before, -1.0)):
        for (n, ls), v in samples.items():
            if n != name + "_bucket":
                continue
            le = dict(ls)["le"]
            bound = float("inf") if le == "+Inf" else float(le)
            bounds[bound] = bounds.get(bound, 0.0) + sign * v
    cum = sorted(bounds.items())
    out, prev = [], 0.0
    for bound, c in cum:
        out.append((bound, c - prev))
        prev = c
    return out


def histogram_quantile(buckets: list, q: float):
    """The q-quantile of a bucketed sample, linear inside the bucket (as
    Prometheus does); None when the window saw nothing."""
    total = sum(c for _b, c in buckets)
    if total <= 0:
        return None
    rank = q * total
    seen, lower = 0.0, 0.0
    for bound, c in buckets:
        if c > 0 and seen + c >= rank:
            if bound == float("inf"):
                return lower
            return lower + (bound - lower) * (rank - seen) / c
        seen += c
        if bound != float("inf"):
            lower = bound
    return lower
