"""Reading the load generator's log (times in seconds from the schedule's
start, on the client's clock)."""

from __future__ import annotations

import numpy as np


def completion_gaps(log: dict):
    """(start, length) of every stretch of the window in which at least
    one request was outstanding and no answer came: the gap between two
    consecutive completions, counted from the later of the first of them
    and the due time of the oldest request still out."""
    sel = log["timed"] & (log["done"] > 0)
    if sel.sum() < 2:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(log["done"][sel], kind="stable")
    done = log["done"][sel][order]
    due = log["due"][sel][order]
    oldest = np.minimum.accumulate(due[::-1])[::-1]
    start = np.maximum(done[:-1], oldest[1:])
    return start, np.maximum(done[1:] - start, 0.0)


def longest_gaps(log: dict, n: int = 3) -> list:
    """[(seconds into the schedule, ms)] of the n longest gaps."""
    start, gaps = completion_gaps(log)
    top = np.argsort(-gaps)[:n]
    return [(round(float(start[i]), 3), round(float(gaps[i] * 1e3), 2))
            for i in top]


def latest_sends(log: dict, n: int = 3) -> list:
    """[(seconds into the schedule, ms late)] of the n requests the
    generator sent latest: a generator frozen together with the server
    shows here at the instant of the server's stall."""
    sel = log["timed"] & (log["sent"] > 0)
    late = (log["sent"] - log["due"])[sel]
    due = log["due"][sel]
    top = np.argsort(-late)[:n]
    return [(round(float(due[i]), 3), round(float(late[i] * 1e3), 2))
            for i in top]
