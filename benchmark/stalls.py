"""The longest stall of one kind in the window, from the serving
process's own histograms (``obs/trace.py``): the upper bound of the
highest bucket the window filled, as ``gc_pause_max_ms`` reads
``pio_gc_pause_seconds``. Read by ``dispatch_phase_max_ms``,
``loop_lag_max_ms`` and ``host_stall_max_ms``."""

from __future__ import annotations

INF = float("inf")


def longest_ms(ctx, metric: str, name: str, by: str | None = None,
               **want) -> float | None:
    """The window's longest observation of histogram ``name`` among the
    children whose labels include ``want``, in ms; 0.0 where the window
    filled no bucket; None where the server exports no such child (a
    program from before the series). Prints the window's count by the
    label ``by`` and the child the longest came from."""
    before, after = ctx["scrape0"], ctx["scrape1"]

    def picked(labels) -> bool:
        return all(labels.get(k) == v for k, v in want.items())

    if not any(n == name + "_count" and picked(dict(ls))
               for n, ls in after):
        return None
    cums: dict = {}     # child → {bound: cumulative count in the window}
    for (n, ls), v in after.items():
        labels = dict(ls)
        if n != name + "_bucket" or not picked(labels):
            continue
        le = labels.pop("le")
        child = labels.get(by, "") if by else ""
        cums.setdefault(child, {})[INF if le == "+Inf" else float(le)] = \
            v - before.get((n, ls), 0.0)
    counts, top, finite = {}, (0.0, None), 0.0
    for child, cum in sorted(cums.items()):
        prev = 0.0
        for bound, c in sorted(cum.items()):
            if c > prev and bound >= top[0]:
                top = (bound, child)
            prev = c
            if bound != INF:
                finite = max(finite, bound)
        counts[child] = cum.get(INF, prev)
    if by:
        longest = "" if top[1] is None else \
            f", the longest in {top[1]!r}"
        print(f"{metric}: by {by} "
              f"{ {k: int(v) for k, v in counts.items()} } in the window"
              f"{longest}", flush=True)
    else:
        print(f"{metric}: {int(sum(counts.values()))} in the window",
              flush=True)
    if top[1] is None:
        return 0.0
    if top[0] == INF:
        print(f"{metric}: a stall ran past the last bucket, {finite} s",
              flush=True)
        return finite * 1e3
    return top[0] * 1e3
