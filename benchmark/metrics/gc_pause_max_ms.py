"""HTTP and render: the longest garbage collection of the serving
process in the window (ms), as the upper bound of the highest bucket of
``pio_gc_pause_seconds`` (all generations) that the window filled; 0.0
when it filled none. Tells a window that met a full collection from one
that met a freeze of the machine (``stall_max_ms`` sees both)."""

NAME = "pio_gc_pause_seconds"


def read(ctx):
    from benchmark import prom

    if not any(n == NAME + "_count" for n, _l in ctx["scrape1"]):
        return None
    buckets = prom.histogram_delta(ctx["scrape0"], ctx["scrape1"], NAME)
    filled = [b for b, c in buckets if c > 0]
    per_gen = {
        dict(ls)["generation"]: v - ctx["scrape0"].get((n, ls), 0.0)
        for (n, ls), v in ctx["scrape1"].items() if n == NAME + "_count"}
    seconds = prom.delta(ctx["scrape0"], ctx["scrape1"], NAME + "_sum")
    print(f"gc_pause_max_ms: collections by generation "
          f"{dict(sorted(per_gen.items()))}, {seconds * 1e3:.3f} ms in all",
          flush=True)
    if not filled:
        return 0.0
    finite = [b for b, _c in buckets if b != float("inf")]
    if filled[-1] == float("inf"):
        print(f"gc_pause_max_ms: a collection ran past the last bucket, "
              f"{finite[-1]} s", flush=True)
    return min(filled[-1], finite[-1]) * 1e3
