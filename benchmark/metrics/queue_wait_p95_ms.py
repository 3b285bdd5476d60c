"""Scheduler: the 95th percentile of the admission-queue wait over the
window, from the delta of ``pio_serve_queue_wait_seconds`` (ms; the
histogram's buckets double, so this is coarse)."""


def read(ctx):
    from benchmark import prom

    buckets = prom.histogram_delta(ctx["scrape0"], ctx["scrape1"],
                                   "pio_serve_queue_wait_seconds")
    q = prom.histogram_quantile(buckets, 0.95)
    return None if q is None else q * 1e3
