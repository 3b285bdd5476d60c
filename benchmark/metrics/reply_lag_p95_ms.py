"""HTTP and render: the 95th percentile of the time an answer the
dispatcher has finished waits for the event loop to pick it up (ms), from
the delta of ``pio_serve_reply_lag_seconds`` (one query of each dispatch is
sampled; buckets in steps of at most 1.5)."""


def read(ctx):
    from benchmark import prom

    buckets = prom.histogram_delta(ctx["scrape0"], ctx["scrape1"],
                                   "pio_serve_reply_lag_seconds")
    q = prom.histogram_quantile(buckets, 0.95)
    if q is None:
        return None
    p50 = prom.histogram_quantile(buckets, 0.5)
    print(f"reply_lag_p95_ms: {sum(c for _b, c in buckets):.0f} samples, "
          f"p50 {p50 * 1e3:.4f} ms, p95 {q * 1e3:.4f} ms", flush=True)
    return q * 1e3
