"""Scheduler: mean queries fused into one dispatch over the window, the
delta of ``pio_serve_batch_size`` sum over count."""


def read(ctx):
    from benchmark import prom

    n = prom.delta(ctx["scrape0"], ctx["scrape1"],
                   "pio_serve_batch_size_count")
    if n <= 0:
        return None
    return prom.delta(ctx["scrape0"], ctx["scrape1"],
                      "pio_serve_batch_size_sum") / n
