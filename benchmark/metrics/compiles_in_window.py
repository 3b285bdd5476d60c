"""Scoring: serving programs compiled inside the window, the delta of
``pio_serve_compile_cache_size`` (should be 0: the ladder is warm)."""


def read(ctx):
    from benchmark import prom

    name = "pio_serve_compile_cache_size"
    if not any(n == name for n, _l in ctx["scrape1"]):
        return None
    return prom.delta(ctx["scrape0"], ctx["scrape1"], name)
