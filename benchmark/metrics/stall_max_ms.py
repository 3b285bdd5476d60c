"""HTTP and render: the longest time in the window during which at least
one request was outstanding and no answer came (ms, client clock); see
``benchmark.clientlog.completion_gaps``."""


def read(ctx):
    from benchmark import clientlog

    _start, gaps = clientlog.completion_gaps(ctx["log"])
    if not len(gaps):
        return None
    return float(gaps.max() * 1e3)
