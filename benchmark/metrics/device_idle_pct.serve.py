"""Device: the share of the traced window in which no op ran on the
chip (%): 1 − the union of the device-op intervals over the window."""


def read(ctx):
    from benchmark import trace_reduce

    if ctx["trace"] is None:
        return None
    busy = trace_reduce.busy_seconds(ctx["trace"])
    if busy is None:
        return None
    a, b = ctx["trace_window"]
    return 100.0 * max(1.0 - busy / (b - a), 0.0)
