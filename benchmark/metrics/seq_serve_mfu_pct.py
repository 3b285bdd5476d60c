"""Whole step: the share of the chip's peak the served sequence queries
amount to (%): the model FLOPs of one window through the block and the
head (``benchmark.seqwork.query_flops``: causal and windowed pairs only,
no padding), times the queries answered in the traced window, over
window × chips × peak FLOP/s."""


def read(ctx):
    from benchmark import seqwork, work

    if ctx["trace_window"] is None or ctx["peaks"] is None \
            or "window_events" not in ctx["config"]:
        return None
    answered = work.answered_in_trace(ctx)
    if answered <= 0:
        return None
    a, b = ctx["trace_window"]
    return 100.0 * answered * seqwork.query_flops(ctx["config"]) / (
        (b - a) * ctx["chips"] * ctx["peaks"]["flops_per_s"])
