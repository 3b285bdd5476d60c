"""Whole step: the share of the chip's peak the served queries amount
to (%): ``2·n_items·rank`` FLOPs per answered query, times the queries
answered in the traced window, over window × chips × peak FLOP/s."""


def read(ctx):
    from benchmark import work

    if ctx["trace_window"] is None or ctx["peaks"] is None:
        return None
    answered = work.answered_in_trace(ctx)
    if answered <= 0:
        return None
    a, b = ctx["trace_window"]
    return 100.0 * work.dispatch_flops(ctx["config"], answered) / (
        (b - a) * ctx["chips"] * ctx["peaks"]["flops_per_s"])
