"""Scoring: the sequence forward's share of its roofline (%).

Device time of the serving program's executions in the trace, found by
its XLA module name (the configuration's ``scoring_module``), against the
least time the chip could take for the work ``benchmark.seqwork`` counts:
each execution reads every weight once, and together they answered the
queries that completed inside the traced window."""


def read(ctx):
    from benchmark import seqwork, trace_reduce, work

    if ctx["trace"] is None or "window_events" not in ctx["config"]:
        return None
    hit = trace_reduce.module_executions(
        ctx["trace"], ctx["config"]["scoring_module"])
    if hit is None or hit[1] <= 0:
        return None
    count, seconds = hit
    k = work.next_pow2(ctx["mix"]["query"]["num"])
    least, bound = seqwork.least_seconds(
        ctx["config"], ctx["peaks"], count, work.answered_in_trace(ctx), k)
    print(f"seq_forward_roofline: {count} executions, {seconds:.6f} s on "
          f"the device, least {least:.6f} s, {bound}-bound", flush=True)
    return 100.0 * least / seconds
