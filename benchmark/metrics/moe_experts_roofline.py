"""Expert layer: the experts' grouped matrix products' share of their
roofline (%).

Device time of the ops that are the experts' feed-forward — found on the
first chip's ``XLA Ops`` line by the op's own name (the head of its HLO
line, before ``=``; the operands' names are not looked at) holding one of
the parts the configuration lists under ``moe_ops``: ``pio_moe_experts``
(the program's Pallas kernel's custom calls, one a layer) or
``ragged-dot`` (what ``jax.lax.ragged_dot`` compiles to) — against the
least time the chip could take for their own FLOPs and bytes
(``benchmark.seqwork.moe_*``): the three tables of every layer once an
execution of the forward, the routed rows in and out once a query."""


def read(ctx):
    from benchmark import seqwork, trace_reduce, work

    parts = ctx["config"].get("moe_ops")
    if ctx["trace"] is None or not parts:
        return None
    planes = trace_reduce.device_planes(ctx["trace"])
    hit = trace_reduce.module_executions(
        ctx["trace"], ctx["config"]["scoring_module"])
    if not planes or hit is None:
        return None
    seconds = sum(d for n, _s, d in trace_reduce._line(
        planes[0], trace_reduce.OPS_LINE)
        if any(p in n.partition(" = ")[0] for p in parts)) / 1e9
    if seconds <= 0:
        return None
    least, bound = seqwork.moe_least_seconds(
        ctx["config"], ctx["peaks"], hit[0], work.answered_in_trace(ctx))
    print(f"moe_experts_roofline: {seconds:.6f} s in the experts' products "
          f"of {hit[0]} executions, least {least:.6f} s, {bound}-bound",
          flush=True)
    return 100.0 * least / seconds
