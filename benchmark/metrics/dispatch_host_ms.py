"""HTTP and render: host work of one dispatch outside the fetch (ms):
the delta of every phase of ``pio_serve_phase_seconds_total`` but
``wait`` and ``fetch``, over the dispatches of the window. What stands
between two launches while the chip has nothing to run."""


def read(ctx):
    from benchmark import cycle

    ms = cycle.per_dispatch_ms(ctx)
    if ms is None:
        return None
    parts = {p: v for p, v in ms.items() if p not in ("wait", "fetch")}
    print(f"dispatch_host_ms: {cycle.show(parts)} ms a dispatch",
          flush=True)
    return sum(parts.values())
