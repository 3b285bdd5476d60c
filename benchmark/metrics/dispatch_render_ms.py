"""HTTP and render: arrays to response bytes, one dispatch (ms): the
delta of phase ``render`` over the dispatches of the window."""


def read(ctx):
    from benchmark import cycle

    ms = cycle.per_dispatch_ms(ctx)
    if ms is None:
        return None
    render = ms.get("render", 0.0)
    print(f"dispatch_render_ms: {render:.4f} ms a dispatch of "
          f"{cycle.dispatches(ctx):.0f}", flush=True)
    return render
