"""Scoring: the blocking fetch of one dispatch (ms): the delta of phase
``fetch`` over the dispatches of the window. Device execution and the
copy down; the device's busy time lies inside it."""


def read(ctx):
    from benchmark import cycle

    ms = cycle.per_dispatch_ms(ctx)
    if ms is None:
        return None
    fetch = ms.get("fetch", 0.0)
    print(f"dispatch_fetch_ms: {fetch:.4f} ms a dispatch of "
          f"{cycle.dispatches(ctx):.0f}, after a launch of "
          f"{ms.get('launch', 0.0):.4f} ms", flush=True)
    return fetch
