"""Scoring, sequence cell: unpacking the fetched array and booking the
sequence engine's counters, one dispatch (ms): the delta of phase
``book`` over the dispatches of the window. Nothing where the server
has no such phase."""


def read(ctx):
    from benchmark import cycle

    ms = cycle.per_dispatch_ms(ctx)
    if ms is None or "book" not in ms:
        return None
    print(f"dispatch_book_ms: {ms['book']:.4f} ms a dispatch of "
          f"{cycle.dispatches(ctx):.0f}", flush=True)
    return ms["book"]
