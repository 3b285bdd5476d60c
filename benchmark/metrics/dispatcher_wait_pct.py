"""Scheduler: the share of the dispatcher thread's time in which it had
nothing it might pick (%): the delta of phase ``wait`` over the delta of
all phases of ``pio_serve_phase_seconds_total``. The idle this share
explains is want of work, not host work in the chip's way."""


def read(ctx):
    from benchmark import cycle

    phases = cycle.phase_seconds(ctx)
    total = sum(phases.values()) if phases else 0.0
    if total <= 0:
        return None
    a, b = ctx["window"]
    print(f"dispatcher_wait_pct: phases sum {total:.4f} s over a window "
          f"of {b - a:g} s ({cycle.show(phases)} s), "
          f"{cycle.dispatches(ctx):.0f} dispatches", flush=True)
    return 100.0 * phases.get("wait", 0.0) / total
