"""The dispatcher's cycle over the window, from the server's own
counters: the delta of ``pio_serve_phase_seconds_total{phase}`` between
the two scrapes (seconds a dispatcher thread spent in each phase; the
phases tile its time) and the dispatches it made (the delta of
``pio_serve_batch_size_count``). Read by the ``dispatch*`` metrics."""

from __future__ import annotations

PHASES = "pio_serve_phase_seconds_total"


def phase_seconds(ctx) -> dict | None:
    """{phase: seconds in the window}; None where the server exports no
    such series (a program from before the spans)."""
    before, after = ctx["scrape0"], ctx["scrape1"]
    out = {}
    for (name, labels), v in after.items():
        if name == PHASES:
            phase = dict(labels).get("phase", "")
            out[phase] = out.get(phase, 0.0) + v - before.get(
                (name, labels), 0.0)
    return out or None


def dispatches(ctx) -> float:
    from benchmark import prom

    return prom.delta(ctx["scrape0"], ctx["scrape1"],
                      "pio_serve_batch_size_count")


def per_dispatch_ms(ctx) -> dict | None:
    """{phase: ms a dispatch}; None without the series or without a
    dispatch in the window."""
    phases = phase_seconds(ctx)
    n = dispatches(ctx)
    if phases is None or n <= 0:
        return None
    return {p: 1e3 * s / n for p, s in sorted(phases.items())}


def show(parts: dict) -> str:
    return ", ".join(f"{p} {v:.4f}" for p, v in parts.items())
