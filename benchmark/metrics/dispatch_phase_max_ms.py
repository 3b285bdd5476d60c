"""Scheduler: the longest run of one phase of the dispatcher's cycle in
the window (ms; its own time, ``wait`` aside), from
``pio_serve_phase_long_seconds{phase}`` (booked over 25 ms); 0.0 where
none ran that long. Prints the phase it came from. Says whether a
window's ``stall_max_ms`` was a dispatch that ran long."""


def read(ctx):
    from benchmark import stalls

    return stalls.longest_ms(ctx, "dispatch_phase_max_ms",
                             "pio_serve_phase_long_seconds", by="phase")
