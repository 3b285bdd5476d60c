"""HTTP and render: the longest the serving process's event loop was
kept from a beat of its heartbeat in the window (ms), from
``pio_serve_loop_lag_seconds`` (booked over 25 ms); 0.0 where it never
was. Says whether a window's ``stall_max_ms`` was the loop."""


def read(ctx):
    from benchmark import stalls

    return stalls.longest_ms(ctx, "loop_lag_max_ms",
                             "pio_serve_loop_lag_seconds")
