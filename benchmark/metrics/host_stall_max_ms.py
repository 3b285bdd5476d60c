"""Serving process: the longest the serving process ran no Python in
the window (ms): its stall watch woke that late, because one thread held
the interpreter or because the process or its host did not run. From
``pio_host_stall_seconds`` (booked over 25 ms); 0.0 where it never
stood. The stall line in the deploy's log names a holder where there
was one; a witness outside the process (the load generator late at the
same instant) names a pause of the host."""


def read(ctx):
    from benchmark import stalls

    return stalls.longest_ms(ctx, "host_stall_max_ms",
                             "pio_host_stall_seconds")
