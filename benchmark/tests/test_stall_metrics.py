"""The readers of the serving process's stalls and of the sequence
engine's ``book`` phase against hand-made scrapes: a window that filled
a bucket, a window that filled none (0.0), and a server without the
series (the parent of the PR that added them), where each returns
nothing and none raises."""

import pytest

from benchmark import prom, run

STALLS = ["dispatch_phase_max_ms", "loop_lag_max_ms", "host_stall_max_ms"]
BOUNDS = ["0.025", "0.037", "0.0549", "0.0813", "0.121", "0.179", "+Inf"]


def histogram(name, cum, labels=""):
    sep = "," if labels else ""
    lines = [f'{name}_bucket{{{labels}{sep}le="{b}"}} {c}'
             for b, c in zip(BOUNDS, cum)]
    tail = f"{{{labels}}}" if labels else ""
    return lines + [f"{name}_count{tail} {cum[-1]}",
                    f"{name}_sum{tail} {cum[-1] * 0.05}"]


def exposition(phase_long, loop, host, book=0.5):
    lines = []
    for phase, cum in phase_long.items():
        lines += histogram("pio_serve_phase_long_seconds", cum,
                           f'phase="{phase}"')
    lines += histogram("pio_serve_loop_lag_seconds", loop)
    lines += histogram("pio_host_stall_seconds", host)
    lines += [f'pio_serve_phase_seconds_total{{phase="{p}"}} {v!r}'
              for p, v in {"wait": 5.0, "fetch": 20.0, "other": 0.4,
                           "book": book}.items()]
    lines += ["pio_serve_batch_size_count 1000",
              "pio_serve_batch_size_sum 1200"]
    return prom.parse("\n".join(lines) + "\n")


ZERO = [0] * 7
BEFORE = exposition({"fetch": [3, 9, 9, 9, 9, 9, 9], "other": ZERO},
                    [1, 1, 1, 1, 1, 1, 1], [0, 0, 0, 0, 0, 2, 2])
# the window: fetches up to (0.0549, 0.0813], one dispatch's own time in
# (0.179, +Inf), a loop beat in (0.0549, 0.0813], the process held in
# (0.0813, 0.121] (the two before the window were longer); book 0.6 s
# more over 2,000 dispatches
AFTER = exposition({"fetch": [40, 50, 70, 80, 80, 80, 80],
                    "other": [0, 0, 0, 0, 0, 0, 1]},
                   [1, 1, 1, 2, 2, 2, 2], [0, 0, 0, 0, 1, 3, 3],
                   book=1.1)
AFTER["pio_serve_batch_size_count", frozenset()] = 3000
EMPTY = prom.parse("pio_serve_batch_size_count 1000\n"
                   'pio_serve_phase_seconds_total{phase="wait"} 1.0\n')


def ctx(before, after):
    return {"scrape0": before, "scrape1": after, "window": (2.0, 12.0)}


def test_a_window_that_filled_a_bucket(capsys):
    got = {n: run.read_metric(n, ctx(BEFORE, AFTER))
           for n in STALLS + ["dispatch_book_ms"]}
    # other's run past the last finite bound reads as that bound
    assert got["dispatch_phase_max_ms"] == pytest.approx(179.0)
    assert got["loop_lag_max_ms"] == pytest.approx(81.3)
    assert got["host_stall_max_ms"] == pytest.approx(121.0)
    assert got["dispatch_book_ms"] == pytest.approx(0.3)
    out = capsys.readouterr().out
    assert "dispatch_phase_max_ms: by phase {'fetch': 71, 'other': 1}" \
        in out and "the longest in 'other'" in out
    assert "loop_lag_max_ms: 1 in the window" in out
    assert "host_stall_max_ms: 1 in the window" in out


def test_the_longest_phase_inside_the_last_finite_bucket():
    after = dict(AFTER)
    for key in [k for k in after if dict(k[1]).get("phase") == "other"]:
        after[key] = 0
    got = run.read_metric("dispatch_phase_max_ms", ctx(BEFORE, after))
    assert got == pytest.approx(81.3)


@pytest.mark.parametrize("name", STALLS)
def test_a_window_that_filled_no_bucket_reads_zero(name):
    assert run.read_metric(name, ctx(AFTER, AFTER)) == 0.0


def test_book_reads_zero_where_no_second_moved():
    still = dict(AFTER)
    key = ("pio_serve_phase_seconds_total", frozenset({("phase", "book")}))
    still[key] = BEFORE[key]
    assert run.read_metric("dispatch_book_ms", ctx(BEFORE, still)) == 0.0


@pytest.mark.parametrize("name", STALLS + ["dispatch_book_ms"])
def test_a_server_without_the_series_reads_as_nothing(name):
    assert run.read_metric(name, ctx(EMPTY, EMPTY)) is None
