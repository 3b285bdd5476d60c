"""The readers of the dispatcher's cycle against two hand-made scrapes:
the window's deltas, the window in which a series does not move, and a
server that exports no such series (the parent of the PR that added
them), where every reader returns nothing and none raises."""

import pytest

from benchmark import prom, run

NAMES = ["dispatcher_wait_pct", "dispatch_host_ms", "dispatch_render_ms",
         "dispatch_fetch_ms", "reply_lag_p95_ms", "gc_pause_max_ms"]

# cumulative le-buckets of the two histograms, as the server prints them
LAG_BOUNDS = ["5e-05", "7.43e-05", "0.00011", "0.000164", "+Inf"]
GC_BOUNDS = ["0.00025", "0.000371", "0.0374", "0.0555", "1", "+Inf"]


def exposition(phases, dispatches, lag_cum, gc_cum_by_gen):
    lines = ["# HELP pio_serve_phase_seconds_total seconds by phase",
             "# TYPE pio_serve_phase_seconds_total counter"]
    lines += [f'pio_serve_phase_seconds_total{{phase="{p}"}} {v!r}'
              for p, v in phases.items()]
    lines += [f"pio_serve_batch_size_count {dispatches}",
              f"pio_serve_batch_size_sum {dispatches * 7}"]
    lines += [f'pio_serve_reply_lag_seconds_bucket{{le="{b}"}} {c}'
              for b, c in zip(LAG_BOUNDS, lag_cum)]
    lines += [f"pio_serve_reply_lag_seconds_count {lag_cum[-1]}"]
    for gen, cum in gc_cum_by_gen.items():
        lines += [f'pio_gc_pause_seconds_bucket{{generation="{gen}",'
                  f'le="{b}"}} {c}' for b, c in zip(GC_BOUNDS, cum)]
        lines += [f'pio_gc_pause_seconds_count{{generation="{gen}"}} '
                  f"{cum[-1]}",
                  f'pio_gc_pause_seconds_sum{{generation="{gen}"}} '
                  f"{cum[-1] * 0.001}"]
    return prom.parse("\n".join(lines) + "\n")


BEFORE = exposition(
    {"wait": 10.0, "other": 1.0, "parse": 0.5, "lookup": 0.25,
     "launch": 2.0, "fetch": 30.0, "render": 4.0, "complete": 1.0},
    1000, [100, 150, 180, 200, 200],
    {"0": [50, 50, 50, 50, 50, 50], "2": [0, 0, 0, 1, 1, 1]})
# 10 s later: 2,000 dispatches; wait 1.0, other 0.2, parse 0.1, lookup
# 0.1, launch 0.6, fetch 7.0, render 0.8, complete 0.2 → 10.0 s
AFTER = exposition(
    {"wait": 11.0, "other": 1.2, "parse": 0.6, "lookup": 0.35,
     "launch": 2.6, "fetch": 37.0, "render": 4.8, "complete": 1.2},
    3000, [100, 150, 1080, 1200, 1200],
    {"0": [90, 95, 95, 95, 95, 95], "2": [0, 0, 1, 2, 2, 2]})
EMPTY = prom.parse("pio_serve_batch_size_count 1000\n"
                   "pio_serve_batch_size_sum 7000\n")


def ctx(before, after):
    return {"scrape0": before, "scrape1": after, "window": (2.0, 12.0)}


def test_a_window_that_moved():
    got = {n: run.read_metric(n, ctx(BEFORE, AFTER)) for n in NAMES}
    assert got["dispatcher_wait_pct"] == pytest.approx(10.0)
    # every phase but wait and fetch: 2.0 s over 2,000 dispatches
    assert got["dispatch_host_ms"] == pytest.approx(1.0)
    assert got["dispatch_render_ms"] == pytest.approx(0.4)
    assert got["dispatch_fetch_ms"] == pytest.approx(3.5)
    # 1,000 replies, 900 of them in (7.43e-05, 0.00011]: rank 950 is
    # past them, 50 of the 100 in the next bucket
    assert got["reply_lag_p95_ms"] == pytest.approx(
        1e3 * (0.00011 + (0.000164 - 0.00011) * 50 / 100))
    # the longest: the full collection that filled (0.000371, 0.0374]
    # in this window; the one in (0.0374, 0.0555] was there before it
    assert got["gc_pause_max_ms"] == pytest.approx(37.4)


def test_a_window_in_which_nothing_moved():
    got = {n: run.read_metric(n, ctx(AFTER, AFTER)) for n in NAMES}
    # no second passed in any phase, no dispatch, no reply: nothing to
    # divide by; the collector's series are there and filled no bucket
    assert got == {"dispatcher_wait_pct": None, "dispatch_host_ms": None,
                   "dispatch_render_ms": None, "dispatch_fetch_ms": None,
                   "reply_lag_p95_ms": None, "gc_pause_max_ms": 0.0}


def test_one_series_stands_still_while_the_others_move():
    # the host-copy path: dispatches, and not one second of fetch
    still = dict(AFTER)
    key = next(k for k in still
               if k[0] == "pio_serve_phase_seconds_total"
               and dict(k[1])["phase"] == "fetch")
    still[key] = BEFORE[key]
    c = ctx(BEFORE, still)
    assert run.read_metric("dispatch_fetch_ms", c) == 0.0
    assert run.read_metric("dispatch_host_ms", c) == pytest.approx(1.0)
    assert run.read_metric("dispatcher_wait_pct", c) == pytest.approx(
        100.0 * 1.0 / 3.0)


@pytest.mark.parametrize("name", NAMES)
def test_a_server_without_the_series_reads_as_nothing(name):
    assert run.read_metric(name, ctx(EMPTY, EMPTY)) is None


def test_a_collection_past_the_last_bucket_reads_as_the_last_bound():
    after = exposition(
        {"wait": 11.0}, 3000, [100, 150, 1080, 1200, 1200],
        {"0": [90, 95, 95, 95, 95, 95], "2": [0, 0, 0, 1, 1, 2]})
    assert run.read_metric("gc_pause_max_ms", ctx(BEFORE, after)) == 1000.0
