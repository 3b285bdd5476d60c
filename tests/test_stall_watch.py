"""The serving process's own account of its stalls (obs/trace.py):

- a dispatcher phase whose own time passed 25 ms is booked under its
  phase in ``pio_serve_phase_long_seconds`` (``serve.wait`` never is),
  at scrape time;
- a beat of the loop's heartbeat that ran late books the lateness in
  ``pio_serve_loop_lag_seconds``;
- a late wake of the stall watch books the lateness in
  ``pio_host_stall_seconds``;
- a stall past its log threshold writes exactly one line, naming the
  open phase, the dispatch ``id`` and a frame of the stuck thread, at
  INFO and marked ``warmup`` until a watched server is ready.

The clocks are injected wherever a threshold is tested, so nothing here
depends on how loaded the box is.
"""

import asyncio
import json
import logging
import threading
import time

import pytest

from incubator_predictionio_tpu.obs import metrics as obs_metrics
from incubator_predictionio_tpu.obs import trace as obs_trace


class _StageClock:
    """Stands in for the ``time`` module inside obs/trace: a clock that
    only the test moves."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


def _count(family, **labels):
    fam = obs_metrics.REGISTRY.get(family)
    return (fam.labels(**labels) if labels else fam._solo()).count


def _phase_long_counts():
    fam = obs_metrics.REGISTRY.get("pio_serve_phase_long_seconds")
    with fam._lock:
        children = dict(fam._children)
    return {key[0]: child.count for key, child in children.items()}


@pytest.mark.parametrize("name, phase, inner_s, outer_s, want", [
    ("serve.fetch", None, 0.0, 0.060, {"fetch": 1}),
    ("serve.book", None, 0.0, 0.060, {"book": 1}),
    ("serve.fetch", None, 0.0, 0.005, {}),
    # a wait is idleness, however long
    ("serve.wait", None, 0.0, 0.060, {}),
    # self time: the dispatch's own 10 ms are short, its fetch's 50 not
    ("serve.dispatch", "other", 0.050, 0.060, {"fetch": 1}),
], ids=["fetch-60ms", "book-60ms", "fetch-5ms", "wait-60ms",
        "dispatch-self-10ms"])
def test_a_phase_that_ran_long_is_booked_under_its_phase(
        monkeypatch, name, phase, inner_s, outer_s, want):
    clock = _StageClock()
    monkeypatch.setattr(obs_trace, "time", clock)
    obs_trace._flush_stalls()
    before = _phase_long_counts()
    with obs_trace.stage(name, phase=phase, id=1):
        if inner_s:
            with obs_trace.stage("serve.fetch"):
                clock.now += inner_s
        clock.now += outer_s - inner_s
    # queued on the way out, booked at scrape time
    assert _moved(before, _phase_long_counts()) == {}
    obs_trace._flush_stalls()
    assert _moved(before, _phase_long_counts()) == want


def _moved(before, after):
    return {p: n - before.get(p, 0) for p, n in after.items()
            if n != before.get(p, 0)}


def test_the_phase_series_is_there_before_anything_ran_long():
    with obs_trace.stage("serve.pre_long_probe"):
        pass
    text = obs_metrics.REGISTRY.expose()
    assert ('pio_serve_phase_long_seconds_count{phase="pre_long_probe"} 0'
            in text)
    assert "pio_host_stall_seconds_count" in text
    assert "pio_serve_loop_lag_seconds_count" in text


def _loop_on_a_thread():
    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    return loop, thread


def _close(loop, thread):
    loop.call_soon_threadsafe(loop.stop)
    thread.join(5)
    assert not thread.is_alive()
    loop.close()


def test_a_loop_callback_that_sleeps_books_loop_lag():
    loop, thread = _loop_on_a_thread()
    obs_trace._flush_stalls()
    n0 = _count("pio_serve_loop_lag_seconds")
    s0 = obs_metrics.REGISTRY.get("pio_serve_loop_lag_seconds")._solo() \
        .snapshot()[1]
    hb = obs_trace.watch_loop(loop)
    try:
        deadline = time.perf_counter() + 5
        while hb.ident is None and time.perf_counter() < deadline:
            time.sleep(0.005)
        assert hb.ident == thread.ident
        slept = threading.Event()
        loop.call_soon_threadsafe(
            lambda: (time.sleep(0.2), slept.set()))
        assert slept.wait(5)
        deadline = time.perf_counter() + 5
        while hb.last < time.perf_counter() - 0.1 \
                and time.perf_counter() < deadline:
            time.sleep(0.005)       # the beat the sleep held back has run
        time.sleep(0.05)
    finally:
        hb.cancel()
    obs_trace._flush_stalls()
    fam = obs_metrics.REGISTRY.get("pio_serve_loop_lag_seconds")
    _counts, total_s, n = fam._solo().snapshot()
    assert n - n0 >= 1
    assert total_s - s0 >= 0.15
    deadline = time.perf_counter() + 5
    while not hb._handle.cancelled() and time.perf_counter() < deadline:
        time.sleep(0.005)
    assert hb._handle.cancelled() and hb not in obs_trace._heartbeats
    _close(loop, thread)


class _Clock:
    """The watch's clock, moved by the test alone."""

    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("late_s, booked, logged", [
    (0.0, False, False),
    (0.024, False, False),            # under the 25 ms floor
    (0.03, True, False),
    (0.09, True, False),              # booked, under the line's 100 ms
    (0.15, True, True),
    (2.0, True, True),
], ids=["on-time", "24ms", "30ms", "90ms", "150ms", "2s"])
def test_a_late_wake_books_a_host_stall(late_s, booked, logged):
    clock = _Clock()
    watch = obs_trace.StallWatch(clock=clock, wall=lambda: 1.7e9)
    watch.reset()
    for _ in range(5):
        clock.t += 0.02
        assert watch.tick() is None
    fam = obs_metrics.REGISTRY.get("pio_host_stall_seconds")
    _c, s0, n0 = fam._solo().snapshot()
    clock.t += 0.02 + late_s
    record = watch.tick()
    _c, s1, n1 = fam._solo().snapshot()
    assert n1 - n0 == int(booked)
    assert s1 - s0 == pytest.approx(late_s if booked else 0.0)
    assert (record is not None) == logged
    if logged:
        assert record["stall"] == "host"
        assert record["ms"] == pytest.approx(late_s * 1e3)


def _stuck_in_fetch(entered, release):
    with obs_trace.stage("serve.dispatch", phase="other", id=4242):
        with obs_trace.stage("serve.fetch"):
            entered.set()
            release.wait(10)


def _stuck_on_loop(entered, release):
    entered.set()
    release.wait(10)


def _spinning_elsewhere(stop):
    while not stop.is_set():
        sum(range(100))


def _lines(caplog, marker):
    """This thread's stall lines that hold ``marker`` (a watch of another
    server this process still runs writes from its own thread)."""
    return [json.loads(r.getMessage())
            for r in caplog.records
            if r.name == "pio.stall" and r.thread == threading.get_ident()
            and marker in r.getMessage()]


def _watch_until_ready(hb):
    deadline = time.perf_counter() + 5
    while hb.ident is None and time.perf_counter() < deadline:
        time.sleep(0.005)


#: (the watch's own lateness at one wake, which wake) of each case
_LATE = {"dispatcher": (0.0, -1), "loop": (0.0, -1), "host": (0.15, 3),
         # under the line's 100 ms, with the loop held through it
         "host-loop-late": (0.06, 3)}


@pytest.mark.parametrize("case", list(_LATE))
def test_a_stall_past_its_threshold_writes_exactly_one_line(caplog, case):
    caplog.set_level(logging.WARNING, logger="pio.stall")
    entered, release, stop = (threading.Event(), threading.Event(),
                              threading.Event())
    loop = hb = None
    workers = [threading.Thread(target=_stuck_in_fetch,
                                args=(entered, release), daemon=True)]
    if case.startswith("loop") or case == "host-loop-late":
        loop, loop_thread = _loop_on_a_thread()
        hb = obs_trace.watch_loop(loop)
        hb.ready = True
        _watch_until_ready(hb)
        workers = []
        loop.call_soon_threadsafe(_stuck_on_loop, entered, release)
    if case == "host":
        workers.append(threading.Thread(
            target=_spinning_elsewhere, args=(stop,), daemon=True))
    for w in workers:
        w.start()
    late_s, late_step = _LATE[case]
    try:
        assert entered.wait(5)
        # the watch's own clock starts where the stuck thread's stage or
        # the loop's last beat stands, and moves by hand from there
        base = [time.perf_counter()]
        watch = obs_trace.StallWatch(clock=lambda: base[0],
                                     wall=lambda: 1.7e9)
        watch.reset()
        written = []
        for step in range(40):          # 0.8 s of wakes
            base[0] += 0.02 + (late_s if step == late_step else 0.0)
            record = watch.tick()
            if record is not None:
                written.append(record)
    finally:
        release.set()
        stop.set()
        for w in workers:
            w.join(5)
        if hb is not None:
            hb.cancel()
            _close(loop, loop_thread)
    cause = case.partition("-")[0]
    assert [r["stall"] for r in written] == [cause]
    line, = written
    assert "warmup" not in line
    marker = {"dispatcher": '"id":4242', "loop": "_stuck_on_loop",
              "host": "_spinning_elsewhere",
              "host-loop-late": "_stuck_on_loop"}[case]
    assert len(_lines(caplog, marker)) == 1
    assert line["end"] == 1.7e9 and line["start"] < line["end"]
    frames = [f for stack in line["frames"].values() for f in stack]
    if case == "dispatcher":
        assert line["ms"] > 250
        opened = [o for o in line["open"] if o["id"] == 4242]
        assert [o["stage"] for o in opened] == ["serve.fetch"]
        assert opened[0]["openMs"] > 250
        assert any(f.endswith(" _stuck_in_fetch") for f in frames)
    elif case == "loop":
        assert line["ms"] > 100 and line["loopLagMs"] > 100
        assert any(f.endswith(" _stuck_on_loop") for f in frames)
    elif case == "host":
        assert line["ms"] == pytest.approx(150.0)
        # the dispatcher stuck in its fetch is named, open, with its id
        assert [o["id"] for o in line["open"]
                if o["stage"] == "serve.fetch"] == [4242]
        assert any(f.endswith(" _spinning_elsewhere") for f in frames)
        assert any(f.endswith(" _stuck_in_fetch") for f in frames)
    else:
        # one stall, seen by the watch and the loop: the watch's line
        assert line["ms"] == pytest.approx(60.0)
        assert line["loopLagMs"] > 100
        assert any(f.endswith(" _stuck_on_loop") for f in frames)


@pytest.mark.parametrize("ready", [False, True],
                         ids=["warming", "serving"])
def test_a_stall_before_the_server_is_ready_is_the_warmups(caplog, ready):
    caplog.set_level(logging.INFO, logger="pio.stall")
    loop, loop_thread = _loop_on_a_thread()
    hb = obs_trace.watch_loop(loop)
    hb.ready = ready
    try:
        _watch_until_ready(hb)
        clock = _Clock()
        watch = obs_trace.StallWatch(clock=clock, wall=lambda: 1.7e9)
        watch.reset()
        clock.t += 0.02 + 0.15
        record = watch.tick()
    finally:
        hb.cancel()
        _close(loop, loop_thread)
    assert record["stall"] == "host"
    assert record.get("warmup", False) is not ready
    mine = [r for r in caplog.records if r.name == "pio.stall"
            and r.thread == threading.get_ident()]
    assert [r.levelno for r in mine] == [
        logging.WARNING if ready else logging.INFO]
