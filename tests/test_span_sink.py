"""The span line and the span sink (obs/trace.py, issue 32).

The line: ``log_span`` / ``log_stage_span`` format it in place, and for
every input it has to equal, byte for byte, what the dict +
``json.dumps(record, separators=(",", ":"))`` they replaced gave. The
sink: an append on the caller's thread, one ``write`` a period on the
sink's own, nothing lost at an orderly stop, nothing printed about a
closed stream, and every other ``logging`` listener still served.
"""

import contextlib
import http
import json
import logging
import sys
import threading
import time
import urllib.request

import pytest

from incubator_predictionio_tpu.obs import trace as obs_trace
from incubator_predictionio_tpu.utils.http import (
    HttpServer,
    Response,
    Router,
)

NOW = 1791072737.4046
WAIT_S = 5.0


def _dumps(record):
    return json.dumps(record, separators=(",", ":"))


def _parent_span(server, method, route, status, duration_s, trace_id,
                 span_id=None, parent_span_id=None, **extra):
    """The record as the parent commit built it."""
    record = {
        "span": "http.request", "server": server, "method": method,
        "route": route, "status": status, "ts": round(NOW, 3),
        "durationMs": round(duration_s * 1e3, 3), "traceId": trace_id,
    }
    if span_id is not None:
        record["spanId"] = span_id
    if parent_span_id is not None:
        record["parentSpanId"] = parent_span_id
    record.update(extra)
    return _dumps(record)


def _parent_stage(span, trace_id, duration_s, **extra):
    record = {"span": span, "ts": round(NOW, 3),
              "durationMs": round(duration_s * 1e3, 3), "traceId": trace_id}
    record.update(extra)
    return _dumps(record)


ORDINARY = ("prediction", "POST", "/queries.json", 200, 0.0123456,
            "abcdef0123456789")

SPAN_CASES = {
    "ordinary": (ORDINARY, dict(span_id="0a1b2c3d",
                                parent_span_id="feedbeef")),
    "no_span_ids": (ORDINARY, {}),
    "span_id_only": (ORDINARY, dict(span_id="0a1b2c3d")),
    "parent_only": (ORDINARY, dict(parent_span_id="up:stream-1.2")),
    "method_quote": (("event", 'PO"ST', "/events.json", 400, 0.001, "t1"),
                     {}),
    "method_backslash": (("event", "PO\\ST", "/e", 400, 0.001, "t1"), {}),
    "route_backslash_quote": (("event", "GET", '/a\\"b', 404, 0.5, "t"), {}),
    "server_control_bytes": (("ev\x01\n\t\x00", "GET", "/", 200, 0.0, "t"),
                             {}),
    "del_byte": (("ev", "G\x7fT", "/", 200, 0.0, "t"), {}),
    "non_ascii": (("préd", "GET", "/ü/日本/\U0001f600", 200, 0.25, "t"),
                  {}),
    "latin1_method": (("ev", "G\xe9T", "/", 405, 1e-9, "t"), {}),
    "hostile_ids": (("ev", "GET", "/", 200, 0.1, 'tr"ace\\'),
                    dict(span_id="s\np", parent_span_id="p ")),
    "empty_strings": (("", "", "", 0, 0.0, ""), dict(span_id="")),
    "status_enum": (("ev", "GET", "/", http.HTTPStatus.OK, 0.1, "t"), {}),
    "status_bool_and_none": (("ev", "GET", "/", True, 0.1, None), {}),
    "duration_int": (("ev", "GET", "/", 200, 3, "t"), {}),
    "duration_nan": (("ev", "GET", "/", 200, float("nan"), "t"), {}),
    "duration_inf": (("ev", "GET", "/", 200, float("-inf"), "t"), {}),
    "duration_huge": (("ev", "GET", "/", 200, 1.5e300, "t"), {}),
    "extra_flat": (ORDINARY, dict(tenant="acme", n=3, ok=False, x=None)),
    "extra_nested": (ORDINARY, dict(
        span_id="0a1b2c3d",
        detail={"a": [1, 2.5, None, True, "q\"x"], "b": {"c": "é"}},
        tags=("t1", "t2"))),
    "extra_hostile_key": (ORDINARY, {'k"ey\n': "v", "ü": 1}),
    "extra_names_fixed_fields": (ORDINARY, dict(
        span_id="0a1b2c3d", ts=1.0, traceId="other", durationMs=None,
        more="m")),
    "extra_names_span": (ORDINARY, dict(span="other.kind")),
}

STAGE_CASES = {
    "ordinary": (("speed.poll", "abc123", 0.0042), {}),
    "with_extra": (("speed.foldin", "abc123", 0.5),
                   dict(events=12, users=["u1", "u2"], lagS=1.25)),
    "hostile_span": (('sp"an\\\x02é', "t\n", 0.1), dict(k='"')),
    "extra_names_fixed_fields": (("mips_rebuild", "t", 2.0),
                                 dict(ts=5, trigger="age")),
    "controller_shape": (("controller.decide", "ctl-0001", 0.0),
                         dict(decision="scale_up", replicas=3,
                              reason={"slo": "serve_p99", "burn": 2.0})),
}


@pytest.fixture
def frozen_clock(monkeypatch):
    monkeypatch.setattr(obs_trace.time, "time", lambda: NOW)


def _messages(caplog):
    return [r.getMessage() for r in caplog.records if r.name == "pio.trace"]


@pytest.mark.parametrize("name", sorted(SPAN_CASES))
def test_span_line_is_the_parents_byte_for_byte(name, caplog, frozen_clock):
    args, kwargs = SPAN_CASES[name]
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        obs_trace.log_span(*args, **kwargs)
    assert _messages(caplog) == [_parent_span(*args, **kwargs)]


@pytest.mark.parametrize("name", sorted(STAGE_CASES))
def test_stage_line_is_the_parents_byte_for_byte(name, caplog, frozen_clock):
    args, kwargs = STAGE_CASES[name]
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        obs_trace.log_stage_span(*args, **kwargs)
    assert _messages(caplog) == [_parent_stage(*args, **kwargs)]


def test_unserialisable_extra_raises_as_json_dumps_did(caplog):
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        with pytest.raises(TypeError):
            obs_trace.log_span(*ORDINARY, what=object())


# ---------------------------------------------------------------------------
# the sink
# ---------------------------------------------------------------------------

class Stream:
    """A stream that remembers each write and who made it."""

    def __init__(self):
        self.writes = []          # (thread name, text)
        self.flushers = []
        self.cv = threading.Condition()

    def write(self, text):
        with self.cv:
            self.writes.append((threading.current_thread().name, text))
            self.cv.notify_all()

    def flush(self):
        self.flushers.append(threading.current_thread().name)

    def lines(self):
        with self.cv:
            return "".join(t for _n, t in self.writes).splitlines()

    def wait_lines(self, n, timeout=WAIT_S):
        end = time.monotonic() + timeout
        with self.cv:
            while sum(t.count("\n") for _n, t in self.writes) < n:
                left = end - time.monotonic()
                if left <= 0:
                    return False
                self.cv.wait(left)
        return True


@contextlib.contextmanager
def _installed(stream, monkeypatch):
    """A sink on ``stream`` as the process's, with the logger as
    ``enable_span_logging`` leaves it."""
    made = obs_trace._SpanSink(stream)
    monkeypatch.setattr(obs_trace, "_sink", made)
    level = obs_trace.span_logger.level
    obs_trace.span_logger.setLevel(logging.INFO)
    try:
        yield made
    finally:
        obs_trace.span_logger.setLevel(level)
        made.close()
        assert not made._thread.is_alive()


@pytest.fixture
def sink(monkeypatch):
    stream = Stream()
    with _installed(stream, monkeypatch) as made:
        yield made, stream


@pytest.fixture
def quiet_sink(monkeypatch):
    """A sink whose writer writes only when woken or asked: whatever
    reaches the stream came by a flush, not by the period."""
    monkeypatch.setattr(obs_trace, "SPAN_WRITE_PERIOD_S", 3600.0)
    stream = Stream()
    with _installed(stream, monkeypatch) as made:
        yield made, stream


@pytest.fixture
def only_the_sink(monkeypatch):
    """As under the CLI: no handler can be reached from ``pio.trace``
    (pytest hangs its own on the root logger for every test)."""
    monkeypatch.setattr(obs_trace.span_logger, "propagate", False)
    assert not obs_trace.span_logger.hasHandlers()

    def no_record(*a, **kw):
        raise AssertionError("a LogRecord was made for a span line")

    monkeypatch.setattr(obs_trace.span_logger, "makeRecord", no_record)


@pytest.fixture
def server():
    router = Router()

    async def hello(request):
        return Response(200, {"ok": True})

    router.add("GET", "/hello", hello)
    srv = HttpServer(router, "127.0.0.1", 0, name="sinktest")
    srv.start_background()
    try:
        yield srv
    finally:
        srv.stop()


def _get(srv, n=1):
    for _ in range(n):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/hello", timeout=10) as r:
            assert r.status == 200


def _counters():
    return (obs_trace._SPAN_LINES.value, obs_trace._SPAN_WRITES.value,
            obs_trace._SPAN_DROPPED.value)


def test_two_threads_lines_arrive_in_each_threads_order(sink, only_the_sink):
    _made, stream = sink
    n = 300

    def worker(tag):
        for i in range(n):
            obs_trace.log_stage_span("order", tag, 0.0, i=i)

    threads = [threading.Thread(target=worker, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(WAIT_S)
        assert not t.is_alive()
    # no flush is asked for: the writer's period brings them
    assert stream.wait_lines(2 * n)
    records = [json.loads(ln) for ln in stream.lines()]
    assert len(records) == 2 * n
    for tag in "ab":
        assert [r["i"] for r in records if r["traceId"] == tag] \
            == list(range(n))


def test_requests_write_from_the_sinks_thread_alone(sink, only_the_sink,
                                                    server):
    """No write, flush or LogRecord for the span line on the loop's
    thread (or any but the sink's) when only the sink listens."""
    _made, stream = sink
    _get(server, 20)
    assert stream.wait_lines(20)
    assert {name for name, _t in stream.writes} == {"pio-span-writer"}
    assert set(stream.flushers) == {"pio-span-writer"}
    records = [json.loads(ln) for ln in stream.lines()]
    assert [r["route"] for r in records] == ["/hello"] * 20
    assert all(list(r) == ["span", "server", "method", "route", "status",
                           "ts", "durationMs", "traceId", "spanId"]
               for r in records)


def test_a_burst_goes_out_in_fewer_writes_than_lines(sink, only_the_sink,
                                                     server):
    _made, stream = sink
    lines0, writes0, dropped0 = _counters()
    _get(server, 200)
    assert stream.wait_lines(200)
    # the counters are booked after the write: wait for the last batch's
    end = time.monotonic() + WAIT_S
    while ((_counters()[0] - lines0 < 200
            or _counters()[1] - writes0 < len(stream.writes))
           and time.monotonic() < end):
        time.sleep(0.01)
    lines1, writes1, dropped1 = _counters()
    assert lines1 - lines0 == 200
    assert writes1 - writes0 == len(stream.writes)
    assert 1 <= writes1 - writes0 < 200
    assert (lines1 - lines0) / (writes1 - writes0) > 1.0
    assert dropped1 == dropped0


def test_http_server_stop_writes_what_is_left(quiet_sink, only_the_sink,
                                              server):
    _made, stream = quiet_sink
    _get(server, 5)
    assert stream.lines() == []
    server.stop()
    assert stream.wait_lines(5)
    # woken, not waited for: stop() may run on an event loop
    assert {name for name, _t in stream.writes} == {"pio-span-writer"}


def test_a_verbs_exit_writes_what_is_left(quiet_sink, only_the_sink, capsys):
    from incubator_predictionio_tpu.cli.main import main as pio

    _made, stream = quiet_sink
    for i in range(3):
        obs_trace.log_span(*ORDINARY, span_id=f"{i:08x}")
    assert pio(["version"]) == 0
    # on the verb's own thread, before it returns
    assert [json.loads(ln)["spanId"] for ln in stream.lines()] \
        == ["00000000", "00000001", "00000002"]
    assert {name for name, _t in stream.writes} \
        == {threading.current_thread().name}


def test_a_closed_stream_raises_nothing_and_prints_nothing(
        only_the_sink, monkeypatch, tmp_path, capsys):
    f = open(tmp_path / "deploy.log", "w", buffering=1)
    with _installed(f, monkeypatch) as made:
        obs_trace.log_span(*ORDINARY)
        obs_trace.flush_span_log()
        f.close()
        dropped0 = _counters()[2]
        for _ in range(4):
            obs_trace.log_span(*ORDINARY)
        obs_trace.flush_span_log()
        assert _counters()[2] - dropped0 == 4
        # the writer met the closed stream too, and lives on
        obs_trace.log_span(*ORDINARY)
        end = time.monotonic() + WAIT_S
        while _counters()[2] - dropped0 < 5 and time.monotonic() < end:
            time.sleep(0.01)
        assert _counters()[2] - dropped0 == 5
        assert made._thread.is_alive()
    assert len((tmp_path / "deploy.log").read_text().splitlines()) == 1
    out = capsys.readouterr()
    assert out.err == "" and out.out == ""


def test_past_the_bound_lines_are_dropped_and_counted(quiet_sink,
                                                      only_the_sink,
                                                      monkeypatch):
    _made, stream = quiet_sink
    monkeypatch.setattr(obs_trace, "SPAN_BUFFER_LINES", 8)
    lines0, _w, dropped0 = _counters()
    for i in range(20):
        obs_trace.log_stage_span("bound", "t", 0.0, i=i)
    obs_trace.flush_span_log()
    assert [json.loads(ln)["i"] for ln in stream.lines()] == list(range(8))
    lines1, _w, dropped1 = _counters()
    assert (lines1 - lines0, dropped1 - dropped0) == (8, 12)
    # room again after the write
    obs_trace.log_stage_span("bound", "t", 0.0, i=20)
    obs_trace.flush_span_log()
    assert json.loads(stream.lines()[-1])["i"] == 20


def test_caplog_still_receives_every_record_beside_the_sink(sink, caplog,
                                                            server):
    _made, stream = sink
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        _get(server, 7)
        obs_trace.log_stage_span("speed.poll", "t", 0.001)
    assert stream.wait_lines(8)
    heard = _messages(caplog)
    assert len(heard) == 8
    assert heard == stream.lines()


@pytest.mark.parametrize("silencer", ["trace_log_off", "sample_zero",
                                      "logger_level"])
def test_each_silencer_leaves_the_buffer_empty(silencer, monkeypatch,
                                               only_the_sink, server):
    stream = Stream()
    monkeypatch.setattr(sys, "stderr", stream)
    monkeypatch.setattr(obs_trace, "_sink", None)
    level = obs_trace.span_logger.level
    if silencer == "trace_log_off":
        monkeypatch.setenv("PIO_TRACE_LOG", "off")
    elif silencer == "sample_zero":
        monkeypatch.setenv("PIO_TRACE_SAMPLE", "0")
    try:
        obs_trace.enable_span_logging()
        made = obs_trace._sink
        if silencer == "trace_log_off":
            assert made is None
        else:
            assert made._stream is stream
            obs_trace.enable_span_logging()          # idempotent
            assert obs_trace._sink is made
        if silencer == "logger_level":
            obs_trace.span_logger.setLevel(logging.WARNING)
        _get(server, 5)
        if silencer != "sample_zero":    # the rate is the requests' alone
            obs_trace.log_stage_span("speed.poll", "t", 0.001)
        if made is not None:
            assert made._lines.qsize() == 0
            made.flush()
        assert stream.writes == []
        if silencer == "sample_zero":
            # the rate silences requests, and comes back
            monkeypatch.setenv("PIO_TRACE_SAMPLE", "1")
            _get(server, 2)
            assert stream.wait_lines(2)
    finally:
        obs_trace.span_logger.setLevel(level)
        if obs_trace._sink is not None:
            obs_trace._sink.close()
