"""The dispatcher's cycle in spans and counters (obs/trace.stage).

- the phases of ``pio_serve_phase_seconds_total`` tile a dispatcher
  thread's life: ``serve.wait`` plus the phases inside ``serve.dispatch``
  sum to its elapsed time, one ``other`` booking per dispatch, and
  ``wait`` grows only while the queue is empty;
- through a real PredictionServer on a tiny ALS model: which phases move
  on the device path and on the host-copy path, the reply-lag histogram
  (one sample a dispatch, however many queries it fused, and no request's
  trace as its exemplar), the one hand-over to the loop a dispatch and
  the 503 a shed, evicted or over-quota handler sends, the collector's
  pauses, ``/ready`` and the device-memory gauges;
- the same ``stage`` calls land in a ``jax.profiler`` trace as nested
  annotations on one host line.
"""

import gc
import glob
import json
import logging
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from incubator_predictionio_tpu.obs import metrics as obs_metrics
from incubator_predictionio_tpu.obs import trace as obs_trace
from incubator_predictionio_tpu.serving.scheduler import BatchScheduler

PHASES = "pio_serve_phase_seconds_total"


def phase_seconds():
    fam = obs_metrics.REGISTRY.get(PHASES)
    with fam._lock:
        children = dict(fam._children)
    return {key[0]: child.value for key, child in children.items()}


def delta(before, after):
    return {p: after[p] - before.get(p, 0.0) for p in after}


class _SpyChild:
    def __init__(self, phase, booked):
        self.phase, self.booked = phase, booked

    def inc(self, n):
        self.booked.append((self.phase, n))


class _SpyChildren(dict):
    """Stands in for obs_trace._phase_children (counter children cached
    by stage name): every booking, in order, as (phase, seconds)."""

    def __init__(self):
        super().__init__()
        self.booked = []

    def __missing__(self, name):
        phase = ("other" if name == "serve.dispatch"
                 else name.rpartition(".")[2])
        child = self[name] = _SpyChild(phase, self.booked)
        return child

    def get(self, name, default=None):
        return self[name]


class _StageClock:
    """Stands in for the ``time`` module inside obs/trace: ``stage``
    reads a clock that only the test moves, on however loaded a box."""

    def __init__(self):
        self.now = 0.0
        self.was_read = threading.Event()

    def perf_counter(self):
        self.was_read.set()
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


def test_geometric_buckets_step_at_most_one_and_a_half():
    for lo, hi in ((50e-6, 1.0), (0.25e-3, 1.0)):
        b = obs_metrics.geometric_buckets(lo, hi)
        assert b[0] == lo and b[-1] == hi
        assert all(y / x <= 1.5 for x, y in zip(b, b[1:]))


def test_phases_tile_the_dispatcher_threads_life(monkeypatch):
    spy = _SpyChildren()
    monkeypatch.setattr(obs_trace, "_phase_children", spy)
    batches = obs_metrics.REGISTRY.get("pio_serve_batch_size")
    n0 = batches.count
    seen = []

    clock = _StageClock()
    monkeypatch.setattr(obs_trace, "time", clock)

    def handler(bodies):
        seen.append(len(bodies))
        clock.now += 0.001
        return bodies

    # cap 1: one query a dispatch, so 64 queued queries are 64
    # dispatches with the queue never empty between them
    s = BatchScheduler(handler, 1, shed=False, wait_bound_s=0.0)
    thread = s._threads[0]
    assert clock.was_read.wait(5)       # the thread is in its first wait
    clock.now += 0.1                    # the queue is empty: wait grows
    futs = [s.submit(i) for i in range(64)]
    assert [f.result(10) for f in futs] == list(range(64))
    busy_s = clock.now - 0.1
    s.stop()
    thread.join(5)
    life_s = clock.now                  # its thread started at 0.0
    assert not thread.is_alive()

    assert len(seen) == 64
    assert batches.count - n0 == 64
    by_phase = {}
    for phase, sec in spy.booked:
        by_phase.setdefault(phase, []).append(sec)
    # one "other" booking per dispatch, each with its complete
    assert len(by_phase["other"]) == 64
    assert len(by_phase["complete"]) == 64
    # the stub handler has no child stages, so its sleep is dispatch's own
    assert sum(by_phase["other"]) >= 64 * 0.001
    # the identity: every second of the thread's life is in one phase
    total = sum(sec for _p, sec in spy.booked)
    assert total == pytest.approx(life_s, rel=1e-9)
    # wait grew while the queue was empty ...
    waits = by_phase["wait"]
    assert waits[0] == pytest.approx(0.1)
    # ... and not while it was not: the 63 waits between two dispatches
    # of the backlog hold a pick and a pop each
    assert sum(waits[1:64]) < 0.05 * busy_s


def test_nested_stage_books_self_time_once(monkeypatch):
    spy = _SpyChildren()
    monkeypatch.setattr(obs_trace, "_phase_children", spy)
    with obs_trace.stage("serve.dispatch", phase="other", id=7):
        time.sleep(0.002)
        with obs_trace.stage("serve.fetch"):
            time.sleep(0.004)
    booked = dict(spy.booked)
    assert booked["fetch"] >= 0.004
    assert 0.002 <= booked["other"] < booked["fetch"]


# ---------------------------------------------------------------------------
# through a real PredictionServer
# ---------------------------------------------------------------------------

def _post(port, body, trace_id=None):
    headers = {"Content-Type": "application/json"}
    if trace_id:
        headers["X-PIO-Trace-Id"] = trace_id
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json",
        data=json.dumps(body).encode(), headers=headers)
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status, json.loads(resp.read())


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _series(text, name):
    """{label text: value} of one family in an exposition."""
    out = {}
    for line in text.splitlines():
        if line.startswith(name) and line[len(name)] in " {":
            head, _, value = line.rpartition(" ")
            out[head[len(name):]] = float(value)
    return out


@pytest.fixture
def als_server():
    """A trained tiny ALS engine behind a real PredictionServer, built
    but not started; yields a function that starts it."""
    from incubator_predictionio_tpu.core import EngineParams
    from incubator_predictionio_tpu.data.datamap import DataMap
    from incubator_predictionio_tpu.data.event import Event
    from incubator_predictionio_tpu.data.storage import App, Storage
    from incubator_predictionio_tpu.models.recommendation import (
        ALSAlgorithmParams,
        DataSourceParams,
        RecommendationEngine,
    )
    from incubator_predictionio_tpu.servers.prediction_server import (
        PredictionServer,
        ServerConfig,
    )
    from incubator_predictionio_tpu.workflow import CoreWorkflow

    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    app_id = Storage.get_meta_data_apps().insert(App(0, "phaseapp"))
    rng = np.random.default_rng(0)
    dao = Storage.get_events()
    for u in range(12):
        for i in range(9):
            if rng.random() < 0.7:
                dao.insert(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap(
                        {"rating": float(rng.integers(1, 6))})), app_id)
    engine = RecommendationEngine().apply()
    params = EngineParams(
        data_source_params=("", DataSourceParams(app_name="phaseapp")),
        algorithm_params_list=[
            ("als", ALSAlgorithmParams(rank=4, num_iterations=2,
                                       lambda_=0.05, seed=1))])
    CoreWorkflow.run_train(engine, params, engine_variant="phases")
    servers = []

    def make():
        ps = PredictionServer(engine, ServerConfig(
            ip="127.0.0.1", port=0, engine_variant="phases"))
        servers.append(ps)
        return ps

    yield make
    for ps in servers:
        ps.stop()
    Storage.reset()


def _wait_ready(port, limit_s=120.0):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < limit_s:
        if _get(port, "/ready")[0] == 200:
            return
        time.sleep(0.05)
    raise AssertionError("the server never became ready")


@pytest.mark.parametrize("device_path", [True, False],
                         ids=["device-path", "host-copy"])
def test_phases_that_move_on_each_scoring_path(als_server, monkeypatch,
                                               device_path):
    if device_path:
        monkeypatch.setenv("PIO_HOST_SERVE_MAX_ELEMS", "0")
    else:
        monkeypatch.delenv("PIO_HOST_SERVE_MAX_ELEMS", raising=False)
    ps = als_server()
    port = ps.start_background()
    _wait_ready(port)                   # the warm-up books phases too
    lag = obs_metrics.REGISTRY.get("pio_serve_reply_lag_seconds")
    batches = obs_metrics.REGISTRY.get("pio_serve_batch_size")
    handed = obs_metrics.REGISTRY.get("pio_serve_reply_handovers_total")
    lag0, batches0, handed0 = lag.count, batches.count, handed.value
    before = phase_seconds()
    for n in range(20):
        # num=10 is the shape the warm-up compiled: a compile on the live
        # path would leave a slow sample in the process-wide latency
        # histogram, and the next test file's scheduler would shed on it
        status, body = _post(port, {"user": f"u{n % 12}", "num": 10})
        assert status == 200 and body["itemScores"]
    # the last dispatch books "other" as it ends, after its reply
    deadline = time.perf_counter() + 5
    while time.perf_counter() < deadline:
        moved = delta(before, phase_seconds())
        if moved.get("other", 0) > 0 and moved.get("wait", 0) > 0:
            break
        time.sleep(0.01)
    on = {p for p, sec in moved.items() if sec > 0}
    common = {"wait", "other", "parse", "lookup", "render", "complete"}
    if device_path:
        assert on >= common | {"launch", "fetch"}
        assert moved.get("host_score", 0.0) == 0.0
    else:
        assert on >= common | {"host_score"}
        assert moved.get("launch", 0.0) == 0.0
        assert moved.get("fetch", 0.0) == 0.0
    # one query at a time: twenty dispatches, a reply-lag sample and a
    # hand-over to the loop each
    assert lag.count - lag0 == batches.count - batches0 == 20
    assert _reaches(lambda: handed.value - handed0, 20, 5.0)


def test_reply_lag_is_sampled_once_a_dispatch(als_server, caplog):
    ps = als_server()
    port = ps.start_background()
    _wait_ready(port)
    lag = obs_metrics.REGISTRY.get("pio_serve_reply_lag_seconds")
    batches = obs_metrics.REGISTRY.get("pio_serve_batch_size")
    lag0, batches0 = lag.count, batches.count
    # hold the first dispatch, queue three queries behind it: the next
    # dispatch takes one (rung 1, and grows it), the one after fuses two
    inner = ps._batcher._handle_batch
    gate, entered = threading.Event(), threading.Event()

    def gated(bodies, engine, tenant):
        if not entered.is_set():
            entered.set()
            gate.wait(10)
        return inner(bodies, engine, tenant)

    ps._batcher._handle_batch = gated
    caplog.set_level(logging.INFO, logger="pio.trace")
    threads = [threading.Thread(
        target=_post, args=(port, {"user": f"u{n}", "num": 10}, f"q{n}"))
        for n in range(4)]
    threads[0].start()
    assert entered.wait(10)
    for t in threads[1:]:
        t.start()
    deadline = time.perf_counter() + 10
    while ps._batcher.depth() < 3 and time.perf_counter() < deadline:
        time.sleep(0.005)
    assert ps._batcher.depth() == 3
    gate.set()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    lines = {}
    for rec in caplog.records:
        doc = json.loads(rec.getMessage())
        if doc.get("route") == "/queries.json":
            lines[doc["traceId"]] = doc
    assert set(lines) == {"q0", "q1", "q2", "q3"}
    # four queries, three dispatches (the last two rode one): three
    # samples, and the request's line is what it was before the cycle
    # was instrumented (nothing is written per request for it)
    assert batches.count - batches0 == 3
    assert lag.count - lag0 == 3
    for doc in lines.values():
        assert set(doc) == {"span", "server", "method", "route", "status",
                            "ts", "durationMs", "traceId", "spanId"}
    # every one of the four was traced, and none became an exemplar: the
    # sample stands for its dispatch, and a plain Prometheus-text reader
    # can parse the bucket lines
    _status, text = _get(port, "/metrics")
    assert [ln for ln in text.splitlines() if ln.startswith(
        "pio_serve_reply_lag_seconds_bucket")]
    assert all("#" not in ln for ln in text.splitlines() if ln.startswith(
        "pio_serve_reply_lag_seconds_bucket"))


def _post_raw(port, body, headers=None, out=None, key=None):
    """(status, headers, body text) of one query, an error's too."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            got = resp.status, dict(resp.headers), resp.read().decode()
    except urllib.error.HTTPError as e:
        got = e.code, dict(e.headers), e.read().decode()
    if out is not None:
        out[key] = got
    return got


def _reaches(read, want, limit_s=10.0):
    deadline = time.perf_counter() + limit_s
    while read() != want and time.perf_counter() < deadline:
        time.sleep(0.005)
    return read() == want


def test_a_fused_batch_is_one_hand_over_and_a_shed_handler_sends_503(
        als_server):
    ps = als_server()
    port = ps.start_background()
    _wait_ready(port)
    batches = obs_metrics.REGISTRY.get("pio_serve_batch_size")
    handed = obs_metrics.REGISTRY.get("pio_serve_reply_handovers_total")
    sched = ps._batcher
    # one query through, so that the queue knows a dispatch's wall
    assert _post_raw(port, {"user": "u0", "num": 10})[0] == 200
    inner = sched._handle_batch
    gate, entered = threading.Event(), threading.Event()
    widths = []

    def gated(bodies, engine, tenant):
        if not entered.is_set():
            entered.set()
            gate.wait(10)
        widths.append(len(bodies))
        return inner(bodies, engine, tenant)

    sched._handle_batch = gated
    sched._shed = False
    sched.wait_bound_s = 0.05
    batches0, handed0, shed0 = batches.count, handed.value, sched.shed_count
    got, threads = {}, {}

    def send(key, user, headers=None):
        threads[key] = threading.Thread(target=_post_raw, args=(
            port, {"user": user, "num": 10}, headers, got, key))
        threads[key].start()

    send("held", "u1")
    assert entered.wait(10)
    for n in range(3):
        send(f"q{n}", f"u{n + 2}")
    assert _reaches(sched.depth, 3)
    # the projection is now over any objective: an arrival of the same
    # priority is shed, one of a higher priority evicts a waiter of the
    # lowest, and a tenant at its quota is refused whatever the load
    sched._shed, sched.slo_s = True, 0.0
    status, headers, text = _post_raw(port, {"user": "u5", "num": 10})
    assert status == 503 and "overloaded" in text
    assert int(headers["Retry-After"]) >= 1
    assert headers["X-PIO-Queue-Depth"] == "3"
    send("vip", "u6", {"X-PIO-Priority": "5"})
    assert _reaches(lambda: sum(k in got for k in ("q0", "q1", "q2")), 1)
    evicted, = [k for k in ("q0", "q1", "q2") if k in got]
    status, headers, _text = got[evicted]
    assert status == 503 and int(headers["Retry-After"]) >= 1
    assert "X-PIO-Queue-Depth" in headers
    assert _reaches(sched.depth, 3)     # two of the three, and the vip
    sched._shed = False
    sched.set_tenant_policy(quotas={"default": 3})
    status, headers, _text = _post_raw(port, {"user": "u7", "num": 10})
    assert status == 503 and int(headers["Retry-After"]) >= 1
    sched.set_tenant_policy(quotas={})
    time.sleep(0.06)                    # past the age bound: one batch
    gate.set()
    for t in threads.values():
        t.join(30)
        assert not t.is_alive()
    answered = sorted(k for k, v in got.items() if v[0] == 200)
    assert answered == sorted({"held", "vip", "q0", "q1", "q2"} - {evicted})
    for k in answered:
        assert json.loads(got[k][2])["itemScores"]
    # the held dispatch and the three that rode one: two dispatches, two
    # calls into the loop, neither the shed's nor the eviction's among them
    assert widths == [1, 3]
    assert batches.count - batches0 == 2
    assert _reaches(lambda: handed.value - handed0, 2)
    assert sched.shed_count - shed0 == 3
    _status, text = _get(port, "/metrics")
    assert _series(text, "pio_serve_reply_handovers_total")[""] \
        == handed.value


def test_ready_gc_pause_and_device_gauges(als_server):
    ps = als_server()
    # bound, not warmed: the probe and the gauge (read off the server at
    # scrape time) say so
    port = ps.http.start_background()
    status, body = _get(port, "/ready")
    assert status == 503 and json.loads(body)["ready"] is False
    assert _series(_get(port, "/metrics")[1], "pio_serve_ready") == {
        "": 0.0}
    ps.load_models()
    ps._watch_gc()
    ps._warmup_async()
    _wait_ready(port)
    _status, text = _get(port, "/metrics")
    assert _series(text, "pio_serve_ready") == {"": 1.0}
    gen2_0 = _series(text, "pio_gc_pause_seconds_count").get(
        '{generation="2"}', 0.0)
    gc.collect()
    _status, text = _get(port, "/metrics")
    counts = _series(text, "pio_gc_pause_seconds_count")
    assert counts['{generation="2"}'] >= gen2_0 + 1
    # booked inside the scrape's own request, whose trace is no pause's:
    # no exemplar on the bucket lines
    assert all("#" not in ln for ln in text.splitlines()
               if ln.startswith("pio_gc_pause_seconds_bucket"))
    # the gauges are there where the backend reports memory (the CPU
    # backend reports none: the families stay empty, and nothing raises)
    import jax

    reports = bool(jax.local_devices()[0].memory_stats())
    assert bool(_series(text, "pio_device_bytes_in_use")) == reports
    assert bool(_series(text, "pio_device_peak_bytes_in_use")) == reports
    # a reload warms before it swaps: ready throughout
    ps.load_models(warm_before_swap=True)
    assert _get(port, "/ready")[0] == 200
    # stop() gives back the hold this server took on the hook
    holders = obs_trace._gc_holders
    ps.stop()
    assert obs_trace._gc_holders == holders - 1
    assert (obs_trace._gc_hook in gc.callbacks) == (holders > 1)


def test_gc_hook_takes_no_lock_and_skips_an_orphan_stop():
    hook = obs_trace.GcPauseHook()
    hook("stop", {"generation": 2})        # its start came before the hook
    assert len(hook._pending) == 0
    fam = obs_metrics.REGISTRY.get("pio_gc_pause_seconds")
    child = fam.labels(generation="1")
    n0 = child.count
    # a collection that starts while the histogram's own lock is held (as
    # it can, between two bytecodes of a scrape) must not wait on it
    with child._lock:
        hook("start", {"generation": 1})
        hook("stop", {"generation": 1})
    assert len(hook._pending) == 1
    hook.flush()
    assert child.count == n0 + 1 and len(hook._pending) == 0


# ---------------------------------------------------------------------------
# the same calls, in a jax.profiler trace
# ---------------------------------------------------------------------------

def test_annotations_nest_in_a_written_profile(tmp_path):
    import jax

    def handler(bodies):
        with obs_trace.stage("serve.launch"):
            time.sleep(0.0005)
        with obs_trace.stage("serve.fetch"):
            time.sleep(0.001)
        return bodies

    s = BatchScheduler(handler, 1, shed=False, wait_bound_s=0.0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        futs = [s.submit(i) for i in range(6)]
        assert [f.result(10) for f in futs] == list(range(6))
        # the sixth dispatch ends (and its annotation closes) after its
        # future resolved: let it
        time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
        s.stop()
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    found = []
    for plane in profile.planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in line.events
                      if e.name.startswith("serve.")]
            if events:
                found.append((plane.name, events))
    # one host line carries them all: the dispatcher thread's
    (plane_name, events), = found
    assert plane_name == "/host:CPU"
    dispatches = [e for e in events if e[0] == "serve.dispatch"]
    fetches = [e for e in events if e[0] == "serve.fetch"]
    assert len(dispatches) == 6 and len(fetches) == 6
    ids = [d[3]["id"] for d in dispatches]
    assert ids == list(range(ids[0], ids[0] + 6))
    assert all(d[3]["batch"] == 1 for d in dispatches)
    for (_n, d0, d1, _s), (_m, f0, f1, _t) in zip(dispatches, fetches):
        assert d0 <= f0 and f1 <= d1
