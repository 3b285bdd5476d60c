"""obs telemetry layer: registry semantics, Prometheus exposition
conformance, /metrics on every server, and trace-ID propagation.

The exposition tests parse the text format with a strict mini-parser
(line grammar + histogram invariants) rather than string-matching, so a
malformed scrape fails loudly. The e2e test drives the real four-server
stack: event ingest and prediction queries carry an ``X-PIO-Trace-Id``
header that must come back on the response AND appear in the JSON span
log line (the docs/observability.md propagation contract).
"""

import json
import logging
import re
import threading
import urllib.error
import urllib.request

import pytest

from fake_engine import AP, make_engine, params
from incubator_predictionio_tpu import native
from incubator_predictionio_tpu.data.storage import AccessKey, App, Storage
from incubator_predictionio_tpu.obs import metrics as obs_metrics
from incubator_predictionio_tpu.obs import trace as obs_trace
from incubator_predictionio_tpu.obs.metrics import Registry
from incubator_predictionio_tpu.servers.admin import AdminServer
from incubator_predictionio_tpu.servers.dashboard import DashboardServer
from incubator_predictionio_tpu.servers.event_server import (
    EventServer,
    EventServerConfig,
)
from incubator_predictionio_tpu.servers.prediction_server import (
    PredictionServer,
    ServerConfig,
)
from incubator_predictionio_tpu.workflow import CoreWorkflow

# -- exposition mini-parser (the conformance oracle) ------------------------
# PROMOTED into obs/expofmt.py when the federation layer needed to
# consume worker scrapes: one strict parser is now both the test oracle
# and the production ingest path (obs/federate.py), so the emitter and
# parser cannot drift apart silently. Malformed input raises
# MalformedExposition (an AssertionError subclass — same failure signal
# the inlined oracle produced).
from incubator_predictionio_tpu.obs.expofmt import (  # noqa: E402
    MalformedExposition,
    histogram_series,
    parse_exposition,
)


def test_promoted_parser_rejects_malformed_lines():
    with pytest.raises(MalformedExposition):
        parse_exposition("no_type_declared 1")
    with pytest.raises(MalformedExposition):
        parse_exposition("# TYPE t gauge\nt{bad 1")
    with pytest.raises(MalformedExposition):
        parse_exposition("# TYPE t nonsense\nt 1")


def scrape(port):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        return resp.read().decode("utf-8")


# -- registry unit behavior -------------------------------------------------

def test_exposition_format_conformance():
    reg = Registry()
    c = reg.counter("t_requests_total", "requests", labels=("route",))
    c.labels(route="/a").inc(3)
    c.labels(route='/with"quote').inc()
    g = reg.gauge("t_depth", "queue depth")
    g.set(7.5)
    h = reg.histogram("t_lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    types, samples = parse_exposition(reg.expose())
    assert types["t_requests_total"] == "counter"
    assert types["t_depth"] == "gauge"
    assert types["t_lat_seconds"] == "histogram"
    assert samples[("t_requests_total",
                    frozenset({("route", "/a")}))] == 3
    assert samples[("t_depth", frozenset())] == 7.5
    buckets, s, total = histogram_series(samples, "t_lat_seconds")
    assert total == 2 and s == pytest.approx(5.05)
    # cumulative buckets are monotone and +Inf equals the count
    assert [b for b, _ in buckets] == [0.1, 1.0, float("inf")]
    assert [v for _, v in buckets] == [1, 1, 2]


def test_metric_and_label_name_validation():
    reg = Registry()
    with pytest.raises(ValueError):
        reg.counter("bad-name", "x")
    with pytest.raises(ValueError):
        reg.counter("ok_name", "x", labels=("bad-label",))


def test_get_or_create_and_kind_mismatch():
    reg = Registry()
    a = reg.counter("t_total", "x")
    assert reg.counter("t_total", "x") is a
    with pytest.raises(ValueError):
        reg.gauge("t_total", "x")
    with pytest.raises(ValueError):
        reg.counter("t_total", "x", labels=("l",))
    # a histogram bucket-layout mismatch raises too (silently sharing
    # a series binned by the wrong bounds would produce lying quantiles)
    h = reg.histogram("t_b_seconds", "x", buckets=(1.0, 2.0))
    assert reg.histogram("t_b_seconds", "x") is h           # no opinion
    assert reg.histogram("t_b_seconds", "x", buckets=(1.0, 2.0)) is h
    with pytest.raises(ValueError):
        reg.histogram("t_b_seconds", "x", buckets=(1.0, 4.0))


def test_counter_rejects_negative_and_labels_mismatch():
    reg = Registry()
    c = reg.counter("t_n_total", "x")
    with pytest.raises(ValueError):
        c.inc(-1)
    lc = reg.counter("t_l_total", "x", labels=("a",))
    with pytest.raises(ValueError):
        lc.labels(b="1")


def test_histogram_bucket_math_and_quantiles():
    reg = Registry()
    h = reg.histogram("t_h_seconds", "x", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 10.0):
        h.observe(v)
    _types, samples = parse_exposition(reg.expose())
    buckets, s, total = histogram_series(samples, "t_h_seconds")
    assert [v for _, v in buckets] == [1, 2, 3, 4]
    assert total == 4 and s == pytest.approx(15.0)
    # boundary value lands in its own le bucket (le semantics)
    h2 = reg.histogram("t_h2_seconds", "x", buckets=(1.0, 2.0))
    h2.observe(1.0)
    assert h2._solo().snapshot()[0] == [1, 0, 0]
    # quantiles: linear interpolation inside the bucket
    assert h.quantile(0.5) == pytest.approx(2.0)
    assert h.quantile(1.0) == pytest.approx(4.0)  # overflow clamps
    assert reg.histogram("t_empty_seconds", "x").quantile(0.5) is None


def test_weighted_observe_counts_n():
    reg = Registry()
    h = reg.histogram("t_w_seconds", "x", buckets=(1.0,))
    h.observe(0.5, 64)
    assert h.count == 64 and h.sum == pytest.approx(32.0)
    assert h.quantile(0.99) <= 1.0


def test_concurrent_increment_correctness():
    reg = Registry()
    c = reg.counter("t_conc_total", "x")
    h = reg.histogram("t_conc_seconds", "x", buckets=(1.0,))
    n_threads, per_thread = 8, 5000

    def work():
        for _ in range(per_thread):
            c.inc()
            h.observe(0.5)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per_thread
    assert h.count == n_threads * per_thread
    assert h.sum == pytest.approx(0.5 * n_threads * per_thread)


def test_collector_runs_at_scrape_and_replaces_by_name():
    reg = Registry()
    g = reg.gauge("t_coll", "x")
    reg.register_collector("k", lambda: g.set(1))
    reg.register_collector("k", lambda: g.set(2))  # replaces
    reg.expose()
    assert g.value == 2
    # a failing collector is skipped, never fails the scrape
    reg.register_collector("boom", lambda: 1 / 0)
    assert "t_coll" in reg.expose()


def test_trace_id_accept_and_generate():
    assert obs_trace.accept_trace_id("abc-123.X:ok") == "abc-123.X:ok"
    fresh = obs_trace.accept_trace_id(None)
    assert re.fullmatch(r"[0-9a-f]{16}", fresh)
    # malformed (spaces / too long / log-breaking bytes) is REPLACED
    assert obs_trace.accept_trace_id("has space") != "has space"
    assert obs_trace.accept_trace_id("x" * 200) != "x" * 200
    assert obs_trace.accept_trace_id('inj"ect\n') != 'inj"ect\n'


# -- the four-server stack --------------------------------------------------

@pytest.fixture(scope="module")
def stack():
    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    app_id = Storage.get_meta_data_apps().insert(App(0, "obs-app"))
    Storage.get_meta_data_access_keys().insert(AccessKey("obskey", app_id))
    engine = make_engine()
    # run_train exports the workflow-phase gauges as a side effect
    CoreWorkflow.run_train(engine, params(ds=9, algos=[("algo0", AP(1))]),
                           engine_variant="obs")
    es = EventServer(EventServerConfig(ip="127.0.0.1", port=0, stats=True))
    ps = PredictionServer(engine, ServerConfig(
        ip="127.0.0.1", port=0, engine_variant="obs"))
    ad = AdminServer(ip="127.0.0.1", port=0)
    db = DashboardServer(ip="127.0.0.1", port=0)
    # the FIFTH server: a storage server over its own memory backend, so
    # the trace/metrics contracts are pinned on every server this repo
    # runs (the cross-process hop target of data/storage/remote.py)
    from incubator_predictionio_tpu.data.storage import (
        StorageClientConfig,
    )
    from incubator_predictionio_tpu.data.storage import (
        memory as memory_backend,
    )
    from incubator_predictionio_tpu.data.storage.server import (
        StorageServer,
    )

    st_config = StorageClientConfig(test=True, properties={})
    st = StorageServer(memory_backend,
                       memory_backend.StorageClient(st_config), st_config,
                       host="127.0.0.1", port=0)
    ports = {
        "event": es.start_background(),
        "prediction": ps.start_background(),
        "admin": ad.start_background(),
        "dashboard": db.start_background(),
        "storage": st.start_background(),
    }
    yield ports
    for srv in (es, ps, ad, db, st):
        srv.stop()
    Storage.reset()


def post(port, path, body, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, dict(resp.headers), \
                json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read() or b"null")


EV = {"event": "rate", "entityType": "user", "entityId": "u1",
      "targetEntityType": "item", "targetEntityId": "i1",
      "properties": {"rating": 5}}


def test_metrics_route_on_all_four_servers(stack):
    # exercise ingest + batch + query first so the scrape has content
    status, _h, _b = post(stack["event"], "/events.json?accessKey=obskey",
                          EV)
    assert status == 201
    status, _h, _b = post(stack["event"],
                          "/batch/events.json?accessKey=obskey", [EV, EV])
    assert status == 200
    status, _h, body = post(stack["prediction"], "/queries.json", {"qx": 4})
    assert status == 200 and body["qx"] == 4

    for name, port in stack.items():
        types, samples = parse_exposition(scrape(port))
        # the shared HTTP-layer metrics exist everywhere
        assert types["pio_http_requests_total"] == "counter", name
        assert types["pio_http_request_seconds"] == "histogram", name

    _types, samples = parse_exposition(scrape(stack["event"]))
    # per-event ingest counters, by route pattern and status
    assert samples[("pio_ingest_events_total", frozenset(
        {("route", "/events.json"), ("status", "201")}))] >= 1
    assert samples[("pio_ingest_events_total", frozenset(
        {("route", "/batch/events.json"), ("status", "201")}))] >= 2
    # batch-size histogram booked once for the 2-event batch
    buckets, _s, total = histogram_series(samples, "pio_ingest_batch_size")
    assert total >= 1

    types, samples = parse_exposition(scrape(stack["prediction"]))
    # per-query latency histogram + queue-depth gauge — both carry the
    # tenant label now (serving/tenancy.py); unregistered traffic books
    # under the bounded "default" child
    _buckets, lat_sum, lat_count = histogram_series(
        samples, "pio_query_latency_seconds",
        frozenset({("tenant", "default")}))
    assert lat_count >= 1 and lat_sum > 0
    assert ("pio_serve_queue_depth",
            frozenset({("tenant", "default")})) in samples
    # workflow-phase gauges exported by run_train (one scrape sees the
    # whole process: serving AND the last training run)
    assert samples[("pio_workflow_phase_seconds", frozenset(
        {("phase", "checkpoint")}))] >= 0
    assert samples[("pio_workflow_runs_total", frozenset())] >= 1


def test_compile_cache_metrics_registered(tmp_path, monkeypatch):
    from incubator_predictionio_tpu.utils import compile_cache

    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(compile_cache, "_enabled", False)
    old = jax.config.jax_compilation_cache_dir
    try:
        compile_cache.enable()
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    text = obs_metrics.REGISTRY.expose()
    types, samples = parse_exposition(text)
    assert types["pio_compile_cache_hits_total"] == "counter"
    assert types["pio_compile_cache_requests_total"] == "counter"
    # the miss gauge derives at scrape time (requests - hits)
    assert ("pio_compile_cache_misses", frozenset()) in samples


def test_status_page_tail_latency(stack):
    post(stack["prediction"], "/queries.json", {"qx": 7})
    with urllib.request.urlopen(
            f"http://127.0.0.1:{stack['prediction']}/", timeout=30) as resp:
        info = json.loads(resp.read())
    # p50/p95/p99 derived from the histogram — visible without a scraper
    assert info["servingSecP50"] is not None
    assert info["servingSecP95"] is not None
    assert info["servingSecP99"] >= info["servingSecP50"] > 0


def test_trace_id_e2e_response_and_span_log(stack, caplog):
    tid = "e2e-trace-0042"
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        status, headers, _b = post(
            stack["event"], "/events.json?accessKey=obskey", EV,
            headers={"X-PIO-Trace-Id": tid})
        assert status == 201
        assert headers["X-PIO-Trace-Id"] == tid
        status, headers, _b = post(
            stack["prediction"], "/queries.json", {"qx": 1},
            headers={"X-PIO-Trace-Id": tid})
        assert status == 200
        assert headers["X-PIO-Trace-Id"] == tid
    spans = [json.loads(r.getMessage()) for r in caplog.records
             if r.name == "pio.trace"]
    mine = [s for s in spans if s["traceId"] == tid]
    routes = {(s["server"], s["route"]) for s in mine}
    assert ("event", "/events.json") in routes
    assert ("prediction", "/queries.json") in routes
    for s in mine:
        assert s["span"] == "http.request"
        assert s["durationMs"] >= 0
        assert s["status"] in (200, 201)
        # every span line carries its own span ID + wall stamp (the
        # cross-process stitching contract, scripts/trace_stitch.py)
        assert re.fullmatch(r"[0-9a-f]{8}", s["spanId"])
        assert s["ts"] > 0


def test_parent_span_header_links_spans(stack, caplog):
    """A hop that forwards X-PIO-Parent-Span gets a span line whose
    parentSpanId is the upstream span — the in-repo client contract
    (obs_trace.client_headers)."""
    tid = "parent-span-e2e-01"
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        status, headers, _b = post(
            stack["event"], "/events.json?accessKey=obskey", EV,
            headers={"X-PIO-Trace-Id": tid})
        assert status == 201
        parent = headers["X-PIO-Span-Id"]      # echoed server-side span
        status, headers2, _b = post(
            stack["prediction"], "/queries.json", {"qx": 1},
            headers={"X-PIO-Trace-Id": tid, "X-PIO-Parent-Span": parent})
        assert status == 200
    spans = [json.loads(r.getMessage()) for r in caplog.records
             if r.name == "pio.trace" and
             json.loads(r.getMessage()).get("traceId") == tid]
    child = [s for s in spans if s.get("parentSpanId")]
    assert child and child[0]["parentSpanId"] == parent
    assert child[0]["server"] == "prediction"
    # malformed parent headers are DROPPED, never echoed into linkage
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        post(stack["prediction"], "/queries.json", {"qx": 1},
             headers={"X-PIO-Trace-Id": "parent-span-e2e-02",
                      "X-PIO-Parent-Span": "bad parent!"})
    bad = [json.loads(r.getMessage()) for r in caplog.records
           if r.name == "pio.trace"
           and json.loads(r.getMessage()).get("traceId")
           == "parent-span-e2e-02"]
    assert bad and "parentSpanId" not in bad[0]


def test_trace_echo_and_span_on_error_paths(stack, caplog):
    """4xx/5xx responses from ALL FIVE servers still echo
    X-PIO-Trace-Id and emit a span line — a failing hop is the one an
    operator most needs to find in the trace tree. (Until this test the
    contract was only pinned on the happy path.)"""
    def get_err(port, path, tid):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            headers={"X-PIO-Trace-Id": tid})
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status, dict(resp.headers)
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers)

    cases = {
        # (server, how to provoke an error) → expected status family
        "event": lambda p: post(p, "/events.json?accessKey=obskey",
                                {"not": "an event"},
                                headers={"X-PIO-Trace-Id": "err-event"})[:2],
        "prediction": lambda p: post(
            p, "/nope.json", {},
            headers={"X-PIO-Trace-Id": "err-prediction"})[:2],
        "admin": lambda p: post(
            p, "/cmd/app", {},
            headers={"X-PIO-Trace-Id": "err-admin"})[:2],
        "dashboard": lambda p: post(
            p, "/no/such/page", {},
            headers={"X-PIO-Trace-Id": "err-dashboard"})[:2],
        # /rpc reports DAO errors in-band (msgpack envelope, 200) by
        # design — the HTTP-layer error path is an unrouted 404
        "storage": lambda p: get_err(p, "/no/such/route", "err-storage"),
    }
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        for name, provoke in cases.items():
            status, headers = provoke(stack[name])
            assert 400 <= status < 600, (name, status)
            # the error response STILL echoes the trace ID...
            assert headers["X-PIO-Trace-Id"] == f"err-{name}", name
            assert headers["X-PIO-Span-Id"], name
    spans = [json.loads(r.getMessage()) for r in caplog.records
             if r.name == "pio.trace"]
    by_trace = {s["traceId"]: s for s in spans}
    for name in cases:
        # ...and the span line was emitted, status included
        s = by_trace.get(f"err-{name}")
        assert s is not None, (name, sorted(by_trace))
        assert s["server"] == name
        assert 400 <= s["status"] < 600


def test_trace_id_generated_when_absent(stack):
    status, headers, _b = post(
        stack["event"], "/events.json?accessKey=obskey", EV)
    assert status == 201
    assert re.fullmatch(r"[0-9a-f]{16}", headers["X-PIO-Trace-Id"])
    # malformed incoming ids are replaced, never echoed
    status, headers, _b = post(
        stack["event"], "/events.json?accessKey=obskey", EV,
        headers={"X-PIO-Trace-Id": "bad id with spaces"})
    assert headers["X-PIO-Trace-Id"] != "bad id with spaces"


def test_unmatched_routes_collapse_to_one_series(stack):
    for path in ("/nope/a", "/nope/b"):
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{stack['event']}{path}", timeout=30)
        except urllib.error.HTTPError as e:
            assert e.code == 404
    _types, samples = parse_exposition(scrape(stack["event"]))
    assert samples[("pio_http_requests_total", frozenset(
        {("server", "event"), ("method", "GET"),
         ("route", "<unmatched>"), ("status", "404")}))] >= 2
    # a method mismatch on a KNOWN path books under the real route
    # pattern, not <unmatched> — 405 traffic is not scanner noise
    status, _h, _b = post(stack["event"], "/", {})
    assert status == 405
    _types, samples = parse_exposition(scrape(stack["event"]))
    assert samples[("pio_http_requests_total", frozenset(
        {("server", "event"), ("method", "POST"),
         ("route", "/"), ("status", "405")}))] >= 1


def test_trace_sample_knob(monkeypatch):
    """PIO_TRACE_SAMPLE gates ONLY the span line; IDs keep flowing."""
    monkeypatch.setenv("PIO_TRACE_SAMPLE", "0")
    assert obs_trace.sample_rate() == 0.0
    assert obs_trace.span_sampled() is False
    monkeypatch.setenv("PIO_TRACE_SAMPLE", "1.0")
    assert obs_trace.span_sampled() is True
    monkeypatch.setenv("PIO_TRACE_SAMPLE", "not-a-number")
    assert obs_trace.sample_rate() == 1.0
    monkeypatch.setenv("PIO_TRACE_SAMPLE", "7")   # clamped
    assert obs_trace.sample_rate() == 1.0
    monkeypatch.delenv("PIO_TRACE_SAMPLE")
    assert obs_trace.sample_rate() == 1.0


def test_sampled_out_requests_keep_trace_ids(stack, monkeypatch, caplog):
    monkeypatch.setenv("PIO_TRACE_SAMPLE", "0")
    tid = "sampled-out-0001"
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        status, headers, _b = post(
            stack["event"], "/events.json?accessKey=obskey", EV,
            headers={"X-PIO-Trace-Id": tid})
    assert status == 201
    # the propagation contract is unconditional...
    assert headers["X-PIO-Trace-Id"] == tid
    # ...only the span LINE was sampled away
    spans = [json.loads(r.getMessage()) for r in caplog.records
             if r.name == "pio.trace"]
    assert not [s for s in spans if s.get("traceId") == tid]


def test_build_info_constant_gauge(stack):
    """pio_build_info{version,jax_version,backend} == 1 on every
    server's scrape (the standard join-target idiom)."""
    for name, port in stack.items():
        _types, samples = parse_exposition(scrape(port))
        hits = [(ls, v) for (n, ls), v in samples.items()
                if n == "pio_build_info"]
        assert hits, name
        labels, value = hits[0]
        assert value == 1
        keys = {k for k, _v in labels}
        assert keys == {"version", "jax_version", "backend"}


def test_latency_buckets_resolve_sub_millisecond():
    """The extended bucket floor: sub-ms observations (device fold-in
    solves) must not all collapse into the first bucket."""
    bounds = obs_metrics.DEFAULT_LATENCY_BUCKETS
    assert bounds[0] < 1e-4          # extended downward...
    assert 1e-4 in bounds            # ...keeping the old bounds aligned
    assert max(bounds) > 10.0
    reg = Registry()
    h = reg.histogram("t_subms_seconds", "x")
    h.observe(20e-6)
    h.observe(300e-6)
    counts = h._solo().snapshot()[0]
    occupied = [i for i, c in enumerate(counts) if c]
    assert len(occupied) == 2        # distinct buckets, not one heap


def test_histogram_snapshot_consistent_under_threaded_observation():
    """snapshot() must return a CONSISTENT (counts, sum, count) triple
    while writers hammer the child — sum/count never drift from the
    per-bucket totals."""
    reg = Registry()
    h = reg.histogram("t_snap_seconds", "x", buckets=(1.0,))
    stop = threading.Event()

    def work():
        while not stop.is_set():
            h.observe(0.5)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(300):
            counts, s, total = h._solo().snapshot()
            assert sum(counts) == total
            assert s == pytest.approx(0.5 * total)
    finally:
        stop.set()
        for t in threads:
            t.join()


def test_concurrent_scrape_during_server_shutdown():
    """Scrapes racing a server shutdown must either answer cleanly or
    fail with a connection error — never hang or corrupt the registry
    (the next scrape still parses)."""
    from incubator_predictionio_tpu.obs.http import add_metrics_route
    from incubator_predictionio_tpu.utils.http import HttpServer, Router

    r = Router()
    add_metrics_route(r)
    srv = HttpServer(r, "127.0.0.1", 0, name="t_shutdown")
    port = srv.start_background()
    errors: list = []
    stop = threading.Event()

    def scraper():
        while not stop.is_set():
            try:
                # short socket timeout: a connection the dying server
                # accepted but never services must resolve well inside
                # the join window below, or a loaded box reads the
                # normal timeout as a "hang"
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=5) as resp:
                    text = resp.read().decode("utf-8")
                parse_exposition(text)
            except AssertionError as e:      # malformed exposition
                errors.append(e)
                return
            except Exception:
                return  # connection refused/reset mid-shutdown: fine

    threads = [threading.Thread(target=scraper) for _ in range(6)]
    for t in threads:
        t.start()
    srv.stop()
    stop.set()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "scraper hung across shutdown"
    assert not errors, errors
    # the registry survived the race: a fresh exposition still parses
    parse_exposition(obs_metrics.REGISTRY.expose())


@pytest.mark.skipif(native.load() is None,
                    reason="native library unavailable")
def test_native_storage_metrics_bridge(tmp_path):
    """cpplog's group-commit and scan counters surface as gauges on the
    process registry at scrape time."""
    import numpy as np

    from incubator_predictionio_tpu.data.storage import base, cpplog
    from incubator_predictionio_tpu.data.storage import (
        StorageClientConfig,
    )

    client = cpplog.StorageClient(
        StorageClientConfig(properties={"PATH": str(tmp_path)}))
    events = cpplog.CppLogEvents(client, client.config, prefix="t_")
    try:
        ids = events.insert_interactions(
            base.Interactions(
                user_idx=np.array([0, 1], np.int32),
                item_idx=np.array([0, 0], np.int32),
                values=np.array([5.0, 3.0], np.float32),
                user_ids=["u1", "u2"], item_ids=["i1"]),
            app_id=1)
        assert len(ids) == 2
        events.scan_interactions(app_id=1, event_names=("rate",),
                                 value_prop="rating")
        types, samples = parse_exposition(obs_metrics.REGISTRY.expose())
        assert samples[("pio_group_commit_events", frozenset())] >= 2
        assert samples[("pio_group_commit_appends", frozenset())] >= 1
        assert ("pio_scan_wall_seconds", frozenset()) in samples
        assert ("pio_scan_lock_held_seconds", frozenset()) in samples
        assert samples[("pio_scan_rows", frozenset())] >= 2
        assert types["pio_scan_shards"] == "gauge"
    finally:
        obs_metrics.REGISTRY.unregister_collector("cpplog_native")
        client.close()
