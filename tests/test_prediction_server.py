"""PredictionServer contract: deploy, query, feedback loop, reload, stop.

Parity: CreateServer.scala behavior incl. the feedback loop posting predict
events back to a live EventServer.
"""

import json
import time
import urllib.error
import urllib.request

import pytest

from fake_engine import AP, make_engine, params
from incubator_predictionio_tpu.data.storage import AccessKey, App, Storage
from incubator_predictionio_tpu.servers.event_server import (
    EventServer,
    EventServerConfig,
)
from incubator_predictionio_tpu.servers.plugins import EngineServerPlugin, PluginContext
from incubator_predictionio_tpu.servers.prediction_server import (
    PredictionServer,
    ServerConfig,
    undeploy,
)
from incubator_predictionio_tpu.workflow import CoreWorkflow


def call(port, method, path, body=None):
    url = f"http://127.0.0.1:{port}{path}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {},
    )
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


class RewritingBlocker(EngineServerPlugin):
    output_blocker = True

    def process(self, variant, query, prediction, context):
        if isinstance(prediction, dict):
            prediction = dict(prediction, blocked_by="RewritingBlocker")
        return prediction


@pytest.fixture
def stack(monkeypatch):
    """memory storage + trained engine + event server + prediction server."""
    # no test of this file tests shedding: off, as the benchmark's cells
    # run, or a loaded box's shed projection answers a query with a 503
    monkeypatch.setenv("PIO_SERVE_SHED", "0")
    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    app_id = Storage.get_meta_data_apps().insert(App(0, "ps-app"))
    Storage.get_meta_data_access_keys().insert(AccessKey("fbkey", app_id))

    engine = make_engine()
    CoreWorkflow.run_train(engine, params(ds=9, algos=[("algo0", AP(1))]),
                           engine_variant="served")

    es = EventServer(EventServerConfig(ip="127.0.0.1", port=0))
    es_port = es.start_background()

    ps = PredictionServer(
        engine,
        ServerConfig(
            ip="127.0.0.1", port=0, engine_variant="served",
            event_server_ip="127.0.0.1", event_server_port=es_port,
            access_key="fbkey", feedback=True, server_key="sekrit",
        ),
        PluginContext([RewritingBlocker()]),
    )
    ps_port = ps.start_background()
    yield ps, ps_port, es, es_port
    ps.stop()
    es.stop()
    Storage.reset()


def test_status_page(stack):
    ps, port, _es, _esp = stack
    status, body = call(port, "GET", "/")
    assert status == 200
    assert body["status"] == "alive"
    assert body["engineVariant"] == "served"
    assert body["algorithms"] == ["Algorithm0"]
    assert body["requestCount"] == 0


def test_query_pipeline_and_bookkeeping(stack):
    ps, port, _es, _esp = stack
    status, body = call(port, "POST", "/queries.json", {"qx": 5})
    assert status == 200
    # Prediction(model=Model(ds_id=9, pp_id=2, ap_id=1), qx=5)
    assert body["qx"] == 5
    assert body["model"]["ds_id"] == 9
    assert body["blocked_by"] == "RewritingBlocker"  # output blocker ran
    status, info = call(port, "GET", "/")
    assert info["requestCount"] == 1
    assert info["lastServingSec"] > 0


def test_query_malformed_400(stack):
    ps, port, _es, _esp = stack
    status, body = call(port, "POST", "/queries.json", {"bogus": True})
    assert status == 400


def test_feedback_event_reaches_event_server(stack):
    ps, port, _es, es_port = stack
    call(port, "POST", "/queries.json", {"qx": 7})
    deadline = time.time() + 5
    found = []
    while time.time() < deadline and not found:
        status, got = call(
            es_port, "GET",
            "/events.json?accessKey=fbkey&event=predict",
        )
        if status == 200:
            found = got
        else:
            time.sleep(0.05)
    assert found, "feedback predict event never arrived"
    ev = found[0]
    assert ev["entityType"] == "pio_pr"
    assert ev["properties"]["query"] == {"qx": 7}
    assert ev["properties"]["engineInstanceId"]


def test_reload_picks_up_new_instance(stack):
    ps, port, _es, _esp = stack
    # train a new instance with different params
    CoreWorkflow.run_train(ps.engine, params(ds=42, algos=[("algo0", AP(2))]),
                           engine_variant="served")
    # unauthorized reload
    assert call(port, "POST", "/reload")[0] == 401
    status, _ = call(port, "POST", "/reload?accessKey=sekrit")
    assert status == 200
    status, body = call(port, "POST", "/queries.json", {"qx": 1})
    assert body["model"]["ds_id"] == 42
    assert body["model"]["ap_id"] == 2


def test_stop_authed_and_shuts_down(stack):
    ps, port, _es, _esp = stack
    assert call(port, "POST", "/stop")[0] == 401
    status, _ = call(port, "POST", "/stop?accessKey=sekrit")
    assert status == 200
    deadline = time.time() + 5
    down = False
    while time.time() < deadline and not down:
        try:
            call(port, "GET", "/")
            time.sleep(0.05)
        except Exception:
            down = True
    assert down
    assert not undeploy("127.0.0.1", port)  # already down


def test_stop_timer_is_daemonized(stack, monkeypatch):
    """Lifecycle regression (pio-lint thread-lifecycle): the /stop
    route's deferred-shutdown Timer must be a daemon — if the process
    is torn down some other way first, a pending non-daemon timer
    would block interpreter exit."""
    import threading

    captured = []

    class FakeTimer:
        def __init__(self, interval, function, *a, **kw):
            self.interval = interval
            self.function = function
            self.daemon = False
            self.started = False
            captured.append(self)

        def start(self):
            self.started = True

        def cancel(self):
            pass

    monkeypatch.setattr(threading, "Timer", FakeTimer)
    ps, port, _es, _esp = stack
    status, _ = call(port, "POST", "/stop?accessKey=sekrit")
    assert status == 200
    assert len(captured) == 1
    timer = captured[0]
    assert timer.started
    assert timer.daemon is True
    assert timer.function == ps.stop
    # the fake never fired, so the server is still up for teardown
    assert call(port, "GET", "/")[0] == 200


def test_plugins_listing(stack):
    ps, port, _es, _esp = stack
    status, body = call(port, "GET", "/plugins.json")
    assert status == 200
    assert "RewritingBlocker" in body["plugins"]["outputblockers"]


def test_concurrent_queries_micro_batch(stack):
    """Concurrent queries fuse into micro-batches (one batch_predict per
    drain) and every client still gets ITS OWN result — no cross-wiring.
    The reference serves queries strictly one-at-a-time
    (CreateServer.scala:523 'TODO: Parallelize')."""
    import threading

    ps, port, _es, _esp = stack
    n_clients, per_client = 16, 4
    errors = []

    def client(cid):
        for j in range(per_client):
            qx = cid * 1000 + j
            status, body = call(port, "POST", "/queries.json", {"qx": qx})
            if status != 200 or body.get("qx") != qx:
                errors.append((cid, j, status, body))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    status, info = call(port, "GET", "/")
    assert info["requestCount"] == n_clients * per_client
    # under 16-way concurrency at least one drain must have fused >1 query
    assert info["maxBatchServed"] > 1


def test_batch_isolates_bad_queries(stack):
    """A malformed query inside a fused batch 400s alone; batchmates
    succeed."""
    import threading

    ps, port, _es, _esp = stack
    results = {}

    def good(i):
        results[i] = call(port, "POST", "/queries.json", {"qx": i})

    def bad():
        results["bad"] = call(port, "POST", "/queries.json", {"bogus": 1})

    threads = [threading.Thread(target=good, args=(i,)) for i in range(8)]
    threads.append(threading.Thread(target=bad))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results["bad"][0] == 400
    for i in range(8):
        assert results[i][0] == 200 and results[i][1]["qx"] == i


# ---------------------------------------------------------------------------
# deploy lifecycle hardening (CreateServer.scala:283-308, :371-381, :449-460)
# ---------------------------------------------------------------------------

def _mini_server(port=0):
    """A dumb HTTP listener standing in for 'something on the port'."""
    from incubator_predictionio_tpu.utils.http import (
        HttpServer,
        Request,
        Response,
        Router,
    )

    r = Router()
    hits = []

    @r.post("/stop")
    def stop(request: Request) -> Response:
        hits.append("stop")
        return Response(404, {"message": "not a pio server"})

    srv = HttpServer(r, "127.0.0.1", port)
    return srv, hits


def test_bind_retry_on_occupied_port():
    """Bind retries on EADDRINUSE: a port freed within the retry window
    binds (MasterActor Http.CommandFailed handling,
    CreateServer.scala:371-381). Tested directly at the HttpServer level
    so the first bind attempt genuinely collides (the prediction server's
    undeploy handshake would otherwise consume time and free the port
    before the first bind)."""
    import socket
    import threading

    from incubator_predictionio_tpu.utils.http import HttpServer, Router

    sock = socket.socket()
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    sock.bind(("127.0.0.1", 0))
    sock.listen(1)
    port = sock.getsockname()[1]

    srv = HttpServer(Router(), "127.0.0.1", port,
                     bind_retries=3, bind_retry_delay=0.4)
    # free the port ~0.6s in: after the first bind failure, within retries
    threading.Timer(0.6, sock.close).start()
    try:
        bound = srv.start_background()
        assert bound == port
    finally:
        srv.stop()


def test_bind_no_retry_on_non_transient_oserror():
    """Non-EADDRINUSE OSErrors (bad host) fail fast, no retry loop."""
    import time as _time

    from incubator_predictionio_tpu.utils.http import HttpServer, Router

    srv = HttpServer(Router(), "256.256.256.256", 1,
                     bind_retries=3, bind_retry_delay=1.0)
    t0 = _time.monotonic()
    with pytest.raises(RuntimeError, match="failed to start"):
        srv.start_background()
    assert _time.monotonic() - t0 < 2.5  # did not burn 3x1s retries


def test_bind_fails_after_retries_exhausted(stack):
    import socket

    from fake_engine import make_engine

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    sock.listen(1)
    port = sock.getsockname()[1]
    try:
        ps2 = PredictionServer(make_engine(), ServerConfig(
            ip="127.0.0.1", port=port, engine_variant="served"))
        ps2.http.bind_retries = 1
        ps2.http.bind_retry_delay = 0.1
        with pytest.raises(RuntimeError, match="failed to start"):
            ps2.start_background()
    finally:
        sock.close()


def _keep_alive_conn(port):
    """A client that has made one request and holds its connection."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
    conn.request("POST", "/stop", body=b"")
    resp = conn.getresponse()
    resp.read()
    assert not resp.will_close
    return conn, resp.status


def test_stop_closes_idle_keep_alive_connections():
    """stop() ends the server, not only its listener: an idle keep-alive
    connection is closed at once and the serving thread ends."""
    srv, _hits = _mini_server()
    port = srv.start_background()
    conn, status = _keep_alive_conn(port)
    try:
        assert status == 404
        srv.stop()
        conn.sock.settimeout(1.0)
        assert conn.sock.recv(1) == b""  # closed by the server
        srv._thread.join(1.0)
        assert not srv._thread.is_alive()
    finally:
        conn.close()


def test_stop_lets_a_request_in_flight_finish_then_closes():
    import http.client
    import threading

    from incubator_predictionio_tpu.utils import http as pio_http

    r = pio_http.Router()
    entered, release = threading.Event(), threading.Event()

    @r.get("/slow")
    def slow(request):
        entered.set()
        release.wait(10)
        return pio_http.Response(200, {"done": True})

    srv = pio_http.HttpServer(r, "127.0.0.1", 0)
    port = srv.start_background()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
    try:
        conn.request("GET", "/slow")
        assert entered.wait(5)
        srv.stop()
        srv._thread.join(0.3)
        assert srv._thread.is_alive()  # the request is still being served
        release.set()
        resp = conn.getresponse()
        assert resp.status == 200 and json.loads(resp.read()) == {"done": True}
        assert resp.will_close  # Connection: close
        srv._thread.join(2.0)
        assert not srv._thread.is_alive()
    finally:
        release.set()
        conn.close()


def test_deploy_returns_after_stop_with_a_client_still_connected(stack):
    """`pio deploy` is `serve_forever()`: it must return after /stop
    though a client still holds a keep-alive connection."""
    ps, port, _es, _esp = stack
    conn, status = _keep_alive_conn(port)  # unauthenticated: 401, kept alive
    try:
        assert status == 401
        assert call(port, "POST", "/stop?accessKey=sekrit")[0] == 200
        ps.http._thread.join(5.0)
        assert not ps.http._thread.is_alive()
    finally:
        conn.close()


def test_undeploy_before_deploy_replaces_stale_server(stack):
    """Deploying onto an address with a live engine server stops the old
    one first (undeploy-before-deploy, CreateServer.scala:283-308)."""
    from fake_engine import make_engine

    ps, port, _es, _esp = stack
    assert call(port, "GET", "/")[0] == 200
    # a second deploy on the SAME port: the stale server must be asked to
    # stop (server-key authed), then the port reused
    ps2 = PredictionServer(make_engine(), ServerConfig(
        ip="127.0.0.1", port=port, engine_variant="served",
        server_key="sekrit"))
    try:
        bound = ps2.start_background()
        assert bound == port
        status, body = call(port, "GET", "/")
        assert status == 200 and body["requestCount"] == 0
    finally:
        ps2.stop()


def test_undeploy_foreign_process_logs_and_continues(stack, caplog):
    """A non-pio process answering /stop with an error is reported, not
    crashed into (MasterActor.undeploy 404 branch)."""
    import logging

    from fake_engine import make_engine

    srv, hits = _mini_server()
    port = srv.start_background()
    ps2 = PredictionServer(make_engine(), ServerConfig(
        ip="127.0.0.1", port=port, engine_variant="served"))
    ps2.http.bind_retries = 0
    with caplog.at_level(logging.ERROR):
        with pytest.raises(RuntimeError):
            ps2.start_background()  # foreign owner keeps the port
    assert hits == ["stop"]
    assert any("Another process is using" in r.message for r in caplog.records)
    srv.stop()


def test_log_url_ships_query_errors(stack):
    """Query errors POST to --log-url with the prefix + engine instance
    (remoteLog, CreateServer.scala:449-460)."""
    import threading

    from incubator_predictionio_tpu.utils.http import (
        HttpServer,
        Request,
        Response,
        Router,
    )

    ps, port, _es, _esp = stack
    received = []
    got_one = threading.Event()
    r = Router()

    @r.post("/collect")
    def collect(request: Request) -> Response:
        received.append(request.body.decode())
        got_one.set()
        return Response(200, {})

    collector = HttpServer(r, "127.0.0.1", 0)
    cport = collector.start_background()
    ps.config.log_url = f"http://127.0.0.1:{cport}/collect"
    ps.config.log_prefix = "PIOLOG "
    try:
        status, _ = call(port, "POST", "/queries.json", {"bogus": 1})
        assert status == 400
        assert got_one.wait(10), "no remote log arrived"
        assert received[0].startswith("PIOLOG ")
        doc = json.loads(received[0][len("PIOLOG "):])
        assert doc["engineInstance"]["id"]
        assert "Stack Trace" in doc["message"]
    finally:
        ps.config.log_url = None
        collector.stop()


def test_warmup_hook_runs_after_bind(stack, caplog):
    """start_background spawns the warmup thread; the fake engine's algo
    has the default no-op warmup, so the pass completes and logs. A
    failing warmup must be swallowed (queries compile on demand)."""
    import logging
    import time

    ps, port, _es, _esp = stack
    # the fixture's own warmup ran during setup; re-trigger under caplog
    # to observe the completion log deterministically
    with caplog.at_level(logging.INFO):
        ps._warmup_async()
        for _ in range(200):
            if any("serving warmup done" in r.message
                   for r in caplog.records):
                break
            time.sleep(0.05)
    assert any("serving warmup done" in r.message for r in caplog.records)

    # a warmup that raises is logged, not fatal: queries still serve
    class Exploding:
        def warmup(self, model, max_batch=1):
            raise RuntimeError("boom")

    ps.algorithms = [Exploding()]
    with caplog.at_level(logging.ERROR):
        ps._warmup_async()
        for _ in range(100):
            if any("warmup failed" in r.message for r in caplog.records):
                break
            time.sleep(0.05)
    assert any("warmup failed" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# batch_serve_json columnar fast path (core/base.py batch_serve_json;
# models/recommendation/engine.py ALSAlgorithm.batch_serve_json)
# ---------------------------------------------------------------------------

def _als_fixture():
    import jax.numpy as jnp
    import numpy as np

    from incubator_predictionio_tpu.data.bimap import BiMap
    from incubator_predictionio_tpu.models.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        ALSModel,
    )

    rng = np.random.default_rng(3)
    nu, ni, k = 40, 25, 8
    model = ALSModel(
        user_factors=jnp.asarray(rng.normal(size=(nu, k)).astype(np.float32)),
        item_factors=jnp.asarray(rng.normal(size=(ni, k)).astype(np.float32)),
        user_bimap=BiMap({f"u{i}": i for i in range(nu)}),
        item_bimap=BiMap({f"i{i}": i for i in range(ni)}),
        item_years={"i3": 1999, "i7": 2004},
        item_categories={},
    )
    return ALSAlgorithm(ALSAlgorithmParams(rank=k)), model


def test_batch_serve_json_byte_identical_to_object_path():
    """The rendered fast-path bytes must be exactly what the object path
    would put on the wire FOR THE SAME BATCH: batch_predict → serve →
    json.dumps(to_jsonable(...)). (Compared against the batched object
    path, not per-query predict: the batched matmul's f32 rounding is the
    wire truth for any batch the micro-batcher forms.)"""
    from incubator_predictionio_tpu.models.recommendation.engine import Query
    from incubator_predictionio_tpu.utils import json_codec

    algo, model = _als_fixture()
    docs = [
        {"user": "u1", "num": 5},
        {"user": "u2", "num": 10},
        {"user": "u39", "num": 3},
    ]
    fast = algo.batch_serve_json(model, docs)
    assert all(isinstance(b, bytes) for b in fast)
    objs = dict(algo.batch_predict(model, [
        (i, Query(user=d["user"], num=d["num"]))
        for i, d in enumerate(docs)]))
    for i, (d, payload) in enumerate(zip(docs, fast)):
        expect = json.dumps(json_codec.to_jsonable(objs[i])).encode()
        assert payload == expect, (d, payload, expect)


def test_batch_serve_json_rejects_non_plain_docs():
    """Anything beyond the exact plain shape falls to the object path."""
    algo, model = _als_fixture()
    docs = [
        {"user": "u1", "num": 5, "creationYear": 2000},  # extra key
        {"user": "nosuch", "num": 5},                    # unknown user
        {"user": "u1"},                                   # missing num
        {"user": "u1", "num": True},                      # bool num
        {"user": "u1", "num": 0},                         # non-positive
        {"user": 7, "num": 5},                            # non-str user
        ["not", "a", "dict"],
        None,
        {"user": "u1", "num": 5},                         # one good slot
    ]
    fast = algo.batch_serve_json(model, docs)
    assert fast[:-1] == [None] * (len(docs) - 1)
    assert isinstance(fast[-1], bytes)


def test_fast_path_negative_gate_through_http(stack):
    """The fake_engine stack's serving is not FIRST_PREDICTION_ONLY, so
    this exercises the NEGATIVE gate: the object path still answers."""
    _ps, port, _es, _es_port = stack
    status, body = call(port, "POST", "/queries.json", {"qx": 1})
    assert status == 200


def test_fast_path_served_through_http():
    """POSITIVE gate end-to-end: an ALS engine with stock serving behind
    the REAL server answers plain queries from the bytes fast path, and
    the wire body is exactly the object path's rendering for the same
    singleton batch; filtered queries still take the object path."""
    import threading

    from incubator_predictionio_tpu.data.storage import (
        EngineInstance,
        Storage,
    )
    from incubator_predictionio_tpu.models.recommendation.engine import (
        Query,
        RecommendationServing,
    )
    from incubator_predictionio_tpu.servers.prediction_server import (
        _AsyncPoster,
        BatchScheduler,
    )
    from incubator_predictionio_tpu.utils import json_codec
    from incubator_predictionio_tpu.utils.http import HttpServer
    from incubator_predictionio_tpu.utils.times import now_utc
    from incubator_predictionio_tpu.workflow.workflow import (
        make_runtime_context,
    )

    algo, model = _als_fixture()
    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    now = now_utc()
    srv = PredictionServer.__new__(PredictionServer)
    srv.engine = None
    srv.config = ServerConfig(ip="127.0.0.1", port=0)
    srv.plugin_context = PluginContext()
    srv.ctx = make_runtime_context(None)
    srv._lock = threading.Lock()
    srv.engine_instance = EngineInstance(
        id="t", status="COMPLETED", start_time=now, end_time=now,
        engine_id="t", engine_version="1", engine_variant="t",
        engine_factory="t")
    srv.engine_params = None
    srv.algorithms = [algo]
    srv.serving = RecommendationServing()
    srv.models = [model]
    srv.start_time = now
    srv.request_count = 0
    srv.avg_serving_sec = 0.0
    srv.last_serving_sec = 0.0
    srv.max_batch_served = 0
    srv._conf_server_key = None
    srv.http = HttpServer(srv._build_router(), "127.0.0.1", 0)
    srv._batcher = BatchScheduler(srv._handle_batch, srv.config.micro_batch)
    srv._feedback_poster = _AsyncPoster("feedback")
    srv._log_poster = _AsyncPoster("log", workers=1)
    port = srv.http.start_background()
    try:
        url = f"http://127.0.0.1:{port}/queries.json"
        req = urllib.request.Request(
            url, data=json.dumps({"user": "u1", "num": 5}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as resp:
            wire = resp.read()
        # the wire body is byte-identical to the object path's rendering
        # for the same singleton batch
        objs = dict(algo.batch_predict(model, [(0, Query(user="u1",
                                                         num=5))]))
        assert wire == json.dumps(json_codec.to_jsonable(objs[0])).encode()
        # a filtered query still answers via the object path
        req = urllib.request.Request(
            url, data=json.dumps({"user": "u1", "num": 3,
                                  "blacklist": ["i1"]}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as resp:
            doc = json.loads(resp.read())
        assert "i1" not in [s["item"] for s in doc["itemScores"]]
        assert srv.request_count == 2  # stats cover both paths
    finally:
        srv.stop()
        Storage.reset()
