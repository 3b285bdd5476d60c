"""Multi-tenant serving platform (serving/tenancy.py + friends).

Covers the bounded tenant registry (PIO_TENANTS grammar, auth, the
metric-safe label gateway), the scheduler's tenant isolation planes
(weighted-fair dispatch, admission quotas, the contention slot caps),
the prediction server's access-key query path + tenant-scoped reload,
the per-tenant SLO specs, and the capacity report's per-tenant sizing
helpers — the PR-20 acceptance surface that is unit-testable without
a fleet of worker processes.
"""

import base64
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from fake_engine import AP, make_engine, params
from incubator_predictionio_tpu.data.storage import Storage
from incubator_predictionio_tpu.obs import capacity, slo
from incubator_predictionio_tpu.serving import tenancy
from incubator_predictionio_tpu.serving.scheduler import (
    BatchScheduler,
    ShedError,
)
from incubator_predictionio_tpu.servers.prediction_server import (
    PredictionServer,
    ServerConfig,
)
from incubator_predictionio_tpu.workflow import CoreWorkflow


# -- registry parsing & bounds ----------------------------------------------

SPEC = ("alpha:alpha-key:weight=4;"
        "beta:beta-key:weight=1,quota=2;"
        "ghost:ghost-key:disabled=1")


def test_registry_parses_full_grammar():
    reg = tenancy.TenantRegistry.from_env(SPEC)
    assert reg.tenant_ids() == ("alpha", "beta", "ghost")
    a, b, g = reg.get("alpha"), reg.get("beta"), reg.get("ghost")
    assert a.weight == 4 and a.quota is None and a.enabled
    assert b.weight == 1 and b.quota == 2 and b.enabled
    assert not g.enabled
    assert reg.weights() == {"alpha": 4, "beta": 1, "ghost": 1}
    assert reg.quotas() == {"alpha": None, "beta": 2, "ghost": None}
    # keys never leak out of the shareable table
    assert "key" not in json.dumps(reg.describe())


def test_registry_empty_and_whitespace_entries():
    assert not tenancy.TenantRegistry.from_env("")
    assert not tenancy.TenantRegistry.from_env(" ; ;")
    assert len(tenancy.TenantRegistry.from_env(" a:k1 ; b:k2 ")) == 2


@pytest.mark.parametrize("bad", [
    "justanid",                        # no key
    "a:k:mystery=1",                   # unknown option
    "a:k1;a:k2",                       # duplicate tenant id
    "a:k;b:k",                         # duplicate access key
    "bad id!:k",                       # id grammar
    "a:k:weight=0",                    # weight must be >= 1
    "a:",                              # empty key
])
def test_registry_rejects_malformed_entries(bad):
    with pytest.raises(ValueError):
        tenancy.TenantRegistry.from_env(bad)


def test_registry_is_bounded():
    spec = ";".join(f"t{i}:k{i}" for i in range(tenancy.MAX_TENANTS + 1))
    with pytest.raises(ValueError, match="bounded"):
        tenancy.TenantRegistry.from_env(spec)
    # exactly at the bound is legal — the label cardinality ceiling
    spec = ";".join(f"t{i}:k{i}" for i in range(tenancy.MAX_TENANTS))
    assert len(tenancy.TenantRegistry.from_env(spec)) == \
        tenancy.MAX_TENANTS


def test_label_gateway_is_metric_safe():
    reg = tenancy.TenantRegistry.from_env(SPEC)
    assert reg.label("alpha") == "alpha"
    # wire values that never registered collapse to the bounded default
    assert reg.label("nope' OR 1=1") == tenancy.DEFAULT_TENANT
    assert reg.label(None) == tenancy.DEFAULT_TENANT
    assert tenancy.TenantRegistry().label("alpha") == \
        tenancy.DEFAULT_TENANT


# -- auth grammar (the event server's, serving edition) ---------------------

class _Req:
    def __init__(self, query=None, headers=None):
        self.query = query or {}
        self.headers = headers or {}


def test_extract_access_key_query_param_and_basic():
    assert tenancy.extract_access_key(
        _Req(query={"accessKey": "k1"})) == "k1"
    basic = base64.b64encode(b"k2:ignored-password").decode()
    assert tenancy.extract_access_key(
        _Req(headers={"authorization": f"Basic {basic}"})) == "k2"
    # query param wins over the header, same as the event server
    assert tenancy.extract_access_key(
        _Req(query={"accessKey": "k1"},
             headers={"authorization": f"Basic {basic}"})) == "k1"
    assert tenancy.extract_access_key(_Req()) is None
    assert tenancy.extract_access_key(
        _Req(headers={"authorization": "Basic %%%notb64"})) is None


def test_authenticate_maps_key_to_tenant_or_401():
    reg = tenancy.TenantRegistry.from_env(SPEC)
    assert reg.authenticate(_Req(query={"accessKey": "alpha-key"})) == \
        "alpha"
    for req in (_Req(),                                  # missing
                _Req(query={"accessKey": "wrong"}),      # unknown
                _Req(query={"accessKey": "ghost-key"})):  # disabled
        with pytest.raises(tenancy.TenantAuthError) as ei:
            reg.authenticate(req)
        assert ei.value.status == 401
    # empty registry = single-tenant compatibility mode: no auth at all
    assert tenancy.TenantRegistry().authenticate(_Req()) == \
        tenancy.DEFAULT_TENANT


def test_registry_singleton_follows_env(monkeypatch):
    tenancy.reset_registry()
    monkeypatch.setenv("PIO_TENANTS", "a:k1")
    assert tenancy.get_registry().tenant_ids() == ("a",)
    monkeypatch.setenv("PIO_TENANTS", "a:k1;b:k2")
    assert tenancy.get_registry().tenant_ids() == ("a", "b")
    monkeypatch.delenv("PIO_TENANTS")
    assert not tenancy.get_registry()
    tenancy.reset_registry()


# -- scheduler isolation planes ---------------------------------------------

def _drain(sched):
    sched.stop()


def test_scheduler_quota_sheds_only_the_quota_tenant():
    done = threading.Event()

    def handle(bodies, engine, tenant):
        done.wait(2.0)
        return list(bodies)

    s = BatchScheduler(handle, max_batch=8, workers=1, shed=False,
                       tenant_quotas={"beta": 2})
    try:
        futs = [s.submit(i, tenant="beta") for i in range(2)]
        # one batch may already be in flight; fill to the quota bound
        # (the shed lands on the FUTURE — admission stays non-raising)
        deadline = time.monotonic() + 2.0
        shed = None
        while time.monotonic() < deadline and shed is None:
            f = s.submit(99, tenant="beta")
            if f.done() and isinstance(f.exception(), ShedError):
                shed = f.exception()
            else:
                futs.append(f)
        assert shed is not None and shed.reason == "quota"
        assert shed.status == 503
        # an unquota'd tenant keeps being admitted through the flood
        ok = s.submit(1, tenant="alpha")
        assert not (ok.done() and ok.exception())
        futs.append(ok)
        done.set()
        for f in futs:
            f.result(timeout=5)
    finally:
        done.set()
        _drain(s)


def test_scheduler_slot_caps_weighted_by_contending_tenants():
    def handle(bodies, engine, tenant):
        return list(bodies)

    s = BatchScheduler(handle, max_batch=8, workers=2,
                       tenant_weights={"victim": 8, "aggressor": 1})
    try:
        with s._cv:
            now = s._clock()
            # one contender → no caps: a tenant alone on the scheduler
            # keeps every dispatcher thread (single-tenant throughput)
            s._t_last_submit = {"aggressor": now}
            assert s._slot_caps_locked(now) is None
            # two contenders → weighted shares of the 2-thread pool:
            # ceil(2·8/9)=2 for the victim (effectively uncapped),
            # ceil(2·1/9)=1 for the aggressor (one slot stays free)
            s._t_last_submit = {"aggressor": now, "victim": now}
            caps = s._slot_caps_locked(now)
            assert caps == {"victim": 2, "aggressor": 1}
            # stale contender ages out of the window
            s._t_last_submit["victim"] = \
                now - s.CONTEND_WINDOW_S - 1.0
            assert s._slot_caps_locked(now) is None
    finally:
        _drain(s)


def test_scheduler_single_worker_never_caps():
    def handle(bodies, engine, tenant):
        return list(bodies)

    s = BatchScheduler(handle, max_batch=8, workers=1,
                       tenant_weights={"a": 1, "b": 1})
    try:
        with s._cv:
            now = s._clock()
            s._t_last_submit = {"a": now, "b": now}
            assert s._slot_caps_locked(now) is None
    finally:
        _drain(s)


def test_scheduler_flooder_never_holds_every_dispatch_slot():
    """The isolation invariant itself: under a closed-loop flood from a
    low-weight tenant, a contending light tenant means the flooder's
    concurrent in-flight dispatches stay under its weighted slot cap —
    one dispatcher thread is always free for the light tenant."""
    floor_s = 0.02

    def handle(bodies, engine, tenant):
        time.sleep(floor_s)
        return list(bodies)

    s = BatchScheduler(handle, max_batch=4, workers=2, shed=False,
                       tenant_weights={"victim": 8, "aggressor": 1})
    stop = threading.Event()

    def flood():
        while not stop.is_set():
            try:
                s.submit({"q": 1}, tenant="aggressor").result(timeout=5)
            except Exception:
                return

    threads = [threading.Thread(target=flood, daemon=True)
               for _ in range(6)]
    try:
        for t in threads:
            t.start()
        max_agg_inflight = 0
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            # victim keeps contending (and must never be starved)
            s.submit({"q": 1}, tenant="victim").result(timeout=5)
            with s._cv:
                max_agg_inflight = max(
                    max_agg_inflight,
                    s._tenant_inflight_locked("aggressor"))
        assert max_agg_inflight <= 1, (
            "aggressor held every dispatch slot despite a contending "
            "light tenant")
    finally:
        stop.set()
        _drain(s)
        for t in threads:
            t.join(timeout=5)


# -- prediction server: access-key query path + tenant-scoped reload --------

@pytest.fixture
def tenant_server(monkeypatch):
    monkeypatch.setenv(
        "PIO_TENANTS",
        "alpha:alpha-key:weight=4;beta:beta-key:quota=8;"
        "ghost:ghost-key:disabled=1")
    tenancy.reset_registry()
    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    engine = make_engine()
    CoreWorkflow.run_train(engine, params(ds=9, algos=[("algo0", AP(1))]),
                           engine_variant="tenants")
    ps = PredictionServer(engine, ServerConfig(
        ip="127.0.0.1", port=0, engine_variant="tenants",
        server_key="sekrit"))
    port = ps.start_background()
    yield ps, port
    ps.stop()
    Storage.reset()
    tenancy.reset_registry()


def _post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


def test_query_path_requires_access_key(tenant_server):
    _ps, port = tenant_server
    status, body = _post(port, "/queries.json", {"qx": 1})
    assert status == 401 and "accessKey" in body["message"]
    status, _ = _post(port, "/queries.json?accessKey=wrong", {"qx": 1})
    assert status == 401
    status, _ = _post(port, "/queries.json?accessKey=ghost-key", {"qx": 1})
    assert status == 401
    status, body = _post(port, "/queries.json?accessKey=alpha-key",
                         {"qx": 7})
    assert status == 200 and body["qx"] == 7


def test_status_renders_per_tenant_block(tenant_server):
    _ps, port = tenant_server
    _post(port, "/queries.json?accessKey=alpha-key", {"qx": 1})
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/",
                                timeout=30) as resp:
        info = json.loads(resp.read())
    blocks = info["tenants"]
    assert set(blocks) == {"alpha", "beta", "ghost"}
    assert blocks["alpha"]["weight"] == 4
    assert blocks["beta"]["quota"] == 8
    assert blocks["ghost"]["enabled"] is False
    # no tenant pinned a variant: all share the default deploy
    assert blocks["alpha"]["sharedDeploy"] is True
    # keys stay out of the shareable status page
    assert "alpha-key" not in json.dumps(info)


def test_tenant_scoped_reload_leaves_default_deploy_alone(tenant_server):
    ps, port = tenant_server
    default_instance = ps.engine_instance.id
    status, body = _post(
        port, "/reload?accessKey=sekrit&tenant=alpha", {})
    assert status == 200 and "alpha" in body["message"]
    # the tenant deploy landed; the default deploy never swapped
    assert "alpha" in ps._deploys
    assert ps.engine_instance.id == default_instance
    # the co-resident deploy serves queries for its tenant
    status, body = _post(port, "/queries.json?accessKey=alpha-key",
                         {"qx": 3})
    assert status == 200 and body["qx"] == 3
    # unknown tenants 404 instead of clobbering anything
    status, _ = _post(port, "/reload?accessKey=sekrit&tenant=nope", {})
    assert status == 404
    # and the reload seam still honors the server key
    status, _ = _post(port, "/reload?accessKey=wrong&tenant=alpha", {})
    assert status == 401


# -- per-tenant SLO specs ---------------------------------------------------

def test_tenant_specs_slice_the_latency_family(monkeypatch):
    monkeypatch.setenv("PIO_TENANTS", "alpha:k1;beta:k2")
    tenancy.reset_registry()
    try:
        specs = slo.tenant_specs()
        assert [s.name for s in specs] == \
            ["serve_p99@alpha", "serve_p99@beta"]
        for s in specs:
            assert s.metric == "pio_query_latency_seconds"
            assert s.labels == (("tenant", s.name.split("@")[1]),)
        # the fleet objectives keep their unlabeled (all-tenant) read
        names = [s.name for s in slo.default_specs()]
        assert "serve_p99" in names and "serve_p99@alpha" in names
        monkeypatch.delenv("PIO_TENANTS")
        tenancy.reset_registry()
        assert slo.tenant_specs() == ()
    finally:
        tenancy.reset_registry()


# -- capacity: per-tenant sizing --------------------------------------------

def test_parse_tenant_demands_drops_malformed():
    assert capacity.parse_tenant_demands(
        "a=100; b=2000 ;typo;c=;d=-5;e=abc") == {"a": 100.0, "b": 2000.0}
    assert capacity.parse_tenant_demands("") == {}


def test_bin_pack_tenants_first_fit_with_chunk_split():
    pack = capacity.bin_pack_tenants({"b": 2000, "a": 100}, 800.0)
    # b splits into 800+800+400; a's 100 first-fits into b's third
    # worker (400+100 <= 800) — co-residency, not a fourth worker
    assert pack["workers"] == 3
    assert pack["assignment"]["b"] == [0, 1, 2]
    assert pack["assignment"]["a"] == [2]
    assert capacity.bin_pack_tenants({}, 800.0)["workers"] == 0
    assert capacity.bin_pack_tenants({"a": 10}, 0.0)["workers"] == 0
