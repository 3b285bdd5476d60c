"""Speed layer: fold-in correctness, compile-cache discipline, cursor +
overlay semantics, serving integration, and the cold-start quality claim.

The acceptance contract this file pins:
- the batched device fold-in matches a dense numpy least-squares
  reference within tolerance at EVERY bucket-ladder size,
- steady-state fold-in serves from the fixed bucket ladder (the jit
  compile-cache counter stops growing),
- the overlay is invalidated wholesale on hot model swap and per-user
  on newer events,
- on a planted cold-start workload the speed layer's recall is strictly
  better than the averaged-recent-views fallback it replaces,
- TTL/staleness decisions run on the injectable clock (no sleeps).
"""

import json
import urllib.request

import numpy as np
import pytest

from incubator_predictionio_tpu.data.datamap import DataMap
from incubator_predictionio_tpu.data.event import Event
from incubator_predictionio_tpu.data.storage import App, Storage
from incubator_predictionio_tpu.data.store import EventStore
from incubator_predictionio_tpu.speed.cache import TTLCache
from incubator_predictionio_tpu.speed.foldin import (
    FoldInSolver,
    dense_reference_solve,
    foldin_compile_cache_size,
)
from incubator_predictionio_tpu.speed.overlay import (
    SpeedOverlay,
    SpeedOverlayConfig,
)
from incubator_predictionio_tpu.utils.times import FakeClock, now_utc


# ---------------------------------------------------------------------------
# storage scaffolding
# ---------------------------------------------------------------------------

@pytest.fixture
def mem_store():
    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    Storage.get_meta_data_apps().insert(App(0, "speedapp"))
    yield "speedapp"
    Storage.reset()


def _rate(app, user, item, value, event="rate", prop="rating"):
    EventStore.write([Event(
        event=event, entity_type="user", entity_id=user,
        target_entity_type="item", target_entity_id=item,
        properties=DataMap({prop: float(value)}),
        event_time=now_utc())], app)


# ---------------------------------------------------------------------------
# fold-in differential vs the dense reference
# ---------------------------------------------------------------------------

def test_foldin_matches_dense_reference_every_bucket():
    rng = np.random.default_rng(0)
    M, K = 300, 16
    other = rng.normal(0, 0.3, (M, K)).astype(np.float32)
    solver = FoldInSolver(other, l2=0.05, reg_nnz=True, implicit=False)
    # degrees landing in every ladder bucket, including the boundaries
    degrees = [1, 7, 8, 9, 31, 32, 33, 127, 128, 200, 511, 512]
    rows = []
    for d in degrees:
        cols = rng.integers(0, M, d).astype(np.int32)
        vals = rng.normal(3.5, 1.0, d).astype(np.float32)
        rows.append((cols, vals))
    out = solver.solve(rows)
    for (cols, vals), got in zip(rows, out):
        ref = dense_reference_solve(other, cols, vals, 0.05)
        err = np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-12)
        assert err < 1e-3, (len(cols), err)


def test_foldin_truncates_over_ladder_history_to_newest():
    rng = np.random.default_rng(1)
    M, K = 100, 8
    other = rng.normal(0, 0.3, (M, K)).astype(np.float32)
    solver = FoldInSolver(other, l2=0.1)
    cols = rng.integers(0, M, 700).astype(np.int32)
    vals = rng.normal(0, 1.0, 700).astype(np.float32)
    got = solver.solve([(cols, vals)])[0]
    ref = dense_reference_solve(other, cols[-512:], vals[-512:], 0.1)
    assert np.max(np.abs(got - ref)) < 1e-3


def test_foldin_implicit_matches_dense_reference():
    rng = np.random.default_rng(2)
    M, K = 150, 8
    other = rng.normal(0, 0.3, (M, K)).astype(np.float32)
    solver = FoldInSolver(other, l2=0.05, implicit=True, alpha=2.0)
    for d in (1, 8, 30, 128):
        cols = rng.integers(0, M, d).astype(np.int32)
        vals = np.abs(rng.normal(1.0, 0.5, d)).astype(np.float32)
        got = solver.solve([(cols, vals)])[0]
        ref = dense_reference_solve(other, cols, vals, 0.05,
                                    implicit=True, alpha=2.0)
        err = np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-12)
        assert err < 1e-3, (d, err)


def test_foldin_empty_history_is_zero():
    other = np.ones((10, 4), np.float32)
    solver = FoldInSolver(other, l2=0.1)
    out = solver.solve([(np.empty(0, np.int32), np.empty(0, np.float32)),
                        (np.asarray([1], np.int32),
                         np.asarray([2.0], np.float32))])
    assert np.all(out[0] == 0.0)
    assert np.any(out[1] != 0.0)


def test_foldin_steady_state_no_recompiles():
    """THE no-per-query-recompilation assert: after the bucket ladder is
    warm, arbitrary (batch, degree) traffic adds ZERO compiled
    variants."""
    rng = np.random.default_rng(3)
    M, K = 80, 8
    other = rng.normal(0, 0.3, (M, K)).astype(np.float32)
    solver = FoldInSolver(other, l2=0.1)

    def random_rows(n):
        out = []
        for _ in range(n):
            d = int(rng.integers(1, 700))
            out.append((rng.integers(0, M, d).astype(np.int32),
                        rng.normal(0, 1, d).astype(np.float32)))
        return out

    # warm the FULL ladder: every width × every power-of-two batch size
    from incubator_predictionio_tpu.speed.foldin import (
        _max_batch,
        _width_ladder,
    )

    solver.warmup()
    for width in _width_ladder():
        b = 1
        while b <= _max_batch():
            solver.solve([(np.arange(width, dtype=np.int32) % M,
                           np.ones(width, np.float32))] * b)
            b *= 2
    warm = foldin_compile_cache_size()
    # the process-wide counter also holds other tests' flag variants
    # (implicit/explicit compile separately); the contract here is that
    # the warm ladder makes further growth impossible.
    # steady state: 30 more rounds of arbitrary traffic — ZERO growth
    for _ in range(30):
        solver.solve(random_rows(int(rng.integers(1, 80))))
    assert foldin_compile_cache_size() == warm, (
        "fold-in recompiled outside the fixed bucket ladder")


# ---------------------------------------------------------------------------
# tail cursor + read_interactions_since
# ---------------------------------------------------------------------------

def test_tail_cursor_memory(mem_store):
    app = mem_store
    assert EventStore.tail_cursor(app) == 0
    _rate(app, "u1", "i1", 4.0)
    _rate(app, "u2", "i2", 3.0)
    c1 = EventStore.tail_cursor(app)
    assert c1 == 2
    inter, times, appends, new_c, reset = \
        EventStore.read_interactions_since(
            0, app, event_names=("rate",), value_prop="rating")
    assert new_c == 2 and len(inter) == 2 and not reset
    assert list(inter.user_ids) == ["u1", "u2"]
    # the memory backend stamps exact per-slot append walls
    assert appends.shape == (2,) and (appends > 0).all()
    # only the tail after the cursor
    _rate(app, "u3", "i1", 5.0)
    inter2, _t, _a, new_c2, _r = EventStore.read_interactions_since(
        c1, app, event_names=("rate",), value_prop="rating")
    assert new_c2 == 3 and len(inter2) == 1
    assert list(inter2.user_ids) == ["u3"]
    # non-matching events advance the cursor but contribute no rows
    EventStore.write([Event(
        event="$set", entity_type="item", entity_id="i9",
        properties=DataMap({"categories": ["x"]}),
        event_time=now_utc())], app)
    inter3, _t, _a, new_c3, _r = EventStore.read_interactions_since(
        new_c2, app, event_names=("rate",), value_prop="rating")
    assert new_c3 == 4 and len(inter3) == 0


def test_tail_skips_deleted_and_superseded_events(mem_store):
    """A deleted event must not replay through the tail read (training
    scans exclude it; the speed layer must match), and an upsert's
    superseded version must not either — while cursor POSITIONS stay
    monotonic."""
    app = mem_store
    eids = EventStore.write([Event(
        event="rate", entity_type="user", entity_id="gdpr",
        target_entity_type="item", target_entity_id="i1",
        properties=DataMap({"rating": 4.0}), event_time=now_utc())], app)
    _rate(app, "u2", "i2", 3.0)
    EventStore.delete([eids[0]], app)
    inter, _t, _a, new_c, reset = EventStore.read_interactions_since(
        0, app, event_names=("rate",), value_prop="rating")
    assert not reset and new_c == 2       # positions preserved
    assert list(inter.user_ids) == ["u2"]  # deleted event gone
    # upsert: only the NEWEST write of an explicit id replays
    EventStore.write([Event(
        event="rate", entity_type="user", entity_id="u3",
        target_entity_type="item", target_entity_id="i3",
        properties=DataMap({"rating": 1.0}), event_time=now_utc(),
        event_id="fixed-id")], app)
    EventStore.write([Event(
        event="rate", entity_type="user", entity_id="u3",
        target_entity_type="item", target_entity_id="i3",
        properties=DataMap({"rating": 2.0}), event_time=now_utc(),
        event_id="fixed-id")], app)
    inter2, _t, _a, _c, _r = EventStore.read_interactions_since(
        0, app, event_names=("rate",), value_prop="rating")
    u3_vals = [float(v) for u, v in zip(inter2.user_idx, inter2.values)
               if inter2.user_ids[int(u)] == "u3"]
    assert u3_vals == [2.0]


def test_tail_cursor_cpplog(tmp_path):
    cpplog = pytest.importorskip(
        "incubator_predictionio_tpu.data.storage.cpplog")
    from incubator_predictionio_tpu.data.storage import StorageClientConfig
    from incubator_predictionio_tpu.data.storage.base import (
        IdTable,
        Interactions,
    )

    cfg = StorageClientConfig(properties={"PATH": str(tmp_path)})
    try:
        client = cpplog.StorageClient(cfg)
    except Exception:
        pytest.skip("native library unavailable")
    dao = cpplog.CppLogEvents(client, cfg, prefix="t_")
    try:
        assert dao.tail_cursor(1) == 0
        dao.import_interactions(
            Interactions(
                user_idx=np.asarray([0, 1], np.int32),
                item_idx=np.asarray([0, 1], np.int32),
                values=np.asarray([4.0, 3.0], np.float32),
                user_ids=IdTable.from_list(["u1", "u2"]),
                item_ids=IdTable.from_list(["i1", "i2"])),
            1, event_name="rate", value_prop="rating")
        c1 = dao.tail_cursor(1)
        assert c1 == 2
        inter, times, appends, new_c, reset = dao.read_interactions_since(
            0, 1, event_names=("rate",), value_prop="rating")
        assert new_c == 2 and len(inter) == 2 and not reset
        assert list(inter.user_ids) == ["u1", "u2"]
        # this process wrote the batch: its append mark covers the tail
        assert appends.shape == (2,) and (appends > 0).all()
        dao.import_interactions(
            Interactions(
                user_idx=np.asarray([0], np.int32),
                item_idx=np.asarray([0], np.int32),
                values=np.asarray([5.0], np.float32),
                user_ids=IdTable.from_list(["u3"]),
                item_ids=IdTable.from_list(["i1"])),
            1, event_name="rate", value_prop="rating")
        inter2, _t, _a, new_c2, _r = dao.read_interactions_since(
            c1, 1, event_names=("rate",), value_prop="rating")
        assert new_c2 == 3 and len(inter2) == 1
        assert list(inter2.user_ids) == ["u3"]
        # empty tail round-trips cleanly
        inter3, _t, _a, new_c3, _r = dao.read_interactions_since(new_c2, 1)
        assert new_c3 == new_c2 and len(inter3) == 0
        # compaction renumbers entries: an old cursor must RESET even
        # when appends push the entry count past its old value (a bare
        # count comparison would silently misread the delta)
        eid = dao.insert(Event(
            event="rate", entity_type="user", entity_id="u9",
            target_entity_type="item", target_entity_id="i1",
            properties=DataMap({"rating": 1.0}),
            event_time=now_utc()), 1)
        dao.delete(eid, 1)
        pre_compact = dao.tail_cursor(1)
        dao.compact(1)
        dao.import_interactions(
            Interactions(
                user_idx=np.zeros(4, np.int32),
                item_idx=np.zeros(4, np.int32),
                values=np.ones(4, np.float32),
                user_ids=IdTable.from_list(["u4"]),
                item_ids=IdTable.from_list(["i1"])),
            1, event_name="rate", value_prop="rating")
        # entry count now exceeds the pre-compaction position...
        assert dao.tail_cursor(1) != pre_compact
        _i, _t, _a, _c, reset = dao.read_interactions_since(
            pre_compact, 1, event_names=("rate",), value_prop="rating")
        assert reset is True  # ...but the generation mismatch catches it
    finally:
        client.close()


# ---------------------------------------------------------------------------
# overlay semantics
# ---------------------------------------------------------------------------

def _overlay(app, other, idx, clock, **cfg_kw):
    kw = dict(app_name=app, event_names=("rate",), value_prop="rating",
              l2=0.05, ttl_s=30.0)
    kw.update(cfg_kw)
    return SpeedOverlay(SpeedOverlayConfig(**kw), other, idx, clock=clock)


def test_overlay_fold_in_and_per_user_invalidation(mem_store):
    app = mem_store
    rng = np.random.default_rng(4)
    other = rng.normal(0, 0.3, (20, 8)).astype(np.float32)
    idx = {f"i{k}": k for k in range(20)}
    clock = FakeClock()
    ov = _overlay(app, other, idx, clock)
    assert ov.enabled
    _rate(app, "alice", "i3", 4.0)
    _rate(app, "alice", "i7", 2.0)
    s = ov.poll()
    assert s["solved"] == 1 and s["tail_rows"] == 2
    vec = ov.lookup("alice")
    assert vec is not None
    ref = dense_reference_solve(other, [3, 7], [4.0, 2.0], 0.05)
    assert np.allclose(vec, ref, atol=1e-3)
    # newer per-user event invalidates the entry the moment the poll
    # sees it — and lookup misses until the re-solve lands
    _rate(app, "alice", "i1", 5.0)
    ov.poll(max_keys=0)  # mark dirty without re-solving
    assert ov.lookup("alice") is None
    assert not ov.covers("alice")
    ov.poll()
    vec2 = ov.lookup("alice")
    ref2 = dense_reference_solve(other, [3, 7, 1], [4.0, 2.0, 5.0], 0.05)
    assert np.allclose(vec2, ref2, atol=1e-3)


def test_overlay_serves_hits_under_concurrent_ingest(mem_store):
    """A writer appends cold users' events while this thread polls the
    overlay and looks every user ingested so far up: users not yet
    folded in miss, folded ones hit, and once the writer is done and
    the dirty set drained every ingested user is served."""
    import threading

    app = mem_store
    rng = np.random.default_rng(23)
    other = rng.normal(0, 0.3, (20, 8)).astype(np.float32)
    idx = {f"i{k}": k for k in range(20)}
    ov = _overlay(app, other, idx, FakeClock(), ttl_s=600.0)
    ingested = []                       # cold user ids, in ingest order
    items = rng.integers(0, 20, (24, 3))

    def writer():
        for j, row in enumerate(items):
            for i in row:
                _rate(app, f"cold{j}", f"i{i}", 4.0)
            ingested.append(f"cold{j}")

    t = threading.Thread(target=writer)
    t.start()
    max_lag = 0
    while t.is_alive() or ov.stats()["dirty"] or ov.poll()["tail_rows"]:
        s = ov.poll()
        max_lag = max(max_lag, int(s.get("lag", 0)))
        for uid in list(ingested):
            ov.lookup(uid)
        t.join(0.001)
    assert not t.is_alive() and len(ingested) == 24
    assert all(ov.lookup(uid) is not None for uid in ingested)
    st = ov.stats()
    assert st["foldins"] >= 1 and st["dirty"] == 0
    looked = st["hits"] + st["misses"]
    assert st["hits"] >= 24 and 0.0 < st["hits"] / looked <= 1.0
    assert max_lag >= 0 and st["cursorLagEvents"] == 0


def test_overlay_ttl_and_wholesale_invalidation(mem_store):
    app = mem_store
    other = np.eye(8, dtype=np.float32)[: 8]
    idx = {f"i{k}": k for k in range(8)}
    clock = FakeClock()
    ov = _overlay(app, other, idx, clock, ttl_s=10.0)
    _rate(app, "bob", "i1", 4.0)
    ov.poll()
    assert ov.covers("bob")
    # TTL expiry through the clock seam — no sleeps
    clock.advance(10.5)
    assert ov.lookup("bob") is None
    # refold, then hot-swap invalidation clears everything at once
    ov.poll()  # bob is no longer dirty: nothing to refold
    _rate(app, "carol", "i2", 3.0)
    ov.poll()
    assert ov.covers("carol")
    ov.invalidate_all()
    assert not ov.covers("carol")
    assert ov.lookup("carol") is None


def test_overlay_key_version_bumps_on_new_events(mem_store):
    app = mem_store
    other = np.eye(4, dtype=np.float32)
    ov = _overlay(app, other, {f"i{k}": k for k in range(4)}, FakeClock())
    assert ov.key_version("dave") == 0
    _rate(app, "dave", "i0", 1.0)
    ov.poll(max_keys=0)
    v1 = ov.key_version("dave")
    assert v1 >= 1
    _rate(app, "dave", "i1", 1.0)
    ov.poll(max_keys=0)
    assert ov.key_version("dave") > v1


def test_overlay_cursor_reset_invalidates(mem_store):
    app = mem_store
    other = np.eye(4, dtype=np.float32)
    ov = _overlay(app, other, {f"i{k}": k for k in range(4)}, FakeClock())
    _rate(app, "erin", "i0", 2.0)
    ov.poll()
    assert ov.covers("erin")
    # simulate a log rewrite: drop the table (cursor goes backwards)
    app_id = Storage.get_meta_data_apps().get_by_name(app).id
    Storage.get_events().remove(app_id)
    Storage.get_events().init(app_id)
    s = ov.poll()
    assert s.get("reset") is True
    assert not ov.covers("erin")


def test_overlay_item_side_fold_in(mem_store):
    """key_side='target': a brand-new ITEM's row is solved from its
    events against frozen user factors (the similarproduct orientation).
    """
    app = mem_store
    rng = np.random.default_rng(5)
    user_factors = rng.normal(0, 0.3, (10, 8)).astype(np.float32)
    uidx = {f"u{k}": k for k in range(10)}
    ov = SpeedOverlay(
        SpeedOverlayConfig(
            app_name=app, event_names=("view",), value_prop=None,
            event_values={"view": 1.0}, key_side="target",
            l2=0.05, implicit=True, alpha=1.0),
        user_factors, uidx, clock=FakeClock())
    for u in ("u1", "u4", "u7"):
        EventStore.write([Event(
            event="view", entity_type="user", entity_id=u,
            target_entity_type="item", target_entity_id="newitem",
            event_time=now_utc())], app)
    s = ov.poll()
    assert s["solved"] == 1
    vec = ov.lookup("newitem")
    ref = dense_reference_solve(user_factors, [1, 4, 7], [1.0, 1.0, 1.0],
                                0.05, implicit=True, alpha=1.0)
    assert np.allclose(vec, ref, atol=1e-3)


# ---------------------------------------------------------------------------
# TTL micro-cache + clock seam
# ---------------------------------------------------------------------------

def test_ttl_cache_clock_and_version():
    clock = FakeClock()
    cache = TTLCache(maxsize=2, ttl_s=5.0, clock=clock)
    loads = []

    def loader():
        loads.append(1)
        return "v"

    assert cache.get_or_load("k", loader, version=1) == "v"
    assert cache.get_or_load("k", loader, version=1) == "v"
    assert len(loads) == 1
    # version bump invalidates immediately
    assert cache.get_or_load("k", loader, version=2) == "v"
    assert len(loads) == 2
    # TTL expiry through the clock seam
    clock.advance(5.1)
    assert cache.get_or_load("k", loader, version=2) == "v"
    assert len(loads) == 3
    # bounded: LRU eviction at maxsize
    cache.put("a", 1)
    cache.put("b", 2)
    cache.put("c", 3)
    assert len(cache) == 2


def test_ecommerce_micro_cache_dedupes_and_invalidates(mem_store):
    """The recent-events read runs once per write window, not once per
    query — and a new write (cursor bump) invalidates immediately."""
    from incubator_predictionio_tpu.models.ecommerce.engine import (
        ECommAlgorithm,
        ECommAlgorithmParams,
    )

    app = mem_store
    algo = ECommAlgorithm(ECommAlgorithmParams(app_name=app, rank=4))
    _rate(app, "fresh", "i0", 1.0, event="view")

    calls = []
    real = EventStore.find_by_entity

    class _Model:
        item_bimap = {"i0": 0, "i1": 1}

        class _B(dict):
            pass
    model = _Model()
    model.item_bimap = __import__(
        "incubator_predictionio_tpu.data.bimap",
        fromlist=["BiMap"]).BiMap({"i0": 0, "i1": 1})

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    EventStore.find_by_entity = staticmethod(counting)
    try:
        r1 = algo._recent_items(model, "fresh")
        r2 = algo._recent_items(model, "fresh")
        assert r1 == r2 == [0]
        assert len(calls) == 1  # second read served from the micro-cache
        # a new write bumps the store cursor → immediate refetch
        _rate(app, "fresh", "i1", 1.0, event="view")
        r3 = algo._recent_items(model, "fresh")
        assert len(calls) == 2
        assert set(r3) == {0, 1}
    finally:
        EventStore.find_by_entity = staticmethod(real)


# ---------------------------------------------------------------------------
# serving integration: prediction server end-to-end
# ---------------------------------------------------------------------------

def _call(port, method, path, body=None):
    url = f"http://127.0.0.1:{port}{path}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {})
    with urllib.request.urlopen(req) as resp:
        return resp.status, json.loads(resp.read() or b"null")


def test_prediction_server_speed_layer_e2e(mem_store, monkeypatch):
    """Deploy the real recommendation engine, ingest events for an
    unknown user, poll the overlay, and watch /queries.json serve them;
    /reload proves the wholesale hot-swap invalidation; /status reports
    staleness + overlay stats."""
    from incubator_predictionio_tpu.core.params import EngineParams
    from incubator_predictionio_tpu.models.recommendation.engine import (
        ALSAlgorithmParams,
        DataSourceParams,
        RecommendationEngine,
    )
    from incubator_predictionio_tpu.servers.prediction_server import (
        PredictionServer,
        ServerConfig,
    )
    from incubator_predictionio_tpu.workflow import CoreWorkflow

    app = mem_store
    rng = np.random.default_rng(7)
    for u in range(12):
        for i in rng.choice(20, 6, replace=False):
            _rate(app, f"u{u}", f"i{i}", float(rng.integers(1, 6)))
    engine = RecommendationEngine().apply()
    ep = EngineParams(
        data_source_params=("", DataSourceParams(app_name=app)),
        algorithm_params_list=[("als", ALSAlgorithmParams(
            rank=4, num_iterations=5, lambda_=0.05, seed=1))],
    )
    CoreWorkflow.run_train(engine, ep, engine_variant="speedtest")
    server = PredictionServer(engine, ServerConfig(
        ip="127.0.0.1", port=0, engine_variant="speedtest",
        server_key="sk", micro_batch=0))
    monkeypatch.setenv("PIO_SPEED_POLL_S", "3600")  # poll manually
    port = server.start_background()
    try:
        assert len(server._speed_overlays) == 1
        overlay = server._speed_overlays[0]
        # unknown user, no events: empty result
        _st, r = _call(port, "POST", "/queries.json",
                       {"user": "newbie", "num": 3})
        assert r["itemScores"] == []
        # events arrive; the overlay folds the user in
        for i in ("i1", "i2", "i3"):
            _rate(app, "newbie", i, 5.0)
        s = overlay.poll()
        assert s["solved"] >= 1
        _st, r2 = _call(port, "POST", "/queries.json",
                        {"user": "newbie", "num": 3})
        assert len(r2["itemScores"]) == 3
        # /status: staleness + overlay stats
        _st, info = _call(port, "GET", "/")
        assert info["modelStalenessSec"] >= 0
        assert info["speedOverlay"]["overlays"] == 1
        assert info["speedOverlay"]["size"] >= 1
        assert info["speedOverlay"]["foldins"] >= 1
        # hot swap: /reload replaces the overlay and invalidates the old
        # one wholesale — the new overlay starts empty
        _st, _ = _call(port, "POST", "/reload?accessKey=sk", {})
        assert _st == 200
        assert not overlay.covers("newbie")       # old overlay: emptied
        new_overlay = server._speed_overlays[0]
        assert new_overlay is not overlay
        assert not new_overlay.covers("newbie")   # fresh overlay: empty
        _st, r3 = _call(port, "POST", "/queries.json",
                        {"user": "newbie", "num": 3})
        assert r3["itemScores"] == []             # until the next poll
        new_overlay.poll()
        _st, r4 = _call(port, "POST", "/queries.json",
                        {"user": "newbie", "num": 3})
        assert len(r4["itemScores"]) == 3
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# planted cold-start workload: fold-in beats averaged recent views
# ---------------------------------------------------------------------------

def test_cold_start_recall_beats_averaged_recent_views():
    """The quality claim: for users the deployed model never saw, the
    exact device fold-in ranks strictly better than the averaged
    recent-views fallback it replaces (ecommerce recentFeatures)."""
    from incubator_predictionio_tpu.ops.als import als_train_implicit

    rng = np.random.default_rng(11)
    K0, n_items, n_train, n_cold = 4, 250, 80, 24
    u_true = rng.normal(0, 1.0, (n_train + n_cold, K0))
    v_true = rng.normal(0, 1.0, (n_items, K0))
    pref = u_true @ v_true.T                       # [U, I] true affinity

    def sample_views(u, n):
        p = np.exp(pref[u] / 1.5)
        p /= p.sum()
        return rng.choice(n_items, size=n, replace=False, p=p)

    users, items = [], []
    for u in range(n_train):
        for i in sample_views(u, 25):
            users.append(u)
            items.append(i)
    state = als_train_implicit(
        np.asarray(users, np.int32), np.asarray(items, np.int32),
        np.ones(len(users), np.float32),
        n_users=n_train, n_items=n_items, rank=8, iterations=12,
        l2=0.05, alpha=2.0, seed=3)
    item_factors = np.asarray(state.item_factors)

    solver = FoldInSolver(item_factors, l2=0.05, implicit=True, alpha=2.0)
    k = 20
    fold_recall, avg_recall = [], []
    for cu in range(n_train, n_train + n_cold):
        viewed = sample_views(cu, 15)
        truth_rank = np.argsort(-pref[cu])
        truth_top = [i for i in truth_rank if i not in set(viewed)][:k]
        # speed layer: exact implicit fold-in
        vec = solver.solve([(viewed.astype(np.int32),
                             np.ones(len(viewed), np.float32))])[0]
        scores_f = item_factors @ vec
        # the replaced fallback: mean of the viewed items' factors
        scores_a = item_factors @ item_factors[viewed].mean(axis=0)
        for scores, acc in ((scores_f, fold_recall),
                            (scores_a, avg_recall)):
            s = scores.copy()
            s[viewed] = -np.inf                    # unseen-only serving
            top = np.argsort(-s)[:k]
            acc.append(len(set(top) & set(truth_top)) / k)
    fold_r, avg_r = float(np.mean(fold_recall)), float(np.mean(avg_recall))
    assert fold_r > avg_r, (fold_r, avg_r)


# ---------------------------------------------------------------------------
# overlay lock discipline (regressions for the races pio-lint's
# unguarded-shared-state pass surfaced: cursor written outside the lock
# on the reset path and read unlocked by enabled/poll, last_lag and the
# fold-in budget rung written by the poller but read by stats() scrapes)
# ---------------------------------------------------------------------------

class _AuditedOverlay(SpeedOverlay):
    """Asserts the overlay lock is held for every post-init write of the
    attributes the race fix moved under it."""

    _AUDITED = frozenset({"cursor", "last_lag", "_budget_rung"})

    def __setattr__(self, name, value):
        if name in self._AUDITED and getattr(self, "_audit_on", False):
            assert self._lock.locked(), (
                f"write of {name} without the overlay lock")
        object.__setattr__(self, name, value)


def test_overlay_guarded_write_discipline(mem_store):
    app = mem_store
    other = np.eye(4, dtype=np.float32)
    idx = {f"i{k}": k for k in range(4)}
    ov = _AuditedOverlay(
        SpeedOverlayConfig(app_name=app, event_names=("rate",),
                           value_prop="rating", l2=0.05, ttl_s=30.0),
        other, idx, clock=FakeClock())
    ov._audit_on = True
    assert ov.enabled                  # cursor read takes the lock now
    _rate(app, "zoe", "i1", 3.0)
    s = ov.poll()   # normal path: cursor advance + lag + rung adapt
    assert s["solved"] == 1
    st = ov.stats()
    assert st["cursor"] == s["cursor"]
    assert st["cursorLagEvents"] == s["lag"]
    assert st["foldinBudget"] >= 1
    # reset path (log rewrite): the cursor rewind must also land under
    # the lock, atomically with the derived-state invalidation
    app_id = Storage.get_meta_data_apps().get_by_name(app).id
    Storage.get_events().remove(app_id)
    Storage.get_events().init(app_id)
    s2 = ov.poll()
    assert s2.get("reset") is True
    assert ov.stats()["cursor"] == s2["cursor"]
