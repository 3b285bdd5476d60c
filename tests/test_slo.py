"""SLO burn-rate engine + freshness tracer + dispatch profiler.

Pins the three new measurement surfaces:

- burn-rate math on planted good/bad observation streams (histogram and
  gauge objectives, fast/slow windows, budget remaining, the breach
  flip) on a FRESH registry with a fake clock — no sleeps;
- ``GET /slo`` end to end on the admin and dashboard servers, including
  the planted-breach flip the autonomous controller will key on;
- the end-to-end freshness tracker's stage accounting (append → poll →
  fold → first serve) with planted wall clocks, the backfill guard, and
  the linked span chain;
- the PIO_PROFILE dispatch profiler: off-by-default free path, on-path
  attribution and MFU math, and the admin ``POST /profile`` validation
  (400/409).
"""

import json
import logging
import urllib.error
import urllib.request

import numpy as np
import pytest

from incubator_predictionio_tpu.obs import freshness as obs_freshness
from incubator_predictionio_tpu.obs import metrics as obs_metrics
from incubator_predictionio_tpu.obs import profile as obs_profile
from incubator_predictionio_tpu.obs import slo as obs_slo
from incubator_predictionio_tpu.obs.metrics import Registry
from incubator_predictionio_tpu.obs.slo import SLOEngine, SLOSpec
from incubator_predictionio_tpu.utils import times
from incubator_predictionio_tpu.utils.times import FakeClock


# ---------------------------------------------------------------------------
# engine unit behavior (fresh registry, fake clock)
# ---------------------------------------------------------------------------

def make_engine(reg, clock, target=0.99, threshold=1.0, kind="histogram",
                metric="t_slo_seconds"):
    spec = SLOSpec(name="t", metric=metric, threshold=threshold,
                   target=target, kind=kind)
    return SLOEngine(specs=(spec,), registry=reg, clock=clock,
                     fast_window_s=60.0, slow_window_s=600.0,
                     min_tick_interval_s=0.0)


def test_burn_rate_zero_when_healthy_then_flips_on_breach():
    reg = Registry()
    clock = FakeClock()
    h = reg.histogram("t_slo_seconds", "x", buckets=(1.0, 2.0))
    eng = make_engine(reg, clock)
    h.observe(0.5, 100)                      # 100 good
    eng.tick(force=True)
    clock.advance(10)
    out = eng.evaluate()[0]
    assert out["noData"] is False
    assert out["windows"]["fast"]["burnRate"] == 0.0
    assert out["errorBudgetRemaining"] == 1.0
    assert out["breached"] is False
    # plant the breach: 50 observations past the threshold
    h.observe(5.0, 50)
    clock.advance(10)
    out = eng.evaluate()[0]
    # bad fraction 50/150 over the window, allowed 1% -> burn >> 1
    assert out["windows"]["fast"]["burnRate"] > 1.0
    assert out["breached"] is True
    assert out["errorBudgetRemaining"] < 1.0


def test_threshold_rounds_down_to_bucket_bound():
    """A threshold between bounds must not overstate the good count —
    cumulative_below rounds DOWN (flag early, never late)."""
    reg = Registry()
    h = reg.histogram("t_r_seconds", "x", buckets=(1.0, 2.0, 4.0))
    h.observe(1.5)   # in the le=2.0 bucket
    below, total = h.cumulative_below(3.0)   # between 2.0 and 4.0
    assert (below, total) == (1, 1)
    below, _ = h.cumulative_below(1.2)       # between 1.0 and 2.0
    assert below == 0                        # the 1.5 obs is NOT granted


def test_gauge_slo_counts_one_observation_per_tick():
    reg = Registry()
    clock = FakeClock()
    g = reg.gauge("t_stale_seconds", "x")
    eng = make_engine(reg, clock, kind="gauge", metric="t_stale_seconds",
                      threshold=100.0)
    g.set(10.0)
    eng.tick(force=True)
    clock.advance(5)
    out = eng.evaluate()[0]
    assert out["windows"]["fast"]["burnRate"] == 0.0
    g.set(5000.0)                            # over the staleness bound
    for _ in range(20):
        clock.advance(1)
        eng.tick(force=True)
    out = eng.evaluate()[0]
    assert out["windows"]["fast"]["burnRate"] > 1.0
    assert out["breached"] is True


def test_missing_metric_reports_no_data_not_breach():
    reg = Registry()
    eng = make_engine(reg, FakeClock())
    out = eng.evaluate()[0]
    assert out["noData"] is True
    assert out["breached"] is False
    assert out["errorBudgetRemaining"] == 1.0


def test_registered_but_never_set_gauge_is_no_data():
    """A gauge REGISTERED at import but never populated (deploy failed,
    no model serving) must not tick healthy observations — 0.0-by-
    default would report a green staleness budget while nothing is
    being measured."""
    reg = Registry()
    clock = FakeClock()
    g = reg.gauge("t_unset_seconds", "x")
    eng = make_engine(reg, clock, kind="gauge",
                      metric="t_unset_seconds", threshold=100.0)
    eng.tick(force=True)
    clock.advance(5)
    out = eng.evaluate()[0]
    assert out["noData"] is True
    assert out["breached"] is False
    g.set(0.0)   # a genuine zero IS data
    clock.advance(5)
    out = eng.evaluate()[0]
    assert out["noData"] is False


def test_slow_window_confirms_sustained_burn():
    reg = Registry()
    clock = FakeClock()
    h = reg.histogram("t_slo_seconds", "x", buckets=(1.0,))
    eng = make_engine(reg, clock)
    eng.tick(force=True)
    # a transient burst of bad, then a long healthy stretch
    h.observe(5.0, 10)
    clock.advance(30)
    eng.tick(force=True)
    h.observe(0.5, 10_000)
    clock.advance(500)
    out = eng.evaluate()[0]
    # the fast window (60 s) no longer covers the burst; the slow one
    # still does but diluted by the healthy traffic
    assert out["windows"]["fast"]["burnRate"] == 0.0
    assert 0.0 < out["windows"]["slow"]["burnRate"] < 1.0


def test_exported_gauges_update_at_evaluate():
    reg = obs_metrics.REGISTRY
    clock = FakeClock()
    h = reg.histogram("t_exp_seconds", "x", buckets=(1.0,))
    spec = SLOSpec(name="t_exp", metric="t_exp_seconds", threshold=1.0,
                   target=0.9)
    eng = SLOEngine(specs=(spec,), registry=reg, clock=clock,
                    min_tick_interval_s=0.0)
    h.observe(9.0, 10)
    eng.tick(force=True)
    clock.advance(10)
    h.observe(9.0, 10)
    eng.evaluate()
    assert obs_slo.BURN_RATE.labels(slo="t_exp", window="fast").value > 1.0
    assert obs_slo.BUDGET_REMAINING.labels(slo="t_exp").value < 1.0


def test_counter_reset_clamps_process_mode():
    """A worker restart mid-window zeroes its cumulative counters. The
    snapshot ring's window delta must CLAMP at zero — a head snapshot
    below the base must never become negative good/bad deltas (negative
    burn, or a breach computed from nonsense fractions)."""
    reg = Registry()
    clock = FakeClock()
    h = reg.histogram("t_reset_seconds", "x", buckets=(1.0, 2.0))
    eng = make_engine(reg, clock, metric="t_reset_seconds")
    eng.tick(force=True)                     # zero baseline snapshot
    h.observe(5.0, 100)                      # 100 bad pre-restart
    clock.advance(10)
    out = eng.evaluate()[0]
    assert out["windows"]["fast"]["burnRate"] > 1.0
    # the restart: a fresh process re-registers the family from zero
    # and has seen LESS traffic than the old cumulative counts
    reg2 = Registry()
    h2 = reg2.histogram("t_reset_seconds", "x", buckets=(1.0, 2.0))
    h2.observe(0.5, 10)                      # 10 good, post-restart
    eng.registry = reg2
    clock.advance(10)
    out = eng.evaluate()[0]
    for w in ("fast", "slow"):
        win = out["windows"][w]
        assert win["burnRate"] >= 0.0, win
        assert win["badFraction"] >= 0.0, win
        assert win["observations"] >= 0, win
    # the clamped window sees no NEW bad observations (the 100 old bad
    # must not re-count, and certainly not count negatively)
    assert out["windows"]["fast"]["burnRate"] == 0.0
    assert 0.0 <= out["errorBudgetRemaining"] <= 1.0


class _ShrinkingFleet:
    """Registry-shaped fleet stub whose histogram family RESETS between
    reads (a worker restart between two controller/engine ticks):
    second and later reads report lower cumulative counts."""

    def __init__(self):
        self.reads = 0

    def get(self, name):
        from incubator_predictionio_tpu.obs import expofmt, federate

        self.reads += 1
        m = federate.FederatedMetric(name, "histogram")
        if self.reads == 1:
            child = expofmt.HistogramChild(
                buckets=[(1.0, 50.0), (2.0, 50.0)], sum=500.0,
                count=150.0)                 # 100 past the last bound
        else:
            # post-restart: counters re-grew from zero, still below
            # the pre-restart cumulative state
            child = expofmt.HistogramChild(
                buckets=[(1.0, 10.0), (2.0, 10.0)], sum=5.0,
                count=10.0)
        m.absorb("w0", expofmt.Family(
            name=name, kind="histogram",
            histograms={frozenset(): child}))
        return m


def test_counter_reset_clamps_fleet_mode():
    """Same clamp through the FEDERATED registry shape: a restarted
    worker's re-scraped exposition carries lower cumulative buckets,
    and the fleet engine's ring must clamp rather than emit negative
    burn (the fleet /slo the freshness controller keys on)."""
    clock = FakeClock()
    fleet = _ShrinkingFleet()
    spec = SLOSpec(name="t", metric="t_fleet_seconds", threshold=1.0,
                   target=0.99)
    eng = SLOEngine(specs=(spec,), registry=fleet, clock=clock,
                    fast_window_s=60.0, slow_window_s=600.0,
                    min_tick_interval_s=0.0, export_gauges=False)
    eng.tick(force=True)                     # sees 150 obs, 100 bad
    clock.advance(10)
    out = eng.evaluate()[0]                  # post-restart read: 10/0
    for w in ("fast", "slow"):
        win = out["windows"][w]
        assert win["burnRate"] >= 0.0, win
        assert win["badFraction"] >= 0.0, win
        assert win["observations"] >= 0, win
    assert out["windows"]["fast"]["burnRate"] == 0.0
    assert out["breached"] is False
    assert 0.0 <= out["errorBudgetRemaining"] <= 1.0


# ---------------------------------------------------------------------------
# GET /slo end to end (admin + dashboard), planted breach flip
# ---------------------------------------------------------------------------

@pytest.fixture
def slo_stack(monkeypatch):
    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.servers.admin import AdminServer
    from incubator_predictionio_tpu.servers.dashboard import DashboardServer

    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    clock = FakeClock(1000.0)
    prev = times.set_monotonic(clock)
    obs_slo.reset_engine()
    ad = AdminServer(ip="127.0.0.1", port=0)
    db = DashboardServer(ip="127.0.0.1", port=0)
    ports = {"admin": ad.start_background(),
             "dashboard": db.start_background(), "clock": clock}
    try:
        yield ports
    finally:
        ad.stop()
        db.stop()
        times.set_monotonic(prev)
        obs_slo.reset_engine()
        Storage.reset()


def get_json(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as resp:
        assert resp.status == 200
        return json.loads(resp.read())


def test_slo_route_on_admin_and_dashboard(slo_stack):
    for name in ("admin", "dashboard"):
        body = get_json(slo_stack[name], "/slo")
        names = {s["name"] for s in body["slos"]}
        # the three shipped objectives are declared
        assert {"serve_p99", "freshness_p95", "staleness"} <= names
        for s in body["slos"]:
            assert "errorBudgetRemaining" in s
            assert set(s["windows"]) == {"fast", "slow"}
            assert "burnRate" in s["windows"]["fast"]
        assert body["windows"]["fastSeconds"] > 0


def test_slo_e2e_planted_breach_flips_burn_rate(slo_stack):
    """THE acceptance contract: plant an SLO breach and observe the
    burn-rate flip through GET /slo."""
    clock = slo_stack["clock"]
    qlat = obs_metrics.REGISTRY.histogram(
        "pio_query_latency_seconds",
        "per-query serving wall (micro-batch members share the batch "
        "wall)", labels=("tenant",)).labels(tenant="default")
    qlat.observe(0.001, 200)          # healthy traffic, under any bound
    body = get_json(slo_stack["admin"], "/slo")
    clock.advance(5)
    serve = [s for s in get_json(slo_stack["admin"], "/slo")["slos"]
             if s["name"] == "serve_p99"][0]
    assert serve["breached"] is False
    # the breach: a flood of queries far over the 0.25 s objective
    qlat.observe(10.0, 500)
    clock.advance(5)
    serve = [s for s in get_json(slo_stack["admin"], "/slo")["slos"]
             if s["name"] == "serve_p99"][0]
    assert serve["windows"]["fast"]["burnRate"] > 1.0
    assert serve["breached"] is True
    assert serve["errorBudgetRemaining"] < 1.0
    # the exported gauges flipped too (what the controller will scrape)
    assert obs_slo.BURN_RATE.labels(
        slo="serve_p99", window="fast").value > 1.0


def test_slo_scrape_collector_refreshes_gauges(slo_stack):
    """/metrics drives the engine via the registry collector."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{slo_stack['admin']}/metrics",
            timeout=30) as resp:
        text = resp.read().decode()
    assert "pio_slo_burn_rate" in text
    assert "pio_slo_error_budget_remaining" in text


# ---------------------------------------------------------------------------
# freshness tracker (planted wall clock — no sleeps)
# ---------------------------------------------------------------------------

@pytest.fixture
def wall():
    box = {"ms": 1_000_000}
    prev = times.set_wall_millis(lambda: box["ms"])
    yield box
    times.set_wall_millis(prev)


def test_freshness_stages_and_histogram(wall, caplog):
    tr = obs_freshness.FreshnessTracker(engine="t_fresh")
    hist = obs_freshness.FRESHNESS_SECONDS.labels(engine="t_fresh")
    before = hist.count
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        tr.on_poll_batch({"u1": 1_000_000 - 2_000})  # appended 2 s ago
        tr.on_folded(["u1"], fold_wall_s=0.25)
        wall["ms"] += 500                            # 0.5 s to first hit
        tr.on_serve_hit("u1")
    assert hist.count == before + 1
    # freshness = 2.0 s (append -> poll) + 0.5 s (publish -> serve)
    assert hist.sum >= 2.4
    assert obs_freshness.POLL_LAG_SECONDS.labels(
        engine="t_fresh").value == pytest.approx(2.0)
    assert obs_freshness.FOLD_SECONDS.labels(
        engine="t_fresh").value == pytest.approx(0.25)
    assert obs_freshness.SERVE_PICKUP_SECONDS.labels(
        engine="t_fresh").value == pytest.approx(0.5)
    # the sampled journey emitted one linked span chain under ONE id
    spans = [json.loads(r.getMessage()) for r in caplog.records
             if r.name == "pio.trace"]
    chain = [s for s in spans if s["span"].startswith("speed.")]
    assert {s["span"] for s in chain} == {
        "speed.poll", "speed.foldin", "speed.serve"}
    assert len({s["traceId"] for s in chain}) == 1
    # a second hit on the same key books nothing further
    tr.on_serve_hit("u1")
    assert hist.count == before + 1


def test_freshness_buckets_resolve_minutes_scale():
    """The freshness histogram uses its own seconds-to-hours ladder —
    the serving-latency default caps at ~13 s and would saturate the
    headline metric exactly when freshness goes bad."""
    bounds = obs_freshness.FRESHNESS_BUCKETS
    assert max(bounds) > 3600.0          # a wedged poller still resolves
    assert min(bounds) <= 0.05           # a hot loop still resolves
    h = obs_freshness.FRESHNESS_SECONDS.labels(engine="t_buckets")
    h.observe(300.0)                     # five minutes stale
    assert h.quantile(0.5) == pytest.approx(300.0, rel=0.7)
    assert h.quantile(0.5) > 13.2        # NOT clamped at the old cap


def test_cpplog_count_marks_never_understate(tmp_path, wall):
    """The count-observation stamp rule: a tail [lo, hi) is bounded by
    the NEWEST observation with count <= lo (every entry past lo was
    appended after that wall — age only ever overstated). Entries that
    predate every observation report -1 instead of borrowing a later
    wall, which would fabricate freshness."""
    cpplog = pytest.importorskip(
        "incubator_predictionio_tpu.data.storage.cpplog")
    from incubator_predictionio_tpu.data.storage import StorageClientConfig

    try:
        client = cpplog.StorageClient(
            StorageClientConfig(properties={"PATH": str(tmp_path)}))
    except Exception:
        pytest.skip("native library unavailable")
    try:
        path = tmp_path / "t.log"
        with client.lock:
            # no observations at all: unattributable
            assert client.append_wall_since_locked(path, 0) == -1
            wall["ms"] = 1_000
            client.note_count_locked(path, 10)
            wall["ms"] = 2_000
            client.note_count_locked(path, 20)
            # entries >= 10 were appended after the count-10 observation
            assert client.append_wall_since_locked(path, 10) == 1_000
            assert client.append_wall_since_locked(path, 15) == 1_000
            # entries >= 20 appended after the newer observation
            assert client.append_wall_since_locked(path, 20) == 2_000
            assert client.append_wall_since_locked(path, 25) == 2_000
            # entries 0..9 predate every known wall: never borrow one
            assert client.append_wall_since_locked(path, 0) == -1
            assert client.append_wall_since_locked(path, 9) == -1
            # re-observing the same count later TIGHTENS the bound
            wall["ms"] = 3_000
            client.note_count_locked(path, 20)
            assert client.append_wall_since_locked(path, 25) == 3_000
    finally:
        client.close()


def test_freshness_skips_historical_backfill(wall):
    tr = obs_freshness.FreshnessTracker(engine="t_backfill")
    hist = obs_freshness.FRESHNESS_SECONDS.labels(engine="t_backfill")
    year_ms = 365 * 24 * 3600 * 1000
    tr.on_poll_batch({"old": 1_000_000 - year_ms, "unknown": -1})
    tr.on_folded(["old", "unknown"], 0.1)
    tr.on_serve_hit("old")
    tr.on_serve_hit("unknown")
    assert hist.count == 0


def test_freshness_discard_and_invalidate(wall):
    tr = obs_freshness.FreshnessTracker(engine="t_disc")
    tr.on_poll_batch({"u1": 999_000, "u2": 999_000})
    tr.discard(["u1"])
    assert tr.stats()["pendingAppend"] == 1
    tr.invalidate()
    assert tr.stats() == {"pendingAppend": 0, "awaitingServe": 0}


def test_overlay_freshness_end_to_end(wall):
    """Through the real overlay: rate -> poll -> fold -> lookup hit
    books one pio_freshness_seconds observation."""
    from incubator_predictionio_tpu.data.datamap import DataMap
    from incubator_predictionio_tpu.data.event import Event
    from incubator_predictionio_tpu.data.storage import App, Storage
    from incubator_predictionio_tpu.data.store import EventStore
    from incubator_predictionio_tpu.speed.overlay import (
        SpeedOverlay,
        SpeedOverlayConfig,
    )
    from incubator_predictionio_tpu.utils.times import now_utc

    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    try:
        Storage.get_meta_data_apps().insert(App(0, "freshapp"))
        rng = np.random.default_rng(0)
        other = rng.normal(0, 0.3, (5, 4)).astype(np.float32)
        overlay = SpeedOverlay(
            SpeedOverlayConfig(app_name="freshapp", engine="t_e2e",
                               event_names=("rate",),
                               value_prop="rating", l2=0.1),
            other_factors=other,
            other_index={f"i{k}": k for k in range(5)})
        hist = obs_freshness.FRESHNESS_SECONDS.labels(engine="t_e2e")
        before = hist.count
        EventStore.write([Event(
            event="rate", entity_type="user", entity_id="cold1",
            target_entity_type="item", target_entity_id="i2",
            properties=DataMap({"rating": 4.0}),
            event_time=now_utc())], "freshapp")
        wall["ms"] += 3_000                 # the poll runs 3 s later
        overlay.poll()
        wall["ms"] += 1_000                 # first query 1 s after fold
        assert overlay.lookup("cold1") is not None
        assert hist.count == before + 1
        # append -> serve spans the planted 4 s
        assert hist.sum >= 3.9
    finally:
        Storage.reset()


# ---------------------------------------------------------------------------
# dispatch profiler
# ---------------------------------------------------------------------------

def test_profiler_off_by_default(monkeypatch):
    monkeypatch.delenv("PIO_PROFILE", raising=False)
    assert obs_profile.enabled() is False
    assert obs_profile.t0() is None
    # record with a None start is the documented free no-op
    obs_profile.record(None, "train", "x", 1e9, object())


def _this_device_kind():
    import jax

    return jax.devices()[0].device_kind


def test_profiler_attribution_and_mfu(monkeypatch):
    monkeypatch.setenv("PIO_PROFILE", "1")
    # the peak comes from the device_kind table and nowhere else: list
    # this (CPU) device for the test; the old PIO_BENCH_* variable is dead
    monkeypatch.setitem(obs_profile.PEAK_FLOPS, _this_device_kind(), 1e12)
    monkeypatch.setenv("PIO_BENCH_PEAK_FLOPS", "5e12")
    t0 = obs_profile.t0()
    assert t0 is not None
    obs_profile.record(t0, "t_phase", "t_op", 2e9)
    assert obs_profile.DEVICE_DISPATCHES.labels(op="t_op").value == 1
    assert obs_profile.DEVICE_FLOPS.labels(op="t_op").value == 2e9
    secs = obs_profile.DEVICE_SECONDS.labels(op="t_op").value
    assert secs > 0
    mfu = obs_profile.MFU.labels(phase="t_phase").value
    assert mfu == pytest.approx(2e9 / secs / 1e12, rel=1e-6)


def test_unknown_device_leaves_mfu_unset_and_logs_once(monkeypatch, caplog):
    """A device_kind the table does not list has no peak: device time
    and FLOPs still book, pio_mfu is never set (no default), and the
    warning is logged once."""
    import logging

    monkeypatch.setenv("PIO_PROFILE", "1")
    monkeypatch.delitem(obs_profile.PEAK_FLOPS, _this_device_kind(),
                        raising=False)
    monkeypatch.setattr(obs_profile, "_no_peak_logged", set())
    assert obs_profile.PEAK_FLOPS["TPU v5 lite"] == 197e12
    with caplog.at_level(logging.WARNING, logger=obs_profile.__name__):
        for _ in range(2):
            obs_profile.record(obs_profile.t0(), "u_phase", "u_op", 2e9)
    assert obs_profile.peak_flops() is None
    assert obs_profile.DEVICE_FLOPS.labels(op="u_op").value == 4e9
    out: list = []
    obs_profile.MFU.expose_into(out)
    assert not any("u_phase" in line for line in out)
    assert sum("no published peak" in r.message
               for r in caplog.records) == 1


def test_profiled_foldin_books_device_time(monkeypatch):
    from incubator_predictionio_tpu.speed.foldin import FoldInSolver

    monkeypatch.setenv("PIO_PROFILE", "1")
    monkeypatch.setitem(obs_profile.PEAK_FLOPS, _this_device_kind(), 1e12)
    rng = np.random.default_rng(0)
    other = rng.normal(0, 0.3, (20, 4)).astype(np.float32)
    solver = FoldInSolver(other, l2=0.1)
    before = obs_profile.DEVICE_DISPATCHES.labels(op="foldin_solve").value
    solver.solve([(np.asarray([1, 2], np.int32),
                   np.asarray([1.0, 2.0], np.float32))])
    assert obs_profile.DEVICE_DISPATCHES.labels(
        op="foldin_solve").value == before + 1
    assert obs_profile.MFU.labels(phase="foldin").value > 0


def test_train_flops_matches_bench_convention():
    from incubator_predictionio_tpu.ops import als

    f = als.train_flops(1000, 50, 40, 8, 4, 0)
    assert f > 0
    # linear in sweeps and at least linear in nnz
    assert als.train_flops(1000, 50, 40, 8, 8, 0) == pytest.approx(2 * f)
    assert als.train_flops(2000, 50, 40, 8, 4, 0) > f


def test_fused_train_books_under_its_own_op_label(monkeypatch):
    """Kernel-path training attributes under op="als_fused", the XLA
    assembly under op="als_train" — separate trajectories in /metrics —
    while both book the SAME als.train_flops formula, so
    pio_mfu{phase="train"} stays comparable across the split."""
    from incubator_predictionio_tpu.ops import als

    monkeypatch.setenv("PIO_PROFILE", "1")
    rng = np.random.default_rng(3)
    users = rng.integers(0, 24, 400).astype(np.int32)
    items = rng.integers(0, 16, 400).astype(np.int32)
    ratings = rng.normal(3.5, 1.0, 400).astype(np.float32)
    kw = dict(n_users=24, n_items=16, rank=4, iterations=2, l2=0.1)

    def booked(op):
        return (obs_profile.DEVICE_DISPATCHES.labels(op=op).value,
                obs_profile.DEVICE_FLOPS.labels(op=op).value)

    monkeypatch.setattr(als, "_ALS_KERNEL", "off")
    d0, f0 = booked("als_train")
    als.als_train(users, items, ratings, **kw)
    d1, f1 = booked("als_train")
    assert d1 == d0 + 1 and f1 > f0

    monkeypatch.setattr(als, "_ALS_KERNEL", "on")
    monkeypatch.setattr(als, "_KERNEL_MIN_D", 0)
    monkeypatch.setenv("PIO_ALS_FUSED_GRAM", "on")  # interpret-mode hook
    k0, g0 = booked("als_fused")
    als.als_train(users, items, ratings, **kw)
    k1, g1 = booked("als_fused")
    assert k1 == k0 + 1
    # ONE FLOP formula across the op split: identical workload, identical
    # booked FLOPs
    assert g1 - g0 == pytest.approx(f1 - f0)
    # the XLA label did not absorb the kernel run
    assert booked("als_train")[0] == d1


def test_profile_route_validation():
    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.servers.admin import AdminServer

    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    ad = AdminServer(ip="127.0.0.1", port=0)
    port = ad.start_background()

    def post(path):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=b"", method="POST")
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status
        except urllib.error.HTTPError as e:
            return e.code

    try:
        assert post("/profile?seconds=abc") == 400
        assert post("/profile?seconds=0") == 400
        assert post("/profile?seconds=9999") == 400
        # a capture in flight answers 409, never a second start_trace
        assert obs_profile._capture_lock.acquire(blocking=False)
        try:
            assert post("/profile?seconds=1") == 409
        finally:
            obs_profile._capture_lock.release()
    finally:
        ad.stop()
        Storage.reset()
