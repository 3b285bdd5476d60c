"""Self-driving freshness controller (obs/controller.py).

The pins, in the order the ISSUE promises them:

- trigger math: staleness-headroom projection acts BEFORE the bound is
  crossed; burn-rate breach acts on a measured breach; healthy fleets
  and no-data fleets never trigger;
- hysteresis (consecutive breached evaluations), cooldown after an
  action, and the capacity budget guard (reason="budget" when the
  measured fit says a retrain cannot finish inside the projected
  budget);
- dry-run: observe mode records the would-act decision, actuates
  nothing;
- THE kill-switch contract: flipping ``PIO_CONTROLLER``/POST
  ``/controller`` mid-run halts actuation within ONE evaluation period;
- the decision audit trail: every evaluation appends a structured
  record, actuation spans land under the decision's own trace ID,
  the trace ID crosses the HTTP reload hop (and the front door's
  rolling-reload choreography forwards it to every worker), and
  ``trace_stitch --decisions`` stitches the tree / flags orphan
  actuations loudly;
- ``GET /controller`` + ``POST /controller`` on the admin server.
"""

import json
import logging
import os
import sys
import time
import urllib.error
import urllib.request

import pytest

from incubator_predictionio_tpu.obs import controller as ctl_mod
from incubator_predictionio_tpu.obs import slo as obs_slo
from incubator_predictionio_tpu.obs.controller import (
    ControllerConfig,
    FreshnessController,
    capacity_budget_fn,
    http_reload_fn,
)
from incubator_predictionio_tpu.obs.metrics import Registry
from incubator_predictionio_tpu.obs.slo import SLOEngine, SLOSpec
from incubator_predictionio_tpu.utils.times import FakeClock

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS_DIR)
sys.path.insert(0, os.path.join(REPO, "scripts"))

import trace_stitch  # noqa: E402


# ---------------------------------------------------------------------------
# harness: a planted fleet signal (staleness gauge SLO on a fresh
# registry, fake clock, no sleeps)
# ---------------------------------------------------------------------------

def planted_engine(clock, threshold=100.0):
    reg = Registry()
    gauge = reg.gauge("pio_model_staleness_seconds", "x")
    spec = SLOSpec(name="staleness",
                   metric="pio_model_staleness_seconds",
                   threshold=threshold, target=0.99, kind="gauge")
    eng = SLOEngine(specs=(spec,), registry=reg, clock=clock,
                    fast_window_s=60.0, slow_window_s=600.0,
                    min_tick_interval_s=0.0, export_gauges=False)
    return eng, gauge


def make_controller(clock, engine, horizon=10.0, breach_evals=1,
                    cooldown=0.0, interval=0.05, **kw):
    calls = {"retrain": 0, "reload": 0}

    def retrain():
        calls["retrain"] += 1
        return f"inst-{calls['retrain']}"

    def reload():
        calls["reload"] += 1
        return {"reloaded": 2}

    ctl = FreshnessController(
        engine=engine,
        retrain_fn=kw.pop("retrain_fn", retrain),
        reload_fn=kw.pop("reload_fn", reload),
        config=ControllerConfig(interval_s=interval,
                                breach_evals=breach_evals,
                                cooldown_s=cooldown,
                                horizon_s=horizon, ring=64),
        clock=clock, mode=kw.pop("mode", "act"), **kw)
    return ctl, calls


# ---------------------------------------------------------------------------
# trigger math
# ---------------------------------------------------------------------------

def test_healthy_fleet_never_triggers():
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)
    ctl, calls = make_controller(clock, eng)
    gauge.set(5.0)                     # headroom 95 >> horizon 10
    d = ctl.evaluate_once()
    assert d["action"] == "none"
    assert d["reason"] == "healthy"
    assert calls == {"retrain": 0, "reload": 0}
    assert d["projection"]["stalenessHeadroomS"] == pytest.approx(95.0)
    assert d["inputs"]["slos"]["staleness"]["fastBurn"] == 0.0


def test_no_data_is_a_skip_not_a_trigger():
    clock = FakeClock(100.0)
    reg = Registry()                   # gauge never registered/set
    spec = SLOSpec(name="staleness",
                   metric="pio_model_staleness_seconds",
                   threshold=100.0, target=0.99, kind="gauge")
    eng = SLOEngine(specs=(spec,), registry=reg, clock=clock,
                    min_tick_interval_s=0.0, export_gauges=False)
    ctl, calls = make_controller(clock, eng)
    d = ctl.evaluate_once()
    assert d["reason"] == "no_data"
    assert calls == {"retrain": 0, "reload": 0}


def test_staleness_headroom_projection_acts_before_the_bound():
    """The controller's whole point: the gauge grows 1 s/s, so it must
    act when threshold − value falls under the horizon — BEFORE the
    SLO ever records a bad tick."""
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock, threshold=100.0)
    ctl, calls = make_controller(clock, eng, horizon=10.0)
    gauge.set(95.0)                    # still UNDER the bound
    d = ctl.evaluate_once()
    assert d["trigger"] == "staleness_projection"
    assert d["action"] == "retrain+reload"
    assert d["outcome"]["actuated"] is True
    assert d["outcome"]["retrain"]["ok"] is True
    assert d["outcome"]["reload"]["ok"] is True
    assert calls == {"retrain": 1, "reload": 1}
    # the SLO itself never breached — the projection did the work
    assert d["inputs"]["slos"]["staleness"]["fastBurn"] == 0.0
    assert d["projection"]["projectionS"] == pytest.approx(5.0)


def test_closed_loop_holds_the_bound_with_no_false_trigger():
    """The loop alone, no human retrain: staleness grows a second a
    second and only the controller's reload takes it back to zero. It
    keeps the fleet under the bound and never acts while the model is
    still comfortably fresh (under half the bound)."""
    bound = 100.0
    clock = FakeClock(1000.0)
    eng, gauge = planted_engine(clock, threshold=bound)
    refreshed = [clock()]

    def reload():
        refreshed.append(clock())
        return {"reloaded": 2}

    ctl, calls = make_controller(clock, eng, horizon=10.0, breach_evals=2,
                                 cooldown=5.0, reload_fn=reload)
    peak, actions = 0.0, []
    for _ in range(500):
        clock.advance(1.0)
        staleness = clock() - refreshed[-1]
        peak = max(peak, staleness)
        gauge.set(staleness)
        d = ctl.evaluate_once()
        if (d.get("outcome") or {}).get("actuated"):
            actions.append(d)
    assert len(actions) >= 4 and calls["retrain"] == len(actions)
    assert peak <= bound
    assert all(d["inputs"]["stalenessMaxS"] >= 0.5 * bound
               for d in actions)
    assert all(d["trigger"] == "staleness_projection" for d in actions)


def test_burn_breach_triggers():
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock, threshold=100.0)
    ctl, calls = make_controller(clock, eng, horizon=1.0)
    gauge.set(5000.0)                  # far over the bound: bad ticks
    eng.tick(force=True)
    clock.advance(5)
    d = ctl.evaluate_once()
    assert d["trigger"] == "staleness_burn"
    assert calls["retrain"] == 1


def test_projection_burn_math():
    """burnExhaustS = slow_window · budget_remaining / fast_burn (the
    projection the exported gauge carries) — checked at a sub-breach
    burn (0 < burn < 1) where every term is non-trivial."""
    clock = FakeClock(100.0)
    reg = Registry()
    h = reg.histogram("t_fresh_seconds", "x", buckets=(1.0,))
    spec = SLOSpec(name="freshness_p95", metric="t_fresh_seconds",
                   threshold=1.0, target=0.95)
    eng = SLOEngine(specs=(spec,), registry=reg, clock=clock,
                    fast_window_s=60.0, slow_window_s=600.0,
                    min_tick_interval_s=0.0, export_gauges=False)
    ctl, _calls = make_controller(clock, eng, mode="observe")
    eng.tick(force=True)               # zero baseline snapshot
    h.observe(0.5, 98)
    h.observe(5.0, 2)                  # 2% bad, allowed 5% -> burn 0.4
    clock.advance(10)
    d = ctl.evaluate_once()
    assert d["reason"] == "healthy"    # burning, but slowly
    proj = d["projection"]
    slos = d["inputs"]["slos"]["freshness_p95"]
    assert 0.0 < slos["fastBurn"] < 1.0
    expected = 600.0 * slos["budgetRemaining"] / slos["fastBurn"]
    assert proj["burnExhaustS"] == pytest.approx(expected, rel=1e-3)
    assert proj["burnExhaustS"] > ctl.config.horizon_s


# ---------------------------------------------------------------------------
# hysteresis / cooldown / budget / observe
# ---------------------------------------------------------------------------

def test_hysteresis_requires_consecutive_breaches():
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)
    ctl, calls = make_controller(clock, eng, breach_evals=3)
    gauge.set(95.0)
    assert ctl.evaluate_once()["reason"] == "hysteresis"
    assert ctl.evaluate_once()["reason"] == "hysteresis"
    d = ctl.evaluate_once()            # third consecutive: act
    assert d["outcome"]["actuated"] is True
    assert d["streak"] == 3
    assert calls["retrain"] == 1
    # a healthy evaluation RESETS the streak
    gauge.set(1.0)
    assert ctl.evaluate_once()["reason"] == "healthy"
    gauge.set(95.0)
    assert ctl.evaluate_once()["reason"] == "hysteresis"


def test_cooldown_blocks_reflap():
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)
    ctl, calls = make_controller(clock, eng, cooldown=60.0)
    gauge.set(95.0)
    assert ctl.evaluate_once()["outcome"]["actuated"] is True
    # the planted reload did not actually refresh the gauge: the
    # trigger holds, but the cooldown must hold fire
    d = ctl.evaluate_once()
    assert d["reason"] == "cooldown"
    assert d["cooldownRemainingS"] > 0
    assert calls["retrain"] == 1
    clock.advance(61.0)
    assert ctl.evaluate_once()["outcome"]["actuated"] is True
    assert calls["retrain"] == 2


def test_budget_guard_skips_when_capacity_is_binding():
    """The capacity fit says the retrain cannot finish before the
    budget empties: reason="budget" — the runbook's 'capacity, not
    freshness, is the binding constraint' signal."""
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)
    ctl, calls = make_controller(clock, eng,
                                 capacity_fn=lambda: 1000.0)
    gauge.set(95.0)                    # projection 5 s << 1000 s wall
    d = ctl.evaluate_once()
    assert d["reason"] == "budget"
    assert d["projection"]["retrainWallEstS"] == 1000.0
    assert calls == {"retrain": 0, "reload": 0}
    # an affordable retrain passes the same gate
    ctl2, calls2 = make_controller(clock, eng,
                                   capacity_fn=lambda: 2.0)
    d = ctl2.evaluate_once()
    assert d["outcome"]["actuated"] is True
    assert d["projection"]["retrainWallEstS"] == 2.0
    assert calls2["retrain"] == 1


def test_observe_mode_is_a_dry_run():
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)
    ctl, calls = make_controller(clock, eng, mode="observe")
    gauge.set(95.0)
    d = ctl.evaluate_once()
    assert d["action"] == "retrain+reload"   # WOULD have acted
    assert d["reason"] == "observe"
    assert d["outcome"] == {"actuated": False, "dryRun": True}
    assert calls == {"retrain": 0, "reload": 0}


def test_failed_retrain_skips_the_reload():
    """A retrain that dies leaves the OLD model serving — hot-swapping
    nothing is the safe degradation, so the reload must not run."""
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)

    def bad_retrain():
        raise RuntimeError("train blew up")

    ctl, calls = make_controller(clock, eng, retrain_fn=bad_retrain)
    gauge.set(95.0)
    d = ctl.evaluate_once()
    assert d["outcome"]["retrain"]["ok"] is False
    assert d["outcome"]["reload"] == {"ok": False,
                                      "skipped": "retrain_failed"}
    assert calls["reload"] == 0


def test_capacity_budget_fn_without_inputs_is_no_guard(monkeypatch):
    monkeypatch.delenv("PIO_CONTROLLER_ROWS", raising=False)
    assert capacity_budget_fn()() is None
    # the env-wired controller reports an inert guard as ABSENT: the
    # operator must never believe retrains are capacity-guarded when
    # the guard cannot veto
    ctl_mod.reset_controller()
    try:
        ctl = ctl_mod.get_controller()
        assert ctl.stats()["actuators"]["capacityGuard"] is False
    finally:
        ctl_mod.reset_controller()


def test_slo_error_resets_hysteresis_and_projection_gauge():
    """A blind evaluation (fleet scrape failed) must break the
    CONSECUTIVE-breach chain — hysteresis cannot count across a gap it
    could not see — and the exported projection gauge goes NaN instead
    of freezing at its last pre-outage value (which a dashboard would
    read as live headroom). The scrape must survive the NaN."""
    import math

    from incubator_predictionio_tpu.obs import metrics as obs_metrics

    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)
    ctl, calls = make_controller(clock, eng, breach_evals=2)
    gauge.set(95.0)
    assert ctl.evaluate_once()["reason"] == "hysteresis"   # streak 1
    real_eval = eng.evaluate
    eng.evaluate = lambda: (_ for _ in ()).throw(
        RuntimeError("fleet down"))
    d = ctl.evaluate_once()
    assert d["reason"] == "slo_error"
    assert math.isnan(ctl_mod._PROJECTION.value)
    assert "pio_controller_budget_projection_seconds NaN" in \
        obs_metrics.REGISTRY.expose()
    eng.evaluate = real_eval
    # the chain restarted: the next trigger is streak 1 again
    d = ctl.evaluate_once()
    assert d["reason"] == "hysteresis"
    assert d["streak"] == 1
    assert calls["retrain"] == 0


# ---------------------------------------------------------------------------
# THE kill switch: halt within one evaluation period
# ---------------------------------------------------------------------------

def test_kill_switch_halts_within_one_evaluation_period():
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)
    ctl, calls = make_controller(clock, eng)   # interval 0.05 s
    gauge.set(95.0)                            # permanent trigger
    ctl.start()
    try:
        deadline = time.monotonic() + 5.0
        while calls["retrain"] < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert calls["retrain"] >= 2, "controller loop never acted"
        ctl.set_mode("off")
        # one evaluation period for the flip to land (plus one possibly
        # in-flight action)
        time.sleep(0.15)
        frozen = calls["retrain"]
        time.sleep(0.5)                       # ten more periods
        assert calls["retrain"] == frozen, (
            "actuation continued after the kill switch")
        # flipping back resumes without a restart
        ctl.set_mode("act")
        deadline = time.monotonic() + 5.0
        while calls["retrain"] == frozen and time.monotonic() < deadline:
            time.sleep(0.01)
        assert calls["retrain"] > frozen
    finally:
        ctl.stop()
    # both flips are audit-trailed
    kinds = [d for d in ctl.decisions(limit=64)
             if d.get("kind") == "mode_change"]
    assert [(d["from"], d["to"]) for d in kinds[::-1]] == [
        ("act", "off"), ("off", "act")]


def test_timed_out_stop_cannot_resurrect_the_old_loop():
    """A stop() whose join times out on a long in-flight actuation must
    not let a later start() revive the old loop into a second
    concurrent controller: each generation owns its own stop event, so
    the old thread exits the moment its actuation returns."""
    import threading

    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)
    release = threading.Event()
    entered = threading.Event()

    def slow_retrain():
        entered.set()
        release.wait(10)
        return "slow"

    ctl, _calls = make_controller(clock, eng, retrain_fn=slow_retrain,
                                  reload_fn=lambda: {"ok": True})
    gauge.set(95.0)
    ctl.start()
    assert entered.wait(5)
    # the audit contract DURING a long actuation: the in-flight action
    # is already in the ring, marked as such — "the ring IS the
    # answer" must hold exactly while the retrain runs
    inflight = [d for d in ctl.decisions(limit=8)
                if (d.get("outcome") or {}).get("inFlight")]
    assert inflight and inflight[0]["action"] == "retrain+reload"
    ctl.stop(timeout=0.05)      # join times out: actuation in flight
    ctl.start()                 # new generation while the old lives
    time.sleep(0.2)             # let the new loop reach its actuation
    ctl.set_mode("off")         # idle the NEW loop before releasing
    release.set()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        alive = [t for t in threading.enumerate()
                 if t.name == "pio-freshness-controller"]
        if len(alive) == 1:
            break
        time.sleep(0.02)
    assert len(alive) == 1, (
        "old controller generation kept looping after its stop")
    ctl.stop()
    assert not any(t.name == "pio-freshness-controller"
                   for t in threading.enumerate())


def test_off_mode_records_nothing_and_scrapes_nothing():
    clock = FakeClock(100.0)
    calls = {"n": 0}

    class _Exploding:
        registry = None

        def evaluate(self):
            calls["n"] += 1
            raise AssertionError("off mode must not consume signals")

    ctl = FreshnessController(engine=_Exploding(), clock=clock,
                              mode="off",
                              config=ControllerConfig(ring=8))
    assert ctl.evaluate_once() is None
    assert calls["n"] == 0
    assert ctl.decisions(limit=8) == []


# ---------------------------------------------------------------------------
# the audit trail: trace-linked actuation + the stitcher
# ---------------------------------------------------------------------------

def _captured_spans(caplog):
    return [json.loads(r.getMessage()) for r in caplog.records
            if r.name == "pio.trace"]


def test_actuation_spans_land_under_the_decision_trace(caplog):
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)
    ctl, _calls = make_controller(clock, eng)
    gauge.set(95.0)
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        d = ctl.evaluate_once()
    assert d["outcome"]["actuated"] is True
    spans = [s for s in _captured_spans(caplog)
             if str(s.get("span", "")).startswith("controller.")]
    by_name = {s["span"]: s for s in spans}
    assert set(by_name) == {"controller.decision", "controller.retrain",
                            "controller.reload"}
    root = by_name["controller.decision"]
    assert root["traceId"] == d["traceId"]
    assert root["spanId"] == d["spanId"]
    assert root["decisionId"] == d["id"]
    for child in ("controller.retrain", "controller.reload"):
        assert by_name[child]["traceId"] == d["traceId"]
        assert by_name[child]["parentSpanId"] == root["spanId"]


def test_http_reload_hop_carries_the_decision_trace():
    """The reload actuator's POST forwards X-PIO-Trace-Id (the decision
    trace) + X-PIO-Parent-Span (the decision span) — what lets the
    front door and every worker behind it link their reload spans under
    the decision."""
    from incubator_predictionio_tpu.utils.http import (
        HttpServer,
        Request,
        Response,
        Router,
    )

    seen = {}
    r = Router()

    @r.post("/reload")
    def reload_route(request: Request) -> Response:
        seen.update(request.headers)
        return Response(200, {"reloaded": 1})

    srv = HttpServer(r, "127.0.0.1", 0, name="fakedoor")
    port = srv.start_background()
    try:
        clock = FakeClock(100.0)
        eng, gauge = planted_engine(clock)
        ctl, _calls = make_controller(
            clock, eng,
            reload_fn=http_reload_fn(f"http://127.0.0.1:{port}/reload"))
        gauge.set(95.0)
        d = ctl.evaluate_once()
        assert d["outcome"]["reload"]["ok"] is True
        assert seen.get("x-pio-trace-id") == d["traceId"]
        assert seen.get("x-pio-parent-span") == d["spanId"]
    finally:
        srv.stop()


def test_frontdoor_rolling_reload_forwards_the_trace():
    """Through the REAL front door: a traced POST /reload fans the same
    trace ID to every worker's reload — the cross-process leg of the
    decision tree."""
    from incubator_predictionio_tpu.serving.frontdoor import (
        FrontDoor,
        FrontDoorConfig,
    )
    from incubator_predictionio_tpu.utils.http import (
        HttpServer,
        Request,
        Response,
        Router,
    )

    worker_headers = []
    servers = []
    ports = []
    for _i in range(2):
        r = Router()

        @r.post("/reload")
        def reload_route(request: Request) -> Response:
            worker_headers.append(dict(request.headers))
            return Response(200, {"ok": True})

        @r.get("/")
        def status(request: Request) -> Response:
            return Response(200, {"status": "alive"})

        srv = HttpServer(r, "127.0.0.1", 0, name="miniworker")
        servers.append(srv)
        ports.append(srv.start_background())
    fd = FrontDoor([("127.0.0.1", p) for p in ports],
                   FrontDoorConfig(probe_interval_s=0.2))
    fport = fd.start_background()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{fport}/reload", data=b"",
            method="POST",
            headers={"X-PIO-Trace-Id": "ctl-e2e-0001"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            out = json.loads(resp.read())
        assert out["reloaded"] == 2
        assert len(worker_headers) == 2
        for h in worker_headers:
            assert h.get("x-pio-trace-id") == "ctl-e2e-0001"
            assert h.get("x-pio-parent-span")   # the door's span
    finally:
        fd.stop()
        for srv in servers:
            srv.stop()


def test_trace_stitch_decisions_view(tmp_path, caplog, capsys):
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)
    ctl, _calls = make_controller(clock, eng)
    gauge.set(95.0)
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        d = ctl.evaluate_once()
    log = tmp_path / "spans.log"
    log.write_text("noise line\n" + "\n".join(
        r.getMessage() for r in caplog.records if r.name == "pio.trace")
        + "\n")
    assert trace_stitch.main([str(log), "--decisions"]) == 0
    out = capsys.readouterr().out
    assert f"decision #{d['id']}" in out
    assert "controller.retrain" in out
    assert "controller.reload" in out
    assert d["traceId"] in out


def test_trace_stitch_flags_orphan_actuations(tmp_path, capsys):
    """An actuation span whose trace has no decision root is exactly
    the unaudited-mutation class the lint rule + stitcher exist to
    catch: loud stderr, exit 1."""
    log = tmp_path / "orphan.log"
    log.write_text(json.dumps({
        "span": "controller.reload", "traceId": "ctl-orphan",
        "spanId": "ab12cd34", "ts": 1000.0, "durationMs": 5.0,
    }) + "\n")
    assert trace_stitch.main([str(log), "--decisions"]) == 1
    err = capsys.readouterr().err
    assert "ORPHAN ACTUATION" in err
    assert "ctl-orphan" in err


# ---------------------------------------------------------------------------
# admin server hosting: GET/POST /controller
# ---------------------------------------------------------------------------

@pytest.fixture
def admin_with_controller():
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
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)
    # a long interval: the admin-hosted loop evaluates once at start,
    # then the tests drive evaluate_once explicitly (no racing ticks)
    ctl, calls = make_controller(clock, eng, mode="observe",
                                 interval=60.0)
    ad = AdminServer(ip="127.0.0.1", port=0, controller=ctl)
    port = ad.start_background()
    try:
        yield {"port": port, "gauge": gauge, "ctl": ctl,
               "calls": calls}
    finally:
        ad.stop()
        ctl_mod.reset_controller()
        Storage.reset()


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as resp:
        return resp.status, json.loads(resp.read())


def _post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_controller_routes_on_admin(admin_with_controller):
    port = admin_with_controller["port"]
    gauge = admin_with_controller["gauge"]
    gauge.set(95.0)
    admin_with_controller["ctl"].evaluate_once()
    status, body = _get(port, "/controller?limit=10")
    assert status == 200
    assert body["mode"] == "observe"
    assert body["running"] is True          # the admin started the loop
    assert body["actuators"] == {"retrain": True, "reload": True,
                                 "capacityGuard": False}
    decisions = body["decisions"]
    assert decisions and decisions[0]["kind"] == "evaluation"
    assert decisions[0]["reason"] == "observe"   # dry-run recorded
    assert decisions[0]["traceId"].startswith("ctl-")
    # the LIVE kill switch flip
    status, body = _post(port, "/controller", {"mode": "act"})
    assert status == 200 and body["mode"] == "act"
    status, body = _post(port, "/controller", {"mode": "sideways"})
    assert status == 400
    status, body = _post(port, "/controller", "off")  # non-object JSON
    assert status == 400
    status, body = _get(port, "/controller")
    assert body["mode"] == "act"
    # the flip landed in the audit ring
    assert any(d.get("kind") == "mode_change" and d["to"] == "act"
               for d in body["decisions"])


def test_controller_metrics_exported(admin_with_controller):
    from incubator_predictionio_tpu.obs import metrics as obs_metrics

    gauge = admin_with_controller["gauge"]
    ctl = admin_with_controller["ctl"]
    gauge.set(95.0)
    before = ctl_mod._SKIPS.labels(reason="observe").value
    ctl.evaluate_once()
    assert ctl_mod._SKIPS.labels(reason="observe").value == before + 1
    assert ctl_mod._STATE.value == 1.0      # observe
    assert ctl_mod._PROJECTION.value == pytest.approx(5.0)
    text = obs_metrics.REGISTRY.expose()
    for name in ("pio_controller_evaluations_total",
                 "pio_controller_skips_total",
                 "pio_controller_state",
                 "pio_controller_budget_projection_seconds"):
        assert name in text


def test_decision_ring_is_bounded():
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock)
    ctl, _calls = make_controller(clock, eng, mode="observe")
    gauge.set(1.0)
    for _ in range(200):
        ctl.evaluate_once()
    ds = ctl.decisions(limit=1000)
    assert len(ds) == 64
    # newest first
    assert ds[0]["id"] > ds[-1]["id"]


# ---------------------------------------------------------------------------
# lock discipline (regressions for the races pio-lint's
# unguarded-shared-state pass surfaced: _mode_override read outside the
# lock by the loop-side `mode` property, _cooldown_until read/written
# outside the lock around actuation)
# ---------------------------------------------------------------------------

class _AuditedController(FreshnessController):
    """Asserts the controller lock is held for every post-init write of
    the attributes the race fix moved under it."""

    _AUDITED = frozenset({"_mode_override", "_cooldown_until", "_streak"})

    def __setattr__(self, name, value):
        if name in self._AUDITED and getattr(self, "_audit_on", False):
            assert self._lock.locked(), (
                f"write of {name} without the controller lock")
        object.__setattr__(self, name, value)


def _audited_controller(clock, engine, cooldown=30.0):
    return _AuditedController(
        engine=engine,
        retrain_fn=lambda: "inst-1",
        reload_fn=lambda: {"reloaded": 1},
        config=ControllerConfig(interval_s=0.05, breach_evals=1,
                                cooldown_s=cooldown, horizon_s=10.0,
                                ring=64),
        clock=clock, mode="act")


def _join_or_fail(fn, timeout=10.0):
    """Run ``fn`` on a thread and fail loudly instead of hanging the
    suite if it deadlocks (the regression this guards against)."""
    import threading as _threading
    out = {}

    def run():
        out["value"] = fn()

    t = _threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "deadlocked: lock-discipline regression"
    return out["value"]


def test_mode_property_and_stats_are_deadlock_free():
    clock = FakeClock(100.0)
    eng, _gauge = planted_engine(clock)
    ctl = _audited_controller(clock, eng)
    ctl._audit_on = True
    ctl.set_mode("observe")
    # `mode` now takes the lock; stats() reads mode while HOLDING the
    # lock (inlined, not via the property) — both must complete
    assert _join_or_fail(lambda: ctl.mode) == "observe"
    st = _join_or_fail(ctl.stats)
    assert st["mode"] == "observe"
    # set_mode's prev-mode read is also inlined under the lock — the
    # audit record must still capture the transition correctly
    ctl.set_mode("act")
    ds = ctl.decisions(limit=1)
    assert ds[0]["kind"] == "mode_change"
    assert ds[0]["from"] == "observe" and ds[0]["to"] == "act"


def test_cooldown_and_streak_writes_hold_the_lock():
    clock = FakeClock(100.0)
    eng, gauge = planted_engine(clock, threshold=100.0)
    ctl = _audited_controller(clock, eng, cooldown=60.0)
    ctl._audit_on = True
    gauge.set(95.0)                    # headroom 5 < horizon 10: act
    d = ctl.evaluate_once()
    assert d["action"] == "retrain+reload"
    # the post-actuation _cooldown_until/_streak writes ran (under the
    # lock, or _AuditedController would have failed above)
    d2 = ctl.evaluate_once()
    assert d2["reason"] == "cooldown"
    assert d2["cooldownRemainingS"] > 0
