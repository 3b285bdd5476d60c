"""Continuous-batching scheduler behavior under load.

The serving plane's contracts, pinned without sleeps wherever a
decision is involved (the FakeClock seam drives every age/wall/shed
decision):

- ladder-rung growth/collapse (``plan_dispatch`` — the pure rule)
- the PIO_SERVE_MAX_WAIT_MS age bound: a query is never held past it
  (the fixed micro-batcher's starvation regression)
- per-engine queue isolation: batches never mix engines, rungs adapt
  independently
- SLO-projected load shedding: overload sheds 503 + Retry-After,
  priority evicts, recovery re-admits (the shed-then-recover flip)
- zero steady-state recompiles: a warm pow2 ladder serves every batch
  width the scheduler can choose from the jit cache
  (``ops.topk.serve_compile_cache_size`` — the foldin-cache pin's
  serving twin)
- one hand-over to the event loop a dispatch: waiters that came with
  their loop get a whole batch's answers in ONE ``call_soon_threadsafe``
  and are resolved on the loop's thread; a waiter that hung up, a loop
  that closed, a shed or an eviction cost nobody else an answer
"""

import asyncio
import threading
import time

import pytest

from incubator_predictionio_tpu.serving.scheduler import (
    BatchScheduler,
    ShedError,
    ladder_cap,
    plan_dispatch,
)
from incubator_predictionio_tpu.utils.times import FakeClock


# ---------------------------------------------------------------------------
# plan_dispatch: the pure ladder rule
# ---------------------------------------------------------------------------

def test_rung_grows_one_ladder_step_under_load():
    # queue deeper than the rung: take the rung now, grow for next time
    assert plan_dispatch(10, 4, 0.0, 512, 0.25) == (4, 8)
    assert plan_dispatch(100, 8, 0.0, 512, 0.25) == (8, 16)
    # growth saturates at the cap
    assert plan_dispatch(1000, 512, 0.0, 512, 0.25) == (512, 512)


def test_rung_collapses_when_idle():
    assert plan_dispatch(1, 8, 0.0, 512, 0.25) == (1, 4)
    assert plan_dispatch(0, 8, 0.0, 512, 0.25) == (0, 8)  # no dispatch
    # floor is rung 1
    assert plan_dispatch(1, 1, 0.0, 512, 0.25) == (1, 1)


def test_rung_hysteresis_band_holds_steady():
    # depth in (rung/2, rung]: no thrash
    assert plan_dispatch(3, 4, 0.0, 512, 0.25) == (3, 4)
    assert plan_dispatch(4, 4, 0.0, 512, 0.25) == (4, 4)


def test_age_breach_drains_whole_backlog():
    # the oldest waiter crossed the bound: take EVERYTHING (up to cap),
    # rung still only steps one ladder rung
    assert plan_dispatch(100, 4, 0.3, 512, 0.25) == (100, 8)
    assert plan_dispatch(1000, 4, 0.3, 512, 0.25) == (512, 8)
    # bound disabled (<=0): never triggers
    assert plan_dispatch(100, 4, 99.0, 512, 0.0) == (4, 8)


def test_ladder_cap_is_pow2(monkeypatch):
    monkeypatch.setenv("PIO_SERVE_MAX_BATCH", "100")
    assert ladder_cap() == 128
    monkeypatch.setenv("PIO_SERVE_MAX_BATCH", "512")
    assert ladder_cap() == 512


# ---------------------------------------------------------------------------
# threaded scheduler behavior
# ---------------------------------------------------------------------------

def _drain(futs, timeout=10.0):
    return [f.result(timeout) for f in futs]


def test_ladder_walkup_batch_sizes():
    """A prefilled queue drains in pow2 ladder steps: 1 (the in-flight
    singleton), then 2, 4, 8, ... — the fused width follows queue
    depth, not a fixed cap."""
    gate = threading.Event()
    first_in = threading.Event()
    batches = []

    def handler(bodies):
        first_in.set()
        gate.wait(10)
        batches.append(len(bodies))
        return bodies

    s = BatchScheduler(handler, 64, shed=False, wait_bound_s=0.0)
    try:
        futs = [s.submit(b"0")]
        assert first_in.wait(5)           # singleton dispatch in flight
        futs += [s.submit(b"%d" % i) for i in range(1, 64)]
        gate.set()
        _drain(futs)
        # the in-flight singleton, then one rung-1 dispatch (the rung
        # only grows AFTER a dispatch observed the deep queue), then
        # the pow2 walk-up
        assert batches == [1, 1, 2, 4, 8, 16, 32], batches
    finally:
        s.stop()


def test_age_bound_never_holds_a_query_past_it():
    """The starvation regression: requests arriving while a full batch
    dispatches must NOT wait multiple rung-limited dispatch cycles —
    once their age crosses the bound, the next dispatch takes the whole
    backlog."""
    clock = FakeClock()
    gate = threading.Event()
    first_in = threading.Event()
    batches = []

    def handler(bodies):
        first_in.set()
        gate.wait(10)
        batches.append(len(bodies))
        return bodies

    s = BatchScheduler(handler, 64, clock=clock, shed=False,
                       wait_bound_s=0.25)
    try:
        futs = [s.submit(b"a")]
        assert first_in.wait(5)
        # ten requests land while the dispatch is in flight (rung is
        # still 1 — without the age bound they would drain one per
        # cycle, the last waiting TEN cycles)
        futs += [s.submit(b"%d" % i) for i in range(10)]
        clock.advance(1.0)                # all ten now exceed the bound
        gate.set()
        _drain(futs)
        assert batches == [1, 10], batches
    finally:
        s.stop()


def test_per_engine_queues_fuse_independently():
    """Batches never mix engines, and each engine's rung adapts to ITS
    queue depth only."""
    gate = threading.Event()
    first_in = threading.Event()
    batches = []

    def handler(bodies, engine):
        first_in.set()
        gate.wait(10)
        batches.append((engine, len(bodies)))
        return bodies

    s = BatchScheduler(handler, 64, shed=False, wait_bound_s=0.0)
    try:
        futs = [s.submit(b"x", engine="reco")]
        assert first_in.wait(5)
        futs += [s.submit(b"%d" % i, engine="reco") for i in range(32)]
        futs += [s.submit(b"e%d" % i, engine="ecom") for i in range(2)]
        gate.set()
        _drain(futs)
        for engine, n in batches:
            assert engine in ("reco", "ecom")
        # totals per engine add up — no cross-engine leakage
        assert sum(n for e, n in batches if e == "reco") == 33
        assert sum(n for e, n in batches if e == "ecom") == 2
        # the busy engine's rung grew; the idle one's never left 1
        assert s.rung("reco") > s.rung("ecom")
        assert s.rung("ecom") == 1
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# load shedding
# ---------------------------------------------------------------------------

class _GatedHandler:
    """Handler whose first call advances the fake clock (planting the
    EWMA dispatch wall) and whose later calls block on a gate."""

    def __init__(self, clock, wall_s):
        self.clock = clock
        self.wall_s = wall_s
        self.gate = threading.Event()
        self.in_handler = threading.Event()
        self.calls = 0

    def __call__(self, bodies):
        self.calls += 1
        if self.calls == 1:
            self.clock.advance(self.wall_s)  # plants ewma_wall
        else:
            self.in_handler.set()
            self.gate.wait(10)
        return bodies


def test_shed_then_recover_flip():
    clock = FakeClock()
    handler = _GatedHandler(clock, wall_s=0.2)
    s = BatchScheduler(handler, 4, clock=clock, shed=True, slo_s=0.5,
                       p99_fn=lambda: 0.1, wait_bound_s=0.0)
    try:
        # dispatch one to plant ewma_wall=0.2
        s.submit(b"w").result(10)
        # block the dispatcher with an in-flight singleton
        inflight = s.submit(b"0")
        assert handler.in_handler.wait(5)
        # queue depth grows; projection = (cycles + in-flight)·0.2 +
        # p99 0.1 against slo 0.5: with cap 4, depth 4 → 1 cycle →
        # (1+1)·0.2 + 0.1 = 0.5, NOT > slo; depth 5 → 2 cycles → 0.7 →
        # SHED. So 4 queued admit, the 5th sheds.
        admitted = [s.submit(b"%d" % i) for i in range(4)]
        shed = s.submit(b"last")
        assert shed.done()
        with pytest.raises(ShedError) as ei:
            shed.result()
        assert ei.value.status == 503
        assert int(ei.value.headers["Retry-After"]) >= 1
        assert ei.value.reason == "overload"
        # recovery: the queue drains, projections fall, admission resumes
        handler.gate.set()
        _drain([inflight] + admitted)
        ok = s.submit(b"again")
        assert ok.result(10) == b"again"
        assert s.shed_count == 1
    finally:
        s.stop()


def test_priority_evicts_lowest_not_highest():
    clock = FakeClock()
    handler = _GatedHandler(clock, wall_s=0.2)
    s = BatchScheduler(handler, 4, clock=clock, shed=True, slo_s=0.5,
                       p99_fn=lambda: 0.1, wait_bound_s=0.0)
    try:
        s.submit(b"w").result(10)
        inflight = s.submit(b"0")
        assert handler.in_handler.wait(5)
        low = [s.submit(b"%d" % i, priority=0) for i in range(4)]
        # overload point reached — a HIGHER-priority arrival evicts the
        # lowest-priority waiter instead of shedding itself
        vip = s.submit(b"vip", priority=5)
        assert not vip.done()
        evicted = [f for f in low if f.done()]
        assert len(evicted) == 1
        with pytest.raises(ShedError) as ei:
            evicted[0].result()
        assert ei.value.reason == "evicted"
        # an equal-priority arrival at the same depth sheds itself
        shed = s.submit(b"eq", priority=0)
        with pytest.raises(ShedError):
            shed.result()
        handler.gate.set()
        _drain([inflight, vip] + [f for f in low if f is not evicted[0]])
        assert s.shed_count == 2
    finally:
        s.stop()


def test_cold_queue_never_sheds():
    """No EWMA evidence (no dispatch yet) → no shedding, whatever the
    depth: admission control must never fire on a cold start."""
    gate = threading.Event()

    def handler(bodies):
        gate.wait(10)
        return bodies

    s = BatchScheduler(handler, 4, shed=True, slo_s=0.01,
                       p99_fn=lambda: 10.0, wait_bound_s=0.0)
    try:
        futs = [s.submit(b"%d" % i) for i in range(20)]
        assert not any(f.done() and f.exception() for f in futs)
        gate.set()
        _drain(futs)
        assert s.shed_count == 0
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# one hand-over to the event loop a dispatch
# ---------------------------------------------------------------------------

class _LoopThread:
    """An event loop on a thread of its own, as the server's is. Counts
    the calls into it that come from a dispatcher thread (the test's own
    ``run`` calls into it too, from the test's thread)."""

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self.handovers = 0      # calls begun
        self.queued = 0         # calls whose callback the loop now holds
        self.handed = threading.Event()
        inner = self.loop.call_soon_threadsafe

        def counted(callback, *args, **kw):
            from_dispatcher = threading.current_thread().name.startswith(
                "pio-serve-sched-")
            if from_dispatcher:
                self.handovers += 1
            handle = inner(callback, *args, **kw)
            if from_dispatcher:
                self.queued += 1
                self.handed.set()
            return handle

        self.loop.call_soon_threadsafe = counted
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()

    def run(self, coro, timeout=10.0):
        return asyncio.run_coroutine_threadsafe(
            coro, self.loop).result(timeout)

    def submit(self, s, bodies, **kw):
        """``s.submit`` for each body ON the loop's thread, as a handler
        calls it: the loop's futures, in order."""
        async def go():
            return [s.submit(b, loop=self.loop, **kw) for b in bodies]
        return self.run(go())

    def gather(self, futs, timeout=10.0):
        """Results (an exception as a value), awaited on the loop."""
        async def go():
            return await asyncio.gather(*futs, return_exceptions=True)
        return self.run(go(), timeout)

    def close(self):
        if not self.loop.is_closed():
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(5)
            self.loop.close()


@pytest.fixture
def on_loop():
    lt = _LoopThread()
    yield lt
    lt.close()


class _HeldFirst:
    """Holds the first dispatch in the handler until ``gate`` is set, so
    that a backlog can build behind it; every batch's bodies are kept. A
    body that starts with ``!`` fails alone, as a per-query exception."""

    def __init__(self):
        self.gate = threading.Event()
        self.in_handler = threading.Event()
        self.batches = []

    def __call__(self, bodies):
        if not self.in_handler.is_set():
            self.in_handler.set()
            assert self.gate.wait(10)
        self.batches.append(list(bodies))
        return [ValueError(b.decode()) if b.startswith(b"!") else b
                for b in bodies]


def _counts(handed_at_least=0.0):
    """(pio_serve_reply_handovers_total, dispatches). The dispatcher books
    a hand-over after the call, so its waiter may be here first: waits
    for the counter to reach ``handed_at_least``."""
    from incubator_predictionio_tpu.obs import metrics as obs_metrics

    handed = obs_metrics.REGISTRY.get("pio_serve_reply_handovers_total")
    deadline = time.monotonic() + 5
    while handed.value < handed_at_least and time.monotonic() < deadline:
        time.sleep(0.002)
    return (handed.value,
            obs_metrics.REGISTRY.get("pio_serve_batch_size").count)


def _one_batch_behind_a_held_one(s, clock, handler, on_loop, bodies,
                                 plain=()):
    """Holds one dispatch (a waiter of the loop), queues ``bodies`` from
    the loop and ``plain`` from this thread behind it and lets the age
    bound take them all as ONE batch. Returns (the held waiter, the
    loop's waiters, the plain-thread futures), the gate still closed."""
    held, = on_loop.submit(s, [b"held"])
    assert handler.in_handler.wait(5)
    futs = on_loop.submit(s, bodies)
    plain_futs = [s.submit(b) for b in plain]
    clock.advance(1.0)      # the backlog is past the bound: taken whole
    return held, futs, plain_futs


@pytest.mark.parametrize("n", [1, 2, 7, 64])
def test_a_batch_from_one_loop_is_one_call_into_it(on_loop, n):
    clock = FakeClock()
    handler = _HeldFirst()
    s = BatchScheduler(handler, 64, clock=clock, shed=False,
                       wait_bound_s=0.25)
    try:
        handed0, batches0 = _counts()
        bodies = [b"%d" % i for i in range(n)]
        held, futs, _ = _one_batch_behind_a_held_one(
            s, clock, handler, on_loop, bodies)
        assert all(isinstance(f, asyncio.Future) for f in futs)
        assert on_loop.handovers == 0
        handler.gate.set()
        assert on_loop.gather([held] + futs) == [b"held"] + bodies
        assert handler.batches == [[b"held"], bodies]
        # two dispatches (1 and n waiters): two calls into the loop, and
        # the counter follows the dispatches', not the queries'
        assert on_loop.handovers == 2
        handed1, batches1 = _counts(handed0 + 2)
        assert handed1 - handed0 == batches1 - batches0 == 2
    finally:
        s.stop()


def test_answers_and_exceptions_reach_their_own_waiter_in_order(on_loop):
    clock = FakeClock()
    handler = _HeldFirst()
    s = BatchScheduler(handler, 64, clock=clock, shed=False,
                       wait_bound_s=0.25)
    try:
        handed0, _b = _counts()
        # loop waiters and plain-thread waiters in one batch, two of the
        # seven failing alone
        held, futs, plain = _one_batch_behind_a_held_one(
            s, clock, handler, on_loop, [b"a", b"!b", b"c", b"d"],
            plain=[b"p", b"!q", b"r"])
        order = []

        async def watch():
            for i, f in enumerate(futs):
                f.add_done_callback(lambda _f, i=i: order.append(i))
        on_loop.run(watch())
        handler.gate.set()
        got = on_loop.gather(futs)
        assert handler.batches[1] == [b"a", b"!b", b"c", b"d",
                                      b"p", b"!q", b"r"]
        assert got[0] == b"a" and got[2:] == [b"c", b"d"]
        assert isinstance(got[1], ValueError) and str(got[1]) == "!b"
        assert order == [0, 1, 2, 3]
        # the plain-thread callers of the same batch: as ever
        assert plain[0].result(10) == b"p" and plain[2].result(10) == b"r"
        with pytest.raises(ValueError, match="!q"):
            plain[1].result(10)
        assert all(not isinstance(f, asyncio.Future) for f in plain)
        # the mixed batch still cost its loop one call (and the held one)
        assert on_loop.handovers == 2
        assert _counts(handed0 + 2)[0] - handed0 == 2
    finally:
        s.stop()


@pytest.mark.parametrize("when", ["before-the-hand-over",
                                  "between-hand-over-and-callback"])
def test_a_waiter_that_hung_up_costs_nobody_else_an_answer(on_loop, when):
    clock = FakeClock()
    handler = _HeldFirst()
    s = BatchScheduler(handler, 64, clock=clock, shed=False,
                       wait_bound_s=0.25)
    try:
        bodies = [b"%d" % i for i in range(7)]
        held, futs, _ = _one_batch_behind_a_held_one(
            s, clock, handler, on_loop, bodies)

        async def hang_up(fut):
            fut.cancel()
        on_loop.run(hang_up(held))      # its dispatch is still running
        if when == "before-the-hand-over":
            on_loop.run(hang_up(futs[3]))
            handler.gate.set()
        else:
            on_loop.handed.clear()

            async def hold_the_loop_then_hang_up():
                # the loop's thread stands still until the dispatcher has
                # queued the batch's callback behind this one
                handler.gate.set()
                while on_loop.queued < 2:
                    assert on_loop.handed.wait(10)
                    on_loop.handed.clear()
                assert not any(f.done() for f in futs)
                futs[3].cancel()
            on_loop.run(hold_the_loop_then_hang_up(), 30)
        got = on_loop.gather(futs)
        assert isinstance(got[3], asyncio.CancelledError)
        assert got[:3] + got[4:] == bodies[:3] + bodies[4:]
        # the dispatcher's thread lives: the next batch is answered
        assert on_loop.gather(on_loop.submit(s, [b"next"])) == [b"next"]
        assert s.submit(b"plain").result(10) == b"plain"
        assert all(t.is_alive() for t in s._threads)
    finally:
        s.stop()


def test_a_loop_closed_before_the_hand_over_leaves_the_dispatcher_alive():
    clock = FakeClock()
    handler = _HeldFirst()
    s = BatchScheduler(handler, 64, clock=clock, shed=False,
                       wait_bound_s=0.25)
    gone = _LoopThread()
    try:
        handed0, batches0 = _counts()
        _one_batch_behind_a_held_one(s, clock, handler, gone,
                                     [b"0", b"1"], plain=[])
        gone.close()                    # a server stopping under its queue
        handler.gate.set()
        deadline = time.monotonic() + 10
        while len(handler.batches) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        # the plain-thread caller behind them is served by the same thread
        assert s.submit(b"after").result(10) == b"after"
        assert all(t.is_alive() for t in s._threads)
        assert handler.batches[:2] == [[b"held"], [b"0", b"1"]]
        handed1, batches1 = _counts()
        assert batches1 - batches0 == 3
        assert handed1 == handed0       # no call went through
    finally:
        gone.close()
        s.stop()


def test_shed_quota_and_evicted_waiters_of_a_loop_get_their_shed_error(
        on_loop):
    clock = FakeClock()
    handler = _GatedHandler(clock, wall_s=0.2)
    s = BatchScheduler(handler, 4, clock=clock, shed=True, slo_s=0.5,
                       p99_fn=lambda: 0.1, wait_bound_s=0.0,
                       tenant_quotas={"capped": 1})
    try:
        assert on_loop.gather(on_loop.submit(s, [b"w"])) == [b"w"]
        inflight, = on_loop.submit(s, [b"0"])
        assert handler.in_handler.wait(5)
        low = on_loop.submit(s, [b"%d" % i for i in range(4)])
        # the overload point of test_shed_then_recover_flip: the arrival
        # sheds, failed on its own (the loop's) thread inside submit
        shed, = on_loop.submit(s, [b"last"])
        assert shed.done()
        err, = on_loop.gather([shed])
        assert isinstance(err, ShedError) and err.reason == "overload"
        assert err.status == 503 and int(err.headers["Retry-After"]) >= 1
        # a higher-priority arrival from a PLAIN thread evicts a waiter of
        # the loop: failed on the loop's thread, by one call into it
        handed0, _b = _counts()
        vip = s.submit(b"vip", priority=5)
        got = on_loop.gather(low[:1])
        assert isinstance(got[0], ShedError) and got[0].reason == "evicted"
        assert int(got[0].headers["Retry-After"]) >= 1
        assert not any(f.done() for f in low[1:]) and not vip.done()
        assert _counts()[0] == handed0  # not a dispatcher's hand-over
        # the tenant's own bound, whatever the load
        first, second = on_loop.submit(s, [b"q1", b"q2"], tenant="capped")
        err, = on_loop.gather([second])
        assert isinstance(err, ShedError) and err.reason == "quota"
        handler.gate.set()
        assert on_loop.gather([inflight] + low[1:] + [first]) == [
            b"0", b"1", b"2", b"3", b"q1"]
        assert vip.result(10) == b"vip"
        assert s.shed_count == 3
    finally:
        s.stop()


def test_a_stopped_scheduler_fails_a_loop_waiter_at_once(on_loop):
    s = BatchScheduler(lambda bodies: bodies, 4, shed=False)
    s.stop()
    fut, = on_loop.submit(s, [b"late"])
    err, = on_loop.gather([fut])
    assert getattr(err, "status", None) == 503


# ---------------------------------------------------------------------------
# zero steady-state recompiles (real jit ladder)
# ---------------------------------------------------------------------------

def test_warm_ladder_serves_with_zero_recompiles():
    """Once every pow2 rung the scheduler can pick has compiled, any
    mixture of live batch widths serves entirely from the jit cache —
    the serving twin of foldin_compile_cache_size's pin."""
    import numpy as np

    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops import topk

    uf = jnp.asarray(np.random.default_rng(0).normal(
        size=(64, 8)).astype(np.float32))
    itf = jnp.asarray(np.random.default_rng(1).normal(
        size=(48, 8)).astype(np.float32))

    def handler(bodies):
        rows = [int(b) % 64 for b in bodies]
        out = topk.batch_score_top_k(uf, itf, rows, k=8)
        assert out.shape[1] >= len(bodies)
        return bodies

    cap = 16
    # warm every pow2 ladder rung directly — exactly what the deploy-
    # time warmup hook (Algorithm.warmup) compiles before traffic lands
    for rung in topk.ladder_rungs(cap):
        handler([b"%d" % i for i in range(rung)])
    warm = topk.serve_compile_cache_size()
    assert warm > 0
    s = BatchScheduler(handler, cap, shed=False, wait_bound_s=0.0)
    try:
        # steady state through the scheduler: arbitrary live widths,
        # every one padding onto an already-compiled rung
        for width in (3, 7, 11, 16, 5, 13):
            futs = [s.submit(b"%d" % i) for i in range(width)]
            _drain(futs)
        assert topk.serve_compile_cache_size() == warm, \
            "steady-state serving recompiled"
    finally:
        s.stop()


@pytest.mark.parametrize("k", [1, 10, 16])
@pytest.mark.parametrize("width", range(1, 17))
def test_rows_as_the_calls_argument_score_as_a_device_array_did(width, k):
    """``batch_score_top_k`` hands the padded int32 rows to the jitted
    program as a host array (one call launches the dispatch). Whatever
    the caller's rows are — a Python list, an int64 array, an int32
    array — the packed result is bitwise what the program returns for
    the same padded rows made into a device array first and passed in:
    the two-call launch, written out here as the plain reference."""
    import numpy as np

    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops import topk

    uf = jnp.asarray(np.random.default_rng(2).normal(
        size=(64, 8)).astype(np.float32))
    itf = jnp.asarray(np.random.default_rng(3).normal(
        size=(48, 8)).astype(np.float32))
    rows = [int(r) for r in
            np.random.default_rng(100 * width + k).integers(0, 64, width)]
    rung = topk.next_pow2(width)
    padded = np.asarray(rows + [rows[0]] * (rung - width), np.int32)
    k_pad = topk.next_pow2(k)
    want = np.asarray(topk._batch_score_top_k_xla(
        uf, itf, jnp.asarray(padded), k_pad))
    assert want.shape == (2, rung, k_pad)
    for given in (rows, np.asarray(rows, np.int64),
                  np.asarray(rows, np.int32)):
        got = np.asarray(topk.batch_score_top_k(uf, itf, given, k))
        assert got.dtype == want.dtype
        assert got.tobytes() == want.tobytes(), type(given)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_batch_size_and_queue_wait_booked():
    from incubator_predictionio_tpu.obs import metrics as obs_metrics

    size_h = obs_metrics.REGISTRY.get("pio_serve_batch_size")
    wait_h = obs_metrics.REGISTRY.get("pio_serve_queue_wait_seconds")
    assert size_h is not None and wait_h is not None
    _n0, t0 = size_h.cumulative_below(float("inf"))
    _w0, w0 = wait_h.cumulative_below(float("inf"))

    s = BatchScheduler(lambda bodies: bodies, 8, shed=False)
    try:
        _drain([s.submit(b"x") for _ in range(5)])
    finally:
        s.stop()
    _n1, t1 = size_h.cumulative_below(float("inf"))
    _w1, w1 = wait_h.cumulative_below(float("inf"))
    assert t1 > t0          # ≥1 dispatch booked its fused width
    assert w1 - w0 == 5     # every query booked its queue wait
