"""PredictionServer — query serving from TPU-resident model state.

Parity: core/.../workflow/CreateServer.scala:115-725 on :8000:

- ``GET  /``              → status (JSON or HTML): engine info, params,
  request count, average/last serving seconds (:426-428,611-618)
- ``POST /queries.json``  → supplement → predict(∀ algorithms) → serve with
  the ORIGINAL query → optional feedback event → output plugins (:498-650)
- ``POST /reload``        → hot-swap to the latest COMPLETED instance
  (key-authed, :340-366)
- ``POST /stop``          → shutdown (key-authed)
- ``GET  /plugins.json``, ``/plugins/...`` engine-plugin passthrough

The feedback loop posts a ``predict`` event (entityType ``pio_pr``) carrying
engineInstanceId/query/prediction back to the EventServer with ``prId``
(:534-604). The MasterActor deploy/undeploy lifecycle collapses into
``PredictionServerLauncher`` semantics: resolve latest COMPLETED instance →
restore models via ``Engine.prepare_deploy`` (device-resident) → bind.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import os
import secrets
import threading
import time
import traceback
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional

from incubator_predictionio_tpu.core.engine import Engine
from incubator_predictionio_tpu.core.params import EngineParams, WorkflowParams
from incubator_predictionio_tpu.data.storage import EngineInstance, Storage
from incubator_predictionio_tpu.obs import metrics as obs_metrics
from incubator_predictionio_tpu.obs import trace as obs_trace
from incubator_predictionio_tpu.obs.http import (
    add_metrics_route,
    add_recorder_route,
)
from incubator_predictionio_tpu.parallel.context import RuntimeContext
from incubator_predictionio_tpu.servers.plugins import PluginContext
from incubator_predictionio_tpu.serving import tenancy
from incubator_predictionio_tpu.serving.scheduler import (
    BatchScheduler,
    ladder_cap,
)
from incubator_predictionio_tpu.utils import json_codec
from incubator_predictionio_tpu.utils.http import (
    HttpError,
    HttpServer,
    Request,
    Response,
    RetryableError,
    RetryPolicy,
    Router,
    parse_retry_after,
)
from incubator_predictionio_tpu.utils.times import (
    ensure_aware,
    format_iso8601,
    now_utc,
)
from incubator_predictionio_tpu.workflow import CoreWorkflow
from incubator_predictionio_tpu.workflow.workflow import make_runtime_context

logger = logging.getLogger(__name__)

#: per-QUERY serving latency (every query in a fused micro-batch took
#: the batch wall — CreateServer.scala:611-618 per-query semantics, at
#: one histogram observe per BATCH). p50/p95/p99 derive from the fixed
#: exponential buckets; /status reports them too (no scraper needed).
#: Booked on the micro-batch dispatcher thread AFTER the device
#: dispatch resolves — host-side ints only, never inside traced code.
#: TENANT-LABELED (serving/tenancy.py): label values come only from the
#: bounded registry (the unscoped-tenant-metric lint contract);
#: unlabeled family reads (quantile/count/sum) aggregate the children.
_QUERY_LATENCY = obs_metrics.REGISTRY.histogram(
    "pio_query_latency_seconds",
    "per-query serving wall (micro-batch members share the batch wall)",
    labels=("tenant",))
#: instantaneous micro-batcher backlog per tenant, read at scrape time
_QUEUE_DEPTH = obs_metrics.REGISTRY.gauge(
    "pio_serve_queue_depth",
    "queries waiting in the micro-batching queue (scrape-time "
    "snapshot, per tenant)",
    labels=("tenant",))
#: age of the deployed instance, read at scrape time — the gauge the
#: staleness SLO (obs/slo.py) evaluates its bound against; /status's
#: modelStalenessSec reports the same figure
_STALENESS = obs_metrics.REGISTRY.gauge(
    "pio_model_staleness_seconds",
    "seconds since the served engine instance finished training "
    "(scrape-time snapshot)")
#: an answer the dispatcher has finished waits for the event loop to pick
#: it up (one hand-over a dispatch, serving/scheduler.py): one query of
#: each dispatch is sampled, on the loop's thread (the scheduler stamps
#: it at the hand-over). Steps of at most 1.5, not doubling: its p95 is
#: read from the buckets
_REPLY_LAG = obs_metrics.REGISTRY.histogram(
    "pio_serve_reply_lag_seconds",
    "answer handed over on the dispatcher's thread to handler resumed "
    "on the event loop's, one query a dispatch",
    buckets=obs_metrics.geometric_buckets(50e-6, 1.0))
#: what GET /ready answers from, the load-balancer probe, read off the
#: newest server at scrape time: 0 until the deploy's warm-up thread has
#: ended, 1 from then on — a /reload warms its new models BEFORE the swap
#: while the old ones serve, so it stays 1
_READY = obs_metrics.REGISTRY.gauge(
    "pio_serve_ready",
    "1 once the serving warm-up has ended (GET /ready answers 200), "
    "0 before")
_DEVICE_BYTES = obs_metrics.REGISTRY.gauge(
    "pio_device_bytes_in_use",
    "device memory in use (memory_stats() at scrape time; absent on a "
    "backend that reports none)", labels=("device",))
_DEVICE_PEAK_BYTES = obs_metrics.REGISTRY.gauge(
    "pio_device_peak_bytes_in_use",
    "peak device memory in use since the process started "
    "(memory_stats() at scrape time)", labels=("device",))


def _collect_device_memory() -> None:
    # scrape time only, never on the serving path. Registered by a
    # PredictionServer, whose models already live on the device: a
    # process that merely shares the registry never initializes a
    # backend for the sake of a gauge
    import jax

    # the label is the device's place among this process's devices: as
    # many values as the host has chips
    for n, d in enumerate(jax.local_devices()):
        stats = d.memory_stats()
        if not stats:
            continue
        if "bytes_in_use" in stats:
            _DEVICE_BYTES.labels(device=str(n)).set(
                float(stats["bytes_in_use"]))
        if "peak_bytes_in_use" in stats:
            _DEVICE_PEAK_BYTES.labels(device=str(n)).set(
                float(stats["peak_bytes_in_use"]))


@dataclasses.dataclass
class ServerConfig:
    """CreateServer.scala:89-113 ServerConfig."""

    ip: str = "0.0.0.0"
    port: int = 8000
    engine_instance_id: Optional[str] = None  # default: latest COMPLETED
    engine_id: str = "default"
    engine_version: str = "NOT_VERSIONED"
    engine_variant: str = "default"
    event_server_ip: str = "0.0.0.0"
    event_server_port: int = 7070
    access_key: Optional[str] = None
    feedback: bool = False
    server_key: Optional[str] = None  # auth for /stop and /reload
    verbose: bool = False
    #: LADDER CAP for the continuous-batching scheduler (0 disables
    #: batching; the reference serves queries one at a time —
    #: CreateServer.scala:523 "TODO: Parallelize"). This is no longer a
    #: fixed fuse width: the scheduler (serving/scheduler.py) picks the
    #: batch per dispatch from live queue depth on a pow2 rung ladder
    #: and only reaches the cap under sustained pressure, so a large
    #: cap costs idle traffic nothing. Default PIO_SERVE_MAX_BATCH
    #: (512) — the old fixed 64 capped concurrent QPS exactly when the
    #: queue was deepest
    micro_batch: int = dataclasses.field(default_factory=ladder_cap)
    #: micro-batch dispatcher threads. 1 measured best on the host-mirror
    #: path at ML-20M shape (3.8k QPS vs 3.3k at 2 and 2.8k at 4: extra
    #: workers fragment the natural batches and fight the BLAS pool for
    #: cores). The knob exists for the device path, where a second worker
    #: can hide host-side parse/render behind the in-flight dispatch
    serve_workers: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get("PIO_SERVE_WORKERS",
                                                   "1")))
    #: ship query errors to a remote collector (CreateServer.scala:449-460)
    log_url: Optional[str] = None
    log_prefix: str = ""


#: retry choreography for the fire-and-forget posters (feedback events,
#: --log-url shipping): the shared utils/http.RetryPolicy — jittered
#: exponential backoff under a hard deadline, honoring Retry-After on a
#: 503 shed. Only failures that provably never executed server-side
#: (connection refused before send) or that the server explicitly
#: deferred (503) are wrapped retryable — see _post_with_retries.
_POST_RETRY = RetryPolicy(attempts=3, base_delay_s=0.5, max_delay_s=5.0,
                          deadline_s=20.0)


def _post_with_retries(url: str, payload: bytes,
                       headers: Dict[str, str], what: str,
                       expect_status: Optional[int] = None) -> None:
    """One JSON POST under _POST_RETRY; runs on a poster worker thread.

    Retry classification: a refused connection never carried the body
    (safe for any payload), and a 503 is the receiving server's own
    shed contract — it did NOT process the event and told us when to
    come back (Retry-After floors the backoff). Anything else — 4xx,
    non-503 5xx, a timeout mid-flight — fails after one try: the event
    may have been applied, and these posters must never double-apply
    training data. Failures only ever log; posters are fire-and-forget.
    """
    def attempt() -> None:
        req = urllib.request.Request(url, data=payload, headers=headers,
                                     method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                if expect_status is not None and resp.status != expect_status:
                    logger.error("%s POST returned status %d", what,
                                 resp.status)
        except urllib.error.HTTPError as e:
            if e.code == 503:
                raise RetryableError(
                    e, retry_after_s=parse_retry_after(
                        e.headers.get("Retry-After"))) from e
            raise
        except urllib.error.URLError as e:
            if isinstance(e.reason, ConnectionRefusedError):
                raise RetryableError(e) from e
            raise

    try:
        _POST_RETRY.call(attempt)
    except Exception as e:
        logger.error("%s failed: %s", what, e)


class _AsyncPoster:
    """Bounded worker pool for fire-and-forget HTTP posts. Bounds the
    resource cost of an error storm against a slow collector: excess posts
    drop with a local log line instead of spawning a thread + socket per
    failure. Feedback events and --log-url shipping get SEPARATE posters so
    a hung diagnostics collector can never starve feedback delivery
    (feedback is training data, not telemetry)."""

    def __init__(self, name: str, workers: int = 2, maxsize: int = 1024):
        import queue

        self._queue: "queue.Queue" = queue.Queue(maxsize=maxsize)
        self.dropped = 0  # surfaced on the status page (feedback is data)
        self._dropped_lock = threading.Lock()
        self._threads = [
            threading.Thread(target=self._run, daemon=True,
                             name=f"pio-poster-{name}-{i}")
            for i in range(max(workers, 1))
        ]
        for t in self._threads:
            t.start()

    def submit(self, fn, what: str) -> None:
        import queue

        # never blocks: submit runs on the serving hot path (a micro-batch
        # dispatcher thread — possibly several under PIO_SERVE_WORKERS>1),
        # where even a brief put(timeout=...) under a collector outage
        # would stall every query behind it
        try:
            self._queue.put_nowait(fn)
        except queue.Full:
            with self._dropped_lock:
                self.dropped += 1
                n = self.dropped
            logger.error("async post queue full; dropping %s (%d dropped "
                         "total)", what, n)

    def stop(self) -> None:
        import queue

        for _ in self._threads:
            try:
                # blocking put with a timeout: when the queue is full of
                # backlog, the sentinel must still land or workers never
                # exit (drained posts run first — stop() is fire-and-forget)
                self._queue.put(None, timeout=5)
            except queue.Full:
                logger.warning(
                    "async post queue still full at stop; a worker may "
                    "keep draining in the background")

    def _run(self) -> None:
        while True:
            fn = self._queue.get()
            if fn is None:
                return
            try:
                fn()
            except Exception:
                logger.exception("async post failed")


class PredictionServer:
    def __init__(
        self,
        engine: Engine,
        config: Optional[ServerConfig] = None,
        plugin_context: Optional[PluginContext] = None,
        ctx: Optional[RuntimeContext] = None,
    ):
        self.engine = engine
        self.config = config or ServerConfig()
        config = self.config
        self.plugin_context = plugin_context or PluginContext()
        self.ctx = ctx or make_runtime_context(None)
        self._lock = threading.Lock()
        #: serializes /reload end-to-end: with pre-swap warmup the
        #: resolve→swap window is seconds long, and two unserialized
        #: reloads could last-writer-swap an OLDER instance back in
        self._reload_lock = threading.Lock()
        # serving state (swapped atomically on /reload)
        self.engine_instance: Optional[EngineInstance] = None
        self.engine_params: Optional[EngineParams] = None
        self.algorithms: List[Any] = []
        self.serving: Any = None
        self.models: List[Any] = []
        # latency bookkeeping (CreateServer.scala:426-428)
        self.start_time = now_utc()
        self.request_count = 0
        self.avg_serving_sec = 0.0
        self.last_serving_sec = 0.0
        self.max_batch_served = 0  # largest micro-batch fused so far
        from incubator_predictionio_tpu.utils.ssl_config import load_server_key

        # loaded once, like the reference's ServerKey config object
        self._conf_server_key = (
            load_server_key() if config.server_key is None else None
        )
        # bind-retry 3×/1 s for occupied ports (CreateServer.scala:371-381)
        self.http = HttpServer.from_conf(self._build_router(), config.ip,
                                         config.port, bind_retries=3,
                                         name="prediction")
        #: per-tenant deploys beyond the default one (tenant id →
        #: {engine_instance, engine_params, algorithms, serving,
        #: models}); a registered tenant with no entry here SHARES the
        #: default deploy — co-resident deploys only materialize when a
        #: tenant pins its own engine/variant or tenant-scoped-reloads
        self._deploys: Dict[str, Dict[str, Any]] = {}
        self._batcher = (
            # the p99 feed takes the tenant (non-defaulted — the
            # scheduler arity-detects per-tenant feeds): the shed
            # projection must read the tenant's OWN tail, never a noisy
            # neighbor's
            BatchScheduler(self._handle_batch, config.micro_batch,
                           workers=config.serve_workers,
                           p99_fn=lambda tenant: _QUERY_LATENCY.labels(
                               tenant=tenancy.get_registry().label(tenant)
                           ).quantile(0.99))
            if config.micro_batch > 0 else None
        )
        self._sync_tenant_policy()
        if self._batcher is not None:
            self.register_queue_collector()
        # scrape-time model-staleness gauge (weakref for the same
        # reason as the queue collector: telemetry must never pin a
        # stopped server's models)
        import weakref as _weakref

        server_ref = _weakref.ref(self)

        def _collect_staleness() -> None:
            s = server_ref()
            if s is None:
                return
            with s._lock:
                instance = s.engine_instance
            if instance is None:
                return
            _STALENESS.set(max(
                (now_utc() - ensure_aware(instance.end_time))
                .total_seconds(), 0.0))

        obs_metrics.REGISTRY.register_collector(
            "prediction_model_staleness", _collect_staleness)
        obs_metrics.REGISTRY.register_collector(
            "device_memory", _collect_device_memory)
        self._ready = False

        def _collect_ready() -> None:
            s = server_ref()
            if s is not None:
                _READY.set(1 if s._ready else 0)

        obs_metrics.REGISTRY.register_collector(
            "serve_ready", _collect_ready)
        self._gc_hook_held = False
        self._heartbeat: Optional[obs_trace.LoopHeartbeat] = None
        # feedback events are training data: a deep queue so only a
        # sustained collector outage drops (drops counted and shown on the
        # status page); --log-url diagnostics stay shallow and lossy
        self._feedback_poster = _AsyncPoster("feedback", maxsize=16384)
        self._log_poster = _AsyncPoster("log", workers=1, maxsize=256)
        #: live speed-layer overlays (speed/overlay.py), rebuilt per
        #: deploy/reload — the Lambda speed leg between retrains
        self._speed_overlays: List[Any] = []

    # -- tenancy ------------------------------------------------------------
    def register_queue_collector(self) -> None:
        """Register the scrape-time ``pio_serve_queue_depth`` collector.

        The named collector replaces any prior server's hook so
        re-deploys never accumulate dead closures, and it weakrefs the
        SERVER (not the batcher — harnesses and tests may swap
        ``_batcher`` after construction; the collector must follow the
        live one) so a stopped server's engine + models stay
        collectable — the registry must never pin model memory.
        Harnesses that build a server via ``__new__`` (tests/
        fleet_worker.py) call this after wiring their own batcher."""
        import weakref

        server_ref = weakref.ref(self)

        def _collect_queue_depth() -> None:
            s = server_ref()
            b = s._batcher if s is not None else None
            if b is None:
                return
            depths = b.depths_by_tenant()
            depths.setdefault(tenancy.DEFAULT_TENANT, 0)
            reg = tenancy.get_registry()
            for t in reg.tenant_ids():
                depths.setdefault(t, 0)
            for t, d in depths.items():
                _QUEUE_DEPTH.labels(tenant=reg.label(t)).set(float(d))

        obs_metrics.REGISTRY.register_collector(
            "prediction_queue_depth", _collect_queue_depth)

    def _sync_tenant_policy(self) -> None:
        """Push the tenant registry's isolation policy (weights, quotas)
        into the scheduler — at construction and after every /reload, so
        a registry change lands without restart."""
        batcher = getattr(self, "_batcher", None)
        if batcher is None:
            return
        reg = tenancy.get_registry()
        batcher.set_tenant_policy(reg.weights(), reg.quotas())

    # -- deploy lifecycle ---------------------------------------------------
    def _resolve_instance(
            self, engine_id: Optional[str] = None,
            engine_variant: Optional[str] = None) -> EngineInstance:
        instances = Storage.get_meta_data_engine_instances()
        if engine_id is None and engine_variant is None \
                and self.config.engine_instance_id:
            instance = instances.get(self.config.engine_instance_id)
            if instance is None:
                raise ValueError(
                    f"Invalid engine instance ID {self.config.engine_instance_id}."
                )
        else:
            instance = instances.get_latest_completed(
                engine_id or self.config.engine_id,
                self.config.engine_version,
                engine_variant or self.config.engine_variant,
            )
            if instance is None:
                raise ValueError(
                    "No valid engine instance found for engine "
                    f"{self.config.engine_id} {self.config.engine_version} "
                    f"{self.config.engine_variant}. The engine id is derived "
                    "from the engine directory's absolute path — if the "
                    "engine was trained from a different path (moved, "
                    "re-cloned, other mount), its instances are keyed under "
                    "a different id; redeploy from the training path or pass "
                    "--engine-instance-id explicitly."
                )
        return instance

    def load_models(self, warm_before_swap: bool = False,
                    tenant: Optional[str] = None) -> None:
        """createServerActorWithEngine (:207-266): restore + prepare_deploy.

        ``warm_before_swap`` is the /reload path's double-buffered
        refresh: the OLD models keep serving while the replacements
        compile their dispatches and build host mirrors (algo.warmup), and
        the swap happens only once they are query-ready — a reload never
        spikes live p50 with compiles or a device→host factor fetch. Initial deploy keeps warmup async (nothing serves yet;
        binding fast matters more).

        ``tenant`` scopes the refresh to ONE co-resident deploy
        (``/reload?tenant=X``): only that tenant's state swaps, so
        rolling-reloading one tenant never drains another's serving."""
        if tenant is not None and tenant != tenancy.DEFAULT_TENANT:
            self._load_tenant_models(tenant, warm_before_swap)
            return
        instance = self._resolve_instance()
        engine_params = self.engine.engine_params_from_instance(instance)
        models = CoreWorkflow.load_models(
            instance.id, self.engine, engine_params, ctx=self.ctx
        )
        _ds, _prep, algorithms, serving = self.engine.components(engine_params)
        if warm_before_swap:
            self._warm_models(algorithms, models)
        overlays = self._build_speed_overlays(engine_params, algorithms,
                                              models)
        with self._lock:
            self.engine_instance = instance
            self.engine_params = engine_params
            self.algorithms = algorithms
            self.serving = serving
            self.models = models
            # getattr: tests build servers via __new__ with
            # hand-injected state
            old_overlays = getattr(self, "_speed_overlays", [])
            self._speed_overlays = overlays
        # hot model swap: the OLD overlays' vectors were solved against
        # the old factors — invalidated wholesale and stopped. Their KEYS
        # (fresh sessions the new model may still not know) carry over as
        # dirty marks so the new overlays re-solve them against the new
        # factors instead of dropping fresh users until their next event.
        # Both lists are ALGORITHM-ALIGNED (None where an algorithm has
        # no overlay), so adoption can never pair across algorithms.
        for old, ov in zip(old_overlays, overlays):
            if old is None or ov is None:
                continue
            try:
                ov.adopt_keys(old.known_keys())
            except Exception:
                logger.exception("speed overlay key adoption failed")
        for ov in old_overlays:
            if ov is None:
                continue
            try:
                ov.invalidate_all()
                ov.stop()
            except Exception:
                logger.exception("speed overlay teardown failed")
        for ov in overlays:
            if ov is not None:
                ov.start()
        # host the MIPS rebuild daemon next to the overlay pollers: it
        # folds published virtual-id tails, re-tiers cold buckets and
        # swaps indexes off the serving path (ops/mips_daemon.py).
        # Acquired ONCE per server — a /reload must not stack refs.
        with self._lock:
            want_daemon = not getattr(self, "_mips_daemon_held", False)
            if want_daemon:
                self._mips_daemon_held = True
        if want_daemon:
            try:
                from incubator_predictionio_tpu.ops import mips_daemon

                mips_daemon.acquire()
            except Exception:
                logger.exception("mips rebuild daemon start failed")
                with self._lock:
                    self._mips_daemon_held = False
        logger.info(
            "Engine instance %s deployed (%d algorithms, %d speed "
            "overlays)", instance.id, len(self.algorithms),
            sum(1 for ov in overlays if ov is not None),
        )

    def _load_tenant_models(self, tenant_id: str,
                            warm_before_swap: bool) -> None:
        """Load/refresh ONE tenant's co-resident deploy (the tenant-
        scoped half of :meth:`load_models`). Rides the same warm-before-
        swap discipline; the swap touches only ``self._deploys[tenant]``
        so every other tenant — including the default deploy — keeps
        serving untouched. Speed overlays stay a default-deploy feature
        (tenant deploys serve the model-of-record)."""
        reg = tenancy.get_registry()
        t = reg.get(tenant_id)
        if t is None:
            raise HttpError(404, f"Unknown tenant {tenant_id!r}.")
        instance = self._resolve_instance(
            engine_id=t.engine_id or self.config.engine_id,
            engine_variant=t.engine_variant or self.config.engine_variant)
        engine_params = self.engine.engine_params_from_instance(instance)
        models = CoreWorkflow.load_models(
            instance.id, self.engine, engine_params, ctx=self.ctx
        )
        _ds, _prep, algorithms, serving = self.engine.components(
            engine_params)
        if warm_before_swap:
            self._warm_models(algorithms, models)
        with self._lock:
            self._deploys[tenant_id] = {
                "engine_instance": instance,
                "engine_params": engine_params,
                "algorithms": algorithms,
                "serving": serving,
                "models": models,
            }
        logger.info(
            "Tenant %s deployed engine instance %s (%d algorithms)",
            tenant_id, instance.id, len(algorithms))

    def _build_speed_overlays(self, engine_params, algorithms,
                              models) -> List[Any]:
        """One overlay per algorithm that offers a fold-in config
        (core/base.py Algorithm.make_speed_overlay), attached to the
        algorithm for its predict path. Gated by PIO_SPEED_LAYER
        (default on); any construction failure disables the overlay for
        that algorithm only — serving never depends on the speed leg.
        The returned list is ALGORITHM-ALIGNED (None placeholders), so
        hot-swap key adoption pairs old and new overlays by algorithm."""
        dsp = engine_params.data_source_params[1]
        app_name = getattr(dsp, "app_name", None)
        channel_name = getattr(dsp, "channel_name", None)
        disabled = os.environ.get("PIO_SPEED_LAYER", "1").lower() in (
            "0", "off", "false")
        overlays: List[Any] = []
        for algo, model in zip(algorithms, models):
            overlay = None
            if not disabled:
                try:
                    overlay = algo.make_speed_overlay(
                        model, app_name, channel_name,
                        data_source_params=dsp)
                    if overlay is not None and not overlay.enabled:
                        overlay = None  # backend without tail support
                except Exception:
                    logger.exception(
                        "speed overlay unavailable for %s",
                        type(algo).__name__)
                    overlay = None
            algo.attach_speed_overlay(overlay)
            overlays.append(overlay)
        return overlays

    # -- query pipeline -----------------------------------------------------
    def _handle_query(self, body: bytes,
                      tenant: str = tenancy.DEFAULT_TENANT) -> Any:
        res = self._handle_batch([body], self.config.engine_id, tenant)[0]
        if isinstance(res, Exception):
            raise res
        return res

    def _handle_batch(self, bodies: List[bytes], engine: str,
                      tenant: str) -> List[Any]:
        """Serve a batch of query bodies in one pass: parse + supplement per
        query, then ONE ``batch_predict`` per algorithm (a single device
        dispatch for the whole batch, ops/topk.py batch_score_top_k), then
        per-query serve/feedback/plugins. Per-query failures become entries
        in the result list — one bad query never fails its batchmates.
        A batch of one is the plain sequential path.

        ``engine``/``tenant`` are non-defaulted so the scheduler's arity
        detection routes each batch here with its queue's tenant — a
        batch is single-tenant by construction, and serves from that
        tenant's own deploy when one is resident."""
        t0 = time.perf_counter()
        with self._lock:
            # getattr: tests build servers via __new__ with
            # hand-injected state
            dep = (getattr(self, "_deploys", {}).get(tenant)
                   if tenant != tenancy.DEFAULT_TENANT else None)
            if dep is not None:
                algorithms = dep["algorithms"]
                serving = dep["serving"]
                models = dep["models"]
                instance = dep["engine_instance"]
            else:
                algorithms = self.algorithms
                serving = self.serving
                models = self.models
                instance = self.engine_instance
        n = len(bodies)
        if not algorithms or instance is None:
            return [HttpError(503, "No engine instance deployed.")] * n
        query_class = algorithms[0].query_class
        results: List[Any] = [None] * n
        raws: List[Any] = [None] * n
        from incubator_predictionio_tpu.core.base import Serving

        with obs_trace.stage("serve.parse"):
            for idx, body in enumerate(bodies):
                try:
                    raws[idx] = json.loads(body.decode("utf-8"))
                except Exception as e:
                    results[idx] = e
            # columnar serving fast path (core/base.py batch_serve_json):
            # only when the rendered bytes are observably identical to the
            # object path — one algorithm, declared first-prediction
            # serving with the inherited identity supplement, and nothing
            # downstream that needs the result as an object (feedback
            # loop, output plugins). The flag must be declared on the
            # serving's OWN class: a subclass that overrides serve() would
            # silently inherit True and its serve() would never run on
            # fast-path responses
            fast_path = (
                len(algorithms) == 1
                and type(serving).__dict__.get("FIRST_PREDICTION_ONLY",
                                               False)
                and type(serving).supplement is Serving.supplement
                and not self.config.feedback
                and not self.plugin_context.output_blockers
                and not self.plugin_context.output_sniffers)
        if fast_path:
            try:
                fast = algorithms[0].batch_serve_json(
                    models[0],
                    [r if results[i] is None else None
                     for i, r in enumerate(raws)])
            except Exception:
                logger.exception(
                    "batch_serve_json failed; using the object path")
                fast = None
            if fast:
                for idx, payload in enumerate(fast):
                    if payload is not None and results[idx] is None:
                        results[idx] = payload
        parsed: List[Any] = []  # [idx, raw, query, supplemented]
        for idx, body in enumerate(bodies):
            if results[idx] is not None:
                continue
            try:
                raw = raws[idx]
                query = (
                    json_codec.extract(query_class, raw)
                    if query_class is not None else raw
                )
                parsed.append([idx, raw, query, serving.supplement(query)])
            except Exception as e:
                results[idx] = e
        # one prediction per algorithm per live query; a batch of >1 goes
        # through the algorithm's batched path
        preds: Dict[int, List[Any]] = {p[0]: [] for p in parsed}
        for a, m in zip(algorithms, models):
            live = [(idx, supp) for idx, _r, _q, supp in parsed
                    if results[idx] is None]
            if not live:
                break
            if len(live) > 1:
                try:
                    got = dict(a.batch_predict(m, live))
                    # all-or-nothing: resolve every idx BEFORE mutating
                    # preds, so a partial batch_predict result (missing
                    # idx → KeyError here) falls through to the per-query
                    # path without leaving duplicate appends behind
                    vals = [got[idx] for idx, _supp in live]
                    for (idx, _supp), v in zip(live, vals):
                        preds[idx].append(v)
                    continue
                except Exception:
                    logger.exception(
                        "batch_predict failed; falling back to per-query")
            for idx, supp in live:
                try:
                    preds[idx].append(a.predict(m, supp))
                except Exception as e:
                    results[idx] = e
        for idx, raw, query, _supp in parsed:
            if results[idx] is not None:
                continue
            try:
                # by design, serve sees the ORIGINAL query
                # (CreateServer.scala:526)
                prediction = serving.serve(query, preds[idx])
                result = json_codec.to_jsonable(prediction)
                if self.config.feedback:
                    result = self._feedback(instance, raw, result)
                for blocker in self.plugin_context.output_blockers.values():
                    result = blocker.process(
                        instance.engine_variant, raw, result,
                        self.plugin_context)
                for sniffer in self.plugin_context.output_sniffers.values():
                    try:
                        sniffer.process(
                            instance.engine_variant, raw, result,
                            self.plugin_context)
                    except Exception:
                        logger.exception("output sniffer failed")
                results[idx] = result
            except Exception as e:
                results[idx] = e
        if self.config.log_url:
            for idx, res in enumerate(results):
                if isinstance(res, Exception) and not isinstance(
                        res, HttpError):
                    self._remote_log(
                        f"Query:\n{bodies[idx][:2048]!r}\n\nStack Trace:\n"
                        + "".join(traceback.format_exception(res)))
        dt = time.perf_counter() - t0
        with self._lock:
            # every query in the batch took dt wall-clock (they shared one
            # dispatch) — the counters keep CreateServer.scala:611-618
            # per-query semantics
            self.request_count += n
            self.avg_serving_sec = (
                self.avg_serving_sec * (self.request_count - n) + dt * n
            ) / self.request_count
            self.last_serving_sec = dt
            self.max_batch_served = max(self.max_batch_served, n)
        # n same-valued observations in one bucket add: per-query tail
        # latency (p50/p95/p99) at per-batch bookkeeping cost; the
        # tenant child comes from the bounded registry (lint contract)
        _QUERY_LATENCY.labels(
            tenant=tenancy.get_registry().label(tenant)).observe(dt, n)
        return results

    def _remote_log(self, message: str) -> None:
        """POST a query error to the --log-url collector, prefixed with
        --log-prefix (remoteLog, CreateServer.scala:449-460). Fire-and-
        forget on a daemon thread; collector failures only log locally."""
        with self._lock:
            instance = self.engine_instance
        payload = (self.config.log_prefix or "") + json.dumps({
            "engineInstance": {
                "id": instance.id if instance else None,
                "engineId": instance.engine_id if instance else None,
                "engineVariant": (
                    instance.engine_variant if instance else None),
            },
            "message": message,
        })

        # trace headers captured HERE: the poster runs on its own daemon
        # thread where the request's contextvars are gone
        trace_headers = obs_trace.client_headers()
        self._log_poster.submit(
            lambda: _post_with_retries(
                self.config.log_url, payload.encode(),
                {"Content-Type": "application/json", **trace_headers},
                "remote log"),
            "remote log")

    def _feedback(
        self, instance: EngineInstance, query_json: Any, prediction_json: Any
    ) -> Any:
        """Post the predict event back to the EventServer (:534-604)."""
        pr_id = prediction_json.get("prId") if isinstance(
            prediction_json, dict) else None
        if not pr_id:
            pr_id = secrets.token_hex(32)
        data = {
            "event": "predict",
            "eventTime": format_iso8601(now_utc()),
            "entityType": "pio_pr",
            "entityId": pr_id,
            "properties": {
                "engineInstanceId": instance.id,
                "query": query_json,
                "prediction": prediction_json,
            },
        }
        if isinstance(query_json, dict) and query_json.get("prId"):
            data["prId"] = query_json["prId"]
        url = (
            f"http://{self.config.event_server_ip}:"
            f"{self.config.event_server_port}/events.json"
            f"?accessKey={self.config.access_key or ''}"
        )

        # trace headers captured before the executor hop (see _remote_log)
        trace_headers = obs_trace.client_headers()
        self._feedback_poster.submit(
            lambda: _post_with_retries(
                url, json.dumps(data).encode(),
                {"Content-Type": "application/json", **trace_headers},
                "feedback event", expect_status=201),
            "feedback event")
        # inject prId into the served result when the prediction carries one
        if isinstance(prediction_json, dict) and "prId" in prediction_json:
            prediction_json = dict(prediction_json, prId=pr_id)
        return prediction_json

    def _speed_status_locked(self) -> Dict[str, Any]:
        """Aggregate speed-overlay stats for /status (caller holds
        self._lock). size/hits/misses/foldins sum over the deployed
        algorithms' overlays; cursorLagEvents is the worst lag."""
        overlays = [ov for ov in getattr(self, "_speed_overlays", [])
                    if ov is not None]
        out = {"overlays": len(overlays), "size": 0,
               "hits": 0, "misses": 0, "foldins": 0, "cursorLagEvents": 0}
        for ov in overlays:
            try:
                s = ov.stats()
            except Exception:
                continue
            out["size"] += s["size"]
            out["hits"] += s["hits"]
            out["misses"] += s["misses"]
            out["foldins"] += s["foldins"]
            out["cursorLagEvents"] = max(out["cursorLagEvents"],
                                         s["cursorLagEvents"])
        return out

    @staticmethod
    def _mips_status() -> Dict[str, Any]:
        """MIPS index lifecycle block for /status: one stats() dict per
        registered index plus the rebuild daemon's state. Never raises
        — /status must survive a racing swap."""
        try:
            from incubator_predictionio_tpu.ops import (
                mips,
                mips_daemon,
            )

            return {"indexes": mips.status_snapshot(),
                    "daemon": mips_daemon.stats()}
        except Exception:
            logger.exception("mips status block failed")
            return {"indexes": [], "daemon": None}

    def _tenant_status_locked(self) -> Optional[Dict[str, Any]]:
        """The /status per-tenant block (caller holds ``self._lock``):
        registry policy + which deploy each tenant serves from + its
        queue depth / shed count / model staleness. ``None`` in
        single-tenant mode so pre-tenancy status readers see nothing
        new to misparse."""
        reg = tenancy.get_registry()
        deploys = getattr(self, "_deploys", {})
        if not reg and not deploys:
            return None
        batcher = getattr(self, "_batcher", None)
        sched = batcher.stats()["tenants"] if batcher is not None else {}
        out: Dict[str, Any] = {}
        for tid, desc in reg.describe().items():
            dep = deploys.get(tid)
            instance = (dep["engine_instance"] if dep is not None
                        else self.engine_instance)
            srow = sched.get(tid, {})
            out[tid] = {
                **desc,
                "engineInstanceId": instance.id if instance else None,
                "sharedDeploy": dep is None,
                "modelStalenessSec": (
                    max((now_utc() - ensure_aware(instance.end_time))
                        .total_seconds(), 0.0)
                    if instance is not None else None),
                "queueDepth": srow.get("depth", 0),
                "shed": srow.get("shed", 0),
                "servingSecP99": _QUERY_LATENCY.labels(
                    tenant=reg.label(tid)).quantile(0.99) or 0.0,
            }
        return out

    # -- auth for /stop, /reload (common/.../KeyAuthentication.scala:34) ----
    def _check_server_key(self, request: Request) -> None:
        provided = request.query.get("accessKey")
        if self.config.server_key is not None:
            if provided != self.config.server_key:
                raise HttpError(401, "Invalid accessKey.")
            return
        # No explicit key on the config: fall back to server.conf enforcement
        # (KeyAuthentication.ServerKey.authEnforced, KeyAuthentication.scala:39)
        if (self._conf_server_key is not None
                and not self._conf_server_key.check(provided)):
            raise HttpError(401, "Invalid accessKey.")

    # -- routes -------------------------------------------------------------
    def _build_router(self) -> Router:
        r = Router()

        @r.get("/")
        def status(request: Request) -> Response:
            with self._lock:
                instance = self.engine_instance
                info = {
                    "status": "alive",
                    "engineInstanceId": instance.id if instance else None,
                    "engineFactory": instance.engine_factory if instance else None,
                    "engineVariant": instance.engine_variant if instance else None,
                    "algorithms": [type(a).__name__ for a in self.algorithms],
                    "startTime": format_iso8601(self.start_time),
                    "requestCount": self.request_count,
                    "avgServingSec": self.avg_serving_sec,
                    "lastServingSec": self.last_serving_sec,
                    # tail latency from the query histogram: the running
                    # average the reference keeps (:426-428) hides tail
                    # regressions entirely — p50/p95/p99 on the status
                    # page make them visible without a scraper. Scope:
                    # process-wide histogram (all queries this process
                    # served), like requestCount after a /reload. 0.0
                    # before the first query — type-stable next to the
                    # always-numeric avgServingSec
                    "servingSecP50": _QUERY_LATENCY.quantile(0.50) or 0.0,
                    "servingSecP95": _QUERY_LATENCY.quantile(0.95) or 0.0,
                    "servingSecP99": _QUERY_LATENCY.quantile(0.99) or 0.0,
                    "maxBatchServed": self.max_batch_served,
                    "feedbackEventsDropped": self._feedback_poster.dropped,
                    # model staleness: seconds since the served instance
                    # finished training — the figure the speed layer
                    # exists to make tolerable (docs/production.md
                    # "Freshness between retrains")
                    "modelStalenessSec": (
                        max((now_utc() - ensure_aware(instance.end_time))
                            .total_seconds(), 0.0)
                        if instance is not None else None),
                    "speedOverlay": self._speed_status_locked(),
                    # per-index MIPS lifecycle state (tail, ext block,
                    # tiering split, age) + the rebuild daemon's trigger
                    # thresholds and recent swaps — the operator's view
                    # of "is churn outrunning the rebuild cadence"
                    # (docs/observability.md runbook)
                    "mips": self._mips_status(),
                    # continuous-batching scheduler state: per-engine
                    # queue depth + live ladder rung + shed count
                    # (serving/scheduler.py; docs/production.md
                    # "Serving fleet")
                    "scheduler": (self._batcher.stats()
                                  if self._batcher is not None else None),
                    # per-tenant block (deploys, queue depth, shed,
                    # staleness) — one status call answers "which
                    # tenant is hurting" (docs/production.md
                    # "Multi-tenant platform")
                    "tenants": self._tenant_status_locked(),
                }
            accept = request.headers.get("accept", "")
            if "text/html" in accept:
                rows = "".join(
                    f"<tr><th>{k}</th><td>{v}</td></tr>" for k, v in info.items()
                )
                return Response(
                    200,
                    body=(
                        "<html><head><title>PredictionIO-TPU Server</title>"
                        f"</head><body><h1>Engine is deployed and running.</h1>"
                        f"<table>{rows}</table></body></html>"
                    ).encode(),
                    content_type="text/html; charset=UTF-8",
                )
            return Response(200, info)

        @r.post("/queries.json")
        async def queries(request: Request) -> Response:
            from incubator_predictionio_tpu.utils.http import sync

            try:
                # access-key auth (serving/tenancy.py): the same
                # accessKey grammar as the event server, mapped to a
                # tenant. Empty registry = single-tenant compatibility
                # mode (unauthenticated, tenant "default"); unknown or
                # disabled keys raise 401 here
                tenant = tenancy.get_registry().authenticate(request)
                if self._batcher is not None:
                    # priority orders only the scheduler's SHED decision
                    # (higher survives an overload longer) — admitted
                    # requests stay FIFO; malformed values mean 0
                    try:
                        prio = int(request.headers.get(
                            "x-pio-priority", "0"))
                    except ValueError:
                        prio = 0
                    # a future of this loop: the dispatcher hands a
                    # whole batch's answers over in one call into it
                    fut = self._batcher.submit(
                        request.body, priority=prio,
                        engine=self.config.engine_id, tenant=tenant,
                        loop=asyncio.get_running_loop())
                    result = await fut
                    t_resolved = getattr(fut, "resolved_at", None)
                    if t_resolved is not None:
                        # this dispatch's sample: handed over on the
                        # dispatcher's thread → resumed here, on the
                        # loop's; inside the client's latency and in no
                        # other series. It stands for the dispatch, so no
                        # one request's trace is its exemplar
                        lag = max(time.perf_counter() - t_resolved, 0.0)
                        token = obs_trace.set_current(None)
                        try:
                            _REPLY_LAG.observe(lag)
                        finally:
                            obs_trace.reset_current(token)
                else:
                    result = await sync(self._handle_query, request.body,
                                        tenant)
            except HttpError as e:
                # the depth signal matters MOST on a shed: without it
                # the front door would keep the overloaded worker's
                # last (pre-overload) low reading and keep routing to
                # it (serving/frontdoor.py placement)
                if self._batcher is not None:
                    e.headers.setdefault("X-PIO-Queue-Depth",
                                         str(self._batcher.depth()))
                raise
            except (ValueError, KeyError) as e:
                return Response(400, {"message": str(e)})
            # queue-depth piggyback: the front door's placement signal,
            # refreshed for free on every response instead of waiting
            # for its next /metrics scrape (serving/frontdoor.py)
            depth_headers = (
                {"X-PIO-Queue-Depth": str(self._batcher.depth())}
                if self._batcher is not None else {})
            if isinstance(result, (bytes, bytearray)):
                # batch_serve_json fast path: body already rendered
                return Response(200, body=bytes(result),
                                headers=depth_headers)
            return Response(200, result, headers=depth_headers)

        @r.get("/ready")
        def ready(request: Request) -> Response:
            # getattr: harnesses build servers via __new__
            if getattr(self, "_ready", False):
                return Response(200, {"ready": True})
            return Response(503, {"ready": False,
                                  "message": "serving warm-up running"})

        @r.post("/reload")
        def reload(request: Request) -> Response:
            self._check_server_key(request)
            # double-buffered: new models warm (compiles + host mirrors,
            # shapes may differ — catalog size, rank) BEFORE the swap;
            # the old models serve every query until then. Serialized so
            # overlapping reloads cannot swap instances out of order.
            # ?tenant=X scopes the refresh to one co-resident deploy —
            # every other tenant keeps serving through it.
            tenant = request.query.get("tenant") or None
            with self._reload_lock:
                self.load_models(warm_before_swap=True, tenant=tenant)
            self._sync_tenant_policy()
            return Response(200, {
                "message": (f"Reloaded tenant {tenant}." if tenant
                            else "Reloaded.")})

        @r.post("/knobs")
        def post_knobs(request: Request) -> Response:
            # the worker half of the audited knob seam (obs/knobs.py):
            # the knob controller's front-door fan-out lands here with
            # the decision's trace headers. Every registered knob is a
            # call-time env read, so writing the env + one scheduler
            # refresh applies the vector without restart or drain. The
            # unaudited-knob-write lint rule sanctions knob env writes
            # in exactly this route (and KnobController._apply).
            self._check_server_key(request)
            from incubator_predictionio_tpu.obs import knobs as obs_knobs

            try:
                payload = json.loads(request.body or b"{}")
                values = payload.get("values") or {}
                items = {str(k): int(v) for k, v in values.items()}
            except (ValueError, TypeError, AttributeError) as e:
                return Response(400, {"message": f"bad knob body: {e}"})
            unknown = sorted(set(items) - obs_knobs.KNOB_ENV_VARS)
            if unknown:
                # reject the WHOLE vector: a partial apply would leave
                # the fleet on a vector no decision record describes
                return Response(400, {
                    "message": "unregistered knob env vars",
                    "unknown": unknown,
                })
            applied = {}
            for env, v in sorted(items.items()):
                os.environ[env] = str(v)
                applied[env] = v
            scheduler = (self._batcher.apply_knobs()
                         if self._batcher is not None else None)
            return Response(200, {"applied": applied,
                                  "scheduler": scheduler})

        @r.post("/stop")
        def stop_route(request: Request) -> Response:
            self._check_server_key(request)
            # daemonized: if the process is torn down some other way
            # first, a pending non-daemon timer would block exit
            timer = threading.Timer(0.2, self.stop)
            timer.daemon = True
            timer.start()
            return Response(200, {"message": "Shutting down."})

        @r.get("/plugins.json")
        def plugins_list(request: Request) -> Response:
            return Response(200, {
                "plugins": {
                    "outputblockers": {
                        n: {"name": n}
                        for n in self.plugin_context.output_blockers
                    },
                    "outputsniffers": {
                        n: {"name": n}
                        for n in self.plugin_context.output_sniffers
                    },
                }
            })

        @r.get("/plugins/{tail...}")
        def plugins_rest(request: Request) -> Response:
            parts = request.path_params["tail"].split("/")
            plugin = self.plugin_context.plugin(parts[0])
            if plugin is None:
                return Response(404, {"message": "Not Found"})
            return Response(
                200, plugin.handle_rest("/".join(parts[1:]), dict(request.query))
            )

        add_metrics_route(r)
        # GET /recorder: pre-breach metric history on the worker itself —
        # the admin's incident capture pulls this (docs/observability.md
        # "Flight recorder & incidents")
        add_recorder_route(r)
        return r

    # -- lifecycle ----------------------------------------------------------
    def undeploy_existing(self) -> None:
        """Stop any engine server already deployed at this address before
        binding (MasterActor.undeploy, CreateServer.scala:283-308): 200 →
        old deployment stopped; connection refused → nothing there; any
        other response → a foreign process owns the port (bind-retry will
        surface the conflict). The scheme follows this server's own TLS
        config (a stale deployment shares server.conf), and the key falls
        back to server.conf like /stop auth itself does."""
        if self.config.port == 0:
            return  # ephemeral port: nothing can be squatting on it
        ip = self.config.ip if self.config.ip != "0.0.0.0" else "127.0.0.1"
        key = self.config.server_key
        if key is None and self._conf_server_key is not None:
            key = self._conf_server_key.key
        scheme = "https" if self.http.ssl_context is not None else "http"
        try:
            status = _stop_request(ip, self.config.port, key, scheme=scheme)
            if status == 200:
                logger.info(
                    "Undeployed existing engine server at %s:%d",
                    ip, self.config.port)
                time.sleep(0.5)  # give the old process time to unbind
            else:
                logger.error(
                    "Another process is using %s:%d (HTTP %d on /stop). "
                    "Unable to undeploy.", ip, self.config.port, status)
        except ConnectionRefusedError:
            logger.debug("Nothing at %s:%d", ip, self.config.port)
        except urllib.error.URLError as e:
            if isinstance(e.reason, ConnectionRefusedError):
                logger.debug("Nothing at %s:%d", ip, self.config.port)
            else:
                # something answered the socket but not the protocol
                # (hung process, TLS mismatch, timeout) — that is NOT
                # "nothing there"; say so before bind-retry fights it
                logger.warning(
                    "A process at %s:%d did not respond properly to "
                    "/stop (%s); unable to undeploy.",
                    ip, self.config.port, e.reason)
        except Exception as e:
            logger.warning(
                "A process at %s:%d did not respond properly to /stop "
                "(%s); unable to undeploy.", ip, self.config.port, e)

    def _warm_models(self, algorithms, models) -> None:
        """Warm every algorithm's serving dispatches (compiles + host
        mirrors). One copy of the max_batch rule and the per-algo
        except-log-continue contract, shared by the async startup warmup
        and the pre-swap /reload warmup. Failures are logged, never
        fatal: warmup is an optimization, the query path compiles on
        demand regardless."""
        # a disabled micro-batcher means live traffic never reaches the
        # batched dispatch — don't compile it
        max_batch = self.config.micro_batch if self._batcher is not None else 0
        for algo, model in zip(algorithms, models):
            try:
                algo.warmup(model, max_batch=max_batch)
            except Exception:
                logger.exception(
                    "serving warmup failed for %s (first queries will "
                    "compile on demand)", type(algo).__name__)

    def _warmup_async(self) -> None:
        """Pre-compile serving dispatches on a daemon thread AFTER the
        server binds — the first real query otherwise pays the XLA compile
        (seconds on TPU). The thread waits on the HTTP server's started
        event so warmup tracing never delays the bind (the foreground
        serve_forever path spawns this before the loop starts)."""
        algorithms, models = self.algorithms, self.models
        heartbeat = self._heartbeat

        def run() -> None:
            try:
                if not self.http.wait_started(60.0):
                    logger.warning(
                        "serving warmup skipped: server did not bind "
                        "within 60s (queries will compile on demand if it "
                        "ever does)")
                    return
                t0 = time.perf_counter()
                self._warm_models(algorithms, models)
                logger.info("serving warmup done in %.1fs",
                            time.perf_counter() - t0)
            finally:
                self._ready = True
                if heartbeat is not None:
                    # stall lines from here on are the serving's own
                    heartbeat.ready = True

        threading.Thread(target=run, daemon=True,
                         name="pio-serving-warmup").start()

    def _watch_gc(self, loop: Optional[asyncio.AbstractEventLoop] = None
                  ) -> None:
        """Serving starts: from here on until stop(), book the
        collector's pauses (pio_gc_pause_seconds, the gc.pause
        annotation) and the process's stalls (obs/trace's stall watch,
        with a heartbeat on ``loop``, this server's event loop)."""
        with self._lock:
            held = getattr(self, "_gc_hook_held", False)
            self._gc_hook_held = True
        if not held:
            obs_trace.acquire_gc_hook()
            obs_trace.acquire_stall_watch()
            loop = loop or self.http._loop
            self._heartbeat = (obs_trace.watch_loop(loop)
                               if loop is not None else None)

    def start_background(self) -> int:
        self.load_models()
        self.undeploy_existing()
        port = self.http.start_background()
        self._watch_gc()
        self._warmup_async()
        logger.info("PredictionServer started on %s:%d", self.config.ip, port)
        return port

    async def serve_forever(self) -> None:
        self.load_models()
        self.undeploy_existing()
        self._watch_gc(asyncio.get_running_loop())
        self._warmup_async()
        await self.http.serve_forever()

    def stop(self) -> None:
        if self._batcher is not None:
            self._batcher.stop()
        with self._lock:
            held = getattr(self, "_mips_daemon_held", False)
            self._mips_daemon_held = False
            gc_held = getattr(self, "_gc_hook_held", False)
            self._gc_hook_held = False
        if gc_held:
            obs_trace.release_gc_hook()
            obs_trace.release_stall_watch()
            if self._heartbeat is not None:
                self._heartbeat.cancel()
        if held:
            try:
                from incubator_predictionio_tpu.ops import mips_daemon

                mips_daemon.release()
            except Exception:
                logger.exception("mips rebuild daemon stop failed")
        for ov in getattr(self, "_speed_overlays", []):
            if ov is None:
                continue
            try:
                ov.stop()
            except Exception:
                logger.exception("speed overlay stop failed")
        self._feedback_poster.stop()
        self._log_poster.stop()
        self.http.stop()


def _stop_request(ip: str, port: int, server_key: Optional[str],
                  scheme: str = "http", timeout: float = 5.0) -> int:
    """POST /stop → HTTP status (one shared implementation for the CLI
    undeploy verb and undeploy-before-deploy). Raises on connection
    failure. https uses an unverified context (the reference's
    allowUnsafeSSL — self-signed server.conf material is the norm)."""
    import ssl as ssl_mod
    from urllib.parse import quote

    url = f"{scheme}://{ip}:{port}/stop"
    if server_key:
        url += f"?accessKey={quote(server_key, safe='')}"
    ctx = ssl_mod._create_unverified_context() if scheme == "https" else None
    req = urllib.request.Request(url, method="POST", data=b"")
    try:
        with urllib.request.urlopen(req, timeout=timeout, context=ctx) as resp:
            return resp.status
    except urllib.error.HTTPError as e:
        return e.code


def undeploy(ip: str, port: int, server_key: Optional[str] = None,
             scheme: str = "http") -> bool:
    """POST /stop to a running server (commands/Engine.undeploy:341)."""
    try:
        return _stop_request(ip, port, server_key, scheme=scheme) == 200
    except Exception:
        return False
