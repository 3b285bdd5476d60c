"""Worker program for the fleet tests.

Run as a REAL separate process by tests/test_federation.py and
tests/test_recorder.py (tests/test_frontdoor.py imports its chaos hooks):

- ``--mode metrics``: an HttpServer exposing ``GET /metrics`` from its
  own process registry, with a planted query-latency histogram and
  queue-depth gauge — one "serving worker" for the admin's
  ``GET /federate`` to scrape.
- ``--mode storage``: a memory-backed StorageServer with span logging
  enabled — the downstream hop of the cross-process trace test: the
  parent's event server forwards ``X-PIO-Trace-Id``/``X-PIO-Parent-
  Span`` on its storage RPCs, and THIS process's ``pio.trace`` span
  lines (on stderr) must link under the parent's spans.
- ``--mode serve``: a full PredictionServer over a planted ALS model
  (random factors, synthetic catalog), serving ``/queries.json``
  through the continuous-batching scheduler (serving/scheduler.py) with
  the pow2 ladder pre-warmed before the port is announced — one worker
  of a fleet behind ``serving.FrontDoor``. ``/metrics`` on the same
  port exposes ``pio_serve_batch_size`` / ``pio_serve_shed_total`` /
  ``pio_serve_compile_cache_size``, and ``POST /reload`` hot-swaps to a
  freshly planted
  model through the real warm-before-swap route (what the front door's
  rolling reload drives).

``--compile-cache`` enables the persistent XLA compile cache before any
jax work, at the FLEET-SHARED directory utils/compile_cache.py resolves
(``JAX_COMPILATION_CACHE_DIR`` if the parent exports one, else the
in-checkout default), so a joining worker pre-warms its pow2 ladder from
disk instead of paying the cold compile wall.

``--chaos SPEC`` arms fault injection (comma-separated; serve mode):

- ``kill-after=S``   — hard-exit the process S seconds after serving
  starts (the in-flight-connection-reset class a crashed worker causes)
- ``stall-after=S``  — after S seconds every dispatch wedges (the
  accepted-but-never-answers class: queue grows, callers time out)
- ``latency-spike=MS:P`` — each dispatch pays +MS ms with probability P
  (tail-latency injection)
- ``refuse-after=S`` — close the listener after S seconds (new
  connections refused; already-open keep-alives keep serving)

Prints ``PORT <n> WARM_S <seconds>`` on stdout once bound (serve mode:
once WARM; WARM_S is the ladder warmup wall, cold or from the compile
cache), then serves until stdin closes
(the parent owns the lifetime; no signals needed).
"""

import argparse
import sys


def _parse_chaos(spec: str) -> dict:
    """``--chaos`` grammar → {kill_after_s, stall_after_s, refuse_after_s,
    latency_ms, latency_prob} (absent hooks None)."""
    out = {"kill_after_s": None, "stall_after_s": None,
           "refuse_after_s": None, "latency_ms": None,
           "latency_prob": None}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, value = part.partition("=")
        if name == "kill-after":
            out["kill_after_s"] = float(value)
        elif name == "stall-after":
            out["stall_after_s"] = float(value)
        elif name == "refuse-after":
            out["refuse_after_s"] = float(value)
        elif name == "latency-spike":
            ms, _, prob = value.partition(":")
            out["latency_ms"] = float(ms)
            out["latency_prob"] = float(prob) if prob else 1.0
        else:
            raise ValueError(f"unknown chaos hook {name!r}")
    return out


def _chaos_wrap(handle, chaos: dict, rng, clock):
    """Wrap the scheduler's handle_batch with the dispatch-level chaos
    hooks (stall / latency-spike). ``clock()`` is seconds since serving
    started; process-level hooks (kill/refuse) arm in _serve_worker."""
    import time as _time

    stall_after = chaos.get("stall_after_s")
    latency_ms = chaos.get("latency_ms")
    latency_prob = chaos.get("latency_prob") or 0.0

    def wrapped(bodies, engine, tenant):
        if stall_after is not None and clock() >= stall_after:
            # wedged worker: accepted the work, never answers — the
            # front door's attempt timeout is what rescues the query
            _time.sleep(3600.0)
        if latency_ms is not None and rng.random() < latency_prob:
            _time.sleep(latency_ms / 1000.0)
        return handle(bodies, engine, tenant)

    return wrapped


def _serve_worker(args) -> tuple:
    """Planted-model serving worker → (bound port, ladder warmup wall
    seconds). The port is announced only after warmup: a worker is not
    IN the fleet until it can serve without compiling."""
    import threading
    import time

    import numpy as np

    import jax.numpy as jnp

    from incubator_predictionio_tpu.data.bimap import BiMap
    from incubator_predictionio_tpu.data.storage import EngineInstance
    from incubator_predictionio_tpu.models.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        ALSModel,
        Query,
        RecommendationServing,
    )
    from incubator_predictionio_tpu.servers.plugins import PluginContext
    from incubator_predictionio_tpu.servers.prediction_server import (
        PredictionServer,
        ServerConfig,
        _AsyncPoster,
    )
    from incubator_predictionio_tpu.serving.scheduler import BatchScheduler
    from incubator_predictionio_tpu.utils.http import HttpServer
    from incubator_predictionio_tpu.utils.times import now_utc
    from incubator_predictionio_tpu.workflow.workflow import (
        make_runtime_context,
    )

    rng = np.random.default_rng(args.seed)
    n_users, n_items, rank = args.users, args.items, args.rank

    def plant_model(seed: int) -> ALSModel:
        r = np.random.default_rng(seed)
        return ALSModel(
            user_factors=jnp.asarray(
                r.normal(0, 0.3, (n_users, rank)).astype(np.float32)),
            item_factors=jnp.asarray(
                r.normal(0, 0.3, (n_items, rank)).astype(np.float32)),
            user_bimap=BiMap({f"u{i}": i for i in range(n_users)}),
            item_bimap=BiMap({f"i{i}": i for i in range(n_items)}),
            item_years={}, item_categories={},
        )

    model = plant_model(args.seed)
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=rank))
    now = now_utc()
    server = PredictionServer.__new__(PredictionServer)
    # direct state injection: this worker exercises the serving plane,
    # not checkpoint restore
    server.engine = None
    server.config = ServerConfig(ip="127.0.0.1", port=0,
                                 micro_batch=args.max_batch)
    server.plugin_context = PluginContext()
    server.ctx = make_runtime_context(None)
    server._lock = threading.Lock()
    server._reload_lock = threading.Lock()
    server.engine_instance = EngineInstance(
        id="fleet", status="COMPLETED", start_time=now, end_time=now,
        engine_id="fleet", engine_version="1", engine_variant="fleet",
        engine_factory="fleet")
    server.engine_params = None
    server.algorithms = [algo]
    server.serving = RecommendationServing()
    server.models = [model]
    server.start_time = now
    server.request_count = 0
    server.avg_serving_sec = 0.0
    server.last_serving_sec = 0.0
    server.max_batch_served = 0
    server._conf_server_key = None
    server.http = HttpServer(server._build_router(), "127.0.0.1", 0,
                             name="prediction")
    server._speed_overlays = []
    server._deploys = {}
    handle = server._handle_batch
    if args.dispatch_floor_ms > 0:
        # CPU-sim stand-in for an accelerator's fixed per-dispatch wall
        # (compile-cache lookup + launch + result fetch — on a real TPU
        # this floor exists regardless of batch width, and it is WHY
        # fusing a deeper queue into one dispatch wins): pad every
        # dispatch to the floor. time.sleep releases the GIL, so the
        # HTTP plane keeps admitting — queue depth builds exactly as it
        # would behind a busy device.
        import time as _time

        floor_s = args.dispatch_floor_ms / 1000.0
        inner = server._handle_batch

        def handle(bodies, engine, tenant):
            t0 = _time.perf_counter()
            out = inner(bodies, engine, tenant)
            left = floor_s - (_time.perf_counter() - t0)
            if left > 0:
                _time.sleep(left)
            return out

    chaos = _parse_chaos(args.chaos)
    serve_t0 = [None]

    def chaos_clock() -> float:
        return 0.0 if serve_t0[0] is None else \
            time.monotonic() - serve_t0[0]

    if chaos["stall_after_s"] is not None or chaos["latency_ms"] is not None:
        handle = _chaos_wrap(handle, chaos,
                             np.random.default_rng(args.seed + 7),
                             chaos_clock)

    from incubator_predictionio_tpu.servers import (
        prediction_server as ps_mod,
    )
    from incubator_predictionio_tpu.serving import tenancy

    server._batcher = BatchScheduler(
        handle, server.config.micro_batch,
        workers=server.config.serve_workers,
        # same live per-tenant p99 feed the real PredictionServer
        # wires in (one positional param → the scheduler slices the
        # SLO signal by tenant)
        p99_fn=lambda tenant: ps_mod._QUERY_LATENCY.labels(
            tenant=tenancy.get_registry().label(tenant)).quantile(0.99))
    # PIO_TENANTS (set in the worker's env by its parent) → weighted-
    # fair weights + admission quotas pushed into the scheduler, same
    # seam the real server syncs after construction and reloads
    server._sync_tenant_policy()
    # __new__-built server skipped __init__: wire the per-tenant
    # pio_serve_queue_depth scrape collector onto OUR batcher
    server.register_queue_collector()
    server._feedback_poster = _AsyncPoster("feedback")
    server._log_poster = _AsyncPoster("log", workers=1)

    # POST /reload support: the real route runs self.load_models(
    # warm_before_swap=True) under _reload_lock — the planted stand-in
    # re-plants fresh factors, warms the NEW model's ladder while the
    # old one keeps serving (compile-cache hits: same shapes), then
    # swaps under the serving lock. Bumped end_time resets staleness,
    # exactly like a real instance swap.
    reload_seq = [0]

    def load_models(warm_before_swap: bool = False,
                    tenant: str = None) -> None:
        reload_seq[0] += 1
        new_model = plant_model(args.seed + 1000 + reload_seq[0])
        if warm_before_swap:
            algo.warmup(new_model, max_batch=server.config.micro_batch)
        instance = EngineInstance(
            id=f"fleet-r{reload_seq[0]}", status="COMPLETED",
            start_time=now_utc(), end_time=now_utc(),
            engine_id="fleet", engine_version="1",
            engine_variant="fleet", engine_factory="fleet")
        if tenant is not None and tenant != tenancy.DEFAULT_TENANT:
            # tenant-scoped reload: swap ONLY this tenant's co-resident
            # deploy — the shared/default deploy (and every other
            # tenant riding it) keeps serving the old model untouched,
            # (tests/test_tenancy.py's tenant-scoped reload holds the
            # server's side of this)
            if tenancy.get_registry().get(tenant) is None:
                from incubator_predictionio_tpu.utils.http import (
                    HttpError,
                )

                raise HttpError(404, f"Unknown tenant {tenant!r}.")
            with server._lock:
                server._deploys[tenant] = {
                    "engine_instance": instance,
                    "engine_params": None,
                    "algorithms": [algo],
                    "serving": server.serving,
                    "models": [new_model],
                }
            return
        with server._lock:
            server.models = [new_model]
            server.engine_instance = instance

    server.load_models = load_models

    # the staleness gauge the fleet /slo (and the freshness controller
    # behind it) evaluates: the real PredictionServer registers this
    # collector in __init__, which the __new__ state-injection path
    # above bypasses — re-plant it here so a fleet-worker /metrics
    # scrape reports the served instance's age, and the planted
    # /reload's end_time bump resets it exactly like a real hot swap
    from incubator_predictionio_tpu.obs import metrics as obs_metrics
    from incubator_predictionio_tpu.utils.times import ensure_aware

    staleness_gauge = obs_metrics.REGISTRY.gauge(
        "pio_model_staleness_seconds",
        "seconds since the served engine instance finished training "
        "(scrape-time snapshot)")

    def _collect_staleness() -> None:
        with server._lock:
            instance = server.engine_instance
        if instance is not None:
            staleness_gauge.set(max(
                (now_utc() - ensure_aware(instance.end_time))
                .total_seconds(), 0.0))

    obs_metrics.REGISTRY.register_collector(
        "fleet_worker_staleness", _collect_staleness)

    # pre-warm EVERY pow2 ladder rung (plus the singleton path) so the
    # load ramp measures serving, not XLA compiles — the zero-steady-
    # state-recompile contract starts from here. With a shared
    # persistent compile cache (--compile-cache) the rungs load from
    # disk and this wall collapses — the measured WARM_S delta.
    t_warm = time.perf_counter()
    algo.warmup(model, max_batch=server.config.micro_batch)
    warm_s = time.perf_counter() - t_warm
    port = server.http.start_background()
    serve_t0[0] = time.monotonic()
    # daemon timers: a worker torn down (stdin closed) before its
    # chaos fires must still exit promptly — a pending non-daemon
    # Timer would pin the process until the timer ran
    if chaos["kill_after_s"] is not None:
        import os as _os

        t = threading.Timer(chaos["kill_after_s"],
                            lambda: _os._exit(137))
        t.daemon = True
        t.start()
    if chaos["refuse_after_s"] is not None:
        t = threading.Timer(chaos["refuse_after_s"], server.http.stop)
        t.daemon = True
        t.start()
    return port, warm_s


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("metrics", "storage", "serve"),
                    required=True)
    ap.add_argument("--observe", default="",
                    help="comma-separated seconds planted into "
                         "pio_query_latency_seconds (metrics mode)")
    ap.add_argument("--depth", type=float, default=0.0,
                    help="pio_serve_queue_depth value (metrics mode)")
    ap.add_argument("--staleness", type=float, default=None,
                    help="pio_model_staleness_seconds value "
                         "(metrics mode)")
    ap.add_argument("--users", type=int, default=2000)
    ap.add_argument("--items", type=int, default=1000)
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=512,
                    help="scheduler ladder cap (serve mode)")
    ap.add_argument("--dispatch-floor-ms", type=float, default=0.0,
                    help="pad every scheduler dispatch to this wall — "
                         "the CPU sim's stand-in for an accelerator's "
                         "fixed per-dispatch cost (serve mode)")
    ap.add_argument("--compile-cache", action="store_true",
                    help="enable the persistent XLA compile cache at "
                         "the fleet-shared directory utils/"
                         "compile_cache.py resolves (serve mode join "
                         "pre-warm)")
    ap.add_argument("--chaos", default="",
                    help="fault injection: kill-after=S, stall-after=S, "
                         "latency-spike=MS:P, refuse-after=S "
                         "(comma-separated; serve mode)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.compile_cache:
        # before any jax work: the join pre-warm reads compiled rungs
        # from the fleet-shared directory instead of re-compiling
        from incubator_predictionio_tpu.utils import compile_cache

        compile_cache.enable()

    from incubator_predictionio_tpu.obs import metrics as obs_metrics
    from incubator_predictionio_tpu.obs import trace as obs_trace

    obs_trace.enable_span_logging()

    srv = None
    warm_s = 0.0
    if args.mode == "metrics":
        from incubator_predictionio_tpu.obs.http import (
            add_metrics_route,
            add_recorder_route,
        )
        from incubator_predictionio_tpu.utils.http import (
            HttpServer,
            Router,
        )

        h = obs_metrics.REGISTRY.histogram(
            "pio_query_latency_seconds",
            "per-query serving wall")
        for raw in args.observe.split(","):
            raw = raw.strip()
            if raw:
                h.observe(float(raw))
        obs_metrics.REGISTRY.gauge(
            "pio_serve_queue_depth", "micro-batcher backlog").set(
            args.depth)
        if args.staleness is not None:
            obs_metrics.REGISTRY.gauge(
                "pio_model_staleness_seconds",
                "age of the served engine instance").set(args.staleness)
        r = Router()
        add_metrics_route(r)
        add_recorder_route(r)
        srv = HttpServer(r, "127.0.0.1", 0, name="worker")
        port = srv.start_background()
    elif args.mode == "serve":
        port, warm_s = _serve_worker(args)
    else:
        from incubator_predictionio_tpu.data.storage import (
            StorageClientConfig,
        )
        from incubator_predictionio_tpu.data.storage import (
            memory as memory_backend,
        )
        from incubator_predictionio_tpu.data.storage.server import (
            StorageServer,
        )

        config = StorageClientConfig(test=True, properties={})
        client = memory_backend.StorageClient(config)
        srv = StorageServer(memory_backend, client, config,
                            host="127.0.0.1", port=0)
        port = srv.start_background()

    # extra tokens ride behind the port: existing parsers split()[1]
    print(f"PORT {port} WARM_S {warm_s:.3f}", flush=True)
    # serve until the parent closes our stdin (its process exit does)
    sys.stdin.read()
    if srv is not None:
        srv.stop()


if __name__ == "__main__":
    main()
