#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpu-pio still starts on the chip.

Drives the system's main path ONCE through the entry points a user calls,
at the full width of the model the repo's north star names (the
recommendation template at MovieLens-20M shape: 138,493 users × 26,744
items × 20,000,000 ratings, rank 128), on one TPU chip, in ONE process
(a chip belongs to one process at a time):

  device   fail unless jax finds a TPU; print what it found
  native   build libpio_native.so from the sources as committed and load it
  train    generate the ratings from --seed, `pio app new`, bulk-import them
           the way `pio import` does, `pio train` (3 sweeps, widths uncut),
           then check a COMPLETED instance, finite factors and a train RMSE
           under the predict-the-mean RMSE of the data
  serve    `pio deploy` that instance on a real socket, POST /queries.json
           (sequential, then one concurrent burst of 64), compare every
           answer with a plain float32 numpy scoring of the same factors,
           and fail unless queries were scored on the device
  kernels  run each Pallas kernel a route can select, compiled, at real
           widths, against its jax.numpy reference

``--chips 4`` runs ONLY the multi-chip phase and what it is compared with:
placed ALS (`pio train --model-parallelism 4`) against a one-chip
`pio train` on the same data, `sharded_top_k` against single-device top-k,
and a check that all four devices hold shards.

``--rehearse`` is the sandbox rehearsal (on-chip-measurement guide §2): the
same control flow at a tiny shape on the CPU backend with Pallas in
interpret mode (four virtual devices with ``--chips 4``). It reports the
platform it really ran on. WITHOUT it, finding no TPU is a failure.

The script exits non-zero at the first failed phase — nothing is caught
and continued. Every wall it prints is a *smoke wall*: a sanity reading
from one cold run, never a result. Its last line of standard output is
one JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

import argparse
import concurrent.futures
import contextlib
import json
import os
import shutil
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

#: MovieLens-20M's shape (ratings.csv: 138,493 users, 26,744 movies,
#: 20,000,263 ratings), the README's "north star". Widths (users,
#: items, rank) are never cut; --ratings may cut the scale, and says so.
ML20M = {"users": 138_493, "items": 26_744, "ratings": 20_000_000}
#: the rehearsal's tiny shape (rank stays 128: widths are not cut)
TINY = {"users": 1_500, "items": 400, "ratings": 60_000}
RANK = 128
SWEEPS = 3          # 2 bf16 sweeps + 1 f32 polish: both train programs run
BF16_SWEEPS = 2
L2 = 0.03
PLANT_RANK = 16
NOISE_SIGMA = 0.35
APP_NAME = "SmokeApp"
BURST = 64


def say(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str):
    say(f"== {name}")
    t0 = time.perf_counter()
    yield
    say(f"== {name}: passed (smoke wall {time.perf_counter() - t0:.1f} s)")


def check(cond: bool, what: str) -> None:
    """A failed check ends the run (no phase is caught and continued)."""
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# ---------------------------------------------------------------------------
# environment — set BEFORE jax or the package is imported
# ---------------------------------------------------------------------------

def setup_environment(args, out_dir: str) -> None:
    home = os.path.join(out_dir, "pio_home")
    env = {
        # never ~/.pio_tpu: everything this run writes lives under out_dir
        "PIO_HOME": home,
        "PIO_STORAGE_SOURCES_LOG_TYPE": "cpplog",
        "PIO_STORAGE_SOURCES_LOG_PATH": os.path.join(home, "eventlog"),
        "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQL_PATH": os.path.join(home, "store", "pio.db"),
        "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_FS_PATH": os.path.join(home, "models"),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "pio_meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio_event",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "LOG",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "pio_model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
        "TPU_LOG_DIR": "disabled",
    }
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            env["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            ).strip()
        # the CPU stand-ins for what the chip does by itself: the ALS
        # kernel route in interpret mode, and the device serving path
        # for a model small enough for the host mirror
        env["PIO_ALS_KERNEL"] = "on"
        env["PIO_HOST_SERVE_MAX_ELEMS"] = "0"
    os.environ.update(env)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(args) -> dict:
    import jax
    import jaxlib

    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # the wheel may be absent where no TPU is either
        libtpu = "not installed"
    from incubator_predictionio_tpu.utils import compile_cache

    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    compile_cache.enable()
    say(f"device: platform={dev['platform']} kind={dev['kind']!r} "
        f"count={dev['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    say(f"compile cache: {compile_cache.cache_dir()} "
        f"({'JAX_COMPILATION_CACHE_DIR' if from_env else 'the in-checkout default'})")
    if not args.rehearse:
        check(dev["platform"] == "tpu",
              f"no TPU: jax.devices()[0].platform == {dev['platform']!r} "
              "(use --rehearse for the CPU rehearsal)")
    if args.chips > 1:
        check(dev["count"] == args.chips,
              f"--chips {args.chips} wants exactly that many devices, "
              f"jax reports {dev['count']}")
    return dev


def phase_native() -> None:
    from incubator_predictionio_tpu import native

    so = native.build(force=True)
    lib = native.load()
    check(lib is not None, "libpio_native.so did not build/load (a 20M-"
          "event import through the Python fallback is a hang)")
    say(f"native: built from {len(native._SOURCES)} sources → {so.name}")


def make_ratings(seed: int, shape: dict):
    """Planted rank-16 ratings with ML-20M-like power-law marginals →
    (users, items, ratings) int32/int32/float32, made in bulk from the
    seed. ratings = 3.5 + u·v + N(0, 0.35), so predict-the-mean scores
    an RMSE near 1.06 and a rank-128 fit can get far under it."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n_u, n_i, nnz = shape["users"], shape["items"], shape["ratings"]
    u_true = rng.normal(0, 1.0 / np.sqrt(PLANT_RANK),
                        (n_u, PLANT_RANK)).astype(np.float32)
    v_true = rng.normal(0, 1.0, (n_i, PLANT_RANK)).astype(np.float32)
    iw = (np.arange(n_i) + 1.0) ** -0.55
    items = rng.choice(n_i, nnz, p=iw / iw.sum()).astype(np.int32)
    uw = (np.arange(n_u) + 1.0) ** -0.3
    users = rng.choice(n_u, nnz, p=uw / uw.sum()).astype(np.int32)
    signal = np.einsum("nk,nk->n", u_true[users], v_true[items])
    ratings = (3.5 + signal + rng.normal(0, NOISE_SIGMA, nnz)).astype(
        np.float32)
    return users, items, ratings


def import_ratings(shape: dict, seed: int):
    """`pio app new` through the CLI, then the bulk import `pio import`
    performs for a uniform rating file (cli/commands.py import_events:
    Storage.get_events().import_interactions)."""
    import numpy as np

    from incubator_predictionio_tpu.cli.main import main as pio
    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.data.storage.base import (
        IdTable,
        Interactions,
    )

    t0 = time.perf_counter()
    users, items, ratings = make_ratings(seed, shape)
    gen_s = time.perf_counter() - t0
    check(pio(["app", "new", APP_NAME]) == 0, "`pio app new` failed")
    app = Storage.get_meta_data_apps().get_by_name(APP_NAME)
    inter = Interactions(
        user_idx=users, item_idx=items, values=ratings,
        user_ids=IdTable.from_list(
            [f"u{k}" for k in range(shape["users"])]),
        item_ids=IdTable.from_list(
            [f"i{k}" for k in range(shape["items"])]))
    t0 = time.perf_counter()
    n = Storage.get_events().import_interactions(
        inter, app.id, None, entity_type="user", target_entity_type="item",
        event_name="rate", value_prop="rating")
    check(n == len(users), f"import landed {n} of {len(users)} events")
    say(f"data: {shape['users']:,} users × {shape['items']:,} items × "
        f"{len(users):,} ratings from seed {seed} "
        f"(generate {gen_s:.1f} s, import {time.perf_counter() - t0:.1f} s"
        " — smoke walls)")
    mean = float(np.mean(ratings, dtype=np.float64))
    mean_rmse = float(np.sqrt(np.mean(
        (ratings.astype(np.float64) - mean) ** 2)))
    return users, items, ratings, mean_rmse


def write_engine(out_dir: str, name: str) -> str:
    """An engine directory holding the variant `pio train`/`pio deploy`
    read. Rank 128; 2 bf16 sweeps + 1 f32 polish."""
    engine_dir = os.path.join(out_dir, name)
    os.makedirs(engine_dir, exist_ok=True)
    path = os.path.join(engine_dir, "engine.json")
    with open(path, "w") as f:
        json.dump({
            "id": "default",
            "description": "chip_smoke: recommendation template, ML-20M "
                           "shape",
            "engineFactory": "incubator_predictionio_tpu.models."
                             "recommendation:RecommendationEngine",
            "datasource": {"params": {"appName": APP_NAME}},
            "algorithms": [{"name": "als", "params": {
                "rank": RANK, "numIterations": SWEEPS, "lambda": L2,
                "bf16Sweeps": BF16_SWEEPS}}],
        }, f, indent=2)
    return path


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling (its own
    monitoring events) — so a phase can say how much of its wall was
    compilation."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event in self._EVENTS:
            self.seconds += duration


def describe_als_route(n_users: int, n_items: int) -> str:
    """Which ALS solve path `pio train` resolves to in this process —
    the same selectors ops/als.py _mixed_run consults."""
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops import als

    warm = als._CG_WARMSTART
    if not als._kernel_enabled(False, warm=warm):
        return "XLA assembly (Gram batch in HBM + batched CG)"
    fused = als._fused_sides(n_users, n_items, False, warm, jnp.bfloat16,
                             RANK)
    rows = max(als._kernel_rows_default(), 1)
    return (f"two-stage Pallas kernel, {rows}-row layout, for buckets of "
            f"width >= {als._KERNEL_MIN_D} ({'warm' if warm else 'cold'} "
            "CG); XLA assembly for narrower buckets and split rows; "
            f"fused gather kernel user/item sweep = {fused}")


def run_pio_train(variant: str, seed: int, clock: CompileClock,
                  model_parallelism: int = 1):
    """`pio train` through the CLI's main(argv) → CoreWorkflow.run_train
    → the COMPLETED EngineInstance it left behind."""
    from incubator_predictionio_tpu.cli import commands
    from incubator_predictionio_tpu.cli.main import main as pio
    from incubator_predictionio_tpu.data.storage import Storage
    from incubator_predictionio_tpu.obs import metrics as obs_metrics

    secs = obs_metrics.REGISTRY.get("pio_device_seconds")
    ops = ("als_train", "als_fused", "als_sharded")
    before = {op: secs.labels(op=op).value for op in ops}
    compile0 = clock.seconds
    argv = ["train", "--variant", variant, "--seed", str(seed)]
    if model_parallelism > 1:
        argv += ["--model-parallelism", str(model_parallelism)]
    os.environ["PIO_PROFILE"] = "1"     # books the train dispatch's wall
    try:
        t0 = time.perf_counter()
        check(pio(argv) == 0, f"`pio {' '.join(argv)}` failed")
        wall = time.perf_counter() - t0
    finally:
        os.environ.pop("PIO_PROFILE", None)
    with open(variant) as f:
        doc = json.load(f)
    instance = Storage.get_meta_data_engine_instances().get_latest_completed(
        commands.engine_id_for_variant_path(variant, doc),
        "NOT_VERSIONED", "default")
    check(instance is not None and instance.status == "COMPLETED",
          "no COMPLETED engine instance after `pio train`")
    booked = {op: secs.labels(op=op).value - before[op] for op in ops}
    op = max(booked, key=booked.get)
    conf = instance.runtime_conf
    compile_s = clock.seconds - compile0

    def ph(name: str) -> float:
        return float(conf.get(f"phase.{name}_s", "nan"))

    say(f"train: instance {instance.id} COMPLETED; smoke walls: "
        f"total {wall:.1f} s = scan {ph('read'):.1f} + prep "
        f"{ph('prepare'):.1f} + train phase {ph('train.algo0'):.1f} + "
        f"checkpoint {ph('checkpoint'):.1f}; inside the train phase the "
        f"{SWEEPS}-sweep dispatch, compile included, took "
        f"{booked[op]:.1f} s (booked as op={op!r}); jax traced, lowered "
        f"and compiled for {compile_s:.1f} s over the whole `pio train`")
    return instance, op


def load_factors(instance_id: str):
    """The instance's factors and id maps as stored, host-side, without
    the serving stack."""
    import numpy as np

    import incubator_predictionio_tpu.models.recommendation  # noqa: F401
    from incubator_predictionio_tpu.workflow import CoreWorkflow

    model = CoreWorkflow.load_models(instance_id)[0]
    uf = np.asarray(model.user_factors, np.float32)
    vf = np.asarray(model.item_factors, np.float32)
    return model, uf, vf


def train_rmse(model, uf, vf, users, items, ratings) -> float:
    """RMSE of the stored factors over the generated ratings, in the
    model's own id space."""
    import numpy as np

    from incubator_predictionio_tpu.ops import als

    u_map = np.asarray([model.user_bimap.get(f"u{k}", -1)
                        for k in range(int(users.max()) + 1)])
    i_map = np.asarray([model.item_bimap.get(f"i{k}", -1)
                        for k in range(int(items.max()) + 1)])
    mu, mi = u_map[users], i_map[items]
    check(bool((mu >= 0).all() and (mi >= 0).all()),
          "a generated user/item has no row in the trained model")
    return als.rmse(als.ALSState(user_factors=uf, item_factors=vf),
                    mu, mi, ratings)


def phase_train(args, shape: dict, out_dir: str, clock: CompileClock):
    import numpy as np

    users, items, ratings, mean_rmse = import_ratings(shape, args.seed)
    variant = write_engine(out_dir, "engine")
    say(f"train: ALS solve path = "
        f"{describe_als_route(shape['users'], shape['items'])}")
    instance, op = run_pio_train(variant, args.seed, clock)
    from incubator_predictionio_tpu.ops import als

    want_op = ("als_fused" if als._kernel_enabled(
        False, warm=als._CG_WARMSTART) else "als_train")
    check(op == want_op, f"train booked op={op!r}, the route says "
          f"{want_op!r}")
    model, uf, vf = load_factors(instance.id)
    n_u, n_i = len(model.user_bimap), len(model.item_bimap)
    check(uf.shape == (n_u, RANK) and vf.shape == (n_i, RANK),
          f"factor shapes {uf.shape} / {vf.shape}")
    check(bool(np.isfinite(uf).all() and np.isfinite(vf).all()),
          "non-finite factors")
    rmse = train_rmse(model, uf, vf, users, items, ratings)
    say(f"train: factors {uf.shape} + {vf.shape} finite; train RMSE "
        f"{rmse:.4f} vs predict-the-mean RMSE {mean_rmse:.4f}")
    check(rmse < mean_rmse, f"train RMSE {rmse} is not under the "
          f"predict-the-mean RMSE {mean_rmse}")
    return variant, model, uf, vf


# -- serve -------------------------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body=None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read()


def scrape(base: str) -> dict:
    """The few series this script reads off the server's /metrics."""
    from incubator_predictionio_tpu.obs.expofmt import parse_exposition

    _status, text = http("GET", base + "/metrics")
    _types, samples = parse_exposition(text.decode())
    none = frozenset()
    return {
        "device_rows": samples.get(
            ("pio_serve_candidates_scanned_total",
             frozenset({("stage", "exhaustive")})), 0.0),
        "batches": samples.get(("pio_serve_batch_size_count", none), 0.0),
        "batched": samples.get(("pio_serve_batch_size_sum", none), 0.0),
        "compiled": samples.get(("pio_serve_compile_cache_size", none), 0.0),
    }


def reference_top_k(uf, vf, row: int, k: int):
    """Plain float32 numpy scoring of the same factors."""
    import numpy as np

    scores = vf @ uf[row]
    order = np.argsort(-scores, kind="stable")[:k]
    return order, scores[order]


def check_answer(doc: dict, model, uf, vf, user: str, k: int) -> float:
    import numpy as np

    got = doc["itemScores"]
    order, ref = reference_top_k(uf, vf, model.user_bimap[user], k)
    inv = model.item_bimap.inverse
    want_items = [inv[int(i)] for i in order]
    got_items = [g["item"] for g in got]
    check(got_items == want_items,
          f"query {user}: items {got_items} != numpy reference "
          f"{want_items}")
    rel = np.abs(np.asarray([g["score"] for g in got]) - ref) / np.maximum(
        np.abs(ref), 1e-6)
    check(float(rel.max()) <= 1e-3,
          f"query {user}: score rel err {rel.max():.2e} > 1e-3 "
          f"(got {[g['score'] for g in got]}, numpy {ref.tolist()})")
    return float(rel.max())


def phase_serve(variant: str, model, uf, vf) -> None:
    from incubator_predictionio_tpu.cli.main import main as pio
    from incubator_predictionio_tpu.ops import host_serving
    from incubator_predictionio_tpu.ops.topk import ladder_rungs
    from incubator_predictionio_tpu.serving.scheduler import ladder_cap
    from incubator_predictionio_tpu.utils.http import (
        RetryableError,
        RetryPolicy,
        parse_retry_after,
    )

    port = free_port()
    base = f"http://127.0.0.1:{port}"
    rc: list = []
    server = threading.Thread(
        target=lambda: rc.append(pio([
            "deploy", "--variant", variant, "--ip", "127.0.0.1",
            "--port", str(port)])),
        name="pio-deploy", daemon=True)
    t0 = time.perf_counter()
    server.start()
    try:
        while True:     # `pio deploy` loads the model, then binds
            check(server.is_alive(), f"`pio deploy` exited early (rc={rc})")
            check(time.perf_counter() - t0 < 600, "`pio deploy` never bound")
            try:
                http("GET", base + "/", timeout=5)
                break
            except OSError:
                time.sleep(0.5)
        say(f"serve: `pio deploy` answering on {base} after "
            f"{time.perf_counter() - t0:.1f} s (smoke wall)")
        # the server warms its serving programs AFTER it binds (the
        # singleton program, then the pow2 batch ladder) and has no
        # readiness signal: wait for the compiled-variant count. Queries
        # sent earlier do answer, but slowly (they fight the warm-up's
        # tracing for the GIL), and the scheduler, pricing its queue at
        # those walls, sheds a burst with 503s — seen on the chip
        want = 1 + len(ladder_rungs(ladder_cap()))
        while scrape(base)["compiled"] < want:
            check(time.perf_counter() - t0 < 900,
                  f"serving warm-up stalled below {want} compiled variants")
            time.sleep(0.5)
        say(f"serve: warm ({want} compiled serving variants: the "
            f"singleton program + batch rungs 1..{ladder_cap()}) "
            f"{time.perf_counter() - t0:.1f} s after start (smoke wall)")

        elems = uf.size + vf.size
        limit = host_serving.host_serve_limit()
        say(f"serve: dispatch+fetch round trip "
            f"{host_serving.dispatch_overhead_s() * 1e3:.3f} ms (smoke "
            f"wall) → host-mirror budget {limit:,} elements; the model has "
            f"{elems:,} → the code's rule sends queries to the "
            f"{'HOST MIRROR' if elems <= limit else 'DEVICE'}")
        before = scrape(base)
        names = list(model.user_bimap)
        k = 10
        worst = 0.0
        walls = []
        for user in (names[0], names[len(names) // 3], names[len(names) // 2],
                     names[-2], names[-1]):
            t1 = time.perf_counter()
            status, body = http("POST", base + "/queries.json",
                                {"user": user, "num": k})
            walls.append(time.perf_counter() - t1)
            check(status == 200, f"query {user}: HTTP {status}")
            worst = max(worst, check_answer(json.loads(body), model, uf, vf,
                                            user, k))
        mid = scrape(base)
        step = max(len(names) // BURST, 1)
        burst_users = [names[(7 + j * step) % len(names)]
                       for j in range(BURST)]

        shed = []
        retry = RetryPolicy(attempts=4, deadline_s=60.0)

        def one(user):
            # a shed (503 + Retry-After: the scheduler protecting its
            # serve SLO) is the server's contract, not a failure — a
            # client retries under the repo's one RetryPolicy. Counted
            # and printed; a query still shed after 4 tries raises
            def send():
                try:
                    return http("POST", base + "/queries.json",
                                {"user": user, "num": k})
                except urllib.error.HTTPError as e:
                    if e.code != 503:
                        raise
                    shed.append(user)
                    raise RetryableError(e, parse_retry_after(
                        e.headers.get("Retry-After")))

            return retry.call(send)

        t1 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(BURST) as pool:
            answers = list(pool.map(one, burst_users))
        burst_s = time.perf_counter() - t1
        for user, (status, body) in zip(burst_users, answers):
            check(status == 200, f"burst query {user}: HTTP {status}")
            worst = max(worst, check_answer(json.loads(body), model, uf, vf,
                                            user, k))
        after = scrape(base)
        n_items = vf.shape[0]
        seq_rows = mid["device_rows"] - before["device_rows"]
        burst_rows = after["device_rows"] - mid["device_rows"]
        say(f"serve: {len(walls)} sequential queries (smoke walls "
            f"{', '.join(f'{w * 1e3:.1f}' for w in walls)} ms) + a burst "
            f"of {BURST} in {burst_s * 1e3:.0f} ms: all {len(walls) + BURST}"
            f" answers equal the numpy reference (same items, worst score "
            f"rel err {worst:.1e})")
        say(f"serve: {len(shed)} burst request(s) were shed with 503 + "
            "Retry-After and retried")
        say(f"serve: the burst ran as "
            f"{int(after['batches'] - mid['batches'])} scheduler "
            f"dispatch(es) of {int(after['batched'] - mid['batched'])} "
            f"queries; device scored {int(seq_rows):,} item rows for the "
            f"sequential queries and {int(burst_rows):,} for the burst "
            f"(catalogue {n_items:,})")
        check(seq_rows >= n_items and burst_rows >= n_items,
              "no query was scored on the device: "
              "pio_serve_candidates_scanned_total{stage=exhaustive} did "
              "not move — the host mirror answered")
        say("serve: answered by the DEVICE")
    finally:
        if server.is_alive():
            pio(["undeploy", "--ip", "127.0.0.1", "--port", str(port)])
            server.join(30)
    check(not server.is_alive() and rc == [0],
          f"`pio deploy` did not exit cleanly on `pio undeploy` (rc={rc})")


# -- kernels -----------------------------------------------------------------

def phase_kernels(rehearse: bool) -> None:
    """Each Pallas family a route can select on this chip, executed once
    compiled at real widths (tiny and interpreted in the rehearsal) and
    compared with its jax.numpy reference. At ML-20M shape the top-k
    kernel (>= 500k items) and flash (S >= 8192) are not reached by the
    train/serve phases: this is the only place the chip runs them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from incubator_predictionio_tpu.ops import als
    from incubator_predictionio_tpu.ops import pallas_kernels as pk
    from incubator_predictionio_tpu.ops.attention import (
        dot_product_attention,
    )
    from incubator_predictionio_tpu.ops.topk import PALLAS_MIN_ITEMS

    interpret = bool(rehearse)
    hp = jax.lax.Precision.HIGHEST
    key = jax.random.key(0)

    def maxerr(a, b) -> float:
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    # 1. blocked top-k: the routed size (>= PALLAS_MIN_ITEMS, a block
    #    multiple) and the ML-20M catalogue
    sizes = (2_000, 16_384) if rehearse else (524_288, ML20M["items"])
    check(rehearse or sizes[0] >= PALLAS_MIN_ITEMS, "routed size moved")
    for n_items in sizes:
        kq, ki = jax.random.split(jax.random.fold_in(key, n_items))
        q = jax.random.normal(kq, (RANK,), jnp.float32)
        items = jax.random.normal(ki, (n_items, RANK), jnp.float32)
        out = pk.score_and_top_k_pallas(q, items, 10, block_items=8192,
                                        interpret=interpret)
        ref_s, ref_i = jax.lax.top_k(
            jnp.einsum("ik,k->i", items, q, precision=hp), 10)
        check(np.array_equal(np.asarray(out[1]).astype(np.int64),
                             np.asarray(ref_i)),
              f"top-k kernel at {n_items} items: indices differ")
        err = maxerr(out[0], ref_s)
        say(f"kernel pio_topk_tile {n_items:,}×{RANK} block 8192: same "
            f"top-10, max abs score err {err:.2e}")
        check(err <= 1e-3, "top-k kernel scores off")

    # 2. flash attention forward (+ the gradient through its custom VJP)
    s = 512 if rehearse else 8192
    qb, kb = pk.default_flash_blocks(s)
    kq, kk, kv = jax.random.split(jax.random.fold_in(key, s), 3)
    shape = (1, s, 8, 64)
    q, k_, v = (jax.random.normal(kx, shape, jnp.float32)
                for kx in (kq, kk, kv))
    out = pk.flash_attention(q, k_, v, q_block=qb, kv_block=kb,
                             interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = dot_product_attention(q, k_, v)
    err = maxerr(out, ref)
    say(f"kernel pio_flash_fwd S={s} 8 heads×64 blocks {qb}×{kb}: max abs "
        f"err vs dense softmax(QKᵀ)V {err:.2e}")
    check(err <= 2e-2, "flash forward off")
    grads = jax.grad(lambda a, b, c: jnp.sum(pk.flash_attention(
        a, b, c, q_block=qb, kv_block=kb, interpret=interpret)),
        argnums=(0, 1, 2))(q, k_, v)
    check(all(g.shape == shape and bool(jnp.isfinite(g).all())
              for g in grads), "flash gradient not finite")
    say(f"kernel pio_flash_fwd S={s}: jax.grad through the custom VJP "
        "finite")

    # 3. two-stage ALS bucket solve: both layouts, warm and cold, narrow
    #    and wide buckets, bf16 table (the bf16 sweeps) — and the f32
    #    table the polish sweep runs
    m = 400 if rehearse else ML20M["items"]
    rows_b = 24 if rehearse else 256
    kt, kc, kv_, km, kx = jax.random.split(jax.random.fold_in(key, 3), 5)
    table = 0.3 * jax.random.normal(kt, (m, RANK), jnp.float32)
    for d in (128, 1024):
        cols = jax.random.randint(kc, (rows_b, d), 0, m, jnp.int32)
        vals = 3.5 + jax.random.normal(kv_, (rows_b, d), jnp.float32)
        mask = (jax.random.uniform(km, (rows_b, d)) < 0.7).astype(
            jnp.float32)
        x0 = 0.1 * jax.random.normal(kx, (rows_b, RANK), jnp.float32)
        for dtype, iters, prec in ((jnp.bfloat16, 3,
                                    jax.lax.Precision.DEFAULT),
                                   (jnp.float32, 16, hp)):
            if dtype == jnp.float32 and d == 128:
                continue
            for rows in (1, 8):
                for warm in (False, True):
                    got = pk.als_solve_cg_pallas(
                        table.astype(dtype), cols, vals, mask, L2,
                        reg_nnz=True, iters=iters, interpret=interpret,
                        rows_per_program=rows, x0=x0 if warm else None)
                    ref = als._solve_bucket(
                        table, cols, vals, mask, L2, reg_nnz=True,
                        compute_dtype=dtype, precision=prec,
                        cg_iters=iters, x0=x0 if warm else None)
                    err = maxerr(got, ref)
                    scale = float(jnp.max(jnp.abs(ref)))
                    name = "pio_als_cg" if rows == 1 else "pio_als_cg_rows"
                    say(f"kernel {name} {jnp.dtype(dtype).name} table "
                        f"{m:,}×{RANK} D={d} rows/program={rows} "
                        f"{'warm' if warm else 'cold'} {iters} CG iters: "
                        f"max abs err vs the XLA solve {err:.2e} "
                        f"(solution scale {scale:.2f})")
                    check(err <= 5e-2 * max(scale, 1.0),
                          "ALS bucket kernel off")
    say("kernel pio_als_fused: NOT run — it does not lower on the "
        "installed compiler and `auto` never selects it (ops/als.py "
        "_fused_enabled)")


# -- four chips --------------------------------------------------------------

def phase_multichip(args, shape: dict, out_dir: str,
                    clock: CompileClock) -> None:
    import jax
    import numpy as np

    from incubator_predictionio_tpu.obs import metrics as obs_metrics
    from incubator_predictionio_tpu.ops import als, topk
    from incubator_predictionio_tpu.parallel.placement import (
        is_distributed,
        make_placement,
    )

    devices = jax.devices()

    def mem(key: str) -> list:
        """Per-device memory figure (None where the backend has none:
        the CPU rehearsal)."""
        return [(d.memory_stats() or {}).get(key) for d in devices]

    users, items, ratings, mean_rmse = import_ratings(shape, args.seed)
    one = write_engine(out_dir, "engine_one_chip")
    four = write_engine(out_dir, f"engine_{args.chips}_chips")
    # two engine directories → two engine ids: the second train must not
    # continue from the first one's model
    inst1, _ = run_pio_train(one, args.seed, clock)
    peak1 = mem("peak_bytes_in_use")
    inst4, op4 = run_pio_train(four, args.seed, clock,
                               model_parallelism=args.chips)
    peak4 = mem("peak_bytes_in_use")
    check(op4 == "als_sharded", f"`--model-parallelism {args.chips}` "
          f"booked op={op4!r}, not the placed trainer")
    mesh_g = obs_metrics.REGISTRY.get("pio_shard_mesh_devices")
    check(mesh_g is not None and int(mesh_g.value) == args.chips,
          "pio_shard_mesh_devices does not say the placed trainer used "
          f"{args.chips} devices")
    say(f"multichip: peak bytes in use per device after the one-chip train "
        f"{peak1}, after the placed train {peak4}")
    if peak4[0] is not None:
        check(all(b > a for a, b in zip(peak1[1:], peak4[1:])),
              "the placed train did not raise the peak memory of devices "
              "1.. — everything ran on device 0")

    model1, uf1, vf1 = load_factors(inst1.id)
    model4, uf4, vf4 = load_factors(inst4.id)
    check(list(model1.user_bimap) == list(model4.user_bimap)
          and list(model1.item_bimap) == list(model4.item_bimap),
          "the two trains interned ids differently")
    r1 = train_rmse(model1, uf1, vf1, users, items, ratings)
    r4 = train_rmse(model4, uf4, vf4, users, items, ratings)

    def rel(a, b) -> float:
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    du, dv = rel(uf4, uf1), rel(vf4, vf1)
    say(f"multichip: train RMSE one chip {r1:.4f}, placed over "
        f"{args.chips} chips {r4:.4f} (predict-the-mean {mean_rmse:.4f}); "
        f"relative Frobenius factor difference users {du:.2e} items "
        f"{dv:.2e}")
    # stated tolerance: same init, same alternation — only the bucket
    # composition (shard-blocked) and hence the rounding order differ
    check(abs(r4 - r1) <= 1e-2 and r4 < mean_rmse,
          "placed and one-chip train RMSE disagree beyond 1e-2")
    check(du <= 5e-2 and dv <= 5e-2,
          "placed and one-chip factors disagree beyond 5e-2 relative")

    # sharded top-k against single-device top-k on those factors
    base_use = mem("bytes_in_use")
    placement = make_placement(None, uf1.shape[0], vf1.shape[0])
    placed = placement.place_state(
        als.ALSState(user_factors=uf1, item_factors=vf1))
    jax.block_until_ready(placed)
    held = {d for t in (placed.user_factors, placed.item_factors)
            for d in (s.device for s in t.addressable_shards)}
    check(is_distributed(placed.item_factors) and len(held) == args.chips,
          f"placed tables live on {len(held)} device(s), not {args.chips}")
    grown = mem("bytes_in_use")
    say(f"multichip: mesh {placement.describe()} — shards on "
        f"{sorted(str(d) for d in held)}; bytes in use per device before "
        f"{base_use} after {grown}")
    if grown[0] is not None:
        check(all(b > a for a, b in zip(base_use, grown)),
              "bytes_in_use did not grow on every device")
    uf_d = jax.device_put(uf1, devices[0])
    vf_d = jax.device_put(vf1, devices[0])
    worst = 0.0
    for row in (0, uf1.shape[0] // 2, uf1.shape[0] - 1):
        got = np.asarray(topk.sharded_top_k(
            (placed.user_factors, row), placed.item_factors, 10,
            valid_items=vf1.shape[0]))
        ref = np.asarray(topk._score_user_top_k_xla(uf_d, vf_d, row, 10))
        check(np.array_equal(got[1], ref[1]),
              f"sharded_top_k row {row}: items {got[1]} != single-device "
              f"{ref[1]}")
        worst = max(worst, float(np.max(
            np.abs(got[0] - ref[0]) / np.maximum(np.abs(ref[0]), 1e-6))))
    say(f"multichip: sharded_top_k == single-device top-k on 3 users "
        f"(worst score rel err {worst:.1e})")
    check(worst <= 1e-3, "sharded_top_k scores off")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7,
                    help="seed of the generated ratings and of the train")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = ONLY the multi-chip phase and what it is "
                         "compared with")
    ap.add_argument("--ratings", type=int, default=None,
                    help="cut the number of ratings (never users, items "
                         "or rank); the cut is printed")
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox rehearsal: tiny shape, CPU backend, "
                         "Pallas in interpret mode")
    args = ap.parse_args(argv)

    out_dir = os.path.join(HERE, "chip_smoke_out")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    setup_environment(args, out_dir)
    shape = dict(TINY if args.rehearse else ML20M)
    if args.ratings is not None:
        say(f"CUT: {args.ratings:,} ratings instead of "
            f"{shape['ratings']:,} (users, items and rank are not cut)")
        shape["ratings"] = args.ratings
    if args.rehearse:
        say("REHEARSAL: tiny shape on the CPU backend, Pallas in interpret "
            "mode — this is not a chip run")

    t0 = time.perf_counter()
    with phase("device"):
        dev = phase_device(args)
    clock = CompileClock()
    with phase("native"):
        phase_native()
    if args.chips > 1:
        with phase(f"multichip ({args.chips} chips)"):
            phase_multichip(args, shape, out_dir, clock)
    else:
        with phase("train"):
            variant, model, uf, vf = phase_train(args, shape, out_dir, clock)
        with phase("serve"):
            phase_serve(variant, model, uf, vf)
        with phase("kernels"):
            phase_kernels(args.rehearse)

    from incubator_predictionio_tpu.obs import metrics as obs_metrics

    hits = obs_metrics.REGISTRY.get("pio_compile_cache_hits_total")
    reqs = obs_metrics.REGISTRY.get("pio_compile_cache_requests_total")
    say(f"pio_compile_cache_hits_total {int(hits.value)} "
        f"pio_compile_cache_requests_total {int(reqs.value)}")
    say(f"all phases passed (smoke wall {time.perf_counter() - t0:.1f} s, "
        f"of which jax trace+lower+compile {clock.seconds:.1f} s)")
    shutil.rmtree(out_dir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
