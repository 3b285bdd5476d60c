"""Headline benchmark: the north-star metric at MovieLens-20M scale.

BASELINE.json's north star is `pio train` wall-clock + deployed query
latency on the Recommendation template at ML-20M scale (≈138k users ×
27k items, 20M ratings, rank 128) — the reference delegates training to
Spark MLlib ALS and serves queries from a driver-local factor map
(CreateServer.scala:498-650). This bench runs the full TPU-native path:

1. SEED    — 20M synthetic rating events written through the native
             columnar bulk import (eventlog.cc pio_evlog_append_interactions)
2. INGEST  — `scan_interactions` streams them back as columnar COO + id
             tables, fully in C++ (the PEvents/HBase-scan role)
3. PREP    — degree-bucketed padded rows (ops/sparse.py, the native
             csr_builder)
4. TRAIN   — fused single-dispatch ALS (ops/als.py), compile + warm timing;
             MFU from the analytic FLOP count over the warm wall-clock
5. SERVE   — the real PredictionServer (HTTP + micro-batcher): sequential
             p50 and 128-async-client concurrent QPS on the device
             serving path

Prints exactly ONE JSON line on stdout: the headline metric
(`als_ml20m_train_wall_s`, vs the measured single-core CPU baseline) plus
the sub-metrics as extra keys (ingest/seed/prep walls, mfu, serving p50 /
QPS) so the driver's parsed record carries the whole story.

Process architecture (one process per chip: a parent that has touched
the accelerator holds it, and a child that needs it then fails. This
parent/child/degraded scheme predates the local chip and is left for the
first `benchmark` PR — ROADMAP Design 1 — to replace; `chip_smoke.py` is
the way to see the main path run on the chip today):

- the PARENT never dials the accelerator. It pins its own jax to CPU,
  runs every host-side stage (seed, ingest scan, prep, REST-ingest
  bench), and supervises a CHILD process that does all TPU work.
- the CHILD initializes the chip as its first act and touches a claim
  file the instant that succeeds; the parent recycles children that fail
  to claim within an exponentially growing window until
  `PIO_BENCH_ACCEL_WAIT_S` runs out. Children are stopped with
  SIGTERM-and-wait.
- if no child ever lands, the parent emits a **degraded** record —
  host-stage walls at full shape plus train quality measured on the
  pinned all-f32 CPU schedule at a reduced `PIO_BENCH_DEGRADED_NNZ`
  shape, `"degraded": true`, exit 0 — so the driver always gets a
  parsed record, never a null round.

`--cpu` reruns the train stage on the host CPU backend to (re)measure the
baseline constant. `PIO_BENCH_NNZ` shrinks the dataset for smoke runs.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

# ---------------------------------------------------------------------------
# Workload: synthetic ML-20M shape (ratings.csv of MovieLens-20M has
# 138,493 users, 26,744 movies, 20,000,263 ratings in 0.5..5.0 steps)
# ---------------------------------------------------------------------------
N_USERS = int(os.environ.get("PIO_BENCH_USERS", 138_493))
N_ITEMS = int(os.environ.get("PIO_BENCH_ITEMS", 26_744))
NNZ = int(os.environ.get("PIO_BENCH_NNZ", 20_000_000))
RANK = int(os.environ.get("PIO_BENCH_RANK", 128))
ITERATIONS = int(os.environ.get("PIO_BENCH_SWEEPS", 10))
#: precision schedule (ops/als.py _mixed_run): bf16 gathers + bf16 Gram
#: batches + single-pass MXU matmuls for the first BF16_SWEEPS sweeps, f32
#: HIGHEST for the rest. The bench default is ALL-bf16: at this exact
#: workload (planted rank-16 + noise 0.35, ML-20M marginals) the all-bf16
#: run measures RMSE parity with all-f32 to 4 decimals on BOTH fit
#: (0.5415 vs 0.5414) and heldout (0.5960 vs 0.5962) at 3.1x the speed
#: (scripts/als_profile.py, v5e). The engine default stays mixed
#: (iterations-2 bf16 + 2 polish) — arbitrary user data may sit far from
#: its noise floor where f32 polish matters; parity is additionally
#: guarded by tests/test_als.py planted-recovery.
BF16_SWEEPS = int(os.environ.get("PIO_BENCH_BF16_SWEEPS", ITERATIONS))
#: ridge weight (ALS-WR λ·nnz scaling). 0.03 is the measured optimum for
#: the planted workload (round-5 sweep at 2M/5M-nnz bench marginals:
#: heldout 0.675/0.494 at λ=0.1 → 0.611/0.472 at 0.03, overfit below) —
#: λ=0.1 was costing ~0.1 heldout RMSE of pure over-regularization.
#: See BASELINE.md "planted-quality gap" for the full decomposition.
L2 = float(os.environ.get("PIO_BENCH_L2", "0.03"))

#: Measured on this image's host CPU (JAX CPU backend, warm compile cache)
#: via `python bench.py --cpu` — the stand-in for the reference's
#: single-box Spark-MLlib driver (Spark 1.4 cannot run here; historically
#: it is far slower than a native CPU solver, so this bar is conservative).
#: Value = warm fused-train wall-clock at the full ML-20M shape above with
#: the same CG solver (measured 2026-07-29).
CPU_BASELINE_TRAIN_S = float(os.environ.get("PIO_BENCH_CPU_BASELINE", 467.7))

#: TPU v5e peak: 197 TFLOP/s bf16 / ~98.5 TFLOP/s fp32 on the MXU. The
#: JSON reports BOTH conventions: `mfu` against the fp32 peak (the series
#: every prior round reported — comparable across rounds) and
#: `mfu_bf16_peak` against the bf16 peak, which is the honest utilization
#: figure when the schedule runs all-bf16 sweeps.
PEAK_FLOPS_F32 = float(os.environ.get("PIO_BENCH_PEAK_FLOPS", 98.5e12))
PEAK_FLOPS_BF16 = float(os.environ.get("PIO_BENCH_PEAK_FLOPS_BF16", 197e12))

#: total budget for landing the TPU child (dial + respawn backoff). The
#: round-4 wedge outlasted a flat 1200 s retry window; the default here is
#: longer AND the wait overlaps the parent's host-side stages, so the
#: worst-case bench wall is max(host stages, wait) + child run, not their
#: sum.
ACCEL_WAIT_S = float(os.environ.get("PIO_BENCH_ACCEL_WAIT_S", "1800"))
#: GLOBAL wall budget for the whole bench process. The driver kills the
#: bench at its own timeout (observed: 870 s, rc=124) — BENCH_r05 lost an
#: already-computed degraded record because the claim-retry loop's third
#: recycle window ran past it. The bench therefore commits to emitting
#: its one JSON record (degraded if need be) BEFORE this deadline: the
#: claim wait is capped at deadline minus an emit margin, and the
#: orchestrator abandons a still-dialing supervisor rather than die
#: recordless. Raise it on drivers with a longer leash.
BENCH_DEADLINE_S = float(os.environ.get("PIO_BENCH_DEADLINE_S", "840"))
#: seconds reserved before the deadline for wrapping up: reading the
#: fragment, joining the degraded thread, serializing the record
EMIT_MARGIN_S = float(os.environ.get("PIO_BENCH_EMIT_MARGIN_S", "30"))
#: how long the degraded fallback (prep + CPU train + quality + serving
#: at DEGRADED_NNZ) is budgeted to take — the orchestrator starts the
#: fallback early enough that it can finish before the deadline, even if
#: that overlaps the accelerator wait from the first second
DEGRADED_BUDGET_S = float(
    os.environ.get("PIO_BENCH_DEGRADED_BUDGET_S", "600"))
#: if no child has claimed the chip this far into the wait, the parent
#: starts computing the degraded record in parallel (a normal dial lands
#: in seconds; by 300 s it is almost certainly a wedge) so the wait and
#: the fallback work overlap instead of adding
DEGRADED_START_S = float(os.environ.get("PIO_BENCH_DEGRADED_START_S", "300"))
#: once a child HAS claimed the chip, how long its full TPU run may take
TPU_RUN_TIMEOUT_S = float(os.environ.get("PIO_BENCH_TPU_RUN_S", "1800"))
#: degraded-mode train shape (events subsampled from the full dataset)
DEGRADED_NNZ = int(os.environ.get("PIO_BENCH_DEGRADED_NNZ", 2_000_000))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


_BENCH_TRACE_ID = None


def _bench_trace_id() -> str:
    """One trace ID per bench process (``bench-<8 hex>``): every HTTP
    request the load generators send carries it, so the servers' span
    logs attribute bench traffic to this run (the bench→servers hop of
    the cross-process trace contract)."""
    global _BENCH_TRACE_ID
    if _BENCH_TRACE_ID is None:
        import secrets

        _BENCH_TRACE_ID = f"bench-{secrets.token_hex(4)}"
    return _BENCH_TRACE_ID


def bench_env() -> dict:
    """Provenance block for the record: enough to answer "what machine,
    what software, what code" about any row of the trajectory without
    archaeology. Every field is best-effort — a missing git binary or
    an uninitialized jax must never cost the round its record."""
    import platform
    import socket

    env = {
        "backend": os.environ.get("JAX_PLATFORMS") or "default",
        "device_count": None,
        "jax_version": None,
        "git_sha": None,
        "hostname": None,
        "python": platform.python_version(),
        "wall_ts": None,
    }
    try:
        env["hostname"] = socket.gethostname()
    except OSError:
        pass
    env["wall_ts"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    mod = sys.modules.get("jax")
    if mod is not None:
        env["jax_version"] = getattr(mod, "__version__", None)
        try:
            env["device_count"] = len(mod.devices())
            # the LIVE backend beats the env var: the TPU child never
            # sets JAX_PLATFORMS, it dials the chip
            env["backend"] = mod.default_backend()
        except Exception:  # backend not initialized / unavailable
            pass
    else:
        try:
            from importlib.metadata import version

            env["jax_version"] = version("jax")
        except Exception:
            pass
    try:
        env["git_sha"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except Exception:
        pass
    return env


#: planted ground truth: ratings = 3.5 + U·Vᵀ + N(0, NOISE_SIGMA) with a
#: rank-PLANT_RANK U, V. The solver (rank 128 ⊇ 16) can recover the
#: structure, so heldout RMSE has a KNOWN floor (= NOISE_SIGMA) and
#: ranking quality a known ceiling — the r3 verdict's "model quality is
#: asserted, not proven" fix. Marginals stay the r3 power-law (identical
#: bucket shapes → timing comparability across rounds).
PLANT_RANK = int(os.environ.get("PIO_BENCH_PLANT_RANK", 16))
NOISE_SIGMA = float(os.environ.get("PIO_BENCH_NOISE_SIGMA", 0.35))
N_HOLDOUT = int(os.environ.get("PIO_BENCH_HOLDOUT", 200_000))


def _sample_pairs(rng, n):
    """Power-law item popularity matching ML-20M's marginals: the real
    ratings.csv tops out at ≈67k ratings for the most-rated movie; an
    i^-0.55 profile over 27k items puts the top item at ≈90k of 20M —
    same order, and it exercises the heavy-row (split-segment) solver.
    Users get a milder i^-0.3 tail (ML-20M users are min-20, median ≈70,
    max ≈9.3k ratings)."""
    iw = (np.arange(N_ITEMS) + 1.0) ** -0.55
    items = rng.choice(N_ITEMS, n, p=iw / iw.sum()).astype(np.int32)
    uw = (np.arange(N_USERS) + 1.0) ** -0.3
    users = rng.choice(N_USERS, n, p=uw / uw.sum()).astype(np.int32)
    return users, items


def make_dataset(rng):
    """→ (users, items, ratings, heldout (u, i, r), true (U, V)). The
    heldout pairs are fresh draws from the same ground truth — never
    stored, never trained on. Deterministic for a given rng seed: the
    TPU child regenerates the identical dataset from seed 7 instead of
    shipping 240 MB of arrays across the process boundary."""
    u_true = rng.normal(0, 1.0 / np.sqrt(PLANT_RANK),
                        (N_USERS, PLANT_RANK)).astype(np.float32)
    v_true = rng.normal(0, 1.0, (N_ITEMS, PLANT_RANK)).astype(np.float32)

    def rate(users, items):
        signal = np.einsum("nk,nk->n", u_true[users], v_true[items])
        return (3.5 + signal
                + rng.normal(0, NOISE_SIGMA, len(users))).astype(np.float32)

    users, items = _sample_pairs(rng, NNZ)
    ho_u, ho_i = _sample_pairs(rng, N_HOLDOUT)
    return (users, items, rate(users, items),
            (ho_u, ho_i, rate(ho_u, ho_i)), (u_true, v_true))


def quality_metrics(state, inter, heldout, truth, rng):
    """Heldout RMSE vs the known noise floor + precision@10 against the
    ground-truth ranking (sampled users, device-scored).

    The trained factors live in the event-log scan's FIRST-SEEN id order
    (``inter.user_ids``/``inter.item_ids``), not the seed's original
    integer order — translate every ground-truth index through the
    interned id tables before touching the model, or the metrics score a
    permutation of the model (the exact bug this comment guards against:
    p@10 ≈ 10/N_ITEMS ≈ 0)."""
    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops import als

    ho_u, ho_i, ho_r = heldout
    u_true, v_true = truth
    # IdTable caches its id→index dict on first .index(); reuse it instead
    # of building a parallel lookup (the scan's tables serve the server too)
    u_tab, i_tab = inter.user_ids, inter.item_ids
    u_scan = np.asarray([
        u_tab.index(s) if s in u_tab else -1
        for s in (f"u{k}" for k in range(N_USERS))])
    i_scan = np.asarray([
        i_tab.index(s) if s in i_tab else -1
        for s in (f"i{k}" for k in range(N_ITEMS))])

    # heldout pairs whose user/item never appeared in training have no
    # factor row (possible at smoke-test NNZ); score only the rest
    mask = (u_scan[ho_u] >= 0) & (i_scan[ho_i] >= 0)
    heldout_rmse = als.rmse(
        state, u_scan[ho_u[mask]], i_scan[ho_i[mask]], ho_r[mask])

    # ranking quality over the trainable universe: items present in
    # training (nothing can recommend an item it never saw)
    present_items = np.flatnonzero(i_scan >= 0)
    probe_pool = np.flatnonzero(u_scan >= 0)
    n_probe = min(1000, len(probe_pool))
    probe = rng.choice(probe_pool, n_probe, replace=False)
    true_scores = u_true[probe] @ v_true[present_items].T   # [P, Ip] host
    true_top = np.argsort(-true_scores, axis=1)[:, :10]
    # gather present-item factors in original-item order BEFORE the matmul:
    # everything stays on device in [P, Ip] and dropped columns never score
    probe_factors = jnp.take(
        state.user_factors, jnp.asarray(u_scan[probe]), axis=0)
    present_factors = jnp.take(
        state.item_factors, jnp.asarray(i_scan[present_items]), axis=0)
    model_top = np.asarray(jax.lax.top_k(
        probe_factors @ present_factors.T, 10)[1])
    hits = np.mean([
        len(set(a.tolist()) & set(b.tolist())) / 10.0
        for a, b in zip(model_top, true_top)
    ])
    return float(heldout_rmse), float(hits)


def als_flops_per_run(bf16_sweeps: int = None) -> float:
    """Analytic FLOPs of the fused training run at the bench shape —
    delegates to ``ops.als.train_flops``, the ONE formula the live
    ``pio_mfu{phase="train"}`` gauge (obs/profile.py) also uses, so the
    offline and live MFU figures agree by construction."""
    from incubator_predictionio_tpu.ops import als

    if bf16_sweeps is None:
        bf16_sweeps = BF16_SWEEPS
    return als.train_flops(NNZ, N_USERS, N_ITEMS, RANK, ITERATIONS,
                           bf16_sweeps)


def seed_store(tmpdir, users, items, ratings):
    """Write NNZ rating events through the native columnar bulk import."""
    from incubator_predictionio_tpu.data.storage import StorageClientConfig
    from incubator_predictionio_tpu.data.storage import cpplog
    from incubator_predictionio_tpu.data.storage.base import (
        IdTable,
        Interactions,
    )

    cfg = StorageClientConfig(properties={"PATH": tmpdir})
    client = cpplog.StorageClient(cfg)
    events = cpplog.CppLogEvents(client, cfg, prefix="bench_")
    user_tab = IdTable.from_list([f"u{k}" for k in range(N_USERS)])
    item_tab = IdTable.from_list([f"i{k}" for k in range(N_ITEMS)])
    inter = Interactions(
        user_idx=users, item_idx=items, values=ratings,
        user_ids=user_tab, item_ids=item_tab,
    )
    t0 = time.perf_counter()
    n = events.import_interactions(
        inter, 1, event_name="rate", value_prop="rating",
        base_time=None)
    seed_s = time.perf_counter() - t0
    assert n == len(users)
    return events, client, seed_s


def scan_store(tmpdir):
    """Re-open the seeded store and stream the training projection back
    out (the warm `pio train` read path). → (inter, ingest_wall_s)."""
    from incubator_predictionio_tpu.data.storage import StorageClientConfig
    from incubator_predictionio_tpu.data.storage import cpplog

    cfg = StorageClientConfig(properties={"PATH": tmpdir})
    client = cpplog.StorageClient(cfg)
    events = cpplog.CppLogEvents(client, cfg, prefix="bench_")
    t0 = time.perf_counter()
    inter = events.scan_interactions(
        app_id=1, entity_type="user", target_entity_type="item",
        event_names=("rate",), value_prop="rating")
    ingest_s = time.perf_counter() - t0
    client.close()
    return inter, ingest_s


def prep_buckets(inter):
    """Degree-bucketed padded rows from the scanned projection."""
    from incubator_predictionio_tpu.ops.sparse import build_both_sides

    n_users, n_items = len(inter.user_ids), len(inter.item_ids)
    t0 = time.perf_counter()
    (u_light, u_heavy), (i_light, i_heavy) = build_both_sides(
        inter.user_idx, inter.item_idx, inter.values, n_users, n_items)
    prep_s = time.perf_counter() - t0
    return (u_light, u_heavy), (i_light, i_heavy), n_users, n_items, prep_s


def build_trees(buckets):
    """Device-resident bucket + heavy trees from prep_buckets output —
    built ONCE per child and shared by the kernel selector and the timed
    train (each build uploads the whole padded interaction set)."""
    from incubator_predictionio_tpu.ops import als

    (u_light, u_heavy), (i_light, i_heavy), n_users, n_items = buckets
    u_tree, i_tree = als._buckets_tree(u_light), als._buckets_tree(i_light)
    u_hv, i_hv = als._heavy_tree(u_heavy), als._heavy_tree(i_heavy)
    return u_tree, i_tree, u_hv, i_hv, n_users, n_items


def select_als_kernel(buckets, trees=None):
    """Measured on-chip choice for the fused Pallas ALS bucket solve.

    ``PIO_ALS_KERNEL=auto``'s Mosaic probe only proves the kernel
    COMPILES on this backend; it says nothing about speed, and a slow
    kernel engaged blind would burn the TPU child's run window. A short
    full-shape run each way — covering BOTH kernel programs (a bf16
    DEFAULT sweep and, when the main schedule has one, an f32 HIGHEST
    polish sweep) — warm-timed; the kernel must beat the XLA path
    outright (ties keep the battle-tested path). Any crash in the probe
    falls back to the XLA path instead of forfeiting the accelerator
    leg. → (use_kernel, rows_per_program, fragment fields recording the
    outcome)."""
    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops import als

    # the timed legs run under the production warm-start default, so the
    # gate must probe that exact kernel variant (warm adds the x0 operand)
    if not als._kernel_enabled(False, warm=als._CG_WARMSTART):
        # distinguish an operator override from backend inability so the
        # fragment's cross-round comparison stays meaningful
        forced_off = als._ALS_KERNEL == "off" or als._SOLVER != "cg"
        return False, 1, {"als_kernel": "disabled" if forced_off
                          else "unavailable"}
    u_tree, i_tree, u_hv, i_hv, n_users, n_items = (
        trees if trees is not None else build_trees(buckets))
    # mirror the main schedule's leg structure: probe the polish program
    # too when the real run will use it
    polish = BF16_SWEEPS < ITERATIONS
    its = 2 if polish else 1
    # (use_kernel, rows-per-program): both kernel layouts compete with
    # the XLA path, so the bench self-selects the best and records every
    # timing — the on-chip layout comparison ships in the fragment
    legs = [(False, 1), (True, 1), (True, 8)]
    times = {}
    for uk, rows in legs:
        def train():
            out = als._mixed_run(
                als.als_init(jax.random.key(0), n_users, n_items, RANK),
                u_tree, i_tree, L2, its, 1, True,
                jnp.float32, jax.lax.Precision.HIGHEST,
                user_heavy=u_hv, item_heavy=i_hv, use_kernel=uk,
                kernel_rows=rows)
            np.asarray(out.user_factors[0:1, 0:1])
            np.asarray(out.item_factors[0:1, 0:1])
        try:
            train()  # compile + first run
            best = None
            for _ in range(2):
                # best-of-2: a single short sweep carries dispatch
                # jitter comparable to the 3% decision threshold
                t0 = time.perf_counter()
                train()
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            times[(uk, rows)] = best
        except Exception as e:  # full-shape-only kernel failure
            if not uk:
                raise  # the XLA path must work; nothing to fall back to
            log(f"ALS kernel probe (rows={rows}) crashed at full shape "
                f"({e!r}); leg skipped")
    xla = times[(False, 1)]
    kernel_times = {rows: t for (uk, rows), t in times.items() if uk}
    frag = {"als_kernel_sweep_xla_s": round(xla, 3)}
    for rows, t in kernel_times.items():
        frag[f"als_kernel_sweep_pallas_r{rows}_s"] = round(t, 3)
    if not kernel_times:
        frag["als_kernel"] = "probe_failed"
        log("ALS kernel probe: every kernel leg crashed; XLA path serves")
        return False, 1, frag
    best_rows = min(kernel_times, key=kernel_times.get)
    best = kernel_times[best_rows]
    choice = bool(best < 0.97 * xla)
    log(f"ALS kernel probe ({its} sweep(s), full shape): xla={xla:.3f}s "
        + " ".join(f"pallas_r{r}={t:.3f}s"
                   for r, t in sorted(kernel_times.items()))
        + f" -> {'pallas' if choice else 'xla'}"
        + (f" rows={best_rows}" if choice else ""))
    frag["als_kernel"] = "on" if choice else "off"
    frag["als_kernel_rows"] = best_rows
    return choice, best_rows, frag


def measure_train(buckets, bf16_sweeps, cache_probe=True, use_kernel=None,
                  trees=None, kernel_rows=None):
    """First-call / warm / warm-persistent-cache timing of the fused
    training run. → (state, dict of timing keys)."""
    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops import als

    u_tree, i_tree, u_hv, i_hv, n_users, n_items = (
        trees if trees is not None else build_trees(buckets))

    def train(state0):
        out = als._mixed_run(
            state0, u_tree, i_tree, L2, ITERATIONS, bf16_sweeps, True,
            jnp.float32, jax.lax.Precision.HIGHEST,
            user_heavy=u_hv, item_heavy=i_hv, use_kernel=use_kernel,
            kernel_rows=kernel_rows)
        # sync via a dependent 1-element device fetch (the timer must
        # cover execution, not the enqueue)
        np.asarray(out.user_factors[0:1, 0:1])
        np.asarray(out.item_factors[0:1, 0:1])
        return out

    # persistent compile cache at the ONE resolved directory
    # (utils/compile_cache.py: JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.xla_cache). The first timed call therefore compiles
    # cold only when that directory holds no entry for this program yet
    # — it is reported as the FIRST call, not as cold. Clearing the
    # in-memory executable cache then forces a re-trace that must hit
    # the persistent entry — the compile cost every later pio process
    # pays. Both compile numbers subtract the warm execution time (each
    # timed call runs the full training once).
    from incubator_predictionio_tpu.utils import compile_cache

    compile_cache.enable()
    xla_cache_dir = compile_cache.cache_dir()

    # both runs under PIO_PROFILE=1: the compile call also compiles the
    # profiler's nnz mask-sum reductions, so the TIMED warm run's outer
    # wall carries only their cached execution — keeping the profiler's
    # attributed wall (whose dt excludes the FLOP-count work entirely,
    # obs/profile.py flops_fn) within the 10% agreement band the
    # test_bench_e2e cross-check asserts.
    from incubator_predictionio_tpu.obs import metrics as obs_metrics

    def device_train_booked():
        """(seconds, dispatches, flops) the profiler attributed to the
        training op so far — als_train (XLA assembly) or als_fused
        (Pallas kernel path), whichever this run routes through."""
        secs = dispatches = flops = 0.0
        m = obs_metrics.REGISTRY.get("pio_device_seconds")
        d = obs_metrics.REGISTRY.get("pio_device_dispatches_total")
        f = obs_metrics.REGISTRY.get("pio_device_flops_total")
        for op in ("als_train", "als_fused"):
            if m is not None:
                secs += m.labels(op=op).value
            if d is not None:
                dispatches += d.labels(op=op).value
            if f is not None:
                flops += f.labels(op=op).value
        return secs, dispatches, flops

    prev_profile = os.environ.get("PIO_PROFILE")
    os.environ["PIO_PROFILE"] = "1"
    try:
        t0 = time.perf_counter()
        state = train(als.als_init(jax.random.key(0), n_users, n_items,
                                   RANK))
        first_call_s = time.perf_counter() - t0
        # per-op device-seconds delta over the TIMED run only (the
        # compile run books its own attribution)
        secs0, disp0, flops0 = device_train_booked()
        t0 = time.perf_counter()
        state = train(als.als_init(jax.random.key(0), n_users, n_items,
                                   RANK))
        train_s = time.perf_counter() - t0
        secs1, disp1, flops1 = device_train_booked()
    finally:
        if prev_profile is None:
            os.environ.pop("PIO_PROFILE", None)
        else:
            os.environ["PIO_PROFILE"] = prev_profile
    # the profiler's own FLOPs over its own wall for the timed run,
    # against THIS bench's peak constant: the live pio_mfu gauge takes
    # its peak from the device_kind table (obs/profile.py) and is unset
    # on a device the table does not list
    obs_mfu_train = ((flops1 - flops0) / (secs1 - secs0) / PEAK_FLOPS_F32
                     if secs1 > secs0 else 0.0)
    compile_s = max(first_call_s - train_s, 0.0)
    compile_warm_cache_s = None
    if (cache_probe and os.path.isdir(xla_cache_dir)
            and os.listdir(xla_cache_dir)):
        jax.clear_caches()  # drop in-memory executables; cache dir stays
        t0 = time.perf_counter()
        state = train(als.als_init(jax.random.key(0), n_users, n_items,
                                   RANK))
        compile_warm_cache_s = round(
            max(time.perf_counter() - t0 - train_s, 0.0), 1)
        log(f"compile: first-call={compile_s:.1f}s warm-persistent-cache="
            f"{compile_warm_cache_s}s (dir {xla_cache_dir})")
    elif cache_probe:
        # PIO_COMPILE_CACHE=off in the environment, or the cache was
        # rejected: do NOT publish a second full compile as "warm"
        log("compile: persistent cache did not engage "
            "(PIO_COMPILE_CACHE=off or cache rejected); "
            f"first-call={compile_s:.1f}s")
    return state, {
        "train_s": train_s,
        "compile_s_first": round(compile_s, 1),
        "compile_s_warm_cache": compile_warm_cache_s,
        # live device-time attribution over the timed warm run (None
        # when the profiler never booked — a mis-wired hook must not
        # masquerade as MFU 0). Six significant digits, NOT fixed
        # decimals: CPU-backend MFU is ~1e-7 and must survive rounding
        "obs_mfu_train": (float(f"{obs_mfu_train:.6g}")
                          if obs_mfu_train > 0 else None),
        # per-op pio_device_seconds cross-check: the profiler's
        # block-until-ready wall over the SAME timed run — must bracket
        # train_s (test_bench_e2e asserts the ratio), and the dispatch
        # counter pins the whole run as ONE attributed dispatch
        "obs_device_train_s": (round(secs1 - secs0, 4)
                               if secs1 > secs0 else None),
        "obs_device_train_dispatches": int(disp1 - disp0),
        # warm wall through the fused Gram+solve kernel path, when the
        # selector engaged it (None = XLA assembly served this round)
        "train_fused_wall_s": (round(train_s, 3) if use_kernel else None),
    }


#: continuation-retrain record keys (docs/performance.md "Steady-state
#: retrain"): the O(delta) steady-state contract — after a ≤5% event
#: tail, continuation (warm factors + early-stop + plan reuse) must
#: finish in ≤ 1/3 of the fresh-retrain wall at RMSE parity
RETRAIN_KEYS = (
    "retrain_fresh_wall_s", "retrain_continue_wall_s",
    "retrain_sweeps_used", "retrain_delta_rows", "retrain_scan_s",
    "retrain_prep_fresh_s", "retrain_prep_continue_s",
    "retrain_heldout_rmse_fresh", "retrain_heldout_rmse_continue",
    "retrain_speedup", "retrain_one_dispatch", "retrain_train_dispatches",
)


def bench_retrain(store_dir, state, inter, heldout, truth):
    """Steady-state retrain leg: append a tail, re-ingest (traincache
    fold), then measure fresh-vs-continuation retrain walls.

    Fresh = full prep + fixed-budget warm train from random init.
    Continue = plan-reuse prep splice + warm factors + convergence
    early-stop, timed end to end (the splice is part of the wall — the
    plan is reset to its pre-tail state before the timed run so the
    O(delta) fold is actually measured). Both train walls are WARM
    (compile excluded, same convention as measure_train). Guarded by the
    global bench deadline: PIO_BENCH_EMIT_BY_EPOCH (set by the
    orchestrator from PIO_BENCH_DEADLINE_S) skips the leg rather than
    cost the record."""
    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.data.storage import (
        StorageClientConfig,
        cpplog,
    )
    from incubator_predictionio_tpu.data.storage.base import (
        IdTable,
        Interactions,
    )
    from incubator_predictionio_tpu.ops import als, retrain
    from incubator_predictionio_tpu.ops.sparse import build_both_sides

    out = dict.fromkeys(RETRAIN_KEYS)
    emit_by = float(os.environ.get("PIO_BENCH_EMIT_BY_EPOCH", "0"))
    if emit_by and time.time() > emit_by - 120.0:
        log("retrain leg skipped: bench deadline too close")
        return out
    tail_frac = float(os.environ.get("PIO_BENCH_RETRAIN_TAIL", "0.05"))
    tail_n = max(int(NNZ * tail_frac), 1)
    rng = np.random.default_rng(13)
    t_users, t_items = _sample_pairs(rng, tail_n)
    u_true, v_true = truth
    signal = np.einsum("nk,nk->n", u_true[t_users], v_true[t_items])
    t_vals = (3.5 + signal
              + rng.normal(0, NOISE_SIGMA, tail_n)).astype(np.float32)

    # -- append the tail through the native columnar import --------------
    cfg = StorageClientConfig(properties={"PATH": store_dir})
    client = cpplog.StorageClient(cfg)
    events = cpplog.CppLogEvents(client, cfg, prefix="bench_")
    try:
        wrote = events.import_interactions(
            Interactions(
                user_idx=t_users, item_idx=t_items, values=t_vals,
                user_ids=IdTable.from_list(
                    [f"u{k}" for k in range(N_USERS)]),
                item_ids=IdTable.from_list(
                    [f"i{k}" for k in range(N_ITEMS)]),
            ), 1, event_name="rate", value_prop="rating")
        assert wrote == tail_n

        # -- re-ingest: the traincache tail fold (O(delta) scan) ---------
        stats: dict = {}
        t0 = time.perf_counter()
        inter2 = events.scan_interactions(
            app_id=1, entity_type="user", target_entity_type="item",
            event_names=("rate",), value_prop="rating", stats=stats)
        scan_s = time.perf_counter() - t0
        delta_rows = int(stats.get("scan_tail_rows", tail_n))
        n_users2, n_items2 = len(inter2.user_ids), len(inter2.item_ids)

        # -- fresh leg: full prep + fixed-budget train from random init --
        t0 = time.perf_counter()
        (uf_l, uf_h), (if_l, if_h) = build_both_sides(
            inter2.user_idx, inter2.item_idx, inter2.values,
            n_users2, n_items2)
        uf_t, if_t = als._buckets_tree(uf_l), als._buckets_tree(if_l)
        uf_hv, if_hv = als._heavy_tree(uf_h), als._heavy_tree(if_h)
        prep_fresh_s = time.perf_counter() - t0

        def train_fresh():
            st = als._mixed_run(
                als.als_init(jax.random.key(0), n_users2, n_items2, RANK),
                uf_t, if_t, L2, ITERATIONS, BF16_SWEEPS, True,
                jnp.float32, jax.lax.Precision.HIGHEST,
                user_heavy=uf_hv, item_heavy=if_hv)
            np.asarray(st.user_factors[0:1, 0:1])
            np.asarray(st.item_factors[0:1, 0:1])
            return st

        state_f = train_fresh()          # compile
        t0 = time.perf_counter()
        state_f = train_fresh()          # warm
        train_fresh_s = time.perf_counter() - t0

        # -- continue leg: plan splice + warm factors + early stop -------
        prev = als.ALSState(
            user_factors=np.asarray(state.user_factors),
            item_factors=np.asarray(state.item_factors))

        def seed_plan():
            retrain.drop_plans()
            retrain.prepare_with_reuse(
                inter.user_idx, inter.item_idx, inter.values,
                len(inter.user_ids), len(inter.item_ids),
                plan_key="bench")

        rs: dict = {}

        def train_cont():
            rs.clear()
            st = retrain.als_retrain(
                inter2.user_idx, inter2.item_idx, inter2.values,
                n_users2, n_items2, rank=RANK, iterations=ITERATIONS,
                l2=L2, seed=0, bf16_sweeps=BF16_SWEEPS,
                prev_state=prev, plan_key="bench", stats=rs)
            np.asarray(st.user_factors[0:1, 0:1])
            np.asarray(st.item_factors[0:1, 0:1])
            return st

        from incubator_predictionio_tpu.obs import metrics as obs_metrics

        seed_plan()
        state_c = train_cont()           # compile + first fold
        seed_plan()                      # reset so the timed run re-folds
        sweeps_before = obs_metrics.REGISTRY.counter(
            "pio_train_sweeps_total", "ALS sweeps actually run by "
            "training, by schedule mode", labels=("mode",)
        ).labels(mode="continue").value
        t0 = time.perf_counter()
        state_c = train_cont()           # warm, O(delta) splice included
        cont_wall_s = time.perf_counter() - t0
        # registry cross-check over the TIMED run only (the compile run
        # books its own sweeps — a raw snapshot would double-count)
        sweeps_booked = obs_metrics.REGISTRY.get(
            "pio_train_sweeps_total").labels(mode="continue").value \
            - sweeps_before
        prep_cont_s = rs.get("prep_wall_s")  # the O(delta) splice wall

        ho_f, _p1 = quality_metrics(state_f, inter2, heldout, truth, rng)
        ho_c, _p2 = quality_metrics(state_c, inter2, heldout, truth, rng)
        fresh_wall = prep_fresh_s + train_fresh_s
        out.update({
            "retrain_fresh_wall_s": round(fresh_wall, 3),
            "retrain_continue_wall_s": round(cont_wall_s, 3),
            "retrain_sweeps_used": int(rs.get("sweeps_used", 0)),
            "retrain_delta_rows": delta_rows,
            # the one-dispatch contract, measured on the timed run:
            # splice + sweeps + early-stop in a single device dispatch
            "retrain_one_dispatch": bool(rs.get("one_dispatch", False)),
            "retrain_train_dispatches": int(rs.get("train_dispatches", 0)),
            "retrain_scan_s": round(scan_s, 3),
            "retrain_prep_fresh_s": round(prep_fresh_s, 3),
            "retrain_prep_continue_s": (None if prep_cont_s is None
                                        else round(prep_cont_s, 3)),
            "obs_train_sweeps_continue": int(sweeps_booked),
            "retrain_heldout_rmse_fresh": round(ho_f, 3),
            "retrain_heldout_rmse_continue": round(ho_c, 3),
            "retrain_speedup": round(fresh_wall / max(cont_wall_s, 1e-9),
                                     2),
        })
        log(f"retrain: tail={tail_n} (delta_rows={delta_rows}) "
            f"scan={scan_s:.2f}s fresh={fresh_wall:.2f}s "
            f"(prep {prep_fresh_s:.2f}s) continue={cont_wall_s:.2f}s "
            f"({rs.get('sweeps_used')} sweeps, "
            f"mode={rs.get('mode')}, plan={rs.get('prep_plan')}) "
            f"heldout fresh={ho_f:.3f} continue={ho_c:.3f}")
        retrain.drop_plans()
    finally:
        client.close()
    return out


#: speed-layer record keys (docs/production.md "Freshness between
#: retrains"): fold-in latency under concurrent ingest + serve, the
#: overlay hit rate, and how far the tail poll ran behind the writers
SPEED_KEYS = (
    "speed_foldin_p50_ms", "speed_foldin_p95_ms", "speed_hit_rate",
    "speed_cursor_lag_events", "speed_foldins", "speed_ingested_keys",
    "obs_freshness_p95_s",
)


def bench_speed(store_dir, state, inter):
    """Speed-layer leg: concurrent cold-user ingest + overlay serve.

    A writer thread streams brand-new users' rate events into the cpplog
    store while the overlay polls the tail cursor and folds the dirty
    users in on device; the serve side looks every ingested cold user up
    after each poll. Emits the fold-in cycle wall (p50/p95), the overlay
    hit rate over those lookups, and the worst cursor lag observed.
    Deadline-guarded like the retrain leg."""
    import threading

    from incubator_predictionio_tpu.data.storage import App, Storage
    from incubator_predictionio_tpu.speed.overlay import (
        SpeedOverlay,
        SpeedOverlayConfig,
    )

    out = dict.fromkeys(SPEED_KEYS)
    emit_by = float(os.environ.get("PIO_BENCH_EMIT_BY_EPOCH", "0"))
    if emit_by and time.time() > emit_by - 90.0:
        log("speed leg skipped: bench deadline too close")
        return out
    run_s = float(os.environ.get("PIO_BENCH_SPEED_S", "8"))
    Storage.configure({
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_SOURCES_CPP_TYPE": "cpplog",
        "PIO_STORAGE_SOURCES_CPP_PATH": store_dir,
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        # repo NAME "bench" → namespace prefix "bench_", the seeded log
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "bench",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "CPP",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    })
    try:
        Storage.get_meta_data_apps().insert(App(1, "bench"))
        item_index = {t: k for k, t in enumerate(inter.item_ids)}
        user_index = {u: k for k, u in enumerate(inter.user_ids)}
        overlay = SpeedOverlay(
            SpeedOverlayConfig(
                app_name="bench", engine="bench", event_names=("rate",),
                value_prop="rating", l2=L2, reg_nnz=True,
                max_keys_per_poll=1024, ttl_s=600.0),
            other_factors=state.item_factors,
            other_index=item_index, key_index=user_index)
        assert overlay.enabled

        from incubator_predictionio_tpu.data.storage.base import (
            IdTable,
            Interactions,
        )

        dao = Storage.get_events()
        stop = threading.Event()
        ingested: list = []  # cold user ids, in ingest order
        rng = np.random.default_rng(23)
        events_per_user = 8
        users_per_batch = 16

        def writer() -> None:
            j = 0
            while not stop.is_set():
                uids = [f"cold{j + k}" for k in range(users_per_batch)]
                n = users_per_batch * events_per_user
                uidx = np.repeat(np.arange(users_per_batch, dtype=np.int32),
                                 events_per_user)
                iidx = rng.integers(0, len(item_index), n).astype(np.int32)
                vals = rng.normal(3.5, 1.0, n).astype(np.float32)
                item_tab = IdTable.from_list(
                    [inter.item_ids[int(i)] for i in iidx])
                dao.import_interactions(
                    Interactions(
                        user_idx=uidx,
                        item_idx=np.arange(n, dtype=np.int32),
                        values=vals,
                        user_ids=IdTable.from_list(uids),
                        item_ids=item_tab),
                    1, event_name="rate", value_prop="rating")
                ingested.extend(uids)
                j += users_per_batch
                stop.wait(0.05)

        t_writer = threading.Thread(target=writer, daemon=True)
        t_writer.start()
        fold_walls: list = []
        max_lag = 0
        t_end = time.perf_counter() + run_s
        while time.perf_counter() < t_end:
            t0 = time.perf_counter()
            s = overlay.poll()
            if s.get("solved"):
                fold_walls.append(time.perf_counter() - t0)
            max_lag = max(max_lag, int(s.get("lag", 0)))
            # serve side: look up every cold user ingested so far — the
            # honest freshness probe (users not yet folded in miss)
            for uid in list(ingested):
                overlay.lookup(uid)
        stop.set()
        t_writer.join(timeout=10)
        # drain the remaining dirty set so the final hit-rate pass
        # reflects steady state, not the shutdown edge
        for _ in range(8):
            if not overlay.poll().get("dirty"):
                break
        st = overlay.stats()
        walls_ms = np.sort(np.asarray(fold_walls)) * 1e3
        looked = st["hits"] + st["misses"]
        # end-to-end freshness (event append -> first folded serve) from
        # the new pio_freshness_seconds histogram — the measured form of
        # the speed layer's promise, not an inference from staleness
        from incubator_predictionio_tpu.obs import metrics as obs_metrics
        fh = obs_metrics.REGISTRY.get("pio_freshness_seconds")
        fresh_p95 = (fh.quantile_over_children(0.95)
                     if fh is not None else None)
        out.update({
            "obs_freshness_p95_s": (round(fresh_p95, 3)
                                    if fresh_p95 else None),
            "speed_foldin_p50_ms": (
                round(float(walls_ms[int(0.50 * (len(walls_ms) - 1))]), 2)
                if len(walls_ms) else None),
            "speed_foldin_p95_ms": (
                round(float(walls_ms[int(0.95 * (len(walls_ms) - 1))]), 2)
                if len(walls_ms) else None),
            "speed_hit_rate": (round(st["hits"] / looked, 3)
                               if looked else None),
            "speed_cursor_lag_events": int(max_lag),
            "speed_foldins": int(st["foldins"]),
            "speed_ingested_keys": int(len(ingested)),
        })
        log(f"speed: {len(ingested)} cold users ingested, "
            f"{st['foldins']} fold-ins, "
            f"foldin p50={out['speed_foldin_p50_ms']}ms "
            f"p95={out['speed_foldin_p95_ms']}ms "
            f"hit_rate={out['speed_hit_rate']} max_lag={max_lag} "
            f"freshness_p95={out['obs_freshness_p95_s']}s")
    finally:
        Storage.reset()
    return out


#: registry cross-check keys (docs/observability.md): the telemetry
#: layer and the bench time THE SAME stages, so their numbers must
#: corroborate — obs_ingest_events_total vs the seeded HTTP load,
#: obs_query_p50_ms vs serve_p50_ms, compile-cache hits vs the
#: warm-cache compile probe. A divergence means one of them lies.
OBS_KEYS = (
    "obs_ingest_events_total", "obs_ingest_batches",
    "obs_http_requests_total", "obs_query_latency_count",
    "obs_query_latency_sum_s", "obs_query_p50_ms", "obs_query_p99_ms",
    "obs_compile_cache_hits", "obs_compile_cache_requests",
    "obs_train_sweeps_continue", "obs_mfu_train", "obs_mfu_vs_offline",
)


def obs_snapshot() -> dict:
    """Snapshot the process-wide metrics registry into obs_* bench
    sub-metrics. Keys for stages THIS process never ran stay None
    (a metric that exists but never booked is indistinguishable from a
    mis-wired one — the count guards keep the cross-check honest)."""
    from incubator_predictionio_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.REGISTRY
    out = dict.fromkeys(OBS_KEYS)
    ingest = reg.get("pio_ingest_events_total")
    if ingest is not None and ingest.total():
        out["obs_ingest_events_total"] = int(ingest.total())
    batches = reg.get("pio_ingest_batch_size")
    if batches is not None and batches.count:
        out["obs_ingest_batches"] = int(batches.count)
    http = reg.get("pio_http_requests_total")
    if http is not None and http.total():
        out["obs_http_requests_total"] = int(http.total())
    qlat = reg.get("pio_query_latency_seconds")
    if qlat is not None and qlat.count:
        out["obs_query_latency_count"] = int(qlat.count)
        out["obs_query_latency_sum_s"] = round(qlat.sum, 3)
        out["obs_query_p50_ms"] = round(qlat.quantile(0.50) * 1e3, 2)
        out["obs_query_p99_ms"] = round(qlat.quantile(0.99) * 1e3, 2)
    hits = reg.get("pio_compile_cache_hits_total")
    if hits is not None:
        out["obs_compile_cache_hits"] = int(hits.value)
    reqs = reg.get("pio_compile_cache_requests_total")
    if reqs is not None:
        out["obs_compile_cache_requests"] = int(reqs.value)
    # obs_train_sweeps_continue is NOT snapshotted here: the retrain leg
    # computes it as the counter delta over its timed run (bench_retrain)
    # so it corroborates retrain_sweeps_used exactly — a raw snapshot
    # would include the compile run's sweeps and read as a 2× lie
    return out


#: mesh-sharded training leg (docs/performance.md "Sharded ALS"): the
#: placed-train wall over the forced-host-device mesh, the analytic
#: collective volume, and the fused-kernel routing story at ML-20M —
#: per-shard slice residency is what re-enables the fused kernel on the
#: big-table side (ROADMAP items 1/5)
SHARD_KEYS = (
    "shard_train_wall_s", "shard_mesh_shape", "shard_devices",
    "shard_nnz", "shard_sweeps",
    "shard_backend", "shard_allgather_bytes", "shard_mfu_train",
    "shard_gather_modes", "shard_fused_user_sweep",
    "shard_fused_item_sweep", "shard_fused_fits_ml20m_user_sweep",
    "shard_fused_fits_ml20m_item_sweep",
)

#: the true MovieLens-20M catalog shape + rank: the fused-VMEM routing
#: keys are computed at THIS shape regardless of any smoke-run
#: PIO_BENCH_* overrides — they are the headline claim, not a sample
ML20M_SHAPE = (138_493, 26_744, 128)


def run_shard_child() -> None:
    """``--shard-child``: the mesh-sharded training leg, in its own
    process so the forced-host-device backend (the parent exports
    ``--xla_force_host_platform_device_count``) never perturbs the main
    bench's single-device timings. Prints ONE JSON line on stdout."""
    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.obs import metrics as obs_metrics
    from incubator_predictionio_tpu.ops import als
    from incubator_predictionio_tpu.ops.pallas_kernels import (
        als_fused_fits,
    )
    from incubator_predictionio_tpu.parallel.mesh import make_mesh
    from incubator_predictionio_tpu.parallel.placement import (
        make_placement,
    )

    nnz = int(os.environ.get("PIO_BENCH_SHARD_NNZ",
                             str(min(NNZ, 1_000_000))))
    sweeps = int(os.environ.get("PIO_BENCH_SHARD_SWEEPS", "2"))
    bf16 = min(BF16_SWEEPS, sweeps)
    rng = np.random.default_rng(17)
    users = rng.integers(0, N_USERS, nnz).astype(np.int32)
    items = rng.integers(0, N_ITEMS, nnz).astype(np.int32)
    vals = rng.uniform(1, 5, nnz).astype(np.float32)
    mesh = make_mesh()
    placement = make_placement(mesh, N_USERS, N_ITEMS)
    # mirror als_train_placed's leg structure explicitly so the timed
    # window covers ONLY the training dispatches (the host-side bucket
    # prep would otherwise dominate the CPU-sim wall and make
    # shard_mfu_train incomparable to the main leg's MFU keys), and so
    # the reported routing comes from the cfg the timed sweeps actually
    # run (all-bf16 schedules route at bfloat16, not f32)
    modes = als._shard_gather_modes(placement, RANK, jnp.float32, False)
    u_data, i_data = als.build_placed_sides(
        users, items, vals, placement, modes)
    cfg_lo = als._placed_cfg(
        placement, RANK, False, True, L2, 0.0, jnp.bfloat16,
        jax.lax.Precision.DEFAULT,
        min(als._CG_ITERS_BF16, als._CG_ITERS), modes=modes)
    cfg_f32 = als._placed_cfg(
        placement, RANK, False, True, L2, 1.0, jnp.float32,
        jax.lax.Precision.HIGHEST, als._CG_ITERS, modes=modes)
    cfg = cfg_lo if bf16 >= sweeps else cfg_f32

    state = placement.place_state(
        als.als_init(jax.random.key(0), N_USERS, N_ITEMS, RANK))

    def run():
        uf, vf = state.user_factors, state.item_factors
        if bf16:
            uf, vf = als._als_run_placed(
                uf, vf, u_data, i_data, placement=placement,
                cfg=cfg_lo, iterations=bf16)
        if sweeps - bf16:
            uf, vf = als._als_run_placed(
                uf, vf, u_data, i_data, placement=placement,
                cfg=cfg_f32, iterations=sweeps - bf16)
        jax.block_until_ready((uf, vf))
        return uf, vf

    run()                                    # compile

    def gather_bytes() -> int:
        gb = obs_metrics.REGISTRY.get("pio_shard_gather_bytes_total")
        if gb is None:
            return 0
        return int(sum(gb.labels(strategy=s).value
                       for s in ("allgather", "ring")))

    t0 = time.perf_counter()
    run()                                    # warm, dispatches only
    wall = time.perf_counter() - t0
    # the analytic per-leg collective volume the trainer books
    before = gather_bytes()
    if bf16:
        als._book_shard_metrics(placement, cfg_lo, RANK, bf16)
    if sweeps - bf16:
        als._book_shard_metrics(placement, cfg_f32, RANK, sweeps - bf16)
    flops = als.train_flops(nnz, N_USERS, N_ITEMS, RANK, sweeps, bf16)
    mfu = flops / wall / PEAK_FLOPS_F32

    # fused-kernel routing at the TRUE ML-20M shape under this mesh:
    # the VMEM math alone (deterministic on every backend — the
    # per-run shard_fused_* keys additionally carry the Mosaic probe)
    mu, mi, mr = ML20M_SHAPE
    p20 = make_placement(mesh, mu, mi)
    modes20 = als._shard_gather_modes(p20, mr, jnp.bfloat16, False)
    out = {
        "shard_train_wall_s": round(wall, 3),
        "shard_mesh_shape": placement.describe(),
        "shard_devices": placement.n_shards,
        # the leg's own workload shape: the capacity model
        # (obs/capacity.py) needs rows+sweeps next to the wall to turn
        # shard timings into a rows/chip rate
        "shard_nnz": nnz,
        "shard_sweeps": sweeps,
        "shard_backend": jax.devices()[0].platform,
        "shard_allgather_bytes": gather_bytes() - before,
        "shard_mfu_train": float(f"{mfu:.6g}"),
        "shard_gather_modes": "+".join((cfg.u_mode, cfg.i_mode)),
        "shard_fused_user_sweep": bool(cfg.fused_u),
        "shard_fused_item_sweep": bool(cfg.fused_i),
        "shard_fused_fits_ml20m_user_sweep": bool(als_fused_fits(
            als.gather_source_rows(p20, "item", modes20[0]),
            mr, jnp.bfloat16)),
        "shard_fused_fits_ml20m_item_sweep": bool(als_fused_fits(
            als.gather_source_rows(p20, "user", modes20[1]),
            mr, jnp.bfloat16)),
    }
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()


def bench_shard(budget_s: float) -> dict:
    """Parent-side mesh-sharded leg: spawn ``--shard-child`` with the
    CPU backend forced to ``PIO_BENCH_SHARD_DEVICES`` (default 8)
    virtual host devices — the sharded path measured without hardware,
    and without perturbing this process's single-device jax. Guarded:
    any failure nulls the shard_* keys, never the record."""
    out = dict.fromkeys(SHARD_KEYS)
    if budget_s < 20.0:
        log("shard leg skipped: bench deadline too close")
        return out
    ndev = int(os.environ.get("PIO_BENCH_SHARD_DEVICES", "8"))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ndev}").strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--shard-child"],
        env=env, capture_output=True, text=True,
        timeout=min(budget_s, float(
            os.environ.get("PIO_BENCH_SHARD_TIMEOUT_S", "300"))))
    if proc.returncode != 0:
        raise RuntimeError(
            f"shard child rc={proc.returncode}: {proc.stderr[-500:]}")
    out.update(json.loads(proc.stdout.splitlines()[-1]))
    log(f"shard: mesh={out['shard_mesh_shape']} "
        f"({out['shard_backend']}) warm={out['shard_train_wall_s']}s "
        f"gather={out['shard_gather_modes']} "
        f"bytes={out['shard_allgather_bytes']} "
        f"fused_ml20m=({out['shard_fused_fits_ml20m_user_sweep']}, "
        f"{out['shard_fused_fits_ml20m_item_sweep']})")
    return out


#: two-stage MIPS serving leg (docs/performance.md "Two-stage MIPS
#: serving"): exhaustive-vs-two-stage per-query device wall and the
#: candidates-scanned fraction on the planted large catalogue, plus the
#: recall@20-vs-exact gate figure. ``mips_sweep`` carries the whole
#: {27k, 256k, 1M} size ladder; the scalar keys are the GATE size (the
#: largest completed ≥ 128k, where the two-stage win must hold). None =
#: the leg's designed deadline-skip (same contract as shard_*/fleet_*).
MIPS_KEYS = (
    "mips_items", "mips_build_s", "mips_exhaustive_per_query_ms",
    "mips_exhaustive_p99_ms", "mips_two_stage_per_query_ms",
    "mips_two_stage_p99_ms", "mips_speedup", "mips_candidates_frac",
    "mips_recall_at_20", "mips_recompiles_steady", "mips_serve_qps",
    "mips_exhaustive_27k_p99_ms", "mips_sweep",
)


def bench_mips(budget_s: float) -> dict:
    """Planted-catalogue MIPS leg, in-process (single device suffices —
    the sharded merge is pinned by tier-1 tests/test_mips.py at mesh
    {1,2,4,8}). Per size: build the index, measure exhaustive and
    two-stage per-query walls through the REAL ops/topk auto-router
    (PIO_SERVE_MIPS=off vs =on), the recall@20 against the exhaustive
    oracle, and the steady-state recompile count. Budget-guarded like
    bench_shard: any failure or deadline squeeze nulls keys, never the
    record."""
    out = dict.fromkeys(MIPS_KEYS)
    if budget_s < 45.0:
        log("mips leg skipped: bench deadline too close")
        return out
    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops import mips as mips_mod
    from incubator_predictionio_tpu.ops import topk
    from incubator_predictionio_tpu.utils.planted import (
        exhaustive_top_k,
        planted_item_factors,
        planted_queries,
        recall_against_oracle,
    )

    sizes = [int(s) for s in os.environ.get(
        "PIO_BENCH_MIPS_ITEMS", "27000,262144,1048576").split(",") if s]
    rank = int(os.environ.get("PIO_BENCH_MIPS_RANK", "64"))
    n_q = int(os.environ.get("PIO_BENCH_MIPS_QUERIES", "32"))
    leg_deadline = time.monotonic() + min(
        budget_s - 15.0,
        float(os.environ.get("PIO_BENCH_MIPS_TIMEOUT_S", "300")))
    prev_mode = os.environ.get("PIO_SERVE_MIPS")

    def _restore_mode() -> None:
        if prev_mode is None:
            os.environ.pop("PIO_SERVE_MIPS", None)
        else:
            os.environ["PIO_SERVE_MIPS"] = prev_mode

    def _per_query_ms(queries) -> tuple:
        """(p50, p99) wall over the real router, one fetch per query."""
        np.asarray(topk.score_and_top_k(queries[0], table, k=20))  # warm
        walls = []
        for q in queries:
            t0 = time.perf_counter()
            np.asarray(topk.score_and_top_k(q, table, k=20))
            walls.append((time.perf_counter() - t0) * 1e3)
        walls = np.asarray(walls)
        return (float(np.quantile(walls, 0.5)),
                float(np.quantile(walls, 0.99)))

    sweep: dict = {}
    try:
        for n_items in sizes:
            # rough leg cost model (measured on the CI box): build +
            # queries scale ~linearly with the catalogue
            est_s = 8.0 + 30.0 * n_items / 262144.0
            if time.monotonic() + est_s * 1.3 > leg_deadline:
                log(f"mips leg: skipping {n_items} items "
                    "(deadline too close)")
                break
            vf = planted_item_factors(n_items, rank, seed=11)
            queries = [jnp.asarray(q) for q in
                       planted_queries(vf, n_q, seed=5)]
            oracle = exhaustive_top_k(
                vf, np.stack([np.asarray(q) for q in queries]), 20)
            table = jax.device_put(vf)
            os.environ["PIO_SERVE_MIPS"] = "off"
            ex_p50, ex_p99 = _per_query_ms(queries)
            t0 = time.perf_counter()
            index = mips_mod.build_index(table, n_items, seed=11,
                                         host_factors=vf)
            build_s = time.perf_counter() - t0
            os.environ["PIO_SERVE_MIPS"] = "on"
            two_p50, two_p99 = _per_query_ms(queries)
            # steady state: repeat the warmed shapes — the compile
            # cache must not move (the pow2-ladder contract)
            cache0 = topk.serve_compile_cache_size()
            got = np.stack([
                np.asarray(topk.score_and_top_k(q, table, k=20))[1]
                .astype(np.int64) for q in queries])
            recompiles = topk.serve_compile_cache_size() - cache0
            recall, _worst = recall_against_oracle(got, oracle, 20)
            _nprobe, coarse, rerank = mips_mod.scan_budget(index, 20)
            frac = (coarse + rerank) / n_items
            mips_mod.recall_probe(table, index, host_factors=vf)
            sweep[str(n_items)] = {
                "exhaustive_p50_ms": round(ex_p50, 3),
                "exhaustive_p99_ms": round(ex_p99, 3),
                "two_stage_p50_ms": round(two_p50, 3),
                "two_stage_p99_ms": round(two_p99, 3),
                "build_s": round(build_s, 2),
                "candidates_frac": round(frac, 4),
                "recall_at_20": round(recall, 4),
                "recompiles_steady": int(recompiles),
            }
            log(f"mips {n_items}: exhaustive {ex_p50:.2f}ms vs "
                f"two-stage {two_p50:.2f}ms (recall {recall:.3f}, "
                f"frac {frac:.3f}, build {build_s:.1f}s)")
            if n_items <= 32768:
                out["mips_exhaustive_27k_p99_ms"] = round(ex_p99, 3)
            mips_mod.unregister_index(table)
            del table, vf, queries, index
    finally:
        _restore_mode()
    gate_sizes = [int(s) for s in sweep if int(s) >= 131072]
    if gate_sizes:
        gate = sweep[str(max(gate_sizes))]
        out.update({
            "mips_items": max(gate_sizes),
            "mips_build_s": gate["build_s"],
            "mips_exhaustive_per_query_ms": gate["exhaustive_p50_ms"],
            "mips_exhaustive_p99_ms": gate["exhaustive_p99_ms"],
            "mips_two_stage_per_query_ms": gate["two_stage_p50_ms"],
            "mips_two_stage_p99_ms": gate["two_stage_p99_ms"],
            "mips_speedup": round(
                gate["exhaustive_p50_ms"]
                / max(gate["two_stage_p50_ms"], 1e-9), 3),
            "mips_candidates_frac": gate["candidates_frac"],
            "mips_recall_at_20": gate["recall_at_20"],
            "mips_recompiles_steady": gate["recompiles_steady"],
            # the capacity model's device-bound QPS projection
            # (obs/capacity.py qps_source_key="mips_serve_qps")
            "mips_serve_qps": round(
                1000.0 / max(gate["two_stage_p50_ms"], 1e-9), 1),
        })
    if sweep:
        out["mips_sweep"] = sweep
    return out


#: catalogue-at-tens-of-millions leg (docs/performance.md "Catalogue at
#: tens of millions"): the ≥10M-item lifecycle under PQ residual codes.
#: The recall@20 gate must hold at PQ bytes-per-item, the serving p99
#: measured WHILE a background rebuild-and-swap folds a planted churn
#: tail must stay ≤1.5× the quiet baseline (``mips_rebuild_p99_flat_x``),
#: ``mips_index_age_max_s`` is the worst index age observed across that
#: churn cycle, and ``mips_device_bytes_per_item`` is the capacity
#: model's sizing key (table f32 rerank rows + quantized coarse views +
#: index bookkeeping). None = deadline/budget skip — the default cost
#: model always skips on the 1-core CI box; give the leg a real box via
#: PIO_BENCH_MIPS_BIG_ITEMS / PIO_BENCH_MIPS_BIG_TIMEOUT_S.
MIPS_BIG_KEYS = (
    "mips_big_items", "mips_big_build_s", "mips_big_recall_at_20",
    "mips_big_two_stage_p50_ms", "mips_rebuild_p99_flat_x",
    "mips_index_age_max_s", "mips_device_bytes_per_item",
)


def bench_mips_big(budget_s: float) -> dict:
    """≥10M-item MIPS lifecycle leg: PQ build, recall gate, then serve
    a query loop WHILE ``rebuild_index`` re-clusters and swaps under a
    planted churn tail — the flat-p99-through-rebuild claim. Budget-
    guarded like every host leg: a squeeze nulls keys, never the
    record."""
    out = dict.fromkeys(MIPS_BIG_KEYS)
    n_big = int(os.environ.get("PIO_BENCH_MIPS_BIG_ITEMS", "10000000"))
    rank = int(os.environ.get("PIO_BENCH_MIPS_RANK", "64"))
    n_q = int(os.environ.get("PIO_BENCH_MIPS_QUERIES", "32"))
    if n_big < 1_000_000:
        log("mips big leg disabled (PIO_BENCH_MIPS_BIG_ITEMS < 1M)")
        return out
    # cost model for the CI box: sample-kmeans + chunked assignment +
    # PQ train/encode scale ~linearly with the catalogue, and the
    # rebuild pays it a second time
    est_s = 90.0 + 180.0 * n_big / 1_000_000.0
    leg_deadline = time.monotonic() + min(
        budget_s - 20.0,
        float(os.environ.get("PIO_BENCH_MIPS_BIG_TIMEOUT_S", "300")))
    if time.monotonic() + est_s > leg_deadline:
        log(f"mips big leg skipped: needs ~{est_s:.0f}s, "
            "deadline too close")
        return out
    import threading

    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops import mips as mips_mod
    from incubator_predictionio_tpu.ops import topk
    from incubator_predictionio_tpu.utils.planted import (
        exhaustive_top_k,
        planted_item_factors,
        planted_queries,
        recall_against_oracle,
    )

    saved = {k: os.environ.get(k)
             for k in ("PIO_SERVE_MIPS", "PIO_SERVE_MIPS_QUANT")}
    os.environ["PIO_SERVE_MIPS"] = "on"
    os.environ["PIO_SERVE_MIPS_QUANT"] = "pq"

    def _timed(q) -> float:
        t0 = time.perf_counter()
        np.asarray(topk.score_and_top_k(q, table, k=20))
        return (time.perf_counter() - t0) * 1e3

    try:
        vf = planted_item_factors(n_big, rank, seed=11)
        queries = [jnp.asarray(q) for q in
                   planted_queries(vf, n_q, seed=5)]
        oracle = exhaustive_top_k(
            vf, np.stack([np.asarray(q) for q in queries]), 20)
        table = jax.device_put(vf)
        t0 = time.perf_counter()
        index = mips_mod.build_index(table, n_big, seed=11,
                                     host_factors=vf)
        build_s = time.perf_counter() - t0
        log(f"mips big: built {n_big} items (pq m={index.pq_m}) "
            f"in {build_s:.1f}s")

        _timed(queries[0])                          # warm
        base = np.asarray([_timed(q) for q in queries])
        got = np.stack([
            np.asarray(topk.score_and_top_k(q, table, k=20))[1]
            .astype(np.int64) for q in queries])
        recall, _worst = recall_against_oracle(got, oracle, 20)

        # planted churn past the fold-out point, then serve THROUGH the
        # background rebuild-and-swap
        churn = planted_queries(vf, 256, seed=9)
        mips_mod.publish_rows(table, churn)
        walls: list = []
        ages: list = []

        def _sample_age() -> None:
            idx = mips_mod.index_for(table)
            if idx is not None:
                ages.append(mips_mod._now() - idx.built_at)

        reb = threading.Thread(
            target=lambda: mips_mod.rebuild_index(table, trigger="tail"),
            daemon=True)
        reb.start()
        i = 0
        while reb.is_alive() and time.monotonic() < leg_deadline:
            walls.append(_timed(queries[i % n_q]))
            _sample_age()
            i += 1
        reb.join(timeout=max(leg_deadline - time.monotonic(), 1.0))
        for j in range(8):                          # post-swap tail
            walls.append(_timed(queries[j % n_q]))
            _sample_age()

        p99_base = float(np.quantile(base, 0.99))
        p99_reb = (float(np.quantile(np.asarray(walls), 0.99))
                   if walls else p99_base)
        new = mips_mod.index_for(table)
        dev_bytes = int(np.asarray(table).nbytes)
        for arr in (new.codes, new.scales, new.bf16, new.pq_codes,
                    new.pq_books, new.centroids, new.cmax,
                    new.crad_cos, new.crad_sin, new.members, new.ext):
            if arr is not None:
                dev_bytes += int(arr.nbytes)
        out.update({
            "mips_big_items": n_big,
            "mips_big_build_s": round(build_s, 2),
            "mips_big_recall_at_20": round(recall, 4),
            "mips_big_two_stage_p50_ms": round(
                float(np.quantile(base, 0.5)), 3),
            "mips_rebuild_p99_flat_x": round(
                p99_reb / max(p99_base, 1e-9), 3),
            "mips_index_age_max_s": (round(float(max(ages)), 3)
                                     if ages else None),
            "mips_device_bytes_per_item": round(dev_bytes / n_big, 2),
        })
        log(f"mips big {n_big}: recall {recall:.3f}, rebuild p99 "
            f"{out['mips_rebuild_p99_flat_x']}x flat, "
            f"{out['mips_device_bytes_per_item']} device B/item")
        mips_mod.unregister_index(table)
        del table, vf, queries, index
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


#: serving-fleet leg (docs/production.md "Serving fleet"): the
#: continuous-batching request plane measured across REAL worker
#: processes — goodput burst (real kernels, no floor) for the capacity
#: fit, then an open-loop load ramp against a simulated fixed dispatch
#: wall where queue-depth-adaptive batching must demonstrably engage
#: (fleet_batch_p50 > the old fixed 64) at flat p99
FLEET_KEYS = (
    "fleet_workers", "fleet_qps", "fleet_qps_per_worker",
    "fleet_p99_s", "fleet_p50_ms", "fleet_batch_p50",
    "fleet_shed_rate", "fleet_shed_total", "fleet_p99_ramp_s",
    "fleet_offered_rps_ramp", "fleet_p99_flat_x",
    "fleet_recompiles_steady", "fleet_dispatch_floor_ms",
    # flight-recorder leg keys (docs/observability.md "Flight recorder
    # & incidents"): serving p99 with the recorder + exemplars ON vs
    # recorder OFF (the ≤1.1× overhead pin), and whether the planted
    # over-saturation breach autonomously froze a validated incident
    # bundle
    "recorder_overhead_p99_x", "fleet_incident_captured",
)


def _fleet_worker_env(floor_ms: float, extra: dict = None) -> dict:
    """Environment for a serve-mode fleet worker subprocess: CPU backend
    forced; floored workers get a proportionally relaxed serve_p99
    objective so the simulated dispatch wall itself is not read as an
    overload. ``extra`` overrides land last (the recorder-off baseline
    and the incident stage's breach tuning use this)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    # never inherit the parent's capture destination: only the incident
    # stage's workers are MEANT to freeze bundles
    env.pop("PIO_INCIDENT_DIR", None)
    env["PYTHONPATH"] = (os.path.dirname(os.path.abspath(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env["PIO_SPEED_LAYER"] = "0"
    if floor_ms > 0:
        # the floored ramp measures BATCHING, not shedding: the
        # objective scales with the simulated dispatch wall (p50 is
        # ~1.5 floors by construction, the live p99 estimate rides on
        # top) so the in-capacity stages stay shed-free and the
        # over-saturation stage still crosses it
        env["PIO_SLO_SERVE_P99_S"] = str(max(8.0 * floor_ms / 1000.0,
                                             0.25))
    if extra:
        env.update(extra)
    return env


def _await_port(proc, deadline: float) -> tuple:
    """Bounded wait for a worker's ``PORT <n> [WARM_S <s>]`` line →
    (port, warm_s): a worker that dies during jax import or ladder
    warmup must fail the leg (nulling its keys), never hang the bench
    past the driver's deadline."""
    import select

    ready, _w, _x = select.select(
        [proc.stdout], [], [], max(deadline - time.monotonic(), 1.0))
    line = proc.stdout.readline() if ready else ""
    if not line.startswith("PORT"):
        raise RuntimeError("fleet worker failed to start")
    parts = line.split()
    warm_s = float(parts[3]) if len(parts) >= 4 else 0.0
    return int(parts[1]), warm_s


def _fleet_spawn(n: int, floor_ms: float, max_batch: int = 512,
                 extra_env: dict = None):
    """Spawn ``n`` serve-mode fleet workers (tests/fleet_worker.py) →
    list of (proc, port)."""
    workers = []
    env = _fleet_worker_env(floor_ms, extra=extra_env)
    worker_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tests", "fleet_worker.py")
    for i in range(n):
        proc = subprocess.Popen(
            [sys.executable, worker_py, "--mode", "serve",
             "--seed", str(i), "--max-batch", str(max_batch),
             "--dispatch-floor-ms", str(floor_ms)],
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True)
        workers.append(proc)
    out = []
    deadline = time.monotonic() + 120.0
    for proc in workers:
        try:
            port, _warm = _await_port(proc, deadline)
        except RuntimeError:
            _fleet_teardown([(p, None) for p in workers])
            raise
        out.append((proc, port))
    return out


def _fleet_teardown(workers) -> None:
    for proc, _port in workers:
        try:
            proc.stdin.close()
        except Exception:
            pass
    for proc, _port in workers:
        try:
            proc.wait(timeout=10)
        except Exception:
            proc.kill()


def _fleet_scrape(port: int) -> tuple:
    """ONE ``/metrics`` fetch + parse per worker per bookkeeping point
    → (``pio_serve_batch_size`` cumulative buckets {le: count},
    ``pio_serve_compile_cache_size`` value) — parsed with the SAME
    exposition grammar the federation layer uses (obs/expofmt)."""
    import urllib.request

    from incubator_predictionio_tpu.obs import expofmt

    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=10) as resp:
        text = resp.read().decode()
    _meta, samples = expofmt.parse_exposition(text)
    buckets, _s, _total = expofmt.histogram_series(
        samples, "pio_serve_batch_size")
    cache = samples.get(("pio_serve_compile_cache_size", frozenset()),
                        0.0)
    return {le: v for le, v in buckets}, float(cache)


def _stage_p99(walls) -> float:
    """One ramp stage's p99: the MEDIAN of the p99s of three
    consecutive sub-windows. The plain full-stage p99 is set by a
    handful of worst samples, and on a small shared box one transient
    scheduling burst flips it by 2×+ run to run — the median-of-thirds
    estimator reports the stage's steady tail instead of its single
    worst second (all stages use the same estimator, so the flatness
    ratio compares like with like)."""
    arr = np.asarray(walls, np.float64)
    thirds = np.array_split(arr, 3)
    p99s = [float(np.quantile(t, 0.99)) for t in thirds if len(t)]
    return float(np.median(p99s))


def _bucket_quantile(cum: dict, q: float):
    """Quantile by linear interpolation over de-cumulated bucket counts
    (the registry's own quantile rule, over scraped buckets)."""
    bounds = sorted(cum.items())
    total = bounds[-1][1] if bounds else 0.0
    if total <= 0:
        return None
    target = q * total
    lo, prev = 0.0, 0.0
    for bound, c in bounds:
        if c >= target:
            in_bucket = c - prev
            if bound == float("inf"):
                return lo
            return lo + (bound - lo) * (
                (target - prev) / in_bucket if in_bucket else 0.0)
        prev, lo = c, bound
    return lo


async def _fleet_request(reader, writer, body: bytes,
                         path: bytes = b"/queries.json"):
    """One framed query request/response on a kept-alive connection →
    (status, wall seconds). The ONE copy of the fleet generators' HTTP
    framing (closed-loop burst and open-loop ramp share it); 503 sheds
    are results, not errors — the Retry-After contract is part of the
    plane under test. ``path`` carries a per-tenant ``?accessKey=`` in
    the multi-tenant leg."""
    t0 = time.perf_counter()
    writer.write(
        b"POST " + path + b" HTTP/1.1\r\nHost: bench\r\n"
        b"Content-Type: application/json\r\n"
        + f"X-PIO-Trace-Id: {_bench_trace_id()}\r\n"
          f"Content-Length: {len(body)}\r\n\r\n".encode()
        + body)
    await writer.drain()
    head = await reader.readuntil(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    clen = next(
        (int(line.split(b":")[1]) for line in head.split(b"\r\n")
         if line.lower().startswith(b"content-length")), 0)
    if clen:
        await reader.readexactly(clen)
    return status, time.perf_counter() - t0


async def _fleet_closed_loop(port: int, n_clients: int, per_client: int,
                             results: list,
                             path: bytes = b"/queries.json") -> None:
    """Closed-loop burst: every client fires its next query the moment
    the previous answers (the max-goodput shape)."""
    import asyncio

    async def one(cid: int) -> None:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            for j in range(per_client):
                body = json.dumps({
                    "user": f"u{(cid * per_client + j) % 2000}",
                    "num": 10}).encode()
                status, dt = await _fleet_request(reader, writer, body,
                                                 path=path)
                results.append((status, dt, False))
        finally:
            writer.close()

    await asyncio.gather(*[one(c) for c in range(n_clients)])


async def _fleet_open_loop(port: int, rate_rps: float, duration_s: float,
                           results: list, period_s: float = 2.0,
                           path: bytes = b"/queries.json") -> None:
    """Open-loop stage: connections send on a fixed schedule (offered
    load is the independent variable), so below saturation the latency
    distribution reflects the serving plane, not Little's-law queueing
    at the generator."""
    import asyncio

    # per-connection send period must comfortably exceed the worst
    # plausible RTT or a slow response silently throttles the offered
    # rate and bunches arrivals (coordinated omission) — the caller
    # scales period_s with the simulated dispatch floor
    conns = max(8, int(rate_rps * period_s))
    per_conn = max(int(rate_rps * duration_s / conns), 1)
    period = conns / rate_rps

    async def one(cid: int) -> None:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            # golden-ratio phase jitter: near-uniform send phases over
            # the whole period (a modulo-N jitter bunches hundreds of
            # conns into N bursts, and the burst shows up as measured
            # tail latency)
            next_t = time.perf_counter() + period * ((cid * 0.618) % 1.0)
            for j in range(per_conn):
                now = time.perf_counter()
                if next_t > now:
                    await asyncio.sleep(next_t - now)
                next_t += period
                body = json.dumps({
                    "user": f"u{(cid * per_conn + j) % 2000}",
                    "num": 10}).encode()
                status, dt = await _fleet_request(reader, writer, body,
                                                 path=path)
                # EVERY response is recorded (shed/offered accounting
                # must see first requests too — the stage-boundary herd
                # is exactly when sheds happen); the True flag marks a
                # connection's first request so only the LATENCY sample
                # excludes its connect + herd transient
                results.append((status, dt, j == 0))
        finally:
            writer.close()

    await asyncio.gather(*[one(c) for c in range(conns)])


def bench_fleet(budget_s: float) -> dict:
    """Serving-fleet leg: N real worker processes behind the
    continuous-batching scheduler, measured in two sub-legs.

    1. **Goodput burst** (no dispatch floor): closed-loop clients
       against every worker at once → ``fleet_qps`` /
       ``fleet_qps_per_worker`` — the REAL per-process serving
       capacity the capacity model (obs/capacity.py) learns from.
    2. **Scheduler ramp** (``fleet_dispatch_floor_ms`` simulated
       per-dispatch device wall — the fixed cost that makes fusing a
       deeper queue win on a real accelerator): open-loop offered-rate
       stages. Queue-depth-adaptive batching must demonstrably engage
       (``fleet_batch_p50`` over the PEAK stage's dispatches, from the
       workers' scraped ``pio_serve_batch_size`` deltas) while p99
       stays flat across the ramp (``fleet_p99_flat_x`` =
       peak-stage p99 / first-stage p99), with zero steady-state
       recompiles (``fleet_recompiles_steady`` — compile-cache gauge
       delta across the peak stage). A final over-saturation burst
       lets the SLO shed path engage (``fleet_shed_rate``).

    Guarded like bench_shard: any failure nulls the fleet_* keys,
    never the record."""
    import asyncio

    out = dict.fromkeys(FLEET_KEYS)
    # the full leg costs ~60-90 s on a quiet box (2 spawn rounds + warm
    # + 3 ramp stages + overload); the floor leaves real margin and the
    # leg DEADLINE below bounds every wait so a loaded box cannot eat
    # the supervised child's window (the bench_shard discipline)
    if budget_s < 180.0:
        log("fleet leg skipped: bench deadline too close")
        return out
    leg_deadline = time.monotonic() + min(
        budget_s - 60.0,
        float(os.environ.get("PIO_BENCH_FLEET_TIMEOUT_S", "300")))

    def left(cap: float) -> float:
        return max(min(cap, leg_deadline - time.monotonic()), 5.0)
    n_workers = int(os.environ.get("PIO_BENCH_FLEET_WORKERS", "2"))
    # floor 500 ms keeps the batch-linear host work (parse + render,
    # ~1 ms/query on the CPU sim) small next to the simulated dispatch
    # wall at every ramp stage, so the p99-flatness measurement
    # reflects the scheduler, not CPU render costs growing with batch
    floor_ms = float(os.environ.get("PIO_BENCH_FLEET_FLOOR_MS", "500"))
    # peak sized for sustained queue depth ≈ rate × floor ≈ 80 (> the
    # old fixed 64 with margin) while staying under the host's
    # admission knee, where tail waits would jump a whole extra
    # dispatch cycle and the flatness figure would measure host
    # contention instead of the scheduler
    ramp = [float(r) for r in os.environ.get(
        "PIO_BENCH_FLEET_RAMP_RPS", "60,100,160").split(",") if r]
    stage_s = float(os.environ.get("PIO_BENCH_FLEET_STAGE_S", "10"))
    #: per-connection send period for the open-loop generators: must
    #: dominate the worst-case RTT (several dispatch floors) or slow
    #: responses bunch the offered schedule (coordinated omission) —
    #: but not much more, since conns = rate × period and a huge conn
    #: count makes the generator itself the bottleneck on small boxes
    period_s = max(2.0, 4.0 * floor_ms / 1000.0)
    out["fleet_workers"] = n_workers
    out["fleet_dispatch_floor_ms"] = floor_ms

    # -- sub-leg 1: goodput burst (real dispatch cost, no floor) ------------
    # run the SAME closed-loop burst against a recorder-off baseline
    # fleet and then the production config (recorder sampling at 1 Hz +
    # histogram trace exemplars — both on by default): the p99 ratio is
    # the flight recorder's serving-overhead pin (≤ 1.1×, asserted in
    # test_bench_e2e). Two measured bursts per config with a min-p99
    # reduction: scheduler noise on a shared box only ever INFLATES a
    # p99, so the min of repeated measurements is the honest estimate
    # of each config's floor — applied symmetrically to both configs.
    recorder_cfgs = (
        ("off", {"PIO_RECORDER": "0", "PIO_EXEMPLARS": "0"}),
        ("on", {"PIO_RECORDER": "1", "PIO_EXEMPLARS": "1"}),
    )
    p99_by_cfg: dict = {}
    for cfg_name, cfg_env in recorder_cfgs:
        workers = _fleet_spawn(n_workers, floor_ms=0.0,
                               extra_env=cfg_env)
        try:
            # untimed warm mini-burst: connects + kernel caches settle
            results: list = []

            async def warm_burst() -> None:
                await asyncio.gather(*[
                    _fleet_closed_loop(port, 16, 5, results)
                    for _proc, port in workers])

            asyncio.run(asyncio.wait_for(warm_burst(),
                                         timeout=left(60.0)))
            p99s = []
            for _rep in range(2):
                results = []
                t0 = time.perf_counter()

                async def burst() -> None:
                    await asyncio.gather(*[
                        _fleet_closed_loop(port, 64, 25, results)
                        for _proc, port in workers])

                asyncio.run(asyncio.wait_for(burst(),
                                             timeout=left(120.0)))
                wall = time.perf_counter() - t0
                served = [d for s, d, _f in results if s == 200]
                if served:
                    p99s.append(_stage_p99(served))
                if cfg_name == "on":
                    # the headline capacity figures come from the
                    # PRODUCTION config (recorder on), best rep
                    qps = round(len(served) / wall, 1)
                    if out["fleet_qps"] is None or qps > out["fleet_qps"]:
                        out["fleet_qps"] = qps
                        out["fleet_qps_per_worker"] = round(
                            len(served) / wall / n_workers, 1)
            if p99s:
                p99_by_cfg[cfg_name] = min(p99s)
        finally:
            _fleet_teardown(workers)
    if p99_by_cfg.get("off") and p99_by_cfg.get("on"):
        out["recorder_overhead_p99_x"] = round(
            p99_by_cfg["on"] / p99_by_cfg["off"], 3)

    # -- sub-leg 2: scheduler ramp against the simulated dispatch wall ------
    workers = _fleet_spawn(n_workers, floor_ms=floor_ms)
    try:
        # untimed warm pass at the base rate: the rung ladder and the
        # EWMA dispatch wall settle BEFORE the first measured stage, so
        # the flatness baseline is steady-state behavior, not the
        # adaptation transient
        results = []

        async def warm() -> None:
            await asyncio.gather(*[
                _fleet_open_loop(port, ramp[0], 3.0, results,
                                 period_s=period_s)
                for _proc, port in workers])

        asyncio.run(asyncio.wait_for(warm(), timeout=left(60.0)))
        stage_p99: list = []
        shed_total = 0
        offered_total = 0
        peak_batch_p50 = None
        recompiles = None
        for si, rate in enumerate(ramp):
            peak = si == len(ramp) - 1
            if peak:
                pre = [_fleet_scrape(port) for _p, port in workers]
                h0 = [h for h, _c in pre]
                c0 = sum(c for _h, c in pre)
            results = []

            async def stage() -> None:
                await asyncio.gather(*[
                    _fleet_open_loop(port, rate, stage_s, results,
                                     period_s=period_s)
                    for _proc, port in workers])

            asyncio.run(asyncio.wait_for(
                stage(), timeout=left(max(6 * stage_s, 60.0))))
            # completion order ≈ time order: the sub-window estimator
            # wants the stage's chronology, not a sorted tail. Latency
            # samples exclude first-per-connection transients; the
            # shed/offered tallies count EVERYTHING.
            served = [d for s, d, f in results if s == 200 and not f]
            shed_total += sum(1 for s, _d, _f in results if s == 503)
            offered_total += len(results)
            if served:
                stage_p99.append(_stage_p99(served))
            if peak:
                post = [_fleet_scrape(port) for _p, port in workers]
                h1 = [h for h, _c in post]
                c1 = sum(c for _h, c in post)
                merged: dict = {}
                for a, b in zip(h0, h1):
                    for le, v in b.items():
                        merged[le] = merged.get(le, 0.0) \
                            + v - a.get(le, 0.0)
                peak_batch_p50 = _bucket_quantile(merged, 0.5)
                recompiles = int(c1 - c0)
                if served:
                    # the headline figures use the same robust stage
                    # estimator as the flatness ratio
                    out["fleet_p99_s"] = round(stage_p99[-1], 4)
                    out["fleet_p50_ms"] = round(
                        float(np.median(served)) * 1e3, 1)
        # over-saturation burst: give the shed path real pressure
        results = []

        async def overload() -> None:
            await asyncio.gather(*[
                _fleet_open_loop(port, 4 * ramp[-1], 3.0, results,
                                 period_s=period_s)
                for _proc, port in workers])

        try:
            if time.monotonic() < leg_deadline:
                asyncio.run(asyncio.wait_for(overload(),
                                             timeout=left(90.0)))
        except asyncio.TimeoutError:
            pass
        shed_total += sum(1 for s, _d, _f in results if s == 503)
        offered_total += len(results)
        out["fleet_p99_ramp_s"] = [round(p, 4) for p in stage_p99]
        out["fleet_offered_rps_ramp"] = ramp
        if len(stage_p99) >= 2 and stage_p99[0] > 0:
            out["fleet_p99_flat_x"] = round(
                stage_p99[-1] / stage_p99[0], 3)
        out["fleet_batch_p50"] = (round(peak_batch_p50, 1)
                                  if peak_batch_p50 else None)
        out["fleet_recompiles_steady"] = recompiles
        out["fleet_shed_total"] = shed_total
        out["fleet_shed_rate"] = round(
            shed_total / max(offered_total, 1), 4)
    finally:
        _fleet_teardown(workers)

    # -- incident stage: over-saturation with the recorder ON must land
    # ONE validated bundle autonomously ------------------------------------
    # A dedicated 2-worker set tuned so the breach is DETERMINISTIC:
    # shed disabled (the shed path was proven above; this stage's job
    # is the capture plane) and a planted sub-microsecond serve_p99
    # objective, so EVERY served query is a bad observation → the
    # worker's own SLO engine (armed by the recorder route +
    # PIO_INCIDENT_DIR) crosses fast burn within a recorder tick and
    # the capture engine freezes the bundle with zero bench-side help.
    if time.monotonic() + 60.0 < leg_deadline:
        import tempfile

        inc_dir = tempfile.mkdtemp(prefix="pio_bench_incidents_")
        workers = _fleet_spawn(2, floor_ms=0.0, extra_env={
            "PIO_INCIDENT_DIR": inc_dir,
            "PIO_RECORDER": "1",
            "PIO_RECORDER_HZ": "5",
            "PIO_SERVE_SHED": "0",
            "PIO_SLO_SERVE_P99_S": "0.000001",
            "PIO_INCIDENT_COOLDOWN_S": "300",
        })
        try:
            results = []

            async def breach_load() -> None:
                await asyncio.gather(*[
                    _fleet_closed_loop(port, 8, 10, results)
                    for _proc, port in workers])

            asyncio.run(asyncio.wait_for(breach_load(),
                                         timeout=left(90.0)))
            bundle_path = None
            poll_until = min(time.monotonic() + 25.0, leg_deadline)
            while time.monotonic() < poll_until:
                found = sorted(f for f in os.listdir(inc_dir)
                               if f.endswith(".json"))
                if found:
                    bundle_path = os.path.join(inc_dir, found[0])
                    break
                time.sleep(0.5)
            captured = False
            if bundle_path is not None:
                # the artifact must also pass the report tool's schema
                # gate — a bundle nobody can render is not a capture
                check = subprocess.run(
                    [sys.executable,
                     os.path.join(os.path.dirname(
                         os.path.abspath(__file__)), "scripts",
                         "incident_report.py"),
                     bundle_path, "--check"],
                    capture_output=True, timeout=60)
                captured = check.returncode == 0
            out["fleet_incident_captured"] = captured
        except Exception as e:  # noqa: BLE001 — leg guard, never the record
            log(f"fleet incident stage failed: {e}")
        finally:
            _fleet_teardown(workers)
    else:
        log("fleet incident stage skipped: leg deadline too close")

    log(f"fleet: {n_workers} workers qps={out['fleet_qps']} "
        f"batch_p50={out['fleet_batch_p50']} "
        f"p99_flat={out['fleet_p99_flat_x']}x "
        f"shed_rate={out['fleet_shed_rate']} "
        f"recompiles={out['fleet_recompiles_steady']} "
        f"recorder_overhead={out['recorder_overhead_p99_x']}x "
        f"incident={out['fleet_incident_captured']}")
    return out


#: fleet front-door leg (docs/production.md "Fleet front door"): the
#: health-checked router proven ADVERSARIALLY — a worker killed
#: mid-ramp, a warm-cache worker joined mid-ramp, and one rolling
#: fleet-wide reload mid-traffic, with zero non-shed 5xx and zero
#: drain drops as the acceptance bars
FRONTDOOR_KEYS = (
    "frontdoor_workers", "frontdoor_qps", "frontdoor_p99_ramp_s",
    "frontdoor_offered_rps_ramp", "frontdoor_p99_flat_x",
    "frontdoor_nonshed_5xx", "frontdoor_shed_total",
    "frontdoor_retries", "frontdoor_reloaded", "frontdoor_drain_dropped",
    "frontdoor_join_cold_s", "frontdoor_join_warm_s",
    "frontdoor_join_to_first_dispatch_s",
)


def _frontdoor_spawn(seed: int, chaos: str = "",
                     max_batch: int = 512, floor_ms: float = 0.0):
    """One serve-mode worker on the FLEET-SHARED persistent XLA compile
    cache (the one directory utils/compile_cache.py resolves, exported
    to the worker) → (proc, port, warm_s). The min-compile-time floor is
    zeroed so even the CPU sim's fast ladder compiles populate the
    cache — the join pre-warm delta stays measurable off-TPU."""
    from incubator_predictionio_tpu.utils import compile_cache

    env = _fleet_worker_env(floor_ms)
    env["JAX_COMPILATION_CACHE_DIR"] = compile_cache.cache_dir()
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.0"
    worker_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tests", "fleet_worker.py")
    cmd = [sys.executable, worker_py, "--mode", "serve",
           "--seed", str(seed), "--max-batch", str(max_batch),
           "--dispatch-floor-ms", str(floor_ms), "--compile-cache"]
    if chaos:
        cmd += ["--chaos", chaos]
    proc = subprocess.Popen(cmd, env=env, stdin=subprocess.PIPE,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True)
    try:
        port, warm_s = _await_port(proc, time.monotonic() + 120.0)
    except RuntimeError:
        _fleet_teardown([(proc, None)])
        raise
    return proc, port, warm_s


def bench_frontdoor(budget_s: float) -> dict:
    """Fleet front-door leg: one address over real worker processes,
    chaos-proven. The ramp runs THROUGH the front door while the leg
    injects every fault the router must absorb:

    - stage 1: steady baseline (the p99 denominator);
    - stage 2: a rolling fleet-wide ``/reload`` fires mid-traffic
      (drain → warm-before-swap → re-admit, one worker at a time), and
      the victim worker hard-exits on its own ``--chaos kill-after``
      timer (in-flight connection resets — the single-retry path);
      the moment the victim dies a REPLACEMENT worker is spawned
      against the fleet-shared compile cache and joined mid-traffic
      (``frontdoor_join_to_first_dispatch_s`` = spawn → its first
      routed query);
    - stage 3: the healed fleet at the same offered rate (recovery
      must hold, not just survive the transient).

    Bars: ``frontdoor_nonshed_5xx`` == 0 (every failure either retried
    to a healthy peer or shed with the 503 + Retry-After contract),
    ``frontdoor_drain_dropped`` == 0 (rolling reload drops nothing),
    ``frontdoor_p99_flat_x`` ≤ 1.5 across the chaos. The cold/warm
    ladder-warmup delta off the shared cache is recorded
    (``frontdoor_join_cold_s`` vs ``frontdoor_join_warm_s``).

    Guarded like bench_fleet: any failure nulls the frontdoor_* keys,
    never the record."""
    import asyncio
    import threading

    from incubator_predictionio_tpu.serving.frontdoor import (
        FrontDoor,
        FrontDoorConfig,
    )

    out = dict.fromkeys(FRONTDOOR_KEYS)
    if budget_s < 120.0:
        log("frontdoor leg skipped: bench deadline too close")
        return out
    leg_deadline = time.monotonic() + min(
        budget_s - 45.0,
        float(os.environ.get("PIO_BENCH_FRONTDOOR_TIMEOUT_S", "240")))

    def left(cap: float) -> float:
        return max(min(cap, leg_deadline - time.monotonic()), 5.0)

    # a FLAT offered rate across the stages: bench_fleet already pins
    # p99-vs-load, so holding load constant makes the flatness ratio
    # measure CHAOS alone (stage 1 = quiet baseline, stages 2-3 =
    # kill + join + rolling reload at the same offered rate)
    ramp = [float(r) for r in os.environ.get(
        "PIO_BENCH_FRONTDOOR_RAMP_RPS", "100,100,100").split(",") if r]
    stage_s = float(os.environ.get("PIO_BENCH_FRONTDOOR_STAGE_S", "8"))
    # a small simulated dispatch floor makes per-query latency
    # deterministic (floor-dominated) instead of scheduler-jitter-
    # dominated, so the p99 ratio resolves chaos, not CPU noise
    floor_ms = float(os.environ.get("PIO_BENCH_FRONTDOOR_FLOOR_MS", "25"))
    workers = []   # (proc, port) for teardown
    fd = None
    # join_thread races the finally-block teardown: the replacement
    # worker must either land in `workers` BEFORE teardown iterates it
    # or not spawn at all — otherwise an early stage failure leaks a
    # jax subprocess into the rest of the bench run
    spawn_lock = threading.Lock()
    leg_done = threading.Event()
    try:
        # worker A first (cold only when the shared cache directory holds
        # nothing for its ladder yet), worker B warm from A's compiles;
        # B is the VICTIM — its kill-after timer (armed at
        # its own serving start) lands ~0.6 into stage 2
        kill_after = 3.0 + 1.6 * stage_s + 1.0
        proc_a, port_a, warm_cold = _frontdoor_spawn(
            0, floor_ms=floor_ms)
        workers.append((proc_a, port_a))
        proc_b, port_b, warm_warm = _frontdoor_spawn(
            1, chaos=f"kill-after={kill_after:.1f}",
            floor_ms=floor_ms)
        workers.append((proc_b, port_b))
        out["frontdoor_join_cold_s"] = round(warm_cold, 3)
        out["frontdoor_join_warm_s"] = round(warm_warm, 3)
        out["frontdoor_workers"] = 2

        fd = FrontDoor(
            [("127.0.0.1", port_a), ("127.0.0.1", port_b)],
            FrontDoorConfig(request_timeout_s=8.0, attempt_timeout_s=3.0,
                            probe_interval_s=0.5, open_cooldown_s=1.0))
        fport = fd.start_background()

        results: list = []
        reload_out: dict = {}
        join_out: dict = {}

        def reload_thread() -> None:
            time.sleep(0.5)  # let stage 2 traffic establish first
            try:
                reload_out.update(fd.rolling_reload(timeout=left(120.0)))
            except Exception as e:  # noqa: BLE001 — nulls the keys
                log(f"frontdoor rolling reload failed ({e!r})")

        def join_thread() -> None:
            # the elasticity path: the moment the victim dies, spawn a
            # replacement against the WARM shared cache and measure
            # spawn → first query the front door routes to it
            proc_b.wait()
            t0 = time.perf_counter()
            with spawn_lock:
                if leg_done.is_set():
                    return  # teardown already ran; don't leak a worker
                try:
                    proc_c, port_c, _w = _frontdoor_spawn(
                        2, floor_ms=floor_ms)
                except Exception as e:  # noqa: BLE001
                    log(f"frontdoor join worker failed to spawn ({e!r})")
                    return
                workers.append((proc_c, port_c))
            name = fd.add_worker("127.0.0.1", port_c)
            while time.monotonic() < leg_deadline:
                served = next(
                    (w["requests"] for w in fd.stats()["workers"]
                     if w["name"] == name), 0)
                if served > 0:
                    join_out["join_s"] = time.perf_counter() - t0
                    return
                time.sleep(0.05)

        # untimed warm pass: ladder rungs + EWMA walls settle before
        # the measured baseline (every response still counts toward
        # the 5xx/shed tallies — chaos accounting is total)
        async def run_stage(rate: float, dur: float) -> None:
            await _fleet_open_loop(fport, rate, dur, results,
                                   period_s=2.0)

        asyncio.run(asyncio.wait_for(run_stage(ramp[0], 3.0),
                                     timeout=left(60.0)))
        warm_end = len(results)  # qps counts measured stages only
        stage_p99: list = []
        chaos_threads: list = []
        stage_walls = 0.0
        for si, rate in enumerate(ramp):
            if si == 1:
                for fn in (reload_thread, join_thread):
                    t = threading.Thread(target=fn, daemon=True)
                    t.start()
                    chaos_threads.append(t)
            stage_results_start = len(results)
            t_stage = time.perf_counter()
            asyncio.run(asyncio.wait_for(
                run_stage(rate, stage_s),
                timeout=left(max(6 * stage_s, 60.0))))
            stage_walls += time.perf_counter() - t_stage
            served = [d for s, d, f in results[stage_results_start:]
                      if s == 200 and not f]
            if served:
                stage_p99.append(_stage_p99(served))
        for t in chaos_threads:
            t.join(timeout=left(60.0))

        ok_total = sum(1 for s, _d, _f in results[warm_end:] if s == 200)
        out["frontdoor_qps"] = round(ok_total / max(stage_walls, 1e-9), 1)
        out["frontdoor_p99_ramp_s"] = [round(p, 4) for p in stage_p99]
        out["frontdoor_offered_rps_ramp"] = ramp
        if len(stage_p99) >= 2 and stage_p99[0] > 0:
            out["frontdoor_p99_flat_x"] = round(
                max(stage_p99[1:]) / stage_p99[0], 3)
        out["frontdoor_nonshed_5xx"] = sum(
            1 for s, _d, _f in results if s >= 500 and s != 503)
        out["frontdoor_shed_total"] = sum(
            1 for s, _d, _f in results if s == 503)
        out["frontdoor_retries"] = fd.counts["retries"]
        out["frontdoor_reloaded"] = reload_out.get("reloaded")
        out["frontdoor_drain_dropped"] = reload_out.get("dropped")
        if "join_s" in join_out:
            out["frontdoor_join_to_first_dispatch_s"] = round(
                join_out["join_s"], 2)
    finally:
        with spawn_lock:
            leg_done.set()
        if fd is not None:
            fd.stop()
        _fleet_teardown(workers)
    log(f"frontdoor: p99_flat={out['frontdoor_p99_flat_x']}x "
        f"nonshed_5xx={out['frontdoor_nonshed_5xx']} "
        f"drain_dropped={out['frontdoor_drain_dropped']} "
        f"retries={out['frontdoor_retries']} "
        f"join={out['frontdoor_join_to_first_dispatch_s']}s "
        f"(warmup cold={out['frontdoor_join_cold_s']}s "
        f"warm={out['frontdoor_join_warm_s']}s)")
    return out


TENANT_KEYS = (
    "tenant_workers", "tenant_victim_solo_p99_s",
    "tenant_victim_flood_p99_s", "tenant_victim_p99_x",
    "tenant_victim_shed_rate", "tenant_aggressor_shed_total",
    "tenant_aggressor_shed_rate", "tenant_isolation",
    "tenant_reload_nonshed_5xx", "tenant_reloaded",
)


#: stage-B aggressor flood driver for bench_tenants — run as a
#: SEPARATE stdlib-only subprocess (``python -c``) so the flood
#: generator never shares an event loop, a GIL, or an import graph
#: with the victim's timing loop. Params via env (FLOOD_TARGETS,
#: FLOOD_PATH, FLOOD_CLIENTS, FLOOD_BACKOFF_S); floods keep-alive
#: closed-loop with a shed backoff until SIGTERM, then prints its
#: {total, shed, other} counts as one JSON line and exits.
_TENANT_FLOOD_SRC = r"""
import asyncio, json, os, signal, sys

targets = [t.rsplit(":", 1)
           for t in os.environ["FLOOD_TARGETS"].split(",")]
path = os.environ["FLOOD_PATH"]
clients = int(os.environ["FLOOD_CLIENTS"])
backoff = float(os.environ["FLOOD_BACKOFF_S"])
counts = {"total": 0, "shed": 0, "other": 0}


async def one(cid, stop):
    host, port = targets[cid % len(targets)]
    reader = writer = None
    j = 0
    while not stop.is_set():
        try:
            if writer is None:
                reader, writer = await asyncio.open_connection(
                    host, int(port))
            body = json.dumps({"user": "u%d" % ((cid * 977 + j) % 2000),
                               "num": 10}).encode()
            j += 1
            writer.write(("POST %s HTTP/1.1\r\nHost: bench\r\n"
                          "Content-Type: application/json\r\n"
                          "Content-Length: %d\r\n\r\n"
                          % (path, len(body))).encode() + body)
            await writer.drain()
            line = await reader.readline()
            if not line:
                raise ConnectionError("closed")
            status = int(line.split()[1])
            clen = 0
            while True:
                h = await reader.readline()
                if h in (b"\r\n", b"", b"\n"):
                    break
                if h.lower().startswith(b"content-length:"):
                    clen = int(h.split(b":", 1)[1])
            if clen:
                await reader.readexactly(clen)
            counts["total"] += 1
            if status == 503:
                counts["shed"] += 1
                await asyncio.sleep(backoff)
            elif status != 200:
                counts["other"] += 1
        except asyncio.CancelledError:
            break
        except Exception:
            if writer is not None:
                try:
                    writer.close()
                except Exception:
                    pass
            reader = writer = None
            await asyncio.sleep(0.1)


async def main():
    stop = asyncio.Event()
    asyncio.get_running_loop().add_signal_handler(
        signal.SIGTERM, stop.set)
    tasks = [asyncio.create_task(one(c, stop)) for c in range(clients)]
    await stop.wait()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    print(json.dumps(counts))
    sys.stdout.flush()


asyncio.run(main())
"""


def bench_tenants(budget_s: float) -> dict:
    """Multi-tenant noisy-neighbor leg: two co-resident tenants on a
    real 2-worker fleet behind the front door, per-tenant accessKey
    auth end to end (serving/tenancy.py).

    - stage A: the VICTIM tenant alone at a modest open-loop rate —
      its solo p99 is the denominator;
    - stage B: the same victim rate while the AGGRESSOR tenant floods
      closed-loop past its admission quota. Weighted-fair dispatch +
      per-tenant quota shedding mean the aggressor sheds ITS OWN
      traffic (503 + Retry-After) while the victim's p99 stays inside
      its own objective;
    - stage C: a TENANT-SCOPED rolling reload of the aggressor's
      deploy fires mid-victim-traffic (``/reload?tenant=aggressor``
      through the front door's drain choreography) — the victim keeps
      serving with zero non-shed 5xx.

    Bars (tests/test_bench_e2e.py): ``tenant_victim_p99_x`` ≤ 1.5,
    ``tenant_victim_shed_rate`` == 0, ``tenant_isolation`` is True
    (aggressor shed > 0 AND victim shed == 0, from the workers' own
    per-tenant /status blocks), ``tenant_reload_nonshed_5xx`` == 0.
    Guarded like bench_fleet: any failure nulls the tenant_* keys,
    never the record."""
    import asyncio
    import threading
    import urllib.request

    from incubator_predictionio_tpu.serving import tenancy
    from incubator_predictionio_tpu.serving.frontdoor import (
        FrontDoor,
        FrontDoorConfig,
    )

    out = dict.fromkeys(TENANT_KEYS)
    if budget_s < 120.0:
        log("tenants leg skipped: bench deadline too close")
        return out
    leg_deadline = time.monotonic() + min(
        budget_s - 45.0,
        float(os.environ.get("PIO_BENCH_TENANT_TIMEOUT_S", "240")))

    def left(cap: float) -> float:
        return max(min(cap, leg_deadline - time.monotonic()), 5.0)

    stage_s = float(os.environ.get("PIO_BENCH_TENANT_STAGE_S", "8"))
    # same rationale as bench_frontdoor: a simulated dispatch floor
    # makes per-query latency floor-dominated, so the victim's p99
    # ratio resolves ISOLATION, not CPU scheduling noise
    floor_ms = float(os.environ.get("PIO_BENCH_TENANT_FLOOR_MS", "25"))
    victim_rps = float(os.environ.get(
        "PIO_BENCH_TENANT_VICTIM_RPS", "60"))
    flood_clients = int(os.environ.get(
        "PIO_BENCH_TENANT_FLOOD_CLIENTS", "12"))
    # the tenant registry BOTH planes parse: the workers admit/shed by
    # it, and the in-process front door authenticates against it. The
    # aggressor's quota is far below its closed-loop concurrency so
    # the flood sheds at admission; the victim's weight buys it the
    # dispatch tie-break under contention.
    spec = ("victim:bench-victim-key:weight=8;"
            "aggressor:bench-aggressor-key:weight=1,quota=2")
    vpath = b"/queries.json?accessKey=bench-victim-key"
    apath = b"/queries.json?accessKey=bench-aggressor-key"

    prev_spec = os.environ.get("PIO_TENANTS")
    os.environ["PIO_TENANTS"] = spec
    tenancy.reset_registry()
    workers = []
    fd = None
    try:
        # 3 dispatcher threads per worker: the floor-padded dispatches
        # sleep, so extra threads hide a victim dispatch behind the
        # aggressor's in-flight one (the documented device-path use of
        # the knob). With the scheduler's weighted slot caps the
        # aggressor holds at most ceil(3·1/9)=1 slot, so the victim
        # keeps ≥2 concurrent slots under flood — the same headroom
        # its solo baseline enjoys — instead of eating a full
        # in-flight flood dispatch before its own turn
        workers = _fleet_spawn(2, floor_ms,
                               extra_env={"PIO_TENANTS": spec,
                                          "PIO_SERVE_WORKERS": "3"})
        out["tenant_workers"] = len(workers)
        fd = FrontDoor(
            [("127.0.0.1", port) for _proc, port in workers],
            FrontDoorConfig(request_timeout_s=8.0, attempt_timeout_s=3.0,
                            probe_interval_s=0.5, open_cooldown_s=1.0))
        fport = fd.start_background()

        # untimed warm pass: ladder rungs + EWMA walls settle before
        # the measured solo baseline
        asyncio.run(asyncio.wait_for(
            _fleet_open_loop(fport, victim_rps, 3.0, [], path=vpath),
            timeout=left(60.0)))

        # stage A: victim solo baseline
        solo: list = []
        asyncio.run(asyncio.wait_for(
            _fleet_open_loop(fport, victim_rps, stage_s, solo,
                             path=vpath),
            timeout=left(max(6 * stage_s, 60.0))))

        # stage B: victim at the same rate + aggressor flood. The
        # flood runs in a SEPARATE dependency-free subprocess aimed
        # straight at the workers (not the in-process front door): on
        # a small box, flood coroutines sharing the bench event loop
        # would bill their own scheduling delay to the victim's
        # measured tail — the victim's p99 must resolve SERVER-side
        # isolation, not generator contention. The flood still crosses
        # the workers' accessKey auth and per-tenant quota admission;
        # stage B waits for shed evidence in the workers' /status
        # tenants blocks before the victim's measured pass begins.
        flood_v: list = []
        flood_counts: dict = {}
        flood_env = dict(os.environ)
        flood_env.update({
            "FLOOD_TARGETS": ",".join(
                f"127.0.0.1:{port}" for _proc, port in workers),
            "FLOOD_PATH": apath.decode("ascii"),
            "FLOOD_CLIENTS": str(flood_clients),
            "FLOOD_BACKOFF_S": "0.5",
        })
        flood_proc = subprocess.Popen(
            [sys.executable, "-c", _TENANT_FLOOD_SRC],
            env=flood_env, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL)
        try:
            ramp_deadline = time.monotonic() + left(20.0)
            while time.monotonic() < ramp_deadline:
                shed = 0
                for _proc, port in workers:
                    try:
                        with urllib.request.urlopen(
                                f"http://127.0.0.1:{port}/",
                                timeout=5) as resp:
                            info = json.loads(resp.read())
                        shed += int(((info.get("tenants") or {})
                                     .get("aggressor") or {})
                                    .get("shed") or 0)
                    except Exception:  # noqa: BLE001 — still ramping
                        pass
                if shed > 0:
                    break
                time.sleep(0.25)
            asyncio.run(asyncio.wait_for(
                _fleet_open_loop(fport, victim_rps, stage_s, flood_v,
                                 path=vpath),
                timeout=left(max(6 * stage_s, 60.0))))
        finally:
            flood_proc.terminate()
            try:
                flood_stdout, _ = flood_proc.communicate(timeout=15)
                flood_counts = json.loads(flood_stdout or b"{}")
            except Exception:  # noqa: BLE001 — counts are best-effort
                flood_proc.kill()
                flood_proc.wait(timeout=10)

        # stage C: tenant-scoped rolling reload of the AGGRESSOR mid-
        # victim-traffic — only the aggressor's co-resident deploy is
        # swapped; the victim rides the drain choreography untouched
        reload_out: dict = {}

        def reload_thread() -> None:
            time.sleep(0.5)  # let stage C traffic establish first
            try:
                reload_out.update(fd.rolling_reload(
                    timeout=left(120.0), tenant="aggressor"))
            except Exception as e:  # noqa: BLE001 — nulls the keys
                log(f"tenant rolling reload failed ({e!r})")

        reload_v: list = []
        t = threading.Thread(target=reload_thread, daemon=True)
        t.start()
        asyncio.run(asyncio.wait_for(
            _fleet_open_loop(fport, victim_rps, stage_s, reload_v,
                             path=vpath),
            timeout=left(max(6 * stage_s, 60.0))))
        t.join(timeout=left(60.0))

        solo_served = [d for s, d, f in solo if s == 200 and not f]
        flood_served = [d for s, d, f in flood_v
                        if s == 200 and not f]
        if solo_served and flood_served:
            p_solo = _stage_p99(solo_served)
            p_flood = _stage_p99(flood_served)
            out["tenant_victim_solo_p99_s"] = round(p_solo, 4)
            out["tenant_victim_flood_p99_s"] = round(p_flood, 4)
            if p_solo > 0:
                out["tenant_victim_p99_x"] = round(p_flood / p_solo, 3)
        vic_all = solo + flood_v + reload_v
        if vic_all:
            out["tenant_victim_shed_rate"] = round(
                sum(1 for s, _d, _f in vic_all if s == 503)
                / len(vic_all), 4)
        if flood_counts.get("total"):
            out["tenant_aggressor_shed_rate"] = round(
                flood_counts.get("shed", 0) / flood_counts["total"], 4)
        out["tenant_reload_nonshed_5xx"] = sum(
            1 for s, _d, _f in reload_v if s >= 500 and s != 503)
        out["tenant_reloaded"] = reload_out.get("reloaded")

        # scheduler-side isolation evidence: per-tenant shed totals
        # from each worker's own /status tenants block (the bounded-
        # registry figures the dashboard renders) — the aggressor shed,
        # the victim never did
        agg_shed = vic_shed = 0
        for _proc, port in workers:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/", timeout=10) as resp:
                info = json.loads(resp.read())
            blocks = info.get("tenants") or {}
            agg_shed += int((blocks.get("aggressor") or {})
                            .get("shed") or 0)
            vic_shed += int((blocks.get("victim") or {})
                            .get("shed") or 0)
        out["tenant_aggressor_shed_total"] = agg_shed
        if out["tenant_victim_shed_rate"] is not None:
            out["tenant_isolation"] = bool(
                agg_shed > 0 and vic_shed == 0
                and out["tenant_victim_shed_rate"] == 0)
    finally:
        if fd is not None:
            fd.stop()
        _fleet_teardown(workers)
        if prev_spec is None:
            os.environ.pop("PIO_TENANTS", None)
        else:
            os.environ["PIO_TENANTS"] = prev_spec
        tenancy.reset_registry()
    log(f"tenants: victim p99 {out['tenant_victim_solo_p99_s']}s solo "
        f"-> {out['tenant_victim_flood_p99_s']}s flooded "
        f"({out['tenant_victim_p99_x']}x), "
        f"victim shed_rate={out['tenant_victim_shed_rate']} "
        f"aggressor shed={out['tenant_aggressor_shed_total']} "
        f"isolation={out['tenant_isolation']} "
        f"reload 5xx={out['tenant_reload_nonshed_5xx']}")
    return out


#: self-driving freshness leg (docs/production.md "Self-driving
#: freshness"): the SLO-burn controller alone — zero human retrains —
#: holds fleet staleness under the declared bound across a compressed
#: serve-while-aging ramp, every action audit-trailed under a trace ID
#: that reaches the rolling-reload spans
CONTROLLER_KEYS = (
    "controller_workers", "controller_staleness_bound_s",
    "controller_staleness_max_s", "controller_staleness_held",
    "controller_actions", "controller_decision_to_fresh_s",
    "controller_false_triggers", "controller_trace_linked",
    "controller_evaluations",
)


def _controller_staleness(port: int):
    """One worker /metrics scrape → its pio_model_staleness_seconds
    reading (None when unscrapeable — a draining worker mid-reload)."""
    import urllib.request

    from incubator_predictionio_tpu.obs import expofmt

    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as resp:
            text = resp.read().decode()
    except Exception:
        return None
    _meta, samples = expofmt.parse_exposition(text)
    v = samples.get(("pio_model_staleness_seconds", frozenset()))
    return float(v) if v is not None else None


def bench_controller(budget_s: float) -> dict:
    """Self-driving freshness leg: two planted fleet workers behind the
    front door, the freshness controller (obs/controller.py) in ``act``
    mode over a COMPRESSED staleness bound, and NO human retrains. The
    controller consumes the fleet staleness gauge through the federated
    SLO engine, projects headroom, and must trigger its continuation-
    retrain + rolling-hot-swap choreography early enough that the
    sampled fleet-max staleness never crosses the bound
    (``controller_staleness_held``). Each action's decision record
    carries a trace ID; the leg verifies it reached the front door's
    reload hop (``controller_trace_linked``) — the audit-trail
    acceptance bar. ``controller_false_triggers`` counts actions fired
    while staleness was still under half the bound (none expected:
    hysteresis + the horizon rule exist to prevent exactly that).

    The retrain actuator here is a planted stand-in (the O(delta)
    continuation-retrain wall is bench_retrain's claim; this leg
    measures the CONTROL LOOP) and the model swap is the workers' real
    warm-before-swap ``/reload`` through the front door's rolling
    choreography. Guarded like the other fleet legs: any failure nulls
    the controller_* keys, never the record."""
    import asyncio
    import logging as _logging
    import threading

    from incubator_predictionio_tpu.obs import federate
    from incubator_predictionio_tpu.obs import slo as obs_slo
    from incubator_predictionio_tpu.obs.controller import (
        ControllerConfig,
        FreshnessController,
        http_reload_fn,
    )
    from incubator_predictionio_tpu.serving.frontdoor import (
        FrontDoor,
        FrontDoorConfig,
    )

    out = dict.fromkeys(CONTROLLER_KEYS)
    if budget_s < 120.0:
        log("controller leg skipped: bench deadline too close")
        return out
    leg_deadline = time.monotonic() + min(
        budget_s - 45.0,
        float(os.environ.get("PIO_BENCH_CONTROLLER_TIMEOUT_S", "180")))

    def left(cap: float) -> float:
        return max(min(cap, leg_deadline - time.monotonic()), 5.0)

    bound_s = float(os.environ.get("PIO_BENCH_CONTROLLER_BOUND_S", "10"))
    run_s = float(os.environ.get("PIO_BENCH_CONTROLLER_RUN_S", "30"))
    rate = float(os.environ.get("PIO_BENCH_CONTROLLER_RPS", "30"))
    out["controller_staleness_bound_s"] = bound_s

    workers = _fleet_spawn(2, floor_ms=0.0)
    fd = None
    ctl = None
    # defined before the try so the finally can always stop the
    # sampler: a mid-leg failure must not leak a daemon thread
    # scraping dead worker ports for the rest of the bench run
    sample_stop = threading.Event()
    sampler_t = None
    # in-process span capture: the trace-linkage bar needs the front
    # door's /reload span lines, which land on the pio.trace logger of
    # THIS process (the workers' spans live in their own stderr)
    spans: list = []

    class _SpanTap(_logging.Handler):
        def emit(self, record: _logging.LogRecord) -> None:
            try:
                spans.append(json.loads(record.getMessage()))
            except Exception:
                pass

    tap = _SpanTap()
    span_logger = _logging.getLogger("pio.trace")
    prev_level = span_logger.level
    span_logger.addHandler(tap)
    span_logger.setLevel(_logging.INFO)
    try:
        fd = FrontDoor(
            [("127.0.0.1", p) for _proc, p in workers],
            FrontDoorConfig(request_timeout_s=8.0,
                            attempt_timeout_s=3.0,
                            probe_interval_s=0.25,
                            drain_timeout_s=10.0,
                            reload_timeout_s=60.0))
        fport = fd.start_background()
        # initial deploy: the workers have been aging since their spawn
        # walls (ladder warmup), so swap in a fresh model before the
        # measured ramp — the run then starts the way a real deploy
        # does, and every staleness excursion the sampler sees is the
        # CONTROLLER's to prevent
        fd.rolling_reload(timeout=left(60.0))

        # the controller's fleet view: the two workers (staleness
        # gauge) plus the front door itself (client-observed
        # pio_query_latency_seconds — the serve_p99 objective evaluates
        # what clients saw through the door)
        targets = [federate.Target(f"w{i}",
                                   f"http://127.0.0.1:{p}/metrics")
                   for i, (_proc, p) in enumerate(workers)]
        targets.append(federate.Target(
            "frontdoor", f"http://127.0.0.1:{fport}/metrics"))
        engine = obs_slo.SLOEngine(
            specs=(
                obs_slo.SLOSpec(
                    name="staleness",
                    metric="pio_model_staleness_seconds",
                    threshold=bound_s, target=0.99, kind="gauge",
                    description="compressed bench staleness bound"),
                obs_slo.SLOSpec(
                    name="serve_p99",
                    metric="pio_query_latency_seconds",
                    threshold=0.25, target=0.99,
                    description="front-door-observed serving wall"),
            ),
            registry=federate.FleetRegistry(
                targets_fn=lambda: targets, max_age_s=0.1),
            min_tick_interval_s=0.0, export_gauges=False)

        def planted_retrain() -> str:
            # continuation-retrain stand-in: the O(delta) retrain wall
            # is bench_retrain's pinned claim; this leg measures the
            # control loop + swap choreography around it
            time.sleep(0.2)
            return "planted-continuation"

        ctl = FreshnessController(
            engine=engine,
            retrain_fn=planted_retrain,
            reload_fn=http_reload_fn(
                f"http://127.0.0.1:{fport}/reload", timeout_s=60.0),
            config=ControllerConfig(
                interval_s=0.5, breach_evals=2,
                cooldown_s=4.0, horizon_s=0.4 * bound_s, ring=1024),
            mode="act")
        ctl.start()

        # serve-while-aging ramp: open-loop load through the front door
        # while a sampler tracks the fleet-max staleness the whole time
        samples: list = []

        def sampler() -> None:
            while not sample_stop.is_set():
                vals = [_controller_staleness(p)
                        for _proc, p in workers]
                vals = [v for v in vals if v is not None]
                if vals:
                    samples.append((time.time(), max(vals)))
                sample_stop.wait(0.25)

        sampler_t = threading.Thread(target=sampler, daemon=True)
        sampler_t.start()
        results: list = []

        async def load() -> None:
            await _fleet_open_loop(fport, rate, run_s, results,
                                   period_s=2.0)

        asyncio.run(asyncio.wait_for(load(),
                                     timeout=left(max(4 * run_s, 60.0))))
        sample_stop.set()
        sampler_t.join(timeout=10)
        ctl.stop()

        stats = ctl.stats()
        actions = [d for d in ctl.decisions(limit=1024)
                   if d.get("kind") == "evaluation"
                   and (d.get("outcome") or {}).get("actuated")]
        out["controller_workers"] = len(workers)
        out["controller_actions"] = stats["actions"]
        out["controller_evaluations"] = sum(
            1 for d in ctl.decisions(limit=1024)
            if d.get("kind") == "evaluation")
        if samples:
            peak = max(v for _t, v in samples)
            out["controller_staleness_max_s"] = round(peak, 2)
            out["controller_staleness_held"] = bool(peak <= bound_s)
        # false trigger = an action fired while the fleet was
        # MEASURABLY still comfortably fresh (under half the bound) —
        # hysteresis and the horizon rule exist to make this zero. An
        # unscrapeable gauge (None: both workers mid-drain) is not
        # evidence of freshness, so it never counts as false
        out["controller_false_triggers"] = sum(
            1 for d in actions
            if (d.get("inputs") or {}).get("stalenessMaxS") is not None
            and d["inputs"]["stalenessMaxS"] < 0.5 * bound_s)
        # decision → fresh: decision wall stamp to the first staleness
        # sample showing the swap landed (fleet max back under the
        # trigger point)
        walls = []
        for d in actions:
            t0 = d["ts"]
            trigger_level = (d.get("inputs") or {}).get(
                "stalenessMaxS") or bound_s
            after = [(t, v) for t, v in samples if t > t0]
            for t, v in after:
                if v < min(trigger_level, 0.5 * bound_s):
                    walls.append(t - t0)
                    break
        if walls:
            out["controller_decision_to_fresh_s"] = round(
                float(np.median(walls)), 2)
        # audit-trail bar: every action's trace ID shows up on the
        # front door's /reload HTTP span — the CROSS-HOP evidence (the
        # controller's own controller.reload span would be emitted even
        # if header forwarding broke, so it deliberately does not
        # count; worker-side propagation is pinned in
        # tests/test_controller.py)
        if actions:
            linked = []
            for d in actions:
                tid = d["traceId"]
                linked.append(any(
                    s.get("traceId") == tid
                    and s.get("span") == "http.request"
                    and s.get("server") == "frontdoor"
                    and s.get("route") == "/reload"
                    for s in spans))
            out["controller_trace_linked"] = all(linked)
    finally:
        sample_stop.set()
        if sampler_t is not None:
            sampler_t.join(timeout=10)
        span_logger.removeHandler(tap)
        span_logger.setLevel(prev_level)
        if ctl is not None:
            ctl.stop()
        if fd is not None:
            fd.stop()
        _fleet_teardown(workers)
    log(f"controller: actions={out['controller_actions']} "
        f"staleness_max={out['controller_staleness_max_s']}s "
        f"(bound {bound_s}s, held={out['controller_staleness_held']}) "
        f"decision_to_fresh={out['controller_decision_to_fresh_s']}s "
        f"false_triggers={out['controller_false_triggers']} "
        f"trace_linked={out['controller_trace_linked']}")
    return out


KNOB_KEYS = (
    "knob_workers", "knob_evaluations", "knob_steps",
    "knob_converged", "knob_recall_final", "knob_false_adjustments",
    "knob_rollbacks", "knob_incident_ring", "knob_trace_linked",
)


def bench_knobs(budget_s: float) -> dict:
    """Self-tuning serving leg (docs/production.md "Self-tuning
    serving"): the knob controller (obs/knobs.py) in ``act`` mode over
    a COMPRESSED timeline, actuating through the REAL fleet seam — a
    front door fanning ``POST /knobs`` to two real worker
    subprocesses — while a planted world model drives the signals it
    reads.

    The planted scenario, in order:

    1. catalogue-growth ramp: the recall gauge sags as the planted
       catalogue "grows" under a fixed nprobe; every doubling the
       controller actuates claws part of it back. The controller must
       hill-climb ``PIO_SERVE_MIPS_NPROBE`` until recall clears the
       target again (``knob_converged``);
    2. traffic-mix flip: queue wait jumps while latency stays under
       the objective — the batch ladder cap must climb, and no knob
       may reverse a direction it committed to during the ramp
       (``knob_false_adjustments`` counts same-knob direction
       reversals: hysteresis + cooldown exist to make this zero);
    3. planted SLO breach INSIDE the newest step's cooldown: the burn
       engine's breach listener must trigger the audited rollback to
       last-known-good (``knob_rollbacks`` — exactly one), and the
       incident bundle frozen by the same breach must carry the knob
       decision ring (``knob_incident_ring``).

    The world model reads the controller's BELIEVED vector
    (``ctl.values()`` — belief commits only when the fan-out
    succeeded), so the feedback loop only closes through the real
    door→worker actuation path. ``knob_trace_linked`` holds when every
    actuated decision's trace ID shows up on the front door's /knobs
    HTTP span — the same cross-hop audit bar as the freshness leg.
    Guarded like the other fleet legs: any failure nulls the knob_*
    keys, never the record."""
    import logging as _logging
    import math
    import shutil
    import tempfile
    import threading

    from incubator_predictionio_tpu.obs import metrics as obs_metrics
    from incubator_predictionio_tpu.obs import slo as obs_slo
    from incubator_predictionio_tpu.obs.controller import export_ring_fn
    from incubator_predictionio_tpu.obs.knobs import (
        KnobConfig,
        KnobController,
        default_knobs,
        http_knobs_fn,
    )
    from incubator_predictionio_tpu.obs.recorder import (
        FlightRecorder,
        IncidentCapture,
    )
    from incubator_predictionio_tpu.serving.frontdoor import (
        FrontDoor,
        FrontDoorConfig,
    )

    out = dict.fromkeys(KNOB_KEYS)
    if budget_s < 120.0:
        log("knobs leg skipped: bench deadline too close")
        return out
    leg_deadline = time.monotonic() + min(
        budget_s - 45.0,
        float(os.environ.get("PIO_BENCH_KNOBS_TIMEOUT_S", "120")))

    workers = _fleet_spawn(2, floor_ms=0.0)
    fd = None
    cap = None
    inc_dir = tempfile.mkdtemp(prefix="pio_bench_knobinc_")
    spans: list = []

    class _SpanTap(_logging.Handler):
        def emit(self, record: _logging.LogRecord) -> None:
            try:
                spans.append(json.loads(record.getMessage()))
            except Exception:
                pass

    tap = _SpanTap()
    span_logger = _logging.getLogger("pio.trace")
    prev_level = span_logger.level
    span_logger.addHandler(tap)
    span_logger.setLevel(_logging.INFO)
    try:
        fd = FrontDoor(
            [("127.0.0.1", p) for _proc, p in workers],
            FrontDoorConfig(request_timeout_s=8.0,
                            attempt_timeout_s=3.0,
                            probe_interval_s=0.25))
        fport = fd.start_background()

        # the planted signal plane: a LOCAL registry + flight recorder
        # carrying exactly the input series the controller consumes in
        # production — the world model writes them, the controller only
        # ever reads them back through the recorder's window API
        reg = obs_metrics.Registry()
        lat_h = reg.histogram("pio_query_latency_seconds", "planted",
                              buckets=(0.05, 0.1, 0.25, 0.5, 1.0))
        queue_h = reg.histogram("pio_serve_queue_wait_seconds",
                                "planted",
                                buckets=(0.01, 0.05, 0.1, 0.25))
        reg.counter("pio_serve_shed_total", "planted")
        recall_g = reg.gauge("pio_serve_mips_recall", "planted")
        rec = FlightRecorder(registry=reg, hz=4.0, window_s=60.0)

        target, margin = 0.95, 0.02
        cooldown_s = 2.5
        ctl = KnobController(
            specs=default_knobs(),
            apply_fn=http_knobs_fn(f"http://127.0.0.1:{fport}/knobs",
                                   timeout_s=15.0),
            recorder_fn=lambda: rec,
            config=KnobConfig(interval_s=0.25, hysteresis_evals=2,
                              cooldown_s=cooldown_s, window_s=8.0,
                              ring=1024, recall_target=target,
                              recall_margin=margin),
            mode="act")

        engine = obs_slo.SLOEngine(
            specs=(obs_slo.SLOSpec(
                name="serve_p99",
                metric="pio_query_latency_seconds",
                threshold=0.25, target=0.99,
                description="compressed bench serving wall"),),
            registry=reg, min_tick_interval_s=0.0,
            export_gauges=False)
        ctl.install(engine)
        cap = IncidentCapture(directory=inc_dir, recorder=rec,
                              window_s=60.0, targets_fn=lambda: [],
                              knobs_fn=export_ring_fn(ctl))
        cap.install(engine)

        def world(phase: str, ramp: float) -> float:
            """One tick of the planted world → current recall. The
            catalogue ramp costs up to 0.12 recall at the default
            nprobe; every actuated doubling buys 0.04 back (capped
            under target+margin so a converged run never invites a
            step-down — a reversal would be a REAL flapping bug)."""
            nprobe = ctl.values()["PIO_SERVE_MIPS_NPROBE"]
            recall = min(target + 0.5 * margin,
                         0.97 - 0.12 * ramp
                         + 0.04 * math.log2(max(nprobe, 64) / 64.0))
            recall_g.set(recall)
            lat_h.observe(0.4 if phase == "breach" else 0.2, 50)
            queue_h.observe(0.15 if phase == "flip" else 0.01, 50)
            rec.sample_now()
            return recall

        def left() -> float:
            return leg_deadline - time.monotonic()

        # phase 1: catalogue-growth ramp (6 s), then hold until the
        # climb converges
        recall = 0.0
        t0 = time.monotonic()
        while left() > 30.0:
            recall = world("ramp", min((time.monotonic() - t0) / 6.0,
                                       1.0))
            ctl.evaluate_once()
            if time.monotonic() - t0 > 7.0 and recall >= target:
                break
            time.sleep(0.12)
        out["knob_recall_final"] = round(recall, 4)
        out["knob_converged"] = bool(
            recall >= target
            and ctl.values()["PIO_SERVE_MIPS_NPROBE"] > 64)

        # phase 2: traffic-mix flip — queue pressure with latency
        # still under the objective; exit on the ladder-cap step
        cap_before = ctl.values()["PIO_SERVE_MAX_BATCH"]
        t0 = time.monotonic()
        stepped = False
        while left() > 20.0 and time.monotonic() - t0 < 10.0:
            world("flip", 1.0)
            d = ctl.evaluate_once()
            if d.get("knob") == "max_batch" \
                    and (d.get("outcome") or {}).get("actuated"):
                stepped = True
                break
            time.sleep(0.12)
        # a baseline burn-engine snapshot BEFORE the planted breach:
        # the fast-window delta is measured against it
        engine.evaluate()

        # phase 3: planted breach INSIDE the fresh step's cooldown
        if stepped:
            t0 = time.monotonic()
            while left() > 10.0 and time.monotonic() - t0 < 5.0:
                world("breach", 1.0)
                engine.evaluate()      # breach → on_breach listeners
                d = ctl.evaluate_once()
                if d.get("action") == "rollback":
                    break
                time.sleep(0.12)
        stats = ctl.stats()
        out["knob_workers"] = len(workers)
        out["knob_rollbacks"] = stats["rollbacks"]
        if stepped and stats["rollbacks"] == 1:
            # the rollback restored the pre-step ladder cap but kept
            # the converged MIPS climb (last-known-good is the vector
            # the newest step departed from)
            assert ctl.values()["PIO_SERVE_MAX_BATCH"] == cap_before

        ring = list(reversed(ctl.decisions(limit=1024)))  # oldest first
        evaluations = [d for d in ring if d.get("kind") == "evaluation"]
        out["knob_evaluations"] = len(evaluations)
        acted = [d for d in evaluations
                 if (d.get("outcome") or {}).get("actuated")]
        steps = [d for d in acted
                 if d.get("action") in ("step_up", "step_down")]
        out["knob_steps"] = len(steps)
        # false adjustment = a knob stepping back against a direction
        # it committed to earlier in the SAME run (audited rollbacks
        # are deliberate reversals, so they don't count)
        reversals = 0
        last_dir: dict = {}
        for d in steps:
            sign = 1 if d["action"] == "step_up" else -1
            if last_dir.get(d["knob"], sign) != sign:
                reversals += 1
            last_dir[d["knob"]] = sign
        out["knob_false_adjustments"] = reversals
        # cross-hop audit bar: every actuated decision's trace ID on
        # the front door's /knobs HTTP span
        if acted:
            out["knob_trace_linked"] = all(
                any(s.get("traceId") == d["traceId"]
                    and s.get("span") == "http.request"
                    and s.get("server") == "frontdoor"
                    and s.get("route") == "/knobs"
                    for s in spans)
                for d in acted)
        # the breach-frozen bundle must carry the knob decision ring
        deadline = time.monotonic() + 10.0
        bundle = None
        while time.monotonic() < deadline:
            names = [n for n in os.listdir(inc_dir)
                     if n.endswith(".json")]
            if names:
                with open(os.path.join(inc_dir, sorted(names)[-1]),
                          encoding="utf-8") as f:
                    bundle = json.load(f)
                break
            time.sleep(0.25)
        if bundle is not None:
            out["knob_incident_ring"] = bool(
                any(d.get("action") in ("step_up", "step_down")
                    for d in bundle.get("knobs") or []))
    finally:
        span_logger.removeHandler(tap)
        span_logger.setLevel(prev_level)
        if cap is not None:
            cap.stop()
        if fd is not None:
            fd.stop()
        _fleet_teardown(workers)
        shutil.rmtree(inc_dir, ignore_errors=True)
    log(f"knobs: steps={out['knob_steps']} "
        f"converged={out['knob_converged']} "
        f"(recall_final={out['knob_recall_final']}) "
        f"false_adjustments={out['knob_false_adjustments']} "
        f"rollbacks={out['knob_rollbacks']} "
        f"incident_ring={out['knob_incident_ring']} "
        f"trace_linked={out['knob_trace_linked']}")
    return out


INGEST_KEYS = (
    "ingest_qps_single", "ingest_qps_sharded", "ingest_shards",
    "ingest_host_cpus",
    "ingest_replication_lag_p99_events",
    "ingest_soak_dropped_events", "ingest_soak_staleness_held",
)


def _ingest_append_qps(shards: int, n_threads: int = 4,
                       batches_per_thread: int = 10,
                       batch_events: int = 10_000) -> float:
    """Concurrent columnar append throughput (events/s) into a fresh
    cpplog store with ``shards`` writer shards. The same DAO call the
    REST batch fast path lands on; with >1 shard the per-shard native
    appends overlap because ctypes releases the GIL for the write."""
    import tempfile
    import threading

    import numpy as np

    from incubator_predictionio_tpu.data.storage import StorageClientConfig
    from incubator_predictionio_tpu.data.storage import cpplog
    from incubator_predictionio_tpu.data.storage.base import (
        IdTable,
        Interactions,
    )

    with tempfile.TemporaryDirectory(prefix="pio_bench_shingest_") as tmp:
        prev = os.environ.get("PIO_LOG_SHARDS")
        os.environ["PIO_LOG_SHARDS"] = str(shards)
        try:
            cfg = StorageClientConfig(parallel=False,
                                      properties={"PATH": tmp})
            client = cpplog.StorageClient(cfg)
            dao = cpplog.CppLogEvents(client, cfg, prefix="b_")
            dao.init(1)
        finally:
            if prev is None:
                os.environ.pop("PIO_LOG_SHARDS", None)
            else:
                os.environ["PIO_LOG_SHARDS"] = prev
        # pre-build every batch OUTSIDE the timed window (the generator
        # shares the core with the appends). Distinct users per thread
        # keep the key-hash spray busy on every shard.
        item_tab = IdTable.from_list([f"i{k}" for k in range(512)])
        rng = np.random.default_rng(7)
        work = []
        for t in range(n_threads):
            batches = []
            for b in range(batches_per_thread):
                users = [f"u{t}_{b}_{k}" for k in range(batch_events)]
                batches.append(Interactions(
                    user_idx=np.arange(batch_events, dtype=np.int32),
                    item_idx=rng.integers(
                        0, 512, batch_events).astype(np.int32),
                    values=np.ones(batch_events, np.float32),
                    user_ids=IdTable.from_list(users),
                    item_ids=item_tab))
            work.append(batches)

        errors: list = []

        def pump(batches) -> None:
            try:
                for inter in batches:
                    dao.insert_interactions(inter, 1)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [threading.Thread(target=pump, args=(w,))
                   for w in work]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]
        total = n_threads * batches_per_thread * batch_events
        got = dao.scan_interactions(
            app_id=1, entity_type="user", target_entity_type="item",
            event_names=("rate",), value_prop="rating")
        assert len(got) == total, (len(got), total)
        client.close()
        return total / wall


def bench_ingest(budget_s: float) -> dict:
    """Planet-scale ingest leg (docs/production.md "Planet-scale
    ingest"): multi-writer sharded append throughput vs the single-
    writer baseline in the SAME run, follower replication lag under
    sustained leader writes, and an ingest soak — event POSTs sprayed
    by the IngestFrontDoor across two live event-server writers over a
    sharded log, with a rolling zero-downtime writer reload mid-stream
    and a tail subscriber holding the freshness bound. Guarded like the
    other fleet legs: any failure nulls the ingest_* keys, never the
    record."""
    import tempfile
    import threading
    import urllib.request

    import numpy as np

    out = dict.fromkeys(INGEST_KEYS)
    if budget_s < 90.0:
        log("ingest leg skipped: bench deadline too close")
        return out
    shards = int(os.environ.get("PIO_BENCH_INGEST_SHARDS", "4"))
    out["ingest_shards"] = shards
    # the sharded-vs-single ratio is a PARALLELISM measurement: on a
    # 1-core host the fan-out has no headroom by construction, so the
    # record carries the host's core count for honest downstream bars
    out["ingest_host_cpus"] = os.cpu_count() or 1

    # -- A. sharded vs single-writer append throughput --------------------
    out["ingest_qps_single"] = round(_ingest_append_qps(1), 1)
    out["ingest_qps_sharded"] = round(_ingest_append_qps(shards), 1)
    log(f"ingest append: single={out['ingest_qps_single']:.0f} ev/s "
        f"sharded({shards})={out['ingest_qps_sharded']:.0f} ev/s "
        f"({out['ingest_qps_sharded'] / out['ingest_qps_single']:.2f}x)")

    # -- B. async replication lag under sustained leader writes -----------
    from incubator_predictionio_tpu.data.storage import StorageClientConfig
    from incubator_predictionio_tpu.data.storage import cpplog
    from incubator_predictionio_tpu.data.storage.base import (
        IdTable,
        Interactions,
    )
    from incubator_predictionio_tpu.data.storage.server import (
        ReplicationTail,
        StorageServer,
    )

    with tempfile.TemporaryDirectory(prefix="pio_bench_repl_") as tmp:
        prev = os.environ.get("PIO_LOG_SHARDS")
        os.environ["PIO_LOG_SHARDS"] = str(shards)
        try:
            lcfg = StorageClientConfig(parallel=False,
                                       properties={"PATH": tmp + "/lead"})
            lclient = cpplog.StorageClient(lcfg)
            ldao = cpplog.CppLogEvents(lclient, lcfg, prefix="b_")
            ldao.init(1)
            fcfg = StorageClientConfig(parallel=False,
                                       properties={"PATH": tmp + "/foll"})
            fclient = cpplog.StorageClient(fcfg)
            fdao = cpplog.CppLogEvents(fclient, fcfg, prefix="b_")
        finally:
            if prev is None:
                os.environ.pop("PIO_LOG_SHARDS", None)
            else:
                os.environ["PIO_LOG_SHARDS"] = prev
        leader_srv = StorageServer(cpplog, lclient, lcfg,
                                   host="127.0.0.1", port=0)
        lport = leader_srv.start_background()
        tail = ReplicationTail(f"http://127.0.0.1:{lport}", fdao, [1],
                               interval_s=0.05, prefix="b_")
        tail.start()
        item_tab = IdTable.from_list([f"i{k}" for k in range(128)])
        stop_w = threading.Event()

        def writer() -> None:
            b = 0
            rng = np.random.default_rng(11)
            while not stop_w.is_set():
                n = 5_000
                ldao.insert_interactions(Interactions(
                    user_idx=np.arange(n, dtype=np.int32),
                    item_idx=rng.integers(0, 128, n).astype(np.int32),
                    values=np.ones(n, np.float32),
                    user_ids=IdTable.from_list(
                        [f"r{b}_{k}" for k in range(n)]),
                    item_ids=item_tab), 1)
                b += 1
                time.sleep(0.01)

        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        lags: list = []
        t_end = time.monotonic() + 5.0
        try:
            while time.monotonic() < t_end:
                try:
                    lags.append(tail._lag_total(1))
                except Exception:
                    pass
                time.sleep(0.05)
        finally:
            stop_w.set()
            wt.join(timeout=10)
        caught = tail.wait_caught_up(timeout_s=30.0)
        tail.stop()
        leader_srv.stop()
        fclient.close()
        if lags and caught:
            out["ingest_replication_lag_p99_events"] = int(
                np.percentile(np.asarray(lags, np.float64), 99))
        log(f"ingest replication: lag_p99="
            f"{out['ingest_replication_lag_p99_events']} events "
            f"over {len(lags)} samples, caught_up={caught}")

    # -- C. front-door ingest soak with rolling writer reload -------------
    from incubator_predictionio_tpu.data.storage import (
        AccessKey,
        App,
        Storage,
    )
    from incubator_predictionio_tpu.servers.event_server import (
        EventServer,
        EventServerConfig,
    )
    from incubator_predictionio_tpu.serving.frontdoor import (
        FrontDoorConfig,
        IngestFrontDoor,
    )

    run_s = float(os.environ.get("PIO_BENCH_INGEST_SOAK_S", "8"))
    stale_bound_s = 5.0
    with tempfile.TemporaryDirectory(prefix="pio_bench_soak_") as tmp:
        prev = os.environ.get("PIO_LOG_SHARDS")
        os.environ["PIO_LOG_SHARDS"] = str(shards)
        door = None
        writers = []
        try:
            Storage.configure({
                "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
                "PIO_STORAGE_SOURCES_EV_TYPE": "cpplog",
                "PIO_STORAGE_SOURCES_EV_PATH": tmp,
                "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
            })
            app_id = Storage.get_meta_data_apps().insert(
                App(0, "bench-soak"))
            Storage.get_meta_data_access_keys().insert(
                AccessKey("soakkey", app_id))
            Storage.get_events().init(app_id)
            writers = [EventServer(EventServerConfig(ip="127.0.0.1",
                                                     port=0))
                       for _ in range(2)]
            ports = [w.start_background() for w in writers]
            door = IngestFrontDoor(
                [("127.0.0.1", p) for p in ports],
                FrontDoorConfig(server_key="soakkey",
                                request_timeout_s=15.0,
                                attempt_timeout_s=8.0,
                                drain_timeout_s=10.0,
                                reload_timeout_s=30.0))
            dport = door.start_background()
            url = (f"http://127.0.0.1:{dport}/batch/events.json"
                   "?accessKey=soakkey")
            accepted: list = []
            pump_errors: list = []
            stop_p = threading.Event()

            def pump(tid: int) -> None:
                b = 0
                while not stop_p.is_set():
                    body = json.dumps([
                        {"event": "rate", "entityType": "user",
                         "entityId": f"s{tid}_{b}_{k}",
                         "targetEntityType": "item",
                         "targetEntityId": f"i{k % 64}",
                         "properties": {"rating": 1.0}}
                        for k in range(50)]).encode()
                    try:
                        req = urllib.request.Request(
                            url, body,
                            {"Content-Type": "application/json"})
                        with urllib.request.urlopen(
                                req, timeout=20) as resp:
                            res = json.loads(resp.read())
                        accepted.append(sum(
                            1 for r in res if r.get("status") == 201))
                    except Exception as e:  # noqa: BLE001
                        pump_errors.append(repr(e))
                        return
                    b += 1

            # tail subscriber: append→visibility staleness across the
            # rolling reload (one poll's rows bound by oldest append)
            events_dao = Storage.get_events()
            stale_max = [0.0]
            stop_s = threading.Event()

            def subscriber() -> None:
                cursor = events_dao.tail_cursor(app_id=app_id)
                while not stop_s.is_set():
                    stop_s.wait(0.25)
                    try:
                        _i, _t, ams, cursor, reset = \
                            events_dao.read_interactions_since(
                                cursor, app_id=app_id,
                                event_names=("rate",),
                                value_prop="rating")
                    except Exception:
                        continue
                    if reset or not len(ams):
                        continue
                    oldest = int(ams.min())
                    if oldest > 0:
                        stale_max[0] = max(
                            stale_max[0],
                            time.time() - oldest / 1000.0)

            pumps = [threading.Thread(target=pump, args=(t,))
                     for t in range(3)]
            sub = threading.Thread(target=subscriber, daemon=True)
            for t in pumps:
                t.start()
            sub.start()
            t_half = time.monotonic() + run_s / 2
            while time.monotonic() < t_half:
                time.sleep(0.1)
            reload_out = door.rolling_reload(timeout=60)
            time.sleep(max(run_s / 2 - 0.1, 0.1))
            stop_p.set()
            for t in pumps:
                t.join(timeout=30)
            stop_s.set()
            sub.join(timeout=10)
            if pump_errors:
                raise RuntimeError(
                    f"soak pump failed: {pump_errors[0]}")
            sent = sum(accepted)
            landed = len(Storage.get_events().scan_interactions(
                app_id=app_id, entity_type="user",
                target_entity_type="item", event_names=("rate",),
                value_prop="rating"))
            out["ingest_soak_dropped_events"] = sent - landed
            out["ingest_soak_staleness_held"] = bool(
                stale_max[0] <= stale_bound_s)
            log(f"ingest soak: {sent} accepted, {landed} landed "
                f"(dropped={out['ingest_soak_dropped_events']}), "
                f"reloaded={reload_out['reloaded']}/2, "
                f"staleness_max={stale_max[0]:.2f}s "
                f"(bound {stale_bound_s}s, "
                f"held={out['ingest_soak_staleness_held']})")
        finally:
            if door is not None:
                door.stop()
            for w in writers:
                w.stop()
            Storage.reset()
            if prev is None:
                os.environ.pop("PIO_LOG_SHARDS", None)
            else:
                os.environ["PIO_LOG_SHARDS"] = prev
    return out


def bench_scan_probe(store_dir: str) -> dict:
    """Sequential vs sharded event-log scan at bench scale, projection
    cache bypassed, plus the pipelined scan→prep leg — the host-pipeline
    sub-metrics (shard count, per-shard walls, native-lock-held wall,
    scan/prep overlap). The headline ``ingest_wall_s`` keeps measuring
    the production warm path (cache serve); this stage measures the cold
    scan machinery those rounds would otherwise never see."""
    from incubator_predictionio_tpu.data.storage import StorageClientConfig
    from incubator_predictionio_tpu.data.storage import cpplog
    from incubator_predictionio_tpu.ops.sparse import StreamingPrep

    cfg = StorageClientConfig(properties={"PATH": store_dir})
    client = cpplog.StorageClient(cfg)
    events = cpplog.CppLogEvents(client, cfg, prefix="bench_")
    out: dict = {}
    old_shards = os.environ.get("PIO_SCAN_SHARDS")
    try:
        t0 = time.perf_counter()
        client.handle("bench_", 1, None)
        out["scan_open_s"] = round(time.perf_counter() - t0, 2)

        # true single-thread leg — the acceptance baseline. PIO_SCAN_
        # SHARDS=1 still uses the scanner's internal auto threading (the
        # pre-sharding production path), so the 1-thread wall is measured
        # through the raw native call with n_threads pinned to 1.
        with client.lock:
            h = events._handle(1, None)
            raw = client.lib.pio_evlog_entry_count(h)
            pin = client.pin("bench_", 1, None)
        try:
            t0 = time.perf_counter()
            inter, _, _ = events._scan_native(
                h, None, None, "user", "item", ["rate"], {}, "rating",
                1.0, min_entry_idx=0, max_entry_idx=raw, n_threads=1)
            out["scan_wall_1thread_s"] = round(time.perf_counter() - t0, 2)
            del inter
        finally:
            client.unpin(pin)

        os.environ["PIO_SCAN_SHARDS"] = "1"
        t0 = time.perf_counter()
        inter = events.scan_interactions(
            app_id=1, entity_type="user", target_entity_type="item",
            event_names=("rate",), value_prop="rating",
            use_cache=False, seed_cache=False)
        seq_s = time.perf_counter() - t0
        n_seq = len(inter)
        del inter

        if old_shards is None:
            os.environ.pop("PIO_SCAN_SHARDS", None)
        else:
            os.environ["PIO_SCAN_SHARDS"] = old_shards
        prep = StreamingPrep()
        stats: dict = {}
        t0 = time.perf_counter()
        inter = events.scan_interactions(
            app_id=1, entity_type="user", target_entity_type="item",
            event_names=("rate",), value_prop="rating",
            use_cache=False, seed_cache=False, stats=stats,
            shard_sink=prep.add_shard)
        sharded_s = time.perf_counter() - t0
        buckets = prep.finish(
            inter, reordered=bool(stats.get("scan_reordered")))
        pipelined_s = time.perf_counter() - t0
        assert len(inter) == n_seq, (len(inter), n_seq)
        del inter, buckets
        out.update({
            "scan_wall_seq_s": round(seq_s, 2),
            "scan_wall_sharded_s": round(sharded_s, 2),
            "scan_speedup_vs_seq": round(seq_s / max(sharded_s, 1e-9), 2),
            "scan_speedup_vs_1thread": round(
                out["scan_wall_1thread_s"] / max(sharded_s, 1e-9), 2),
            "scan_shards": stats.get("scan_shards"),
            "scan_shard_walls_s": stats.get("scan_shard_walls_s"),
            "scan_lock_held_s": stats.get("scan_lock_held_s"),
            "scan_merge_wall_s": stats.get("scan_merge_wall_s"),
            "scan_prep_pipelined_wall_s": round(pipelined_s, 2),
            "scan_prep_overlap_s": round(prep.overlap_s, 3),
        })
        log(f"scan probe: seq={seq_s:.1f}s sharded={sharded_s:.1f}s "
            f"(shards={stats.get('scan_shards')}, "
            f"lock-held={stats.get('scan_lock_held_s')}s) "
            f"pipelined scan+prep={pipelined_s:.1f}s "
            f"(overlap {prep.overlap_s:.2f}s)")
    finally:
        if old_shards is None:
            os.environ.pop("PIO_SCAN_SHARDS", None)
        else:
            os.environ["PIO_SCAN_SHARDS"] = old_shards
        client.close()
    return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def run_cpu_baseline() -> None:
    """`--cpu`: re-measure CPU_BASELINE_TRAIN_S on the host backend with
    the pinned all-f32 schedule (BASELINE.md convention: bf16 is emulated
    — slower — on the host, so letting the bf16 schedule leak into a
    --cpu re-measure would inflate vs_baseline unfairly)."""
    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")
    rng = np.random.default_rng(7)
    log(f"dataset: {N_USERS}x{N_ITEMS}, nnz={NNZ}, rank={RANK}, "
        f"sweeps={ITERATIONS} (all f32 — CPU convention)")
    users, items, ratings, heldout, truth = make_dataset(rng)
    with tempfile.TemporaryDirectory(prefix="pio_bench_") as tmpdir:
        events, client, seed_s = seed_store(tmpdir, users, items, ratings)
        log(f"seed: {NNZ} events in {seed_s:.1f}s")
        client.close()
        inter, ingest_s = scan_store(tmpdir)
    assert len(inter) == NNZ, len(inter)
    u_b, i_b, n_users, n_items, prep_s = prep_buckets(inter)
    state, t = measure_train((u_b, i_b, n_users, n_items), 0,
                             cache_probe=False)
    log(f"CPU baseline measured: warm train = {t['train_s']:.1f}s "
        "(update CPU_BASELINE_TRAIN_S)")
    print(json.dumps({
        "metric": "als_ml20m_train_wall_s_cpu",
        "value": round(t["train_s"], 2),
        "unit": "s",
        "vs_baseline": 1.0,
    }))


def run_tpu_child(store_dir: str, out_path: str, claim_path: str,
                  parent_pid: int = 0) -> None:
    """All accelerator work, in a disposable process. First act:
    initialize the chip (JAX_PLATFORMS, set by the parent before this
    process imports jax, names the platform — tests run this child on
    the CPU backend). On success, touch the claim file so the parent
    switches from 'init watchdog' to 'run watchdog'."""
    from incubator_predictionio_tpu.utils.lease import install_sigterm_exit

    import jax

    jax.devices()
    # holding the chip from here on: SIGTERM tears the process down via
    # normal interpreter shutdown (utils/lease.py)
    install_sigterm_exit()
    # Recycled-child guard: only the first child to get the chip should
    # run. A later one whose fragment already exists — or whose bench
    # parent is gone entirely — exits NOW, releasing the chip instead of
    # re-running the whole TPU leg against nobody.
    if os.path.exists(out_path):
        log("tpu child: fragment already landed by an earlier child; "
            "exiting to free the chip")
        return
    # explicit PID handshake, not getppid()==1: the bench itself can BE
    # pid 1 (container entrypoint), and orphans reparent to a subreaper
    # rather than init under systemd/tini
    if parent_pid and os.getppid() != parent_pid:
        log("tpu child: bench parent is gone (orphaned waiter); "
            "exiting to free the chip")
        return
    with open(claim_path, "w") as f:
        f.write(str(os.getpid()))
    log(f"tpu child: accelerator up ({jax.devices()[0]})")

    rng = np.random.default_rng(7)
    users, items, ratings, heldout, truth = make_dataset(rng)
    del users, items, ratings  # events already seeded by the parent

    inter, ingest_s = scan_store(store_dir)
    assert len(inter) == NNZ, len(inter)
    log(f"ingest scan: {ingest_s:.1f}s ({NNZ / ingest_s / 1e6:.2f}M ev/s)")

    from incubator_predictionio_tpu.ops import als
    from incubator_predictionio_tpu.ops.sparse import build_both_sides

    # pipelined prep→device: each side's bucket/heavy trees are uploaded
    # (H2D) from the prep worker the moment that side finishes padding,
    # overlapping the other side's bucket fill. prep_wall_s therefore now
    # INCLUDES the device upload that used to run untimed after prep;
    # prep_h2d_s records the upload share.
    n_users, n_items = len(inter.user_ids), len(inter.item_ids)
    side_box: dict = {}

    def _on_side(side, light, heavy):
        t0 = time.perf_counter()
        side_box[side] = (als._buckets_tree(light), als._heavy_tree(heavy))
        side_box[side + "_h2d_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    (u_b_light, u_b_heavy), (i_b_light, i_b_heavy) = build_both_sides(
        inter.user_idx, inter.item_idx, inter.values, n_users, n_items,
        on_side=_on_side)
    prep_s = time.perf_counter() - t0
    h2d_s = side_box["user_h2d_s"] + side_box["item_h2d_s"]
    log(f"prep+H2D (bucketed padded rows; per-side device upload "
        f"overlaps the other side's padding): {prep_s:.1f}s "
        f"(H2D {h2d_s:.1f}s, users={n_users}, items={n_items})")

    buckets = ((u_b_light, u_b_heavy), (i_b_light, i_b_heavy),
               n_users, n_items)
    trees = (side_box["user"][0], side_box["item"][0],
             side_box["user"][1], side_box["item"][1], n_users, n_items)
    use_kernel, kernel_rows, kernel_probe = select_als_kernel(
        buckets, trees=trees)
    state, t = measure_train(buckets, BF16_SWEEPS, use_kernel=use_kernel,
                             trees=trees, kernel_rows=kernel_rows)
    train_s = t["train_s"]
    fit = als.rmse(state, inter.user_idx, inter.item_idx, inter.values)
    # FLOPs over the rows the child ACTUALLY trained (the scan compacts
    # ids, so at sub-ML-20M shapes len(user_ids) < N_USERS and the env
    # shape would overcount solves ~3x; at the full shape every user has
    # events and this is identical to als_flops_per_run)
    flops = als.train_flops(NNZ, n_users, n_items, RANK, ITERATIONS,
                            BF16_SWEEPS)
    mfu = flops / train_s / PEAK_FLOPS_F32
    mfu_bf16 = flops / train_s / PEAK_FLOPS_BF16
    heldout_rmse, prec10 = quality_metrics(state, inter, heldout, truth, rng)
    log(f"device={jax.devices()[0]} compile={t['compile_s_first']:.1f}s "
        f"warm={train_s:.2f}s rmse={fit:.3f} "
        f"heldout_rmse={heldout_rmse:.3f} (noise floor {NOISE_SIGMA}) "
        f"p@10={prec10:.3f} flops={flops:.3e} mfu={mfu:.3f}")

    attn = bench_attention()
    serve = bench_serving(state, inter)
    # steady-state retrain leg last: a failure here must never cost the
    # train/serve numbers already measured
    retrain_frag = dict.fromkeys(RETRAIN_KEYS)
    try:
        retrain_frag.update(
            bench_retrain(store_dir, state, inter, heldout, truth))
    except Exception as e:  # noqa: BLE001 — sub-metrics are optional
        log(f"retrain leg failed ({e!r}); retrain_* keys null this round")
    speed_frag = dict.fromkeys(SPEED_KEYS)
    try:
        speed_frag.update(bench_speed(store_dir, state, inter))
    except Exception as e:  # noqa: BLE001 — sub-metrics are optional
        log(f"speed leg failed ({e!r}); speed_* keys null this round")

    fragment = {
        # the CHILD's provenance overrides the parent's: the child is
        # the process that actually touched the accelerator, so its
        # backend/device view is the one the trajectory should carry
        "bench_env": bench_env(),
        "value": round(train_s, 3),
        "vs_baseline": round(CPU_BASELINE_TRAIN_S / train_s, 1),
        "train_rmse": round(float(fit), 3),
        "heldout_rmse": round(heldout_rmse, 3),
        "precision_at_10_vs_truth": round(prec10, 3),
        "mfu": round(mfu, 4),
        "mfu_bf16_peak": round(mfu_bf16, 4),
        # live pio_mfu{phase=train} gauge over the same timed warm run —
        # must agree with the offline mfu within 10% (the
        # bench↔telemetry cross-check; test_bench_e2e asserts the
        # ratio, computed against the UNROUNDED offline figure)
        "obs_mfu_train": t["obs_mfu_train"],
        "obs_mfu_vs_offline": (
            round(t["obs_mfu_train"] / mfu, 4)
            if t["obs_mfu_train"] and mfu > 0 else None),
        "obs_device_train_s": t["obs_device_train_s"],
        "obs_device_train_dispatches": t["obs_device_train_dispatches"],
        "train_fused_wall_s": t["train_fused_wall_s"],
        "compile_s_first": t["compile_s_first"],
        "compile_s_warm_cache": t["compile_s_warm_cache"],
        "ingest_wall_s": round(ingest_s, 1),
        "prep_wall_s": round(prep_s, 1),
        "prep_h2d_s": round(h2d_s, 1),
        "e2e_train_wall_s": round(ingest_s + prep_s + train_s, 1),
        **kernel_probe,
        **attn,
        **retrain_frag,
        **speed_frag,
        "serve_p50_ms": serve["p50_ms"],
        "serve_p99_ms": serve["p99_ms"],
        "serve_qps": serve["qps_sequential"],
        "serve_qps_concurrent": serve["qps_concurrent"],
        "serve_max_batch": serve["max_batch"],
        # registry cross-check for the stages the CHILD ran (serving,
        # compiles; the retrain leg ships its own obs_train_* delta);
        # the ingest-side obs_* keys belong to the parent — never
        # shipped from here, even as None (update() overwrites)
        **{k: v for k, v in obs_snapshot().items()
           if k.startswith(("obs_query_", "obs_compile_"))},
    }
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(fragment, f)
    os.replace(tmp, out_path)


def supervise_tpu_child(store_dir: str, out_path: str,
                        claim_event=None, deadline_mono=None,
                        last_rc=None) -> bool:
    """Spawn/recycle the TPU child until it lands a fragment or the
    ACCEL_WAIT_S budget runs out. Returns True iff `out_path` exists
    (checked on every exit path — an abandoned SIGTERM-ignoring child
    that completes late still counts). Sets `claim_event` the moment any
    child claims the chip so the parent can cancel fallback work.

    ``deadline_mono`` (time.monotonic value) caps the CUMULATIVE claim
    wait: past it the supervisor returns so the orchestrator can emit
    its record before the driver's kill — terminating an unclaimed dial
    waiter (safe: it holds nothing), but leaving a claimed child running
    (a holder is never cut down; it finishes and exits on its own).

    A child that has not claimed the chip within its window is stopped
    with SIGTERM (it is *waiting* on the lease, not holding it — killing
    a waiter cannot wedge the chip; killing a holder can, which is why a
    claimed child gets the long run window and is never force-killed
    while healthy) and respawned with a doubled window: only a fresh
    process gets a fresh PJRT dial.

    ``last_rc``: optional single-slot list; the most recent child exit
    code observed lands in it, so the record's ``skipped_reason`` can
    carry the REAL rc instead of a guessed one."""
    deadline = time.monotonic() + ACCEL_WAIT_S
    if deadline_mono is not None:
        deadline = min(deadline, deadline_mono)
    window = 180.0
    attempt = 0
    fast_fails = 0
    while time.monotonic() < deadline:
        attempt += 1
        claim_path = f"{out_path}.claim{attempt}"
        t_spawn = time.monotonic()
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--tpu-child",
             store_dir, out_path, claim_path, str(os.getpid())],
            stdout=sys.stderr, stderr=sys.stderr)
        claimed = False
        win_end = min(time.monotonic() + window, deadline)
        while True:
            if (not claimed and proc.poll() is None
                    and os.path.exists(out_path)):
                # an earlier abandoned child landed the fragment while
                # this attempt was still dialing — stop the waiter (TERM;
                # it is not holding the lease) and take the result. A
                # CLAIMED child is never cut down here: its own fragment
                # write precedes a slow PJRT teardown, and a TERM in that
                # window is the abrupt-death-while-holding hazard
                log("fragment landed via an abandoned child; stopping "
                    f"attempt {attempt}")
                proc.terminate()
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    pass
                return True
            rc = proc.poll()
            if rc is not None:
                if last_rc is not None:
                    last_rc[:] = [rc]
                if rc == 0 and os.path.exists(out_path):
                    return True
                log(f"tpu child attempt {attempt} exited rc={rc} "
                    f"(claimed={claimed})")
                if claimed and attempt >= 2:
                    # the chip worked but the bench itself failed twice —
                    # a real error, not a lease wait; stop burning budget
                    return os.path.exists(out_path)
                if not claimed and time.monotonic() - t_spawn < 30:
                    # died before even reaching the dial (import error,
                    # bad store path …) — respawning cannot fix that
                    fast_fails += 1
                    if fast_fails >= 3:
                        log("tpu child crashes immediately; giving up on "
                            "the accelerator path")
                        return os.path.exists(out_path)
                break
            if not claimed and os.path.exists(claim_path):
                claimed = True
                if claim_event is not None:
                    claim_event.set()
                win_end = time.monotonic() + TPU_RUN_TIMEOUT_S
                log(f"tpu child claimed the accelerator "
                    f"(attempt {attempt}); run window "
                    f"{TPU_RUN_TIMEOUT_S:.0f}s")
            if claimed and time.monotonic() >= deadline:
                # global deadline with the TPU leg mid-run: the record
                # must go out NOW. The claimed child is left running —
                # a chip holder is never cut down — and its late
                # fragment simply goes unused this round.
                log("bench deadline reached during the TPU run; emitting "
                    "the record without waiting (child left running)")
                return os.path.exists(out_path)
            if time.monotonic() >= win_end:
                log(f"tpu child attempt {attempt} "
                    + ("overran its run window"
                       if claimed else
                       f"did not claim within {window:.0f}s — likely a "
                       "stale chip lease; recycling for a fresh dial"))
                proc.terminate()  # SIGTERM, never SIGKILL (lease safety)
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    log("tpu child ignored SIGTERM for 60s; abandoning it "
                        "(NOT escalating to SIGKILL — that wedges the "
                        "lease)")
                break
            time.sleep(2)
        window = min(window * 2, 960.0)
    log(f"accelerator never became available within {ACCEL_WAIT_S:.0f}s")
    return os.path.exists(out_path)


def run_degraded(inter, heldout, truth, rng, cancel=None):
    """TPU never landed: measure train quality on the pinned all-f32 CPU
    schedule at a reduced shape so the record still carries real RMSE /
    ranking numbers (flagged degraded), then serve from those factors.

    `cancel` (threading.Event) aborts between stages: when a TPU child
    claims the chip mid-fallback, this thread stops at the next stage
    boundary so parent CPU load stops perturbing the child's timed
    sections as soon as possible (a jitted stage in flight can't be
    interrupted)."""
    n_sub = min(DEGRADED_NNZ, len(inter.user_idx))
    log(f"DEGRADED mode: CPU all-f32 schedule on a {n_sub}-event "
        f"subsample (full-shape host walls already measured)")
    sub = np.random.default_rng(11).choice(
        len(inter.user_idx), n_sub, replace=False)
    sub.sort()

    class _Sub:
        user_idx = inter.user_idx[sub]
        item_idx = inter.item_idx[sub]
        values = inter.values[sub]
        user_ids = inter.user_ids
        item_ids = inter.item_ids

    from incubator_predictionio_tpu.ops import als

    def cancelled() -> bool:
        if cancel is not None and cancel.is_set():
            log("degraded fallback cancelled — a TPU child claimed the "
                "chip")
            return True
        return False

    if cancelled():
        return None
    u_b, i_b, n_users, n_items, prep_s = prep_buckets(_Sub)
    if cancelled():
        return None
    state, t = measure_train((u_b, i_b, n_users, n_items), 0,
                             cache_probe=False)
    fit = als.rmse(state, _Sub.user_idx, _Sub.item_idx, _Sub.values)
    if cancelled():
        return None
    heldout_rmse, prec10 = quality_metrics(state, _Sub, heldout, truth, rng)
    log(f"degraded train: warm={t['train_s']:.1f}s fit={fit:.3f} "
        f"heldout={heldout_rmse:.3f} p@10={prec10:.3f}")
    if cancelled():
        return None
    serve = bench_serving(state, _Sub)
    # vs_baseline against the baseline scaled to the degraded nnz (the
    # train wall is ~linear in nnz at fixed shape) — an honest ~1.0, not
    # a fake speedup
    scaled_base = CPU_BASELINE_TRAIN_S * n_sub / NNZ
    return {
        "value": round(t["train_s"], 3),
        "vs_baseline": round(scaled_base / t["train_s"], 2),
        "obs_mfu_train": t.get("obs_mfu_train"),
        "obs_device_train_s": t.get("obs_device_train_s"),
        "obs_device_train_dispatches": t.get("obs_device_train_dispatches"),
        "train_rmse": round(float(fit), 3),
        "heldout_rmse": round(heldout_rmse, 3),
        "precision_at_10_vs_truth": round(prec10, 3),
        "degraded_nnz": n_sub,
        "serve_p50_ms": serve["p50_ms"],
        "serve_p99_ms": serve["p99_ms"],
        "serve_qps": serve["qps_sequential"],
        "serve_qps_concurrent": serve["qps_concurrent"],
        "serve_max_batch": serve["max_batch"],
    }


def run_orchestrator() -> None:
    """Default entry: host-side stages in THIS process (jax pinned to
    CPU — the parent never dials the chip), TPU stages in a supervised
    child. Always prints one parsed JSON record; exit 0 even in degraded
    mode (a degraded record is a result, not an error)."""
    import atexit
    import shutil
    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")

    t_bench0 = time.monotonic()
    emit_by = t_bench0 + BENCH_DEADLINE_S - EMIT_MARGIN_S
    # wall-clock deadline for the CHILD (monotonic clocks don't cross
    # process boundaries): optional legs (retrain) skip themselves when
    # the record must go out soon
    os.environ["PIO_BENCH_EMIT_BY_EPOCH"] = str(
        time.time() + BENCH_DEADLINE_S - EMIT_MARGIN_S)

    rng = np.random.default_rng(7)
    log(f"dataset: {N_USERS}x{N_ITEMS}, nnz={NNZ}, rank={RANK}, "
        f"sweeps={ITERATIONS} ({BF16_SWEEPS} bf16 + "
        f"{ITERATIONS - BF16_SWEEPS} f32-polish), planted rank "
        f"{PLANT_RANK} + noise {NOISE_SIGMA}")
    users, items, ratings, heldout, truth = make_dataset(rng)

    store_dir = tempfile.mkdtemp(prefix="pio_bench_store_")
    atexit.register(shutil.rmtree, store_dir, True)
    frag_path = os.path.join(store_dir, "tpu_fragment.json")

    # -- THE record, created before any stage runs. Every stage fills it
    # in place, so at any instant it is the best-available parsed record
    # — and the SIGTERM handler below can flush it if the DRIVER's
    # deadline (not ours) lands first. BENCH_r05 ended rc=124 with
    # parsed:null because an already-computed degraded record was still
    # waiting for the orchestrator's own emit point when the driver
    # killed the process; now the kill itself emits. Stable key set
    # across modes: every key a prior round's record had is present
    # (None when the mode can't measure it), so round-over-round
    # comparisons never hit a missing key on a degraded round.
    record = {
        "metric": "als_ml20m_train_wall_s",
        "value": None,
        "unit": "s",
        "vs_baseline": None,
        "degraded": True,
        # provenance (obs/capacity.py reads these): what machine/software
        # produced this row of the trajectory, and — when the round could
        # not measure the accelerator — a STRUCTURED reason, so no record
        # is ever unexplainable (the BENCH_r04/r05 parsed:null class)
        "bench_env": bench_env(),
        "skipped_reason": None,
        "train_rmse": None,
        "heldout_rmse": None,
        "noise_floor": NOISE_SIGMA,
        "precision_at_10_vs_truth": None,
        # pre-declared so the degraded-fallback thread's record.update
        # never INSERTS a key: a dict resize racing the SIGTERM
        # handler's json.dumps would raise mid-flush (value swaps are
        # GIL-atomic; popped again when a child fragment lands)
        "degraded_nnz": None,
        "mfu": None,
        "mfu_bf16_peak": None,
        "compile_s_first": None,
        "compile_s_warm_cache": None,
        "seed_wall_s": None,
        "ingest_wall_s": None,
        "prep_wall_s": None,
        "prep_h2d_s": None,  # child-only (pipelined prep→device upload)
        # host-pipeline sub-metrics (bench_scan_probe): sharded-scan
        # walls, native-lock-held wall, scan→prep overlap
        **{k: None for k in (
            "scan_open_s", "scan_wall_1thread_s", "scan_wall_seq_s",
            "scan_wall_sharded_s", "scan_speedup_vs_seq",
            "scan_speedup_vs_1thread", "scan_shards",
            "scan_shard_walls_s", "scan_lock_held_s",
            "scan_merge_wall_s", "scan_prep_pipelined_wall_s",
            "scan_prep_overlap_s")},
        "e2e_train_wall_s": None,
        "ingest_http_eps": None,
        "ingest_http_eps_cap500": None,
        "movielens_rmse": None,
        "movielens_rmse_bound": None,
        "serve_p50_ms": None,
        "serve_p99_ms": None,
        "serve_qps": None,
        "serve_qps_concurrent": None,
        "serve_max_batch": None,
        # child-fragment fields (overwritten when the child lands; a
        # degraded round carries the honest null markers so every
        # deterministic key a successful round emits is present)
        "als_kernel": None,
        "als_kernel_rows": None,
        "als_kernel_sweep_xla_s": None,
        "flash_kernel_active": None,
        "train_fused_wall_s": None,
        "obs_device_train_s": None,
        "obs_device_train_dispatches": None,
        # steady-state retrain leg (child-only; docs/performance.md)
        **dict.fromkeys(RETRAIN_KEYS),
        # speed-layer leg (child-only; docs/production.md "Freshness
        # between retrains")
        **dict.fromkeys(SPEED_KEYS),
        # mesh-sharded training leg (parent-side subprocess on the
        # forced-host-device CPU sim; docs/performance.md "Sharded ALS")
        **dict.fromkeys(SHARD_KEYS),
        **dict.fromkeys(MIPS_KEYS),
        # ≥10M-item MIPS lifecycle leg (in-process; PQ + background
        # rebuild-and-swap; docs/performance.md "Catalogue at tens of
        # millions")
        **dict.fromkeys(MIPS_BIG_KEYS),
        # serving-fleet leg (parent-side worker subprocesses;
        # docs/production.md "Serving fleet")
        **dict.fromkeys(FLEET_KEYS),
        # fleet front-door leg (parent-side router over worker
        # subprocesses; docs/production.md "Fleet front door")
        **dict.fromkeys(FRONTDOOR_KEYS),
        # multi-tenant noisy-neighbor leg (two tenants on a real
        # 2-worker fleet; docs/production.md "Multi-tenant platform")
        **dict.fromkeys(TENANT_KEYS),
        # self-driving freshness leg (controller over fleet workers +
        # front door; docs/production.md "Self-driving freshness")
        **dict.fromkeys(CONTROLLER_KEYS),
        # self-tuning serving leg (knob controller over fleet workers +
        # front door; docs/production.md "Self-tuning serving")
        **dict.fromkeys(KNOB_KEYS),
        # planet-scale ingest leg (sharded writers + replication +
        # front-door soak; docs/production.md "Planet-scale ingest")
        **dict.fromkeys(INGEST_KEYS),
        "accel_waited_s": None,
        "accel_outcome": "never_available",
        "sasrec_epoch_s": None,
        **{f"attn_{kind}_ms_{s // 1024}k": None
           for s in (int(v) for v in os.environ.get(
               "PIO_BENCH_ATTN_SEQS", "4096,8192,32768").split(",") if v)
           for kind in ("flash", "xla")},
        "nnz": NNZ,
        "rank": RANK,
        "sweeps": ITERATIONS,
        "bf16_sweeps": BF16_SWEEPS,
        # telemetry cross-check (docs/observability.md): stable None
        # defaults; child-fragment values and the parent registry
        # snapshot below fill what each process actually ran
        **dict.fromkeys(OBS_KEYS),
    }
    emitted: list = []

    def _emit_record(from_signal: bool = False) -> None:
        # contract: ONE complete JSON line on stdout. `emitted` is set
        # only AFTER the full line is flushed: a SIGTERM landing while
        # the main emit is mid-write still re-emits (the handler
        # prefixes a newline so any partial main-thread write becomes
        # its own garbage line and the record line stays parseable —
        # the worst case is a duplicated valid line, never a missing
        # one, which was the parsed:null class). The dumps retry guards
        # a worker thread mutating the record mid-serialization: value
        # swaps are GIL-atomic (all keys pre-declared above), but one
        # retry keeps even an unexpected resize from costing the round
        # its record.
        if emitted:
            return
        try:
            line = json.dumps(record)
        except RuntimeError:
            line = json.dumps(dict(record))
        sys.stdout.write(("\n" if from_signal else "") + line + "\n")
        sys.stdout.flush()
        emitted.append(True)

    def _deadline_flush(signum, frame):
        # the DRIVER's kill (timeout → SIGTERM, the rc=124 path): flush
        # the best-available record NOW — a late child fragment is
        # picked up if one landed — and exit cleanly. Machine-readable
        # metrics from every run, even one the driver cut short.
        try:
            if os.path.exists(frag_path):
                with open(frag_path) as f:
                    record.update(json.load(f))
                record["degraded"] = False
                record["skipped_reason"] = None
        except Exception:
            pass
        if record.get("degraded") and record.get("skipped_reason") is None:
            record["skipped_reason"] = {
                "class": "driver_deadline",
                "stage": "tpu_child",
                "detail": "driver SIGTERM before the bench's own emit "
                          "point; best-available degraded record flushed",
                "rc": 124,
            }
        log("SIGTERM before the bench's own emit point: flushing the "
            "best-available record")
        _emit_record(from_signal=True)
        os._exit(0)

    import signal

    signal.signal(signal.SIGTERM, _deadline_flush)

    # -- 1. SEED (host) ----------------------------------------------------
    events, client, seed_s = seed_store(store_dir, users, items, ratings)
    client.close()
    record["seed_wall_s"] = round(seed_s, 1)
    log(f"seed: {NNZ} events in {seed_s:.1f}s "
        f"({NNZ / seed_s / 1e6:.2f}M ev/s)")

    # -- 2a. SCAN PROBES (host): the sharded-scan sub-metrics. The
    #        ingest stage below serves from the projection cache (the
    #        production warm path), so the native scan machinery is
    #        measured here explicitly — sequential vs sharded, cache
    #        bypassed, plus the pipelined scan→prep leg. Runs before the
    #        ingest stage so its transient full-shape arrays are freed
    #        before the parent holds its own copy, and GUARDED: a probe
    #        failure nulls the sub-metrics, never costs the record (the
    #        BENCH_r05 recordless-exit class)
    try:
        record.update(bench_scan_probe(store_dir))
    except Exception as e:  # noqa: BLE001 — sub-metrics are optional
        log(f"scan probe failed ({e!r}); sub-metrics null this round")

    # -- 2+3. INGEST + PREP (host, parent's own copy for the degraded
    #         record; the child measures its own on the TPU path) ----------
    inter, ingest_s = scan_store(store_dir)
    assert len(inter) == NNZ, len(inter)
    record["ingest_wall_s"] = round(ingest_s, 1)
    log(f"ingest scan: {ingest_s:.1f}s ({NNZ / ingest_s / 1e6:.2f}M ev/s)")
    prep_probe = prep_buckets(inter)
    prep_s = prep_probe[4]
    del prep_probe
    record["prep_wall_s"] = round(prep_s, 1)
    log(f"prep (bucketed padded rows): {prep_s:.1f}s")

    # -- 6. INGEST-HTTP (host; needs no accelerator) -----------------------
    record["ingest_http_eps"] = bench_ingest_http()
    record["ingest_http_eps_cap500"] = bench_ingest_http(batch_size=500)

    # -- 6b. REAL-DATA QUALITY BOUND (host CPU; tiny) ----------------------
    record.update(bench_movielens_quality())

    # -- 6c. MESH-SHARDED TRAINING LEG (host CPU, own subprocess with
    #        the backend forced to 8 virtual devices) ----------------------
    try:
        record.update(bench_shard(emit_by - time.monotonic()))
    except Exception as e:  # noqa: BLE001 — sub-metrics are optional
        log(f"shard leg failed ({e!r}); shard_* keys null this round")

    # -- 6d. SERVING-FLEET LEG (host CPU, real worker subprocesses +
    #        parent-side load generators) ----------------------------------
    try:
        record.update(bench_fleet(emit_by - time.monotonic()))
    except Exception as e:  # noqa: BLE001 — sub-metrics are optional
        log(f"fleet leg failed ({e!r}); fleet_* keys null this round")

    # -- 6d2. FLEET FRONT-DOOR LEG (host CPU, in-process router over
    #         worker subprocesses; chaos-injected) ------------------------
    try:
        record.update(bench_frontdoor(emit_by - time.monotonic()))
    except Exception as e:  # noqa: BLE001 — sub-metrics are optional
        log(f"frontdoor leg failed ({e!r}); frontdoor_* keys null "
            "this round")

    # -- 6d3. SELF-DRIVING FRESHNESS LEG (host CPU, controller over
    #         fleet workers + front door; zero human retrains) ------------
    try:
        record.update(bench_controller(emit_by - time.monotonic()))
    except Exception as e:  # noqa: BLE001 — sub-metrics are optional
        log(f"controller leg failed ({e!r}); controller_* keys null "
            "this round")

    # -- 6d4. SELF-TUNING SERVING LEG (host CPU, knob controller over
    #         fleet workers + front door; planted world model) ----------
    try:
        record.update(bench_knobs(emit_by - time.monotonic()))
    except Exception as e:  # noqa: BLE001 — sub-metrics are optional
        log(f"knobs leg failed ({e!r}); knob_* keys null this round")

    # -- 6d5. MULTI-TENANT NOISY-NEIGHBOR LEG (host CPU, two tenants on
    #         a real 2-worker fleet behind the front door) ---------------
    try:
        record.update(bench_tenants(emit_by - time.monotonic()))
    except Exception as e:  # noqa: BLE001 — sub-metrics are optional
        log(f"tenants leg failed ({e!r}); tenant_* keys null this round")

    # -- 6e. TWO-STAGE MIPS SERVING LEG (in-process; planted catalogue
    #        past ML-20M scale, exhaustive stays the oracle) ---------------
    try:
        record.update(bench_mips(emit_by - time.monotonic()))
    except Exception as e:  # noqa: BLE001 — sub-metrics are optional
        log(f"mips leg failed ({e!r}); mips_* keys null this round")

    # -- 6e2. MIPS CATALOGUE-AT-SCALE LEG (in-process; ≥10M items under
    #         PQ with a background rebuild-and-swap mid-serve; skips on
    #         budget via its own cost model — the 1-core box never pays
    #         for it by accident) --------------------------------------
    try:
        record.update(bench_mips_big(emit_by - time.monotonic()))
    except Exception as e:  # noqa: BLE001 — sub-metrics are optional
        log(f"mips big leg failed ({e!r}); mips_big_* keys null")

    # -- 6f. PLANET-SCALE INGEST LEG (host CPU; sharded writers vs
    #        single-writer in the same run, replication lag, front-door
    #        soak with a rolling zero-downtime writer reload). LAST of
    #        the host legs: its soak saturates the CPU, and the timed
    #        legs before it must not inherit that heat or lose budget
    #        to it (it budget-skips to null keys gracefully). ------------
    try:
        record.update(bench_ingest(emit_by - time.monotonic()))
    except Exception as e:  # noqa: BLE001 — sub-metrics are optional
        log(f"ingest leg failed ({e!r}); ingest_* keys null this round")

    # -- 4/5/7. TRAIN + ATTENTION + SERVE: supervised TPU child ------------
    # (started after the host stages so parent CPU load never perturbs the
    # child's timed sections — on a 1-core driver box that skew is real).
    # If no child claims the chip within DEGRADED_START_S, the parent
    # starts computing the degraded record in parallel with the remaining
    # wait; the overlap bounds the worst-case bench wall at roughly
    # host stages + ACCEL_WAIT_S instead of their sum plus the fallback.
    import threading

    sup_done = threading.Event()
    claim_seen = threading.Event()
    sup_ok: list = []
    child_last_rc: list = []

    def _supervise() -> None:
        try:
            sup_ok.append(
                supervise_tpu_child(store_dir, frag_path, claim_seen,
                                    deadline_mono=emit_by - 5.0,
                                    last_rc=child_last_rc))
        finally:
            sup_done.set()

    t_sup0 = time.monotonic()
    threading.Thread(target=_supervise, daemon=True).start()

    degraded_result: list = []
    t_deg = None
    # start the fallback at DEGRADED_START_S — or earlier when the global
    # deadline demands it: the degraded record needs DEGRADED_BUDGET_S to
    # compute, and a record MUST be on stdout before the driver's kill
    # (the BENCH_r05 failure mode). Worst case the fallback overlaps the
    # dial wait from the first second; cancel-on-claim keeps the CPU
    # perturbation window as short as possible.
    deg_start_wait = max(0.0, min(
        DEGRADED_START_S,
        (emit_by - DEGRADED_BUDGET_S) - time.monotonic()))

    def _run_degraded_into_record() -> None:
        res = run_degraded(inter, heldout, truth, rng, cancel=claim_seen)
        degraded_result.append(res)
        if res:
            # fold into the live record the moment it exists, so a
            # driver kill from here on flushes REAL train-quality
            # numbers (the child fragment, if one still lands, is
            # applied after and overrides)
            record.update(res)
            record["bf16_sweeps"] = 0  # degraded = all-f32 CPU schedule
            if record["ingest_wall_s"] is not None \
                    and record["prep_wall_s"] is not None:
                record["e2e_train_wall_s"] = round(
                    record["ingest_wall_s"] + record["prep_wall_s"]
                    + record["value"], 1)

    if not sup_done.wait(deg_start_wait) and not claim_seen.is_set():
        log(f"no accelerator claim after {deg_start_wait:.0f}s — "
            "computing the degraded record in parallel with the wait")
        t_deg = threading.Thread(target=_run_degraded_into_record,
                                 daemon=True)
        t_deg.start()
    if not sup_done.wait(max(emit_by - time.monotonic(), 0.0)):
        log("bench deadline: abandoning the supervisor thread and "
            "emitting the record now")
    accel_waited_s = time.monotonic() - t_sup0
    child_ok = bool(sup_ok and sup_ok[0]) or os.path.exists(frag_path)
    if not child_ok and t_deg is not None:
        # never start a second run_degraded while the thread lives — the
        # two would race on the process-global Storage registry; wait it
        # out up to the deadline instead
        t_deg.join(timeout=max(emit_by - time.monotonic(), 5.0))
        if t_deg.is_alive():
            log("degraded fallback still running at the deadline — "
                "emitting the record without train-quality keys")
    # how long the supervised-child leg ran and how it ended — makes
    # a wedged-lease round diagnosable from the record alone.
    # child_ok counts as claiming evidence too: a fragment can land
    # via an abandoned child whose claim file the supervisor no
    # longer polls
    record["accel_waited_s"] = round(accel_waited_s, 1)
    record["accel_outcome"] = ("claimed"
                               if claim_seen.is_set() or child_ok
                               else "never_available")
    if child_ok and os.path.exists(frag_path):
        with open(frag_path) as f:
            record.update(json.load(f))
        record["degraded"] = False
        record["skipped_reason"] = None
        record["bf16_sweeps"] = BF16_SWEEPS
        # a degraded fallback may have folded in before the child landed
        # — the fragment overrode every shared key; drop its marker
        record.pop("degraded_nnz", None)
        record["e2e_train_wall_s"] = round(
            record["ingest_wall_s"] + record["prep_wall_s"]
            + record["value"], 1)
    else:
        record["degraded"] = True
        # the structured why (satellite of the capacity model): this
        # round's accelerator story, machine-readable — the r04 class
        # ("accelerator init still blocked") ends up here instead of an
        # unexplained parsed:null; rc is the last child exit actually
        # observed, null when no child ever exited in view
        record["skipped_reason"] = {
            "class": ("accelerator_unavailable"
                      if record["accel_outcome"] == "never_available"
                      else "tpu_child_failed"),
            "stage": "tpu_child",
            "detail": (f"accel_outcome={record['accel_outcome']} after "
                       f"{record['accel_waited_s']}s wait; degraded CPU "
                       "record emitted in its place"),
            "rc": child_last_rc[0] if child_last_rc else None,
        }
        record["bf16_sweeps"] = 0  # degraded runs the all-f32 CPU schedule
        if degraded_result and degraded_result[0]:
            pass  # already folded into the record by the fallback thread
        elif t_deg is not None and t_deg.is_alive():
            pass  # fallback thread hung — never race a second run
        elif time.monotonic() + DEGRADED_BUDGET_S <= emit_by:
            # no fallback ran, or it was cancelled by a claim from a child
            # that then failed — the thread is dead and there is still
            # budget before the deadline, so run it fresh
            deg = run_degraded(inter, heldout, truth, rng)
            if deg:
                record.update(deg)
                # full-shape read/prep walls + degraded-shape train wall:
                # the degraded flag marks the mixed provenance
                record["e2e_train_wall_s"] = round(
                    record["ingest_wall_s"] + record["prep_wall_s"]
                    + record["value"], 1)
        else:
            log("no time left for a fresh degraded run before the "
                "deadline — emitting the record without train-quality "
                "keys")
    # parent-side registry snapshot: fills the obs_* keys for the stages
    # THIS process ran (ingest HTTP always; serving too on a degraded
    # round) without overriding anything the child fragment measured
    for k, v in obs_snapshot().items():
        if record.get(k) is None:
            record[k] = v
    _emit_record()


#: the reference's own bundled MovieLens sample (user::item::rating, 1.5k
#: real ratings) — the only real interaction dataset in this egress-free
#: environment. Loaded AT RUN TIME from the read-only reference tree
#: (never copied into the repo); the stage reports null when absent.
MOVIELENS_SAMPLE = os.environ.get(
    "PIO_BENCH_MOVIELENS",
    "/root/reference/examples/experimental/data/movielens.txt")
#: regression bound for the real-data stage: measured 1.076/1.058/1.024
#: across seeds 0..2 (rank 8, λ=0.1, 10 sweeps, 80/20 split; the sample
#: is 30 users × 100 items, rating std 1.19 — the model beats the
#: constant predictor by ~10%, which is what 1.2k training ratings
#: support). 1.20 is ~11% headroom over the worst seed and below the
#: 1.31 a mis-regularized run measures — tight enough to catch a solver
#: regression, loose enough for seed noise.
MOVIELENS_RMSE_BOUND = float(
    os.environ.get("PIO_BENCH_MOVIELENS_BOUND", "1.20"))


def load_movielens_sample():
    """→ (users, items, vals, n_users, n_items) from the sample file, or
    None when missing/unparseable (the stage must never crash the
    orchestrator's always-emit-a-record contract — the path is
    env-overridable and an operator may point it at a file in another
    format)."""
    try:
        with open(MOVIELENS_SAMPLE) as f:
            rows = [line.strip().split("::") for line in f if line.strip()]
        users = np.asarray([int(r[0]) for r in rows], np.int32)
        items = np.asarray([int(r[1]) for r in rows], np.int32)
        vals = np.asarray([float(r[2]) for r in rows], np.float32)
    except (OSError, ValueError, IndexError) as e:
        log(f"movielens sample unusable at {MOVIELENS_SAMPLE} ({e}); "
            "real-data stage skipped")
        return None
    # dense reindex (ids in the file are sparse)
    uu, users = np.unique(users, return_inverse=True)
    ii, items = np.unique(items, return_inverse=True)
    return (users.astype(np.int32), items.astype(np.int32), vals,
            len(uu), len(ii))


#: the stage's own hyperparameters: 1.2k training ratings cannot support
#: the bench shape's rank-128/λ=0.03 config (it would overfit to
#: noise) — this is a SEPARATE tiny-data solver-health bound, tuned for
#: the sample (rank 8, λ=0.1 measured best of a small grid), NOT a
#: validation of the big bench's λ. The planted stage owns that.
MOVIELENS_RANK = 8
MOVIELENS_L2 = 0.1


def bench_movielens_quality():
    """Real-data RMSE regression bound (VERDICT r4 item 4): train on 80%
    of the reference's bundled MovieLens sample, report heldout RMSE and
    whether it clears the pinned bound. Synthetic planted quality proves
    recovery against a KNOWN floor; this proves the solver stays healthy
    on real human ratings (at the sample's own tuned tiny-data
    hyperparameters — see MOVIELENS_RANK/MOVIELENS_L2). → dict of record
    keys (nulls if the sample file is unavailable)."""
    from incubator_predictionio_tpu.ops import als

    out = {"movielens_rmse": None, "movielens_rmse_bound": None}
    loaded = load_movielens_sample()
    if loaded is None:
        return out
    users, items, vals, n_users, n_items = loaded
    rng = np.random.default_rng(0)
    perm = rng.permutation(len(vals))
    cut = int(0.8 * len(vals))
    tr, te = perm[:cut], perm[cut:]
    state, _ = als.als_train(
        users[tr], items[tr], vals[tr], n_users, n_items,
        rank=MOVIELENS_RANK, iterations=10, l2=MOVIELENS_L2, seed=0)
    rmse_te = als.rmse(state, users[te], items[te], vals[te])
    ok = rmse_te <= MOVIELENS_RMSE_BOUND
    log(f"movielens sample ({len(vals)} real ratings): heldout RMSE "
        f"{rmse_te:.3f} (bound {MOVIELENS_RMSE_BOUND}) "
        f"{'OK' if ok else 'REGRESSION'}")
    return {
        "movielens_rmse": round(float(rmse_te), 3),
        "movielens_rmse_bound": MOVIELENS_RMSE_BOUND,
    }


def bench_attention():
    """Driver-verified attention numbers (r3 verdict item 9): flash
    (Pallas) vs the XLA blockwise scan at 8k/32k, plus one SASRec
    train-epoch wall — so kernel claims land in BENCH json, and a Mosaic
    rejection (flash_available() False → XLA fallback serving the flash
    call via interpret-free blockwise) is visible instead of silent."""
    import jax
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops.attention import blockwise_attention
    from incubator_predictionio_tpu.ops.pallas_kernels import (
        flash_attention,
        flash_available,
    )

    out = {"flash_kernel_active": bool(flash_available())}
    if not out["flash_kernel_active"]:
        log("attention: Mosaic rejected the flash family on this backend "
            "— XLA blockwise path serves (numbers below are XLA vs XLA)")
    h, d = 8, 64
    # 4096 rides along to place the flash/scan crossover (the per-length
    # block table serves ≥8192; 4k is the scan's side of the line today)
    seqs_env = os.environ.get("PIO_BENCH_ATTN_SEQS", "4096,8192,32768")
    # enough calls to amortize the per-dispatch floor (a 3-call loop
    # would measure dispatch, not the kernel)
    reps = int(os.environ.get("PIO_BENCH_ATTN_REPS", 20))
    for s in (int(v) for v in seqs_env.split(",") if v):
        key = jax.random.key(0)
        q, k, v = (
            jax.random.normal(kk, (1, s, h, d), jnp.bfloat16)
            for kk in jax.random.split(key, 3)
        )

        def timed(fn):
            r = fn(q, k, v, causal=True)
            np.asarray(r[0:1, 0:1, 0:1, 0:1])  # dependent fetch = sync
            t0 = time.perf_counter()
            for _ in range(reps):
                r = fn(q, k, v, causal=True)
            np.asarray(r[0:1, 0:1, 0:1, 0:1])
            return (time.perf_counter() - t0) / reps

        t_flash = timed(flash_attention)
        t_xla = timed(blockwise_attention)
        out[f"attn_flash_ms_{s // 1024}k"] = round(t_flash * 1e3, 2)
        out[f"attn_xla_ms_{s // 1024}k"] = round(t_xla * 1e3, 2)
        log(f"attention S={s}: flash={t_flash * 1e3:.2f}ms "
            f"xla={t_xla * 1e3:.2f}ms ({t_xla / t_flash:.2f}x)")

    from incubator_predictionio_tpu.ops.transformer import sasrec_fit

    rng = np.random.default_rng(5)
    seqs = rng.integers(1, 2000, (512, 128)).astype(np.int32)
    t0 = time.perf_counter()
    sasrec_fit(seqs, n_items=2000, d_model=64, n_heads=2, n_layers=2,
               epochs=1, batch_size=128)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    sasrec_fit(seqs, n_items=2000, d_model=64, n_heads=2, n_layers=2,
               epochs=1, batch_size=128)
    warm = time.perf_counter() - t0
    out["sasrec_epoch_s"] = round(warm, 2)
    log(f"sasrec: 1-epoch wall first={first:.1f}s warm={warm:.2f}s "
        f"(512x128 seqs, d=64)")
    return out


async def _http_post_loop(port, path, bodies) -> None:
    """One async keep-alive connection POSTing each body in turn — the
    shared load-generator leg of the ingest and serving benches. Every
    request carries the bench's trace ID (one per process, prefixed
    ``bench-``) so the servers' span logs attribute the load to this
    bench run — the bench→servers hop of the cross-process trace
    contract (docs/observability.md "Fleet")."""
    import asyncio

    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        for body in bodies:
            writer.write(
                f"POST {path} HTTP/1.1\r\nHost: bench\r\n"
                "Content-Type: application/json\r\n"
                f"X-PIO-Trace-Id: {_bench_trace_id()}\r\n"
                f"Content-Length: {len(body)}\r\n\r\n".encode()
                + body)
            await writer.drain()
            head = await reader.readuntil(b"\r\n\r\n")
            status_line = head.split(b"\r\n", 1)[0]
            if b" 200 " not in status_line:
                raise RuntimeError(f"request failed: {status_line!r}")
            clen = next(
                (int(line.split(b":")[1])
                 for line in head.split(b"\r\n")
                 if line.lower().startswith(b"content-length")), None)
            if clen is None:
                raise RuntimeError("response without Content-Length")
            await reader.readexactly(clen)
    finally:
        writer.close()


def bench_ingest_http(batch_size: int = 50):
    """REST ingest throughput through the real EventServer into the cpplog
    backend: async keep-alive clients posting ``batch_size``-event batches
    to POST /batch/events.json. 50 is the reference's wire-contract cap
    (EventServer.scala:269-289's hot path); a second pass at 500 measures
    the raised --batch-cap headroom the bulk-loader path advertises.
    Returns events/s."""
    import asyncio
    import tempfile

    from incubator_predictionio_tpu.data.storage import (
        AccessKey,
        App,
        Storage,
    )
    from incubator_predictionio_tpu.servers.event_server import (
        EventServer,
        EventServerConfig,
    )

    n_clients = int(os.environ.get("PIO_BENCH_INGEST_CLIENTS", 32))
    # 100 batches/client (160k events at the contract cap) ≈ 2 s: long
    # enough that connection setup and first-append warmup stop shaving
    # ~20% off the number. The batch COUNT stays constant across caps —
    # a bigger cap means more events and a comparable (slightly longer)
    # wall, keeping both measurements sustained-rate, not burst
    batches_per_client = int(os.environ.get("PIO_BENCH_INGEST_BATCHES",
                                            100))

    with tempfile.TemporaryDirectory(prefix="pio_bench_ingest_") as tmpdir:
        Storage.configure({
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            "PIO_STORAGE_SOURCES_EV_TYPE": "cpplog",
            "PIO_STORAGE_SOURCES_EV_PATH": tmpdir,
            "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        })
        apps = Storage.get_meta_data_apps()
        app_id = apps.insert(App(0, "bench-ingest"))
        Storage.get_meta_data_access_keys().insert(
            AccessKey("benchkey", app_id))
        srv = EventServer(EventServerConfig(ip="127.0.0.1", port=0,
                                            max_batch=batch_size))
        port = srv.start_background()

        def batch_body(cid: int, b: int) -> bytes:
            return json.dumps([
                {
                    "event": "rate",
                    "entityType": "user",
                    "entityId": f"u{cid}_{b}_{k}",
                    "targetEntityType": "item",
                    "targetEntityId": f"i{k}",
                    "properties": {"rating": float(1 + k % 5)},
                }
                for k in range(batch_size)
            ]).encode()

        path = "/batch/events.json?accessKey=benchkey"
        # pre-render every request body OUTSIDE the timed window: the
        # load generator shares the box (often the core) with the server,
        # and its json.dumps would otherwise count against the server's
        # measured throughput
        bodies = [
            [batch_body(c, b) for b in range(batches_per_client)]
            for c in range(n_clients)
        ]

        async def load() -> float:
            t0 = time.perf_counter()
            await asyncio.wait_for(
                asyncio.gather(*[
                    _http_post_loop(port, path, bodies[c])
                    for c in range(n_clients)
                ]),
                timeout=600.0)
            return time.perf_counter() - t0

        wall = asyncio.run(load())
        total = n_clients * batches_per_client * batch_size
        landed = Storage.get_events().scan_interactions(
            app_id=app_id, event_names=("rate",), value_prop="rating")
        assert len(landed) == total, (len(landed), total)
        eps = total / wall
        log(f"ingest-http: {total} events in {wall:.1f}s "
            f"({eps:.0f} ev/s, {n_clients} clients x "
            f"{batches_per_client} batches of {batch_size})")
        srv.stop()
        Storage.reset()
        return round(eps, 1)


def bench_serving(state, inter):
    """Deploy the trained factors behind the real PredictionServer and
    measure the device serving path over HTTP: sequential p50/p99/QPS and
    128-async-client concurrent QPS (the micro-batcher fuses those into
    batch_predict dispatches — CreateServer.scala:523's 'TODO')."""
    import threading
    import urllib.request

    from incubator_predictionio_tpu.data.bimap import BiMap
    from incubator_predictionio_tpu.data.storage import (
        EngineInstance,
        Storage,
    )
    from incubator_predictionio_tpu.models.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        ALSModel,
        RecommendationServing,
    )
    from incubator_predictionio_tpu.servers.prediction_server import (
        PredictionServer,
        ServerConfig,
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
    model = ALSModel(
        user_factors=state.user_factors,   # device-resident
        item_factors=state.item_factors,
        user_bimap=BiMap({u: i for i, u in enumerate(inter.user_ids)}),
        item_bimap=BiMap({t: i for i, t in enumerate(inter.item_ids)}),
        item_years={}, item_categories={},
    )
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=RANK))
    now = now_utc()
    instance = EngineInstance(
        id="bench", status="COMPLETED", start_time=now, end_time=now,
        engine_id="bench", engine_version="1", engine_variant="bench",
        engine_factory="bench")
    server = PredictionServer.__new__(PredictionServer)
    # direct state injection: the bench measures the serving path, not the
    # checkpoint restore (engine=None is never touched by /queries.json)
    server.engine = None
    # micro_batch default = the scheduler's ladder cap
    # (PIO_SERVE_MAX_BATCH): the serving leg measures the adaptive
    # plane, not a hand-pinned fuse width; the env knob remains for
    # fixed-width comparisons
    mb = os.environ.get("PIO_BENCH_SERVE_MICRO_BATCH")
    server.config = (
        ServerConfig(ip="127.0.0.1", port=0, micro_batch=int(mb))
        if mb else ServerConfig(ip="127.0.0.1", port=0))
    from incubator_predictionio_tpu.servers.plugins import PluginContext
    from incubator_predictionio_tpu.servers.prediction_server import (
        _AsyncPoster,
        _MicroBatcher,
    )
    from incubator_predictionio_tpu.utils.http import HttpServer
    from incubator_predictionio_tpu.workflow.workflow import (
        make_runtime_context,
    )
    server.plugin_context = PluginContext()
    server.ctx = make_runtime_context(None)
    server._lock = threading.Lock()
    server.engine_instance = instance
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
    server.http = HttpServer(server._build_router(), "127.0.0.1", 0)
    server._speed_overlays = []
    # shed=False: this leg measures raw device serving throughput, and
    # its closed-loop burst deliberately drives queue depths whose
    # projection would cross the default serve_p99 objective — a 503
    # here would abort the whole child leg (the load loop raises on
    # non-200). Shed behavior is bench_fleet's jurisdiction.
    server._batcher = _MicroBatcher(server._handle_batch,
                                    server.config.micro_batch,
                                    workers=server.config.serve_workers,
                                    shed=False)
    server._feedback_poster = _AsyncPoster("feedback")
    server._log_poster = _AsyncPoster("log", workers=1)
    port = server.http.start_background()

    def query_once(user: str) -> None:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/queries.json",
            data=json.dumps({"user": user, "num": 10}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
            resp.read()

    # warm the serving dispatch (compiles the scoring kernels)
    query_once("u1")
    query_once("u2")

    # sequential latency distribution
    n_seq = int(os.environ.get("PIO_BENCH_SERVE_N", 200))
    lat = []
    t_seq0 = time.perf_counter()
    for i in range(n_seq):
        t0 = time.perf_counter()
        query_once(f"u{i % N_USERS}")
        lat.append(time.perf_counter() - t0)
    seq_wall = time.perf_counter() - t_seq0
    lat_ms = np.sort(np.asarray(lat)) * 1e3
    p50 = float(lat_ms[int(0.50 * (n_seq - 1))])
    p99 = float(lat_ms[int(0.99 * (n_seq - 1))])
    qps_seq = n_seq / seq_wall

    # concurrent: async keep-alive clients (thread-per-client load
    # generators are GIL-bound ~400 QPS and under-measure the server; 128
    # async connections measured best — 647 vs 426 at 64 and 281 at 256);
    # the micro-batcher fuses the in-flight queries
    n_clients = int(os.environ.get("PIO_BENCH_SERVE_CLIENTS", 128))
    per_client = int(os.environ.get("PIO_BENCH_SERVE_CONC", 25))
    # warm the batched kernel shapes (powers of two up to the PADDED batch
    # cap — batch_score_top_k pads B to the next power of two, so a
    # non-power-of-two micro_batch still lands on 1 << ceil(log2(cap))) so
    # the concurrent window measures serving, not XLA compiles
    from incubator_predictionio_tpu.models.recommendation.engine import Query
    cap = 1 << max(server.config.micro_batch - 1, 0).bit_length()
    size = 1
    while size <= cap:
        algo.batch_predict(model, [
            (i, Query(user=f"u{i % N_USERS}", num=10)) for i in range(size)])
        size *= 2

    import asyncio

    async def _load() -> float:
        def bodies(cid: int):
            return (
                json.dumps({
                    "user": f"u{(cid * per_client + j) % N_USERS}",
                    "num": 10}).encode()
                for j in range(per_client)
            )
        t0 = time.perf_counter()
        # per-phase deadline replacing the old per-request urlopen timeout
        await asyncio.wait_for(
            asyncio.gather(*[
                _http_post_loop(port, "/queries.json", bodies(c))
                for c in range(n_clients)
            ]),
            timeout=max(120.0, 0.5 * n_clients * per_client))
        return time.perf_counter() - t0

    conc_wall = asyncio.run(_load())
    qps_conc = n_clients * per_client / conc_wall
    max_batch = server.max_batch_served
    log(f"serving: p50={p50:.2f}ms p99={p99:.2f}ms seq={qps_seq:.0f}qps "
        f"conc{n_clients}={qps_conc:.0f}qps max_batch={max_batch}")
    server.stop()
    Storage.reset()
    return {
        "p50_ms": round(p50, 2),
        "p99_ms": round(p99, 2),
        "qps_sequential": round(qps_seq, 1),
        "qps_concurrent": round(qps_conc, 1),
        "max_batch": int(max_batch),
    }


if __name__ == "__main__":
    if "--cpu" in sys.argv:
        run_cpu_baseline()
    elif "--shard-child" in sys.argv:
        run_shard_child()
    elif "--tpu-child" in sys.argv:
        i = sys.argv.index("--tpu-child")
        run_tpu_child(sys.argv[i + 1], sys.argv[i + 2], sys.argv[i + 3],
                      int(sys.argv[i + 4]) if len(sys.argv) > i + 4 else 0)
    else:
        run_orchestrator()
