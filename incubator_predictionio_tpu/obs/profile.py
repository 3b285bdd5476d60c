"""Device-time / MFU attribution for the hot dispatch entry points.

Without it a deployment's device time is invisible. This module wraps the training, retrain, fold-in and serving dispatches
with **block-until-ready wall deltas** plus known-FLOP counters, off by
default and enabled with ``PIO_PROFILE=1``:

- ``pio_device_seconds{op}`` — attributed device+dispatch wall,
- ``pio_device_dispatches_total{op}`` — dispatches attributed,
- ``pio_device_flops_total{op}`` — analytic useful FLOPs (padding waste
  is *not* counted — it shows up as lower MFU),
- ``pio_mfu{phase}`` — the LAST dispatch's model-FLOP utilization in
  that phase against the device's published peak (:data:`PEAK_FLOPS`,
  one table keyed by ``device_kind``). A device the table does not
  list has no peak: the gauge stays unset — never a default.

Op labels are a BOUNDED set chosen by the call sites: ``als_train``
(XLA-assembly training), ``als_fused`` (training through the fused
Gram+solve Pallas kernel path — its own label so the kernel's measured
trajectory is separable in /metrics, while ``als.train_flops`` stays
the ONE FLOP formula for both, keeping ``pio_mfu{phase="train"}``
comparable across the split), ``als_retrain`` (continuation retrain on
the XLA path), ``foldin_solve`` (speed-layer fold-in buckets — same
label on both its XLA and fused-kernel solve paths) and the serving
``serve_topk``/``serve_topk_batch`` entries.

OFF is the contract: with ``PIO_PROFILE`` unset, a call site pays one
``t0()`` env read returning None and one ``record()`` None-check —
no block_until_ready, no metrics, no jax import. The profiler is the
ONLY module allowed to call ``block_until_ready`` on a serve-reachable
path (the ``blocking-profiler`` pio-lint rule enforces this): when ON,
every attributed dispatch becomes synchronous, which is exactly what a
wall measurement means — never leave it on for latency-critical
production serving, use a canary.

``capture_trace`` is the on-demand ``jax.profiler`` xplane capture
behind the admin server's ``POST /profile?seconds=N`` — the raw input
for the ROADMAP-5 kernel work.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Optional

from incubator_predictionio_tpu.obs import metrics as obs_metrics

logger = logging.getLogger(__name__)

DEVICE_SECONDS = obs_metrics.REGISTRY.counter(
    "pio_device_seconds",
    "device+dispatch wall attributed by the PIO_PROFILE=1 profiler, "
    "by op", labels=("op",))
DEVICE_DISPATCHES = obs_metrics.REGISTRY.counter(
    "pio_device_dispatches_total",
    "dispatches attributed by the profiler, by op", labels=("op",))
DEVICE_FLOPS = obs_metrics.REGISTRY.counter(
    "pio_device_flops_total",
    "analytic useful FLOPs attributed by the profiler, by op",
    labels=("op",))
MFU = obs_metrics.REGISTRY.gauge(
    "pio_mfu",
    "last attributed dispatch's model-FLOP utilization vs the device's "
    "published peak (obs/profile.py PEAK_FLOPS; unset on a device the "
    "table does not list), by phase", labels=("phase",))

#: published peak dense-matmul FLOP/s of ONE chip, keyed by
#: ``jax.devices()[0].device_kind``, each with its source. The only
#: place a peak lives; a device that is not here has none.
PEAK_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    # (16 GB HBM at 819 GB/s). jax reports the chip as "TPU v5 lite".
    "TPU v5 lite": 197e12,
}

_no_peak_logged: set = set()


def enabled() -> bool:
    """True when the dispatch profiler is on (``PIO_PROFILE=1``). Read
    per call — a live process can be toggled — and cheap enough for the
    serving hot path (one env dict lookup)."""
    return os.environ.get("PIO_PROFILE", "0").lower() not in (
        "0", "", "false", "off")


def peak_flops() -> Optional[float]:
    """The peak ``pio_mfu`` divides by: :data:`PEAK_FLOPS` for this
    process's ``device_kind``, or None — logged once per kind — when
    the table has no entry (the CPU backend among them)."""
    import jax

    kind = jax.devices()[0].device_kind
    peak = PEAK_FLOPS.get(kind)
    if peak is None and kind not in _no_peak_logged:
        _no_peak_logged.add(kind)
        logger.warning(
            "no published peak for device_kind %r in obs/profile.py "
            "PEAK_FLOPS: pio_mfu stays unset", kind)
    return peak


def t0() -> Optional[float]:
    """Dispatch-entry stamp: ``time.perf_counter()`` when profiling is
    on, None otherwise. The None is the whole off-path cost — callers
    hand it straight back to :func:`record`."""
    if not enabled():
        return None
    return time.perf_counter()


def record(start: Optional[float], phase: str, op: str,
           flops: float = 0.0, result: Any = None,
           flops_fn: Any = None) -> None:
    """Close one attributed dispatch: block until ``result`` is device-
    complete, book the wall under ``op`` and refresh ``pio_mfu{phase}``.
    No-op when ``start`` is None (profiling was off at :func:`t0`).

    ``flops_fn`` (a zero-arg callable) defers a FLOP count whose
    computation itself touches the device (e.g. nnz from tree mask
    sums) until AFTER ``dt`` is captured — otherwise its dispatches and
    fetches would contaminate the measured wall. Plain ``flops`` is for
    host-arithmetic counts.

    This is the one sanctioned ``block_until_ready`` on serve-reachable
    paths (pio-lint ``blocking-profiler``): a wall measurement *is* a
    sync point. Telemetry must never fail the dispatch — any error here
    logs and returns."""
    if start is None:
        return
    try:
        if result is not None:
            import jax

            jax.block_until_ready(result)
        dt = time.perf_counter() - start
        if flops_fn is not None:
            flops = float(flops_fn())
        DEVICE_SECONDS.labels(op=op).inc(dt)
        DEVICE_DISPATCHES.labels(op=op).inc()
        if flops > 0:
            DEVICE_FLOPS.labels(op=op).inc(flops)
            peak = peak_flops()
            if dt > 0 and peak:
                MFU.labels(phase=phase).set(flops / dt / peak)
    except Exception:
        logger.exception("dispatch profiler record failed (op=%s)", op)


# ---------------------------------------------------------------------------
# on-demand jax.profiler capture (admin POST /profile?seconds=N)
# ---------------------------------------------------------------------------

#: serializes captures: jax.profiler supports one active trace per
#: process, and a second start_trace would raise mid-capture
_capture_lock = threading.Lock()

MAX_CAPTURE_SECONDS = 120.0


def capture_trace(seconds: float, out_dir: Optional[str] = None) -> dict:
    """Capture ``seconds`` of ``jax.profiler`` trace into ``out_dir``
    (default ``$PIO_PROFILE_DIR`` or a per-capture temp dir) and return
    ``{"traceDir", "seconds"}``. Blocks the caller for the capture
    window — the admin route runs it on the executor, so the server
    keeps serving. Raises RuntimeError when a capture is already
    running (the route maps it to 409) and ValueError on a bad window.
    """
    seconds = float(seconds)
    if not 0.0 < seconds <= MAX_CAPTURE_SECONDS:
        raise ValueError(
            f"seconds must be in (0, {MAX_CAPTURE_SECONDS:.0f}]")
    if out_dir is None:
        out_dir = os.environ.get("PIO_PROFILE_DIR")
    if not _capture_lock.acquire(blocking=False):
        raise RuntimeError("a profiler capture is already running")
    try:
        # dir created only once the capture is actually ours to run (a
        # rejected 409 must not leak an empty temp dir per request)
        if out_dir is None:
            import tempfile

            out_dir = tempfile.mkdtemp(prefix="pio_profile_")
        import jax

        jax.profiler.start_trace(out_dir)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
    finally:
        _capture_lock.release()
    return {"traceDir": out_dir, "seconds": seconds}
