"""Multi-host (multi-process) runtime for TPU pods and pod slices.

The reference scales out by submitting to a Spark cluster
(tools/.../Runner.scala:101-213 builds the spark-submit line; executors talk
through Spark's shuffle service). The TPU-native equivalent is JAX's
multi-controller runtime: one Python process per host, every process runs
the same program, and arrays are globally sharded over all hosts' devices —
collectives ride ICI inside a slice and DCN across slices.

``ensure_initialized`` is the single entry point; it is safe to call on a
laptop (no-op), under pytest's forced-CPU mesh, and on a real pod where the
coordinator env vars are set.
"""

from __future__ import annotations

import logging
import os
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh

logger = logging.getLogger(__name__)

_initialized = False
#: True only when this process actually joined a multi-controller runtime
_multiprocess = False


def ensure_initialized() -> bool:
    """Initialize ``jax.distributed`` when a coordinator is configured.

    Configuration comes from the standard JAX env vars (auto-detected on
    Cloud TPU) or the explicit ``PIO_COORDINATOR_ADDRESS`` /
    ``PIO_NUM_PROCESSES`` / ``PIO_PROCESS_ID`` trio, mirroring how the
    reference forwards ``PIO_*`` env across process boundaries
    (Runner.scala:129-131). Returns True when running multi-process.
    """
    global _initialized, _multiprocess
    if _initialized:
        return jax.process_count() > 1
    coord = os.environ.get("PIO_COORDINATOR_ADDRESS")
    if coord and "PIO_NUM_PROCESSES" not in os.environ:
        # fail loudly: silently defaulting to 1 would make every host of
        # a misconfigured pod train its own duplicate model
        raise RuntimeError(
            "PIO_COORDINATOR_ADDRESS is set but PIO_NUM_PROCESSES is not "
            "— set the full coordinator env trio (launcher.py does)")
    n_proc = int(os.environ.get("PIO_NUM_PROCESSES", "1") or 1)
    if coord and n_proc <= 1:
        # a 1-host pod has nothing to coordinate: plain single-controller
        # JAX is the correct runtime
        logger.info("distributed: single process — coordinator skipped")
        coord = None
    if coord:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=n_proc,
            process_id=int(os.environ["PIO_PROCESS_ID"]),
        )
        _multiprocess = True
        logger.info(
            "distributed: process %d/%d via coordinator %s",
            jax.process_index(), jax.process_count(), coord,
        )
    _initialized = True
    return jax.process_count() > 1


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def is_multihost() -> bool:
    return jax.process_count() > 1


def barrier(name: str) -> None:
    """Pod-wide sync point: returns only when EVERY process has reached it.

    Used as the completion gate before process 0 persists an
    EngineInstance as COMPLETED — a worker that crashed mid-train leaves
    its peers parked here until the launcher tears the pod down, so a
    failed pod run can never publish a COMPLETED instance (the
    supervision contract of Runner.scala:101-213, proven by
    tests/test_launcher.py's killed-worker drill). No-op off-pod.

    Gates on an ACTUAL multi-controller runtime — either one this module
    joined (``_multiprocess``) or an externally-provisioned
    ``jax.distributed`` client (Cloud TPU auto-init) — NOT on
    process_count(): tests fake process counts to simulate pod roles in
    one process, and the sync primitive only functions on a real
    runtime."""
    if not _runtime_active() or jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def _runtime_active() -> bool:
    """True when a jax.distributed client genuinely exists in this
    process, however it was initialized."""
    if _multiprocess:
        return True
    try:  # externally-provisioned runtime (auto-init on Cloud TPU)
        from jax._src import distributed as _jax_distributed

        return getattr(_jax_distributed.global_state, "client",
                       None) is not None
    except Exception:  # pragma: no cover - private-API drift
        return False


def is_pod_worker() -> bool:
    """True on a multi-process pod's non-zero processes — the ones that
    run the SPMD program but never own storage writes (the Spark
    executor role; CoreWorkflow gates persistence on this)."""
    return jax.process_count() > 1 and jax.process_index() != 0


def make_pod_mesh(
    axis_names: Sequence[str],
    axis_sizes: Sequence[int],
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """A named mesh over all (global) devices, DCN-aware on multi-host.

    The FIRST axis is the cross-host axis: on a real multi-slice topology it
    is laid out over DCN (via ``create_hybrid_device_mesh``) so that only
    that axis's collectives cross the data-center network, while every later
    axis stays inside a slice on ICI — put ``dp`` first and ``mp``/``sp``
    after it (the scaling-book layout).

    ``axis_sizes`` may use -1 once to absorb the remaining device count.
    """
    import numpy as np

    devs = list(devices if devices is not None else jax.devices())
    sizes = list(axis_sizes)
    if -1 in sizes:
        known = 1
        for s in sizes:
            if s != -1:
                known *= s
        if len(devs) % known != 0:
            raise ValueError(
                f"{len(devs)} devices not divisible by fixed axes {known}"
            )
        sizes[sizes.index(-1)] = len(devs) // known
    total = 1
    for s in sizes:
        total *= s
    if total != len(devs):
        raise ValueError(
            f"mesh {dict(zip(axis_names, sizes))} needs {total} devices, "
            f"have {len(devs)}"
        )

    if is_multihost() and devices is None:
        from jax.experimental import mesh_utils

        per_host = sizes[0] // jax.process_count() or 1
        try:
            grid = mesh_utils.create_hybrid_device_mesh(
                mesh_shape=(per_host, *sizes[1:]),
                dcn_mesh_shape=(sizes[0] // per_host,) + (1,) * (len(sizes) - 1),
            )
            return Mesh(grid, tuple(axis_names))
        except Exception:
            logger.warning(
                "hybrid DCN mesh layout failed; falling back to flat device "
                "order (collectives on the first axis may cross DCN "
                "suboptimally)", exc_info=True,
            )
    grid = np.array(devs).reshape(*sizes)
    return Mesh(grid, tuple(axis_names))


def host_local_batch_slice(global_batch: int) -> slice:
    """Which rows of a global batch this host is responsible for feeding.

    Multi-host input pipelines load only their slice and form global arrays
    with ``jax.make_array_from_process_local_data``; this gives the row
    range, replacing the reference's per-executor RDD partition assignment.
    """
    n = jax.process_count()
    if global_batch % n != 0:
        raise ValueError(
            f"global_batch={global_batch} is not divisible by "
            f"process_count={n}; remainder rows would silently be fed by "
            "no host — pad or trim the batch first"
        )
    per = global_batch // n
    start = per * jax.process_index()
    return slice(start, start + per)


def global_array_from_local(local, sharding):
    """Assemble a globally-sharded array from this host's local rows."""
    return jax.make_array_from_process_local_data(sharding, local)
