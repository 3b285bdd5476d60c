"""Host-resident serving helpers (the reference's driver-local locality).

Every per-query device call pays a blocking dispatch+fetch round trip.
Models whose factor tables fit a host mirror serve singleton queries
faster from numpy (matvec + argpartition — the reference's driver-local
serving locality, CreateServer.scala:498-650).

How big "fits" is is ADAPTIVE: the first caller measures the device
dispatch+fetch overhead once (a dependent 1-element fetch). On a local
chip (sub-ms dispatch) the mirror budget is 4M elements, so small models
serve from the host and large catalogs — ML-20M's 21M factor elements
among them — keep the device path, where the MXU wins. When the round
trip is expensive (≥5 ms: a remote device), the budget grows to 64M
elements (256 MB f32). A measurement that FAILS raises: a device that
cannot run a one-op program must not be read as "free dispatch".

``PIO_HOST_SERVE_MAX_ELEMS`` overrides the measurement entirely
(0 disables host serving).

Used by the recommendation / similarproduct / ecommerce serving code.
"""

from __future__ import annotations

import os
import time
from typing import Optional, Tuple

import numpy as np

NEG_INF = -3.4e38

#: mirror budget when the device round trip is cheap (local chip)
HOST_SERVE_MAX_ELEMS = 1 << 22
#: mirror budget when every device call pays an expensive round trip
HOST_SERVE_BIG_ELEMS = 1 << 26
#: dispatch+fetch round trip above this means "expensive device"
DISPATCH_EXPENSIVE_S = 5e-3

_dispatch_overhead: Optional[float] = None


def dispatch_overhead_s() -> float:
    """Measured device dispatch+fetch round trip (cached; best of 3).
    Raises whatever the device raises — never a default."""
    global _dispatch_overhead
    if _dispatch_overhead is None:
        import jax
        import jax.numpy as jnp

        fn = jax.jit(lambda v: v + 1)
        x = jnp.zeros(8, jnp.float32)
        np.asarray(fn(x))  # compile + warm outside the timed window
        samples = []
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(fn(x))
            samples.append(time.perf_counter() - t0)
        _dispatch_overhead = min(samples)
    return _dispatch_overhead


def host_serve_limit() -> int:
    """Current mirror budget in elements (env override, else adaptive)."""
    env = os.environ.get("PIO_HOST_SERVE_MAX_ELEMS", "").strip()
    if env:
        try:
            return int(env)
        except ValueError:
            import logging

            logging.getLogger(__name__).warning(
                "ignoring malformed PIO_HOST_SERVE_MAX_ELEMS=%r "
                "(want an integer element count); using the adaptive "
                "budget", env)
    if dispatch_overhead_s() >= DISPATCH_EXPENSIVE_S:
        return HOST_SERVE_BIG_ELEMS
    return HOST_SERVE_MAX_ELEMS


def warm_host_arrays(model, **field_arrays: np.ndarray) -> None:
    """Seed the host mirror from numpy copies already in hand (e.g. inside
    ``prepare_model`` before factors are device_put), so the first query
    never pays a device→host fetch. Owns the same cache-key contract as
    :func:`host_arrays`; respects the budget and any disabled cache."""
    cache = getattr(model, "_np_cache", None)
    if cache is False:
        return
    names = tuple(field_arrays)
    arrays = tuple(field_arrays.values())
    if sum(a.size for a in arrays) > host_serve_limit():
        return
    if cache is None:
        cache = {}
        object.__setattr__(model, "_np_cache", cache)
    cache[names] = arrays


def host_arrays(model, *field_names: str, max_elems: Optional[int] = None):
    """Lazy host copies of the named model fields, or None for big models.

    ``max_elems=None`` uses the adaptive budget (``host_serve_limit``).
    The copy is cached on the model object itself (``_np_cache``, keyed by
    the requested field names) so reloads naturally invalidate it. A benign
    race under concurrent first queries computes the same value twice."""
    cache = getattr(model, "_np_cache", None)
    if cache is False:   # host serving disabled for this model
        return None
    if cache is None:
        cache = {}
        object.__setattr__(model, "_np_cache", cache)
    entry = cache.get(field_names)
    if entry is None:
        if max_elems is None:
            max_elems = host_serve_limit()
        total = sum(
            int(np.prod(getattr(model, f).shape)) for f in field_names)
        if total <= max_elems:
            # one device→host fetch per field, paid once per deploy
            entry = tuple(
                np.asarray(getattr(model, f)) for f in field_names)
        else:
            entry = False
        cache[field_names] = entry
    return entry or None


def host_batch_top_k(
    scores: np.ndarray,      # [B, I]
    k: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized :func:`host_top_k` over a [B, I] score block: one
    argpartition + one argsort for the whole batch (both GIL-released) —
    per-row calls cost ~0.1 ms of serialized Python each on the
    concurrent-serving hot path. Returns ([B, k] scores, [B, k] indices)
    descending, row-for-row IDENTICAL to host_top_k (the [::-1] after an
    ascending argsort reproduces its tie ordering exactly; the serving
    byte-identity tests pin this)."""
    k = min(k, scores.shape[-1])
    if k <= 0:
        b = scores.shape[0]
        return (np.empty((b, 0), scores.dtype), np.empty((b, 0), np.int64))
    part = np.argpartition(scores, -k, axis=1)[:, -k:]
    ps = np.take_along_axis(scores, part, axis=1)
    order = np.argsort(ps, axis=1)[:, ::-1]
    return (np.take_along_axis(ps, order, axis=1),
            np.take_along_axis(part, order, axis=1))


def host_top_k(
    scores: np.ndarray,
    k: int,
    allowed_mask: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """numpy equivalent of ops.topk.top_k_with_exclusions: returns
    (top_scores[k], top_indices[k]) descending; masked slots score
    ``NEG_INF`` (callers already filter ``<= -1e37``)."""
    if allowed_mask is not None:
        scores = np.where(allowed_mask, scores, NEG_INF)
    k = min(k, scores.shape[-1])
    if k <= 0:
        return np.empty(0, scores.dtype), np.empty(0, np.int64)
    top = np.argpartition(scores, -k)[-k:]
    top = top[np.argsort(scores[top])[::-1]]
    return scores[top], top
