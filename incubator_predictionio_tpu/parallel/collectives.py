"""XLA collective wrappers — the framework's distributed communication
backend.

The reference's "communication backend" is Spark shuffle/broadcast inside
MLlib plus HTTP between servers (SURVEY.md §2.7); it has no NCCL/MPI layer.
The TPU-native equivalent is XLA collectives over ICI (intra-slice) and DCN
(across slices), expressed as ``jax.lax`` primitives under ``shard_map`` /
``pjit``. This module is the single place the rest of the framework goes for
them, so the mapping from "what the algorithm needs" to "which collective
rides which interconnect" lives in one file.

All functions take ``axis_name`` (a mesh axis as seen inside ``shard_map``)
and are traceable — they compile to the corresponding XLA collective and are
no-ops (or cheap copies) when the axis has size 1.
"""

from __future__ import annotations

from typing import Any, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map  # noqa: F401 - re-exported: THE import the framework uses

AxisName = Union[str, Sequence[str]]


def axis_size(axis_name: AxisName) -> int:
    """Number of shards along ``axis_name`` (inside shard_map) — a python
    int at trace time, usable for static permutation tables."""
    return lax.axis_size(axis_name)


def axis_index(axis_name: AxisName):
    """This shard's coordinate along ``axis_name`` (inside shard_map)."""
    return lax.axis_index(axis_name)


def all_reduce_sum(x: Any, axis_name: AxisName) -> Any:
    """Sum over the axis — one XLA all-reduce on ICI/DCN (lax.psum)."""
    return lax.psum(x, axis_name)


def all_reduce_mean(x: Any, axis_name: AxisName) -> Any:
    """Mean over the axis — the DP gradient-sync collective (lax.pmean)."""
    return lax.pmean(x, axis_name)


def all_reduce_max(x: Any, axis_name: AxisName) -> Any:
    return lax.pmax(x, axis_name)


def all_gather(x: Any, axis_name: AxisName, axis: int = 0,
               tiled: bool = True) -> Any:
    """Gather shards along ``axis`` from every member of the mesh axis.

    ``tiled=True`` concatenates (shard dim multiplies by axis size), matching
    the layout produced by sharding an array over that axis.
    """
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x: Any, axis_name: AxisName, axis: int = 0,
                   tiled: bool = True) -> Any:
    """Sum then scatter: each shard keeps its slice of the reduced result.
    Half the bandwidth of all-reduce when the consumer is itself sharded —
    the right primitive for sharded optimizer states (ZeRO-style)."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=tiled)


def ppermute_next(x: Any, axis_name: AxisName) -> Any:
    """Rotate shards one step around the axis ring (i → i+1 mod n).

    This is the ring-attention / ring-exchange building block: on TPU the
    permutation maps onto neighbor ICI links, so every step moves all shards
    concurrently at full ring bandwidth.
    """
    n = axis_size(axis_name)
    return lax.ppermute(x, axis_name, [(i, (i + 1) % n) for i in range(n)])


def ppermute_prev(x: Any, axis_name: AxisName) -> Any:
    """Rotate shards one step the other way (i → i-1 mod n)."""
    n = axis_size(axis_name)
    return lax.ppermute(x, axis_name, [(i, (i - 1) % n) for i in range(n)])


def all_to_all(x: Any, axis_name: AxisName, split_axis: int,
               concat_axis: int, tiled: bool = True) -> Any:
    """Transpose shard ownership between two array dims — the Ulysses-style
    sequence↔head resharding collective for long-context attention."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def broadcast_from(x: Any, axis_name: AxisName, src_index: int = 0) -> Any:
    """Every shard receives ``x`` as seen by shard ``src_index`` (the Spark
    ``broadcast`` analogue, but over ICI instead of the driver network)."""
    idx = lax.axis_index(axis_name)
    masked = jax.tree_util.tree_map(
        lambda t: jnp.where(idx == src_index, t, jnp.zeros_like(t)), x
    )
    return lax.psum(masked, axis_name)
