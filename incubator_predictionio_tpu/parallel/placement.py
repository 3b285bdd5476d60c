"""FactorPlacement — the placement abstraction over ALS factor state.

ALX (PAPERS.md: arxiv 2112.02194) scales ALS to billion-row catalogs by
sharding BOTH factor tables across the TPU mesh and structuring each
half-sweep as shard-local solves over the rows a device owns, with the
other side's factor shards moved by collectives. This module is the
single source of truth for that layout:

- **Ownership** is contiguous row blocks: the padded table is split into
  ``n_shards`` equal slices and shard ``s`` owns global rows
  ``[s·shard_rows, (s+1)·shard_rows)``. Contiguous blocks mean the
  global↔local index maps are pure arithmetic (``owner = id // rows``,
  ``local = id − owner·rows``) — no lookup tables ride the trace.
- **Tables** shard on rows over the WHOLE mesh (both axes flattened):
  per-device HBM/VMEM footprint divides by the full device count, which
  is what re-enables the fused Gram+solve kernel's VMEM table residency
  at big-table shapes (docs/performance.md "Sharded ALS").
- **Interaction buckets** are shard-blocked: rows grouped into equal
  per-shard blocks along axis 0 (parallel/sharding.py
  ``shard_block_buckets``), so the SAME flat arrays serve the
  single-chip path (n_shards=1) and the shard_map path (each device
  sees exactly its block).

A :class:`FactorPlacement` is a frozen, hashable dataclass — it rides
``ALSState`` as static pytree metadata and jit cache keys, so resharding
(a different mesh shape) naturally recompiles while steady-state
retrains under a fixed placement never do.

Cross-replica update sharding (arxiv 2004.13336) falls out of the
layout: each device solves and scatters ONLY its own row block, so
factor updates are shard-local by construction — no update collective
exists to optimize away.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True, eq=False)
class FactorPlacement:
    """Mesh + per-table sharding + shard-local↔global index arithmetic.

    ``n_users``/``n_items`` are the TRUE table sizes; padded sizes (to a
    multiple of the shard count) are derived. Hashable and cheap to
    compare: jit paths take it as a static argument — and because the
    traced programs depend only on the shard GEOMETRY (mesh + padded
    table shapes), eq/hash are keyed on exactly that, not the true
    sizes. With ``grow=True`` capacities, ids appending within capacity
    produce an EQUAL placement: steady-state retrains hit the jit cache,
    only a geometry change (reshard / capacity doubling) recompiles.
    True sizes stay host-side data (``unplace_state`` slicing, the
    serving ``valid_items`` mask).
    """

    mesh: Mesh
    n_users: int
    n_items: int
    #: fixed padded capacities (multiples of the shard count). None =
    #: tight fit; the continuation-retrain path sets pow2-per-shard
    #: capacities (:func:`make_placement` ``grow=True``) so the shard
    #: geometry — and with it the resident prep plan and every compiled
    #: program — stays stable while new ids append within capacity.
    users_capacity: Optional[int] = None
    items_capacity: Optional[int] = None

    def _geometry(self) -> Tuple[Any, int, int]:
        return (self.mesh, self.n_users_padded, self.n_items_padded)

    def __eq__(self, other: Any) -> bool:
        return (isinstance(other, FactorPlacement)
                and self._geometry() == other._geometry())

    def __hash__(self) -> int:
        return hash(self._geometry())

    # -- mesh geometry ------------------------------------------------------
    @property
    def axes(self) -> Tuple[str, ...]:
        """The flattened logical shard axis (every mesh axis)."""
        return tuple(self.mesh.axis_names)

    @property
    def n_shards(self) -> int:
        return int(self.mesh.devices.size)

    # -- padded table shapes ------------------------------------------------
    def _padded(self, n: int, cap: Optional[int]) -> int:
        m = self.n_shards
        tight = -(-max(n, 1) // m) * m
        return max(cap, tight) if cap else tight

    @property
    def n_users_padded(self) -> int:
        return self._padded(self.n_users, self.users_capacity)

    @property
    def n_items_padded(self) -> int:
        return self._padded(self.n_items, self.items_capacity)

    def shard_rows(self, side: str) -> int:
        """Rows per shard of one table ("user" | "item")."""
        n = self.n_users_padded if side == "user" else self.n_items_padded
        return n // self.n_shards

    # -- shardings ----------------------------------------------------------
    @property
    def table_spec(self) -> P:
        return P(self.axes)

    def table_sharding(self) -> NamedSharding:
        """Rows sharded over the flattened mesh — both factor tables."""
        return NamedSharding(self.mesh, self.table_spec)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    # -- index maps ---------------------------------------------------------
    def owner_of(self, side: str, ids: np.ndarray) -> np.ndarray:
        """Global row ids → owning shard index (host-side numpy)."""
        return np.asarray(ids) // self.shard_rows(side)

    def localize(self, side: str, ids: np.ndarray) -> np.ndarray:
        """Global row ids → shard-local indices; negatives pass through
        (the padding sentinel the scatter drops)."""
        ids = np.asarray(ids)
        local = ids - self.owner_of(side, ids) * self.shard_rows(side)
        return np.where(ids >= 0, local, ids)

    def globalize(self, side: str, shard: int, local: np.ndarray) -> np.ndarray:
        return np.asarray(local) + shard * self.shard_rows(side)

    # -- state movement -----------------------------------------------------
    def place_table(self, arr: Any, side: str) -> jax.Array:
        """Pad a [n, K] factor table to the padded size and shard it."""
        arr = jnp.asarray(arr, jnp.float32)
        n = self.n_users_padded if side == "user" else self.n_items_padded
        if arr.shape[0] < n:
            arr = jnp.pad(arr, ((0, n - arr.shape[0]), (0, 0)))
        elif arr.shape[0] > n:
            arr = arr[:n]
        return jax.device_put(arr, self.table_sharding())

    def place_state(self, state: Any) -> Any:
        """ALSState → placed (padded + sharded) ALSState carrying this
        placement. Re-placing a state trained at a DIFFERENT mesh shape
        is the continuation resharding path: the true-size prefix is the
        model; padding is recomputed for the new shard count."""
        from incubator_predictionio_tpu.ops.als import ALSState

        uf = state.user_factors
        vf = state.item_factors
        prev = getattr(state, "placement", None)
        if prev is not None:
            uf = uf[: prev.n_users]
            vf = vf[: prev.n_items]
        return ALSState(
            user_factors=self.place_table(uf, "user"),
            item_factors=self.place_table(vf, "item"),
            placement=self,
        )

    def unplace_state(self, state: Any) -> Any:
        """Placed state → plain state sliced back to the true sizes."""
        from incubator_predictionio_tpu.ops.als import ALSState

        return ALSState(
            user_factors=state.user_factors[: self.n_users],
            item_factors=state.item_factors[: self.n_items],
        )

    # -- bookkeeping --------------------------------------------------------
    def describe(self) -> str:
        """e.g. "4x2": the mesh's shape along the placement's axes."""
        return "x".join(str(self.mesh.shape[a]) for a in self.axes)

    def cache_key(self) -> str:
        """Plan-invalidation key: a prep plan built under one placement
        must not be spliced under another (resharding rebuilds). Keyed
        on the shard GEOMETRY (mesh + padded capacities), not the exact
        live sizes — ids appending within capacity keep the plan."""
        return (f"{self.describe()}:{self.n_users_padded}:"
                f"{self.n_items_padded}:"
                f"{hash(self.mesh) & 0xFFFFFFFF:x}")

    def allgather_bytes(self, side_gathered: str, sweeps: int,
                        rank: int, itemsize: int = 4) -> int:
        """Analytic collective volume of ``sweeps`` half-sweeps that
        all-gather the ``side_gathered`` table: each device receives the
        (n−1)/n of the table it does not hold."""
        n = self.n_shards
        if n <= 1:
            return 0
        rows = (self.n_users_padded if side_gathered == "user"
                else self.n_items_padded)
        per_dev = rows * rank * itemsize * (n - 1) // n
        return per_dev * n * sweeps


def is_distributed(x: Any) -> bool:
    """True when ``x`` is a jax array actually SHARDED over >1 device
    (not merely replicated) — the serving/fold-in routing predicate for
    placed factor tables."""
    s = getattr(x, "sharding", None)
    if s is None:
        return False
    try:
        return (len(s.device_set) > 1
                and not s.is_fully_replicated)
    except Exception:
        return False


def placement_for_ctx(ctx: Any, n_users: int, n_items: int,
                      ) -> Optional[FactorPlacement]:
    """THE engine seam: the training placement for this RuntimeContext,
    or None for the single-chip path. Sharding engages when the context
    asks for model parallelism (``pio train --model-parallelism N``) or
    `PIO_SHARD_TABLES=1` forces it, AND more than one device exists.
    ``grow=True`` keeps the shard geometry stable across continuation
    retrains while ids append."""
    import os

    forced = os.environ.get("PIO_SHARD_TABLES", "0") not in (
        "0", "off", "false")
    want = int(getattr(ctx, "model_parallelism", 1) or 1) > 1 or forced
    if not want:
        return None
    placement = make_placement(ctx.mesh, n_users, n_items, grow=True)
    # gate on the mesh the placement will actually use (which honors
    # the PIO_MESH_DEVICES cap), not the raw global device count — a
    # capped 1-device mesh is the single-chip path
    if placement.n_shards <= 1:
        return None
    return placement


def make_placement(mesh: Optional[Mesh], n_users: int, n_items: int,
                   grow: bool = False) -> FactorPlacement:
    """Placement over ``mesh`` (default: the standard full-device mesh).

    ``grow=True`` (the steady-state retrain policy) rounds each table's
    per-shard rows up to a power of two: capacity doubles occasionally
    instead of shifting every retrain, so the shard geometry — the prep
    plan, the compiled sharded programs, the index arithmetic — is
    stable while new ids append. Padding rows hold zero factors and are
    never solved or served (ops/topk.py masks them)."""
    if mesh is None:
        from incubator_predictionio_tpu.parallel.mesh import make_mesh

        mesh = make_mesh()
    uc = ic = None
    if grow:
        n = int(mesh.devices.size)

        def cap(rows: int) -> int:
            per = -(-max(rows, 1) // n)
            return n * (1 << max(per - 1, 0).bit_length())

        uc, ic = cap(n_users), cap(n_items)
    return FactorPlacement(mesh=mesh, n_users=int(n_users),
                           n_items=int(n_items),
                           users_capacity=uc, items_capacity=ic)
