"""Quantized two-stage MIPS serving: coarse bucket scan + exact rerank.

Exhaustive serving dot-products the full (sharded) item table per query
(ops/topk.py) — fine at ML-20M's ~27k items, a linear wall at catalogue
scale. This module is the approximate-MIPS path the top-k auto-routers
fall forward to when an index is registered:

1. **Coarse stage.** Spherical k-means centroid buckets are computed at
   train/retrain time (per shard under ``FactorPlacement`` — the
   centroid scan and the candidate gather never cross a shard
   boundary). A query scans the tiny centroid table (C×K f32, ~0.5 MB
   at C=1024/rank=128 — VMEM-resident), weighted by each bucket's max
   row norm (an upper bound on the bucket's best inner product — plain
   cosine probing under-ranks buckets holding popular high-norm items),
   probes the top ``nprobe`` buckets and scores their member rows with
   the int8 (symmetric per-row scale) or bf16 quantized view — 4×/2×
   less HBM than the f32 scan it replaces.
2. **Exact rerank.** The top ``candidates`` coarse survivors are
   re-scored against the exact f32 factor rows and ranked. Both stage
   widths are static pow2 knobs, so steady state compiles once per
   (batch rung, k) exactly like the exhaustive ladder — zero
   steady-state recompiles, counted by ``mips_compile_cache_size`` in
   ``ops.topk.serve_compile_cache_size``.

Exhaustive stays the FALLBACK and the ORACLE: ``PIO_SERVE_MIPS=off``,
an unregistered table, a filtered query (``allowed_mask``), or a
small-catalogue ``auto`` route all take the exhaustive path unchanged,
and the recall@k gate (tests/test_mips.py) compares the
two-stage result against it.

Speed-overlay seam: fold-in vectors published for ITEM-side keys are
not in the quantized buckets yet — :func:`publish_rows` re-quantizes
known rows in place AND records the fresh vector in an **exact tail**
(scored in f32 on the host, merged after the device stage), so a
just-folded key is findable at recall 1.0 the moment it publishes.

Continuation-retrain seam: :func:`update_index` re-quantizes and
re-assigns only the touched rows (O(delta)); a geometry change (reshard
/ capacity growth) rebuilds.

Catalogue-scale seams (ops/mips_daemon.py drives them):

- ``PIO_SERVE_MIPS_QUANT=pq`` materializes **product-quantized
  residual codes** instead of a dense per-row view: M subquantizers ×
  256 codewords (``PIO_SERVE_MIPS_PQ_M``) over residuals from the
  assigned centroid, scored asymmetrically via per-query LUTs computed
  once per dispatch — rank/M bytes per row (8–16× vs f32), with the
  exact f32 rerank stage unchanged in kind.
- :func:`rebuild_index` is the background-rebuild entry: re-clusters
  off the serving path, folds the virtual-id tail into a dense **ext
  block** (ids stay stable — the overlay's key→id map survives), and
  atomically swaps the registry entry with zero serving downtime.
- Cold buckets can be **tiered to host memory** at rebuild time from
  probe-hit statistics: demoted buckets leave the device arrays
  entirely and are served by an exact host-side scan when probed —
  never a serving-path blocking transfer.

Knobs (all read at call time): ``PIO_SERVE_MIPS`` (off|auto|on),
``PIO_SERVE_MIPS_NPROBE``, ``PIO_SERVE_MIPS_CANDIDATES``,
``PIO_SERVE_MIPS_MIN_ITEMS``, ``PIO_SERVE_MIPS_CENTROIDS``,
``PIO_SERVE_MIPS_QUANT`` (int8|bf16|pq), ``PIO_SERVE_MIPS_PQ_M``,
``PIO_SERVE_MIPS_PQ_CANDIDATES``, ``PIO_MIPS_TIER`` (off|auto|on).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from incubator_predictionio_tpu.obs import metrics as obs_metrics

logger = logging.getLogger(__name__)

NEG_INF = jnp.float32(-3.4e38)

#: serving-stage scan accounting (docs/observability.md): rows touched
#: per stage — ``centroid`` (coarse centroid rows), ``coarse``
#: (quantized candidate slots in probed buckets, padding included: a
#: padded slot costs the same HBM read), ``rerank`` (exact f32 rows),
#: ``exhaustive`` (full-table rows on the fallback path). The
#: candidates-scanned fraction is (coarse + rerank) / (exhaustive-
#: equivalent rows)
_CAND_SCANNED = obs_metrics.REGISTRY.counter(
    "pio_serve_candidates_scanned_total",
    "item rows scanned by serving top-k, by stage (see "
    "docs/observability.md)", labels=("stage",))
_SCAN_CENTROID = _CAND_SCANNED.labels(stage="centroid")
_SCAN_COARSE = _CAND_SCANNED.labels(stage="coarse")
_SCAN_RERANK = _CAND_SCANNED.labels(stage="rerank")
_SCAN_EXHAUSTIVE = _CAND_SCANNED.labels(stage="exhaustive")
_RECALL = obs_metrics.REGISTRY.gauge(
    "pio_serve_mips_recall",
    "last planted-probe recall@k of the two-stage path vs the "
    "exhaustive oracle (recall_probe; sag below the 0.95 gate -> raise "
    "PIO_SERVE_MIPS_NPROBE)")
_INDEX_AGE = obs_metrics.REGISTRY.gauge(
    "pio_mips_index_age_seconds",
    "age of the OLDEST live MIPS index since its last build/update/"
    "publish/daemon-swap — climbing without bound means retrain, "
    "fold-in AND the rebuild daemon are all failing to republish")
_TAIL_SIZE = obs_metrics.REGISTRY.gauge(
    "pio_mips_tail_size",
    "exact-tail entries awaiting fold-out, per serving engine — "
    "climbing past the rebuild-tail trigger means the rebuild daemon "
    "is dead or churn outruns its cadence (docs/observability.md "
    "runbook)", labels=("engine",))
_TIER_ROWS = obs_metrics.REGISTRY.gauge(
    "pio_mips_tier_rows",
    "catalogue rows by residence tier: device (quantized coarse "
    "views in HBM) vs host (cold buckets + exact tail served from "
    "host memory)", labels=("tier",))
_REBUILDS = obs_metrics.REGISTRY.counter(
    "pio_mips_rebuilds_total",
    "background index rebuild-and-swaps by trigger "
    "(tail|age|churn|promote|manual)", labels=("trigger",))


def _now() -> float:
    """THE clock for index freshness: every ``built_at`` stamp and the
    age collector read this seam, so a FakeClock patch sees exactly the
    ages production would (tests pin the adopt/swap reset through it)."""
    return time.time()


def _collect_index_age() -> None:
    ages = []
    tails: Dict[str, int] = {}
    dev_rows = host_rows = 0
    for e in list(_REGISTRY.values()):
        idx = e.index
        ages.append(_now() - idx.built_at)
        tail = idx.tail_size()
        tails[idx.engine] = tails.get(idx.engine, 0) + tail
        d, h = idx.tier_rows()
        dev_rows += d
        host_rows += h + tail
    if ages:
        _INDEX_AGE.set(max(ages))
    for engine, t in tails.items():
        _TAIL_SIZE.labels(engine=engine).set(t)
    _TIER_ROWS.labels(tier="device").set(dev_rows)
    _TIER_ROWS.labels(tier="host").set(host_rows)


obs_metrics.REGISTRY.register_collector("mips_index_age",
                                        _collect_index_age)


# ---------------------------------------------------------------------------
# knobs (call-time reads — serving routes can be flipped live)
# ---------------------------------------------------------------------------

def serving_mode() -> str:
    """off | auto | on (default auto: route when an index exists for
    the table — indexes are only built past the auto threshold)."""
    mode = os.environ.get("PIO_SERVE_MIPS", "auto").strip().lower()
    return mode if mode in ("off", "auto", "on") else "auto"


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def min_items() -> int:
    """auto-mode catalogue floor: below it the exhaustive scan's fixed
    cost wins and the index is neither built nor routed (the measured
    crossover narrative of docs/performance.md)."""
    return _env_int("PIO_SERVE_MIPS_MIN_ITEMS", 65536)


def build_enabled(n_items: int) -> bool:
    mode = serving_mode()
    if mode == "off":
        return False
    if mode == "on":
        return n_items >= 2
    return n_items >= min_items()


def _next_pow2(n: int) -> int:
    from incubator_predictionio_tpu.ops.topk import next_pow2

    return next_pow2(n)


def default_centroids(n_items: int) -> int:
    """C ≈ sqrt(I) rounded to pow2, clamped [16, 4096] — the measured
    sweet spot of centroid-scan cost vs bucket granularity on the
    planted fixture (docs/performance.md)."""
    c = _env_int("PIO_SERVE_MIPS_CENTROIDS", 0)
    if c > 0:
        return max(_next_pow2(c), 1)
    return min(max(_next_pow2(int(np.sqrt(max(n_items, 1)))), 16), 4096)


def _nprobe_for(index: "MIPSIndex") -> int:
    """Buckets probed per query across the whole index (the sharded
    path splits it evenly, with a small per-shard floor). The default
    1/16 of the buckets — with the balanced bucket cap (≤ 2× the mean)
    — bounds the coarse gather at ~1/8 of the catalogue.

    Knob seam: ``PIO_SERVE_MIPS_NPROBE`` is a REGISTERED serving knob
    (obs/knobs.py) — read per call, so the knob controller's audited
    ``POST /knobs`` env rewrite takes effect on the very next query;
    the unaudited-knob-write lint rule pins who may write it."""
    n = _env_int("PIO_SERVE_MIPS_NPROBE", 0)
    if n <= 0:
        # 1/16 of the buckets, with a ~2048-coarse-slot floor: small
        # catalogues probe a deeper fraction (where the scan is cheap
        # anyway), the floor vanishes at scale
        n = max(index.c_total // 16, 2048 // max(index.cap, 1), 4)
    return min(max(n, 1), index.c_total)


def _candidates_for(index: "MIPSIndex", k: int) -> int:
    """Exact-rerank width (pow2): wide enough that the int8 coarse
    ranking essentially never drops a true top-k row, narrow enough
    that the rerank gather + the coarse top-k cut stay a small
    fraction of a full scan.

    Knob seam: ``PIO_SERVE_MIPS_CANDIDATES`` is a REGISTERED serving
    knob (obs/knobs.py), read per call like nprobe — the recall/latency
    trade the knob controller's hill-climb works against the live
    ``pio_serve_mips_recall`` probe. A PQ index reads its OWN width
    knob (``PIO_SERVE_MIPS_PQ_CANDIDATES``, default 2× the dense
    default): the lossier coarse ranking needs a wider exact rerank to
    hold the same recall gate, and tying the two modes to one knob
    would make the controller's hill-climb fight itself across a
    quant flip."""
    if index.quant == "pq":
        n = _env_int("PIO_SERVE_MIPS_PQ_CANDIDATES", 0)
        if n <= 0:
            n = 2048
    else:
        n = _env_int("PIO_SERVE_MIPS_CANDIDATES", 0)
        if n <= 0:
            n = 1024
    n = max(_next_pow2(n), _next_pow2(max(k, 1)))
    return min(n, _next_pow2(index.n_items))


def _quant_mode() -> str:
    q = os.environ.get("PIO_SERVE_MIPS_QUANT", "int8").strip().lower()
    return q if q in ("int8", "bf16", "pq") else "int8"


def _pq_m(rank: int) -> int:
    """Subquantizer count for PQ builds: ``PIO_SERVE_MIPS_PQ_M``
    (default 16, ~rank/16 bytes per row at rank 128) snapped DOWN to a
    divisor of the rank so every subspace gets the same width. A knob
    step lands at the next rebuild, like a quant flip."""
    m = _env_int("PIO_SERVE_MIPS_PQ_M", 16)
    m = max(1, min(m, rank))
    while rank % m:
        m -= 1
    return m


# ---------------------------------------------------------------------------
# index structure + registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ColdTier:
    """Host-memory residence for cold buckets (a rebuild-daemon
    decision — see :func:`rebuild_index`). Demoted rows leave the
    device arrays entirely; they are clustered into their OWN host
    mini-index and served by an exact f32 numpy scan of the probed
    buckets, merged after the device stage like the tail. ``hits`` is
    the promotion signal: probe pressure on a cold bucket sends its
    rows back to the device at the next rebuild."""

    centroids: np.ndarray       # [Cc, K] f32 unit centroids
    cmax: np.ndarray            # [Cc] f32 probe bound norms
    crad_cos: np.ndarray        # [Cc] f32 ball radius (cos)
    crad_sin: np.ndarray        # [Cc] f32 ball radius (sin)
    member_ids: List[np.ndarray]    # per-bucket global ids
    member_vecs: List[np.ndarray]   # per-bucket exact f32 rows
    rows: int                   # total demoted rows
    hits: np.ndarray            # [Cc] int64 probe-hit counters


@dataclasses.dataclass
class MIPSIndex:
    """Quantized views + coarse buckets over ONE item factor table.

    Device arrays share the table's sharding (row-sharded when placed;
    centroid arrays shard on the bucket axis with ``c_local`` buckets
    per shard, so every bucket's members are rows the same shard owns).
    Host mirrors (``assign``, ``members_np``, ``centroids_np``,
    ``counts``) exist for the O(delta) update path. The exact tail
    (``_tail``) holds published-but-not-yet-rebuilt vectors, merged in
    f32 after the device stage."""

    codes: jax.Array          # [I_pad, K] int8 symmetric per-row quant
    scales: jax.Array         # [I_pad] f32 per-row scale (max|v|/127)
    bf16: jax.Array           # [I_pad, K] bfloat16 view
    centroids: jax.Array      # [C, K] f32 unit centroids
    cmax: jax.Array           # [C] f32 max member row norm (probe bound)
    crad_cos: jax.Array       # [C] f32 cos of the bucket's max member
    crad_sin: jax.Array       # [C] f32 ...angle to its centroid (ball
    #                         # radius — the probe bound must stay an
    #                         # UPPER bound for off-centroid members)
    members: jax.Array        # [C, cap] int32 GLOBAL row ids, -1 pad
    assign: np.ndarray        # [n_items] int32 host bucket of each row
    members_np: np.ndarray    # [C, cap] host mirror of members
    centroids_np: np.ndarray  # [C, K] host mirror
    counts: np.ndarray        # [C] live members per bucket
    n_items: int              # true (servable) row count
    n_shards: int
    c_local: int              # buckets per shard (C = n_shards*c_local)
    cap: int                  # member slots per bucket (pow2)
    rank: int
    seed: int
    #: the quantized view this index materialized ("int8" | "bf16") —
    #: chosen from PIO_SERVE_MIPS_QUANT at BUILD time; the unselected
    #: view is a 1-row placeholder (at 1M×128 the spare view would pin
    #: hundreds of MB of HBM that nothing ever reads). A knob flip
    #: takes effect at the next rebuild.
    quant: str = "int8"
    built_at: float = 0.0     # wall ts of last build/update/publish
    rebuilds: int = 0         # full builds that produced this index
    delta_updates: int = 0    # O(delta) update_index applications
    #: PQ residual codes (quant == "pq"): bucket-major [C, cap, M]
    #: uint8 codes + [M, 256, rank/M] f32 codebooks, host mirrors for
    #: the O(delta) splice path. Placeholder-shaped under int8/bf16.
    pq_codes: Optional[jax.Array] = None
    pq_books: Optional[jax.Array] = None
    pq_codes_np: Optional[np.ndarray] = None
    pq_books_np: Optional[np.ndarray] = None
    pq_m: int = 0
    #: daemon-rebuild ext block: folded virtual-id rows [E_pad, K] f32
    #: at ids [capacity, capacity + n_ext) — the published id space
    #: stays stable across a swap, so the overlay's key→id map and any
    #: in-flight exclusion list survive unchanged
    ext: Optional[jax.Array] = None
    ext_np: Optional[np.ndarray] = None
    n_ext: int = 0
    #: true table capacity (padded row count). Under PQ every dense
    #: view is a placeholder, so the ``capacity`` property can no
    #: longer derive it from a view shape.
    capacity_rows: int = 0
    #: host cold tier (rebuild-daemon decision) — None when every
    #: bucket is device-resident
    cold: Optional[ColdTier] = None
    #: serving-engine label for the pio_mips_tail_size gauge
    engine: str = "default"
    #: host mirrors of the probe-bound arrays (the host-side probe
    #: used by cold-tier serving and the probe-hit sampler)
    cmax_np: Optional[np.ndarray] = None
    crad_cos_np: Optional[np.ndarray] = None
    crad_sin_np: Optional[np.ndarray] = None

    def __post_init__(self) -> None:
        self._lock = threading.Lock()
        #: exact tail: global/virtual id -> fresh f32 vector (host)
        self._tail: "Dict[int, np.ndarray]" = {}
        self._tail_pack: Optional[Tuple[np.ndarray, np.ndarray]] = None
        #: publish sequence numbers per tail id: the rebuild daemon
        #: snapshots a watermark, folds everything at-or-below it, and
        #: the swap carries newer entries into the successor's tail —
        #: a key published DURING a rebuild is never lost
        self._tail_seqs: Dict[int, int] = {}
        self._tail_seq = 0
        #: set under ``_lock`` at swap time: a publisher that raced the
        #: swap re-routes its entries to the successor index
        self._superseded: Optional["MIPSIndex"] = None
        self._next_virtual = self.capacity + self.n_ext
        self._table_ref: Optional[weakref.ref] = None
        #: per-bucket probe-hit counters (host, sampled) — the tiering
        #: daemon's demotion signal
        self.probe_hits = np.zeros(self.c_total, np.int64)
        self._probe_samples = 0
        self._dispatches = 0
        #: rows churned (published / delta-updated) since this index
        #: was built — a rebuild-daemon trigger input
        self.churn_rows = 0
        if not self.built_at:
            self.built_at = _now()

    @property
    def c_total(self) -> int:
        return int(self.centroids.shape[0])

    @property
    def capacity(self) -> int:
        if self.capacity_rows:
            return self.capacity_rows
        # legacy derivation: the MATERIALIZED view carries the padded
        # table shape (the unselected view is a placeholder)
        view = self.bf16 if self.quant == "bf16" else self.codes
        return int(view.shape[0])

    def geometry(self) -> Tuple[int, int, int, int]:
        """What must match for an O(delta) update to splice in place —
        a change here is a reshard/regrow and means full rebuild."""
        return (self.capacity, self.rank, self.n_shards, self.cap)

    def tier_rows(self) -> Tuple[int, int]:
        """(device rows, host cold rows) — the pio_mips_tier_rows
        split (the exact tail is counted by the collector)."""
        host = self.cold.rows if self.cold is not None else 0
        return (self.n_items + self.n_ext - host, host)

    # -- exact tail ---------------------------------------------------------
    def tail_arrays(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(ids [T] int64, vecs [T, K] f32) or None when empty; packed
        lazily and cached until the next publish."""
        with self._lock:
            if not self._tail:
                return None
            if self._tail_pack is None:
                ids = np.fromiter(self._tail, np.int64,
                                  count=len(self._tail))
                vecs = np.stack([self._tail[int(i)] for i in ids])
                self._tail_pack = (ids, vecs.astype(np.float32))
            return self._tail_pack

    def tail_size(self) -> int:
        with self._lock:
            return len(self._tail)

    def tail_virtual_size(self) -> int:
        """Virtual-id tail entries (new keys not yet folded into the
        index) — the rebuild daemon's tail trigger input. Known-row
        overrides are excluded: they live in the tail until the next
        retrain by design and must not force rebuilds forever."""
        with self._lock:
            return sum(1 for g in self._tail if g >= self.capacity)

    def stats(self) -> Dict[str, Any]:
        dev, host = self.tier_rows()
        return {
            "items": self.n_items,
            "capacity": self.capacity,
            "centroids": self.c_total,
            "bucketCap": self.cap,
            "shards": self.n_shards,
            "tail": self.tail_size(),
            "tailVirtual": self.tail_virtual_size(),
            "ageSec": round(_now() - self.built_at, 1),
            "rebuilds": self.rebuilds,
            "deltaUpdates": self.delta_updates,
            "quant": self.quant,
            "pqM": self.pq_m,
            "ext": self.n_ext,
            "deviceRows": dev,
            "hostRows": host,
            "churnRows": self.churn_rows,
            "engine": self.engine,
        }


@dataclasses.dataclass
class _Entry:
    ref: weakref.ref
    index: MIPSIndex


#: id(table) -> entry; the weakref callback unregisters when the table
#: is collected, so a dropped model never pins its index
_REGISTRY: Dict[int, _Entry] = {}


def register_index(table: Any, index: MIPSIndex) -> MIPSIndex:
    key = id(table)

    def _drop(_ref: Any, key: int = key) -> None:
        _REGISTRY.pop(key, None)

    index._table_ref = weakref.ref(table, _drop)
    _REGISTRY[key] = _Entry(ref=index._table_ref, index=index)
    return index


def unregister_index(table: Any) -> None:
    _REGISTRY.pop(id(table), None)


def index_for(table: Any) -> Optional[MIPSIndex]:
    entry = _REGISTRY.get(id(table))
    if entry is None:
        return None
    # id() reuse guard: the key survives only while THIS table does
    if entry.ref() is not table:
        _REGISTRY.pop(id(table), None)
        return None
    return entry.index


def registered_index_count() -> int:
    return len(_REGISTRY)


def registered_tables() -> List[Tuple[Any, MIPSIndex]]:
    """Live (table, index) pairs — the rebuild daemon's scan set.
    Holding the returned table reference pins it for the rebuild."""
    out = []
    for entry in list(_REGISTRY.values()):
        table = entry.ref()
        if table is not None:
            out.append((table, entry.index))
    return out


def adopt_index(prev_table: Any, new_table: Any) -> Optional[MIPSIndex]:
    """Move a registered index onto a VALUE-IDENTICAL replacement table
    (the deploy-time ``prepare_model`` re-device_put of factors that
    were just trained in this process) — skipping the full rebuild the
    new object identity would otherwise force. The caller owns the
    equal-values contract; a shape mismatch refuses."""
    index = index_for(prev_table)
    if index is None or prev_table is new_table:
        return index
    if tuple(new_table.shape) != (index.capacity, index.rank):
        return None
    unregister_index(prev_table)
    register_index(new_table, index)
    # an adoption IS a swap: the index now serves a freshly deployed
    # table, so the age collector's baseline resets exactly like a
    # retrain build/update would reset it (pio_mips_index_age_seconds
    # must never report a hot-swapped index as stale)
    index.built_at = _now()
    return index


def status_snapshot() -> List[Dict[str, Any]]:
    """One ``stats()`` dict per live registered index — the ``mips``
    block of the prediction server's ``/status``."""
    out = []
    for e in list(_REGISTRY.values()):
        if e.ref() is None:
            continue
        try:
            out.append(e.index.stats())
        except Exception:     # a racing swap must never break /status
            logger.exception("mips status snapshot failed")
    return out


def route(table: Any, *, k: int,
          allowed_mask: Optional[Any] = None,
          exclude: Optional[Any] = None) -> Optional[MIPSIndex]:
    """THE auto-router predicate (ops/topk.py calls it on every serve
    entry): the registered index when the two-stage path should serve
    this query, else None → exhaustive. Filtered queries
    (``allowed_mask``) always fall back — an arbitrary mask can
    invalidate any candidate budget, and exhaustive honors it exactly.
    So does a query whose exclusion list rivals the candidate budget
    (a power user's seen set is exactly the rows that dominate the
    coarse cut — masking most of a fixed-width rerank would return far
    fewer than k real rows where exhaustive returns a full top-k)."""
    mode = serving_mode()
    if mode == "off" or allowed_mask is not None:
        return None
    index = index_for(table)
    if index is None or index.n_items < 2:
        return None
    if k >= index.n_items:
        return None  # top-"everything": the scan IS the answer
    if exclude is not None:
        width = int(getattr(exclude, "shape", (len(exclude),))[-1])
        if 2 * width >= _candidates_for(index, k):
            return None
    return index


def book_exhaustive(rows: int) -> None:
    """Scan accounting for the exhaustive fallback path (called by the
    ops/topk wrappers — never from inside a trace)."""
    _SCAN_EXHAUSTIVE.inc(rows)


# ---------------------------------------------------------------------------
# build / update / publish
# ---------------------------------------------------------------------------

#: members whose norm is at least this fraction of their bucket's max
#: participate in the probe-bound ball radius (see build_index): only
#: near-max rows can win a query through the bound, and letting every
#: moderate-norm member widen the ball degrades the probe ranking to
#: cmax alone (measured: recall 1.0 -> 0.93 on the planted fixture)
_RADIUS_NORM_FRAC = 0.8


def _quantize_int8(vf: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    scales = np.abs(vf).max(axis=1) / 127.0
    scales = np.maximum(scales, 1e-12).astype(np.float32)
    codes = np.rint(vf / scales[:, None]).astype(np.int8)
    return codes, scales


def _bf16(vf: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return vf.astype(ml_dtypes.bfloat16)


#: PQ training budget: 256 codewords per subspace, Lloyd on a bounded
#: residual sample — build cost stays O(sample · 256 · K) however
#: large the catalogue is (the 10M-item build trains on the same 16k
#: rows a 100k build would)
_PQ_CODEBOOK = 256
_PQ_TRAIN_SAMPLE = 16384
_PQ_ITERS = 6


def _pq_train_books(res: np.ndarray, m: int, seed: int) -> np.ndarray:
    """[M, 256, rank/M] euclidean Lloyd codebooks over the residual
    subspaces. Residuals (row − assigned centroid) are what the codes
    must reconstruct — the centroid part of the score is exact (the
    probe stage already computed q·c for every bucket)."""
    n, rank = res.shape
    d = rank // m
    rng = np.random.default_rng(seed + 17)
    fit = (res if n <= _PQ_TRAIN_SAMPLE
           else res[rng.choice(n, _PQ_TRAIN_SAMPLE, replace=False)])
    books = np.zeros((m, _PQ_CODEBOOK, d), np.float32)
    if len(fit) == 0:
        return books
    for mi in range(m):
        sub = fit[:, mi * d:(mi + 1) * d].astype(np.float32)
        c = sub[rng.choice(len(sub), _PQ_CODEBOOK,
                           replace=len(sub) < _PQ_CODEBOOK)].copy()
        for _ in range(_PQ_ITERS):
            # nearest codeword by euclidean distance, via the
            # BLAS-shaped argmax(2·x·c − |c|²) expansion
            sc = 2.0 * sub @ c.T - (c * c).sum(axis=1)[None, :]
            a = np.argmax(sc, axis=1)
            sums = np.zeros((_PQ_CODEBOOK, d), np.float64)
            np.add.at(sums, a, sub)
            cnt = np.bincount(a, minlength=_PQ_CODEBOOK)
            nz = cnt > 0
            c[nz] = (sums[nz] / cnt[nz, None]).astype(np.float32)
        books[mi] = c
    return books


def _pq_encode(res: np.ndarray, books: np.ndarray,
               chunk: int = 65536) -> np.ndarray:
    """[n, M] uint8 nearest-codeword ids per subspace, chunked so the
    [chunk, 256] score block stays cache-sized."""
    n = len(res)
    m, _cb, d = books.shape
    codes = np.empty((n, m), np.uint8)
    for mi in range(m):
        sub = res[:, mi * d:(mi + 1) * d].astype(np.float32)
        bt = books[mi]
        pen = (bt * bt).sum(axis=1)[None, :]
        for s in range(0, n, chunk):
            sc = 2.0 * sub[s:s + chunk] @ bt.T - pen
            codes[s:s + chunk, mi] = np.argmax(sc, axis=1).astype(
                np.uint8)
    return codes


def _pq_pack(assign: np.ndarray, codes: np.ndarray, c: int,
             cap: int) -> np.ndarray:
    """Bucket-major [c, cap, M] uint8 code slots, laid out with the
    SAME stable-argsort slot order as :func:`_pack_members` — slot i of
    bucket b in ``members`` and in the PQ codes is the same row."""
    out = np.zeros((c, cap, codes.shape[1]), np.uint8)
    counts = np.bincount(assign, minlength=c)
    order = np.argsort(assign, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(len(order)) - starts[assign[order]]
    out[assign[order], pos] = codes[order]
    return out


def _spherical_kmeans(rows: np.ndarray, c: int, seed: int,
                      iters: int = 8,
                      sample_cap: int = 0) -> np.ndarray:
    """[c, K] unit centroids via seeded Lloyd on normalized rows; fitted
    on a bounded sample (64 rows per centroid) so build cost stays
    O(C²·K·iters) however large the shard is."""
    rng = np.random.default_rng(seed)
    unit = rows / np.maximum(
        np.linalg.norm(rows, axis=1, keepdims=True), 1e-9)
    cap = sample_cap or 64 * c
    fit = unit if len(unit) <= cap else unit[
        rng.choice(len(unit), cap, replace=False)]
    if len(fit) == 0:
        return np.zeros((c, rows.shape[1]), np.float32)
    cent = fit[rng.choice(len(fit), c, replace=len(fit) < c)].copy()
    for _ in range(iters):
        assign = np.argmax(fit @ cent.T, axis=1)
        for j in range(c):
            m = fit[assign == j]
            if len(m):
                mu = m.mean(axis=0)
                cent[j] = mu / max(float(np.linalg.norm(mu)), 1e-9)
    return cent.astype(np.float32)


def _assign_chunked(vf: np.ndarray, cent: np.ndarray,
                    chunk: int = 65536) -> np.ndarray:
    """argmax-cosine bucket of every row (norm cancels in the argmax),
    chunked so the [rows, C] score block never exceeds ~256 MB."""
    out = np.empty(len(vf), np.int32)
    for s in range(0, len(vf), chunk):
        out[s:s + chunk] = np.argmax(vf[s:s + chunk] @ cent.T, axis=1)
    return out


#: bucket preferences kept per row for the balanced spill (a row
#: overflowing its 8 best buckets goes to the emptiest open one)
_BALANCE_PREFS = 8


def _balanced_assign(vf: np.ndarray, cent: np.ndarray, cap: int,
                     chunk: int = 65536) -> np.ndarray:
    """Capacity-bounded bucket assignment: best-centroid first, spill
    to the next-best OPEN bucket when full.

    The bucket cap is the member-gather width the coarse stage pays
    for EVERY probed bucket (a padding slot reads like a real row), so
    bounding it near the mean — instead of letting k-means skew set it
    — is what holds the candidates-scanned fraction at the analytic
    nprobe/C × cap/mean figure. Fully vectorized: per-chunk top-8
    preference lists, then round-based greedy fill (rows contending
    for one bucket are admitted best-score-first, deterministically)."""
    n, c = len(vf), len(cent)
    p = min(_BALANCE_PREFS, c)
    pref = np.empty((n, p), np.int32)
    pscore = np.empty((n, p), np.float32)
    for s in range(0, n, chunk):
        scores = vf[s:s + chunk] @ cent.T
        top = np.argpartition(-scores, p - 1, axis=1)[:, :p]
        ts = np.take_along_axis(scores, top, axis=1)
        order = np.argsort(-ts, axis=1, kind="stable")
        pref[s:s + chunk] = np.take_along_axis(top, order, axis=1)
        pscore[s:s + chunk] = np.take_along_axis(ts, order, axis=1)
    assign = np.full(n, -1, np.int32)
    fill = np.zeros(c, np.int64)
    for _round in range(p):
        un = np.nonzero(assign < 0)[0]
        if not len(un):
            break
        open_ = fill < cap
        ok = open_[pref[un]]                        # [U, p]
        first = np.argmax(ok, axis=1)
        has = np.take_along_axis(ok, first[:, None], 1)[:, 0]
        un = un[has]
        if not len(un):
            break
        first = first[has]
        target = pref[un, first]
        score = pscore[un, first]
        # admit best-score-first within each contended bucket
        order = np.lexsort((-score, target))
        tsorted = target[order]
        counts = np.bincount(tsorted, minlength=c)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        pos = np.arange(len(order)) - starts[tsorted]
        accept = pos < (cap - fill)[tsorted]
        rows = un[order][accept]
        assign[rows] = tsorted[accept]
        fill += np.bincount(tsorted[accept], minlength=c)
    left = np.nonzero(assign < 0)[0]
    for row in left:  # bounded leftovers: total capacity > n by build
        b = int(np.argmin(fill))
        assign[row] = b
        fill[b] += 1
    return assign


def _pack_members(assign: np.ndarray, row_ids: np.ndarray, c: int,
                  cap: int) -> Tuple[np.ndarray, np.ndarray]:
    """Bucket member lists [c, cap] (global ids, -1 padded) + counts —
    one stable argsort, no Python loop over rows."""
    members = np.full((c, cap), -1, np.int32)
    counts = np.bincount(assign, minlength=c)
    order = np.argsort(assign, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(len(order)) - starts[assign[order]]
    members[assign[order], pos] = row_ids[order].astype(np.int32)
    return members, counts.astype(np.int64)


def _device_put_index(arr: np.ndarray, table: Any) -> jax.Array:
    """Place an index array alongside its table: same mesh, axis-0
    sharded when the table is distributed, else plain device_put."""
    from incubator_predictionio_tpu.parallel.placement import (
        is_distributed,
    )

    if is_distributed(table):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = table.sharding.mesh
        return jax.device_put(
            arr, NamedSharding(mesh, P(tuple(mesh.axis_names))))
    return jax.device_put(arr)


def _device_put_replicated(arr: np.ndarray, table: Any) -> jax.Array:
    """Replicated placement (PQ codebooks: [M, 256, d] is KB-scale and
    every shard needs the full set — axis-0 sharding would split the
    subquantizers)."""
    from incubator_predictionio_tpu.parallel.placement import (
        is_distributed,
    )

    if is_distributed(table):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(
            arr, NamedSharding(table.sharding.mesh, P()))
    return jax.device_put(arr)


def build_index(
    table: Any,                # [I_pad, K] f32 device table (maybe sharded)
    n_items: int,
    *,
    seed: int = 0,
    n_centroids: Optional[int] = None,
    host_factors: Optional[np.ndarray] = None,
    register: bool = True,
    probe_recall: bool = False,
    engine: str = "default",
) -> MIPSIndex:
    """Full build at train/retrain/publish time (host k-means + one
    assignment pass + quantization, then device placement). Per-shard
    when the table is distributed: shard ``s`` gets ``C/n`` buckets
    fitted and filled ONLY from the rows it owns."""
    from incubator_predictionio_tpu.parallel.placement import (
        is_distributed,
    )

    t0 = time.perf_counter()
    i_pad, rank = int(table.shape[0]), int(table.shape[1])
    n_items = min(int(n_items), i_pad)
    n_shards = 1
    if is_distributed(table):
        n_shards = int(table.sharding.mesh.devices.size)
    vf = (np.asarray(host_factors[:n_items], np.float32)
          if host_factors is not None
          else np.asarray(table[:n_items], np.float32))
    shard_rows = i_pad // n_shards
    # bucket granularity is sized from the PER-SHARD catalogue (each
    # shard keeps a full-resolution mini-index over the rows it owns);
    # splitting one global budget n ways would coarsen buckets with the
    # mesh and sink the sharded recall gate
    if n_centroids:
        c_local = max(_next_pow2(n_centroids) // n_shards, 1)
    else:
        c_local = default_centroids(-(-n_items // n_shards))
    c_total = c_local * n_shards

    # balanced-bucket cap ≈ 1.25× the mean bucket size (8-aligned):
    # every probed bucket's cap slots are gathered whether occupied or
    # not, so padding headroom is pure wasted HBM read — the spill
    # assignment keeps recall while the cap pins the scanned fraction
    # at the analytic nprobe/C figure
    biggest_shard = max(
        (min((s + 1) * shard_rows, n_items) - s * shard_rows
         for s in range(n_shards)
         if min((s + 1) * shard_rows, n_items) > s * shard_rows),
        default=1)
    mean_bucket = -(-biggest_shard // c_local)
    cap = max(-(-int(mean_bucket * 1.25) // 8) * 8, 8)
    assign = np.zeros(n_items, np.int32)
    cent = np.zeros((c_total, rank), np.float32)
    for s in range(n_shards):
        lo = s * shard_rows
        hi = min(lo + shard_rows, n_items)
        if hi <= lo:
            # an empty shard keeps zero centroids; its buckets stay
            # empty and its coarse scan scores NEG_INF everywhere
            continue
        local = vf[lo:hi]
        cent_s = _spherical_kmeans(local, c_local, seed + s)
        assign[lo:hi] = (s * c_local
                         + _balanced_assign(local, cent_s, cap))
        cent[s * c_local:(s + 1) * c_local] = cent_s
    members_np, counts = _pack_members(
        assign, np.arange(n_items, dtype=np.int64), c_total, cap)
    norms = np.linalg.norm(vf, axis=1).astype(np.float32)
    cmax = np.zeros(c_total, np.float32)
    np.maximum.at(cmax, assign, norms)
    # bucket ball radius over the HIGH-NORM members (≥ ½·cmax): the
    # probe ranks buckets by cmax·|q|·cos(θ_qc − r). Plain cmax·cosθ_qc
    # under-ranks a bucket whose best match sits off-centroid (the
    # retrain-moved-outlier case); the FULL worst-member radius swings
    # the other way — one spilled ordinary row balloons every bucket's
    # ball and the ranking degenerates to cmax alone. Only rows with
    # norm comparable to the bucket max can actually win a query, so
    # only they widen the ball.
    unit = vf / np.maximum(norms[:, None], 1e-9)
    row_cos = np.einsum("ik,ik->i", unit, cent[assign])
    crad_cos = np.ones(c_total, np.float32)
    high = norms >= _RADIUS_NORM_FRAC * cmax[assign]
    np.minimum.at(crad_cos, assign[high],
                  row_cos[high].astype(np.float32))
    crad_cos = np.clip(crad_cos, -1.0, 1.0)
    crad_sin = np.sqrt(1.0 - crad_cos * crad_cos).astype(np.float32)

    # materialize ONLY the selected quantized view (the others would
    # pin table-scale HBM nothing reads); placeholders keep the jit
    # signatures uniform — the static `quant` branch never touches them
    quant = _quant_mode()
    pq_m = 0
    pq_codes_np = np.zeros((n_shards, 1, 1), np.uint8)
    pq_books_np = np.zeros((1, _PQ_CODEBOOK, 1), np.float32)
    if quant == "pq":
        # residuals vs the ASSIGNED centroid: the probe stage computes
        # q·c exactly for every bucket, so the codes only need to
        # carry the residual part of the inner product
        pq_m = _pq_m(rank)
        res = vf - cent[assign]
        pq_books_np = _pq_train_books(res, pq_m, int(seed))
        pq_codes_np = _pq_pack(assign, _pq_encode(res, pq_books_np),
                               c_total, cap)
        codes = np.zeros((n_shards, rank), np.int8)
        scales = np.zeros(n_shards, np.float32)
        bf16_view = _bf16(np.zeros((n_shards, rank), np.float32))
    elif quant == "bf16":
        vf_pad = (np.concatenate(
            [vf, np.zeros((i_pad - n_items, rank), np.float32)])
            if i_pad > n_items else vf)
        # placeholder rows = n_shards so the uniform axis-0 sharding
        # still divides
        codes = np.zeros((n_shards, rank), np.int8)
        scales = np.zeros(n_shards, np.float32)
        bf16_view = _bf16(vf_pad)
    else:
        codes, scales = _quantize_int8(vf)
        if i_pad > n_items:
            pad = i_pad - n_items
            codes = np.concatenate(
                [codes, np.zeros((pad, rank), np.int8)])
            scales = np.concatenate([scales, np.zeros(pad, np.float32)])
        bf16_view = _bf16(np.zeros((n_shards, rank), np.float32))

    index = MIPSIndex(
        codes=_device_put_index(codes, table),
        scales=_device_put_index(scales, table),
        bf16=_device_put_index(bf16_view, table),
        centroids=_device_put_index(cent, table),
        cmax=_device_put_index(cmax, table),
        crad_cos=_device_put_index(crad_cos, table),
        crad_sin=_device_put_index(crad_sin, table),
        members=_device_put_index(members_np, table),
        assign=assign, members_np=members_np, centroids_np=cent,
        counts=counts, n_items=n_items, n_shards=n_shards,
        c_local=c_local, cap=cap, rank=rank, seed=int(seed),
        quant=quant, rebuilds=1,
        pq_codes=_device_put_index(pq_codes_np, table),
        pq_books=_device_put_replicated(pq_books_np, table),
        pq_codes_np=pq_codes_np, pq_books_np=pq_books_np, pq_m=pq_m,
        capacity_rows=i_pad, engine=engine,
        cmax_np=cmax.copy(), crad_cos_np=crad_cos.copy(),
        crad_sin_np=crad_sin.copy(),
    )
    if register:
        register_index(table, index)
    if probe_recall and register:
        try:
            recall_probe(table, index, host_factors=vf)
        except Exception:
            logger.exception("mips recall probe failed at build")
    logger.info(
        "mips index built: %d items, %d centroids (cap %d, %d shard%s) "
        "in %.2fs", n_items, c_total, cap, n_shards,
        "s" if n_shards != 1 else "", time.perf_counter() - t0)
    return index


def update_index(
    prev_table: Any,
    new_table: Any,
    n_items: int,
    touched_rows: Optional[np.ndarray],
) -> Optional[MIPSIndex]:
    """O(delta) continuation-retrain splice: re-quantize + re-assign
    ONLY the touched/new rows of the index registered for
    ``prev_table`` and re-register it under ``new_table``. Returns None
    (caller rebuilds) when no index is registered, the shard geometry
    or capacity changed (reshard → full rebuild is the contract), or
    the new ids outgrew the padded capacity."""
    index = index_for(prev_table)
    if index is None or touched_rows is None:
        return None
    i_pad, rank = int(new_table.shape[0]), int(new_table.shape[1])
    n_shards = 1
    from incubator_predictionio_tpu.parallel.placement import (
        is_distributed,
    )

    if is_distributed(new_table):
        n_shards = int(new_table.sharding.mesh.devices.size)
    if (i_pad, rank, n_shards) != (index.capacity, index.rank,
                                   index.n_shards):
        return None
    if index.n_ext or index.cold is not None:
        # a daemon-rebuilt index carries folded virtual rows (ext) or
        # a host cold tier keyed to a probe-stats window the retrain
        # invalidates — the splice contract doesn't cover either, so
        # the caller full-rebuilds (which also re-homes the ext rows)
        return None
    n_items = int(n_items)
    if n_items > index.capacity:
        return None
    touched = np.unique(np.concatenate([
        np.asarray(touched_rows, np.int64).ravel(),
        np.arange(index.n_items, n_items, dtype=np.int64),
    ]))
    touched = touched[(touched >= 0) & (touched < n_items)]
    if len(touched):
        tj = jnp.asarray(touched.astype(np.int32))
        vt = np.asarray(new_table[tj], np.float32)
        if index.quant == "pq":
            # PQ codes live bucket-major and encode residuals vs the
            # ASSIGNED centroid — re-home first, then encode against
            # the final (bucket, slot) home
            _reassign_rows(index, touched, vt)
            _requantize_rows(index, tj, vt)
        else:
            _requantize_rows(index, tj, vt)
            _reassign_rows(index, touched, vt)
    index.n_items = n_items
    index.delta_updates += 1
    index.churn_rows += len(touched)
    index.built_at = _now()
    with index._lock:
        # republished rows supersede their tail overrides; genuinely
        # new virtual entries (ids past capacity) survive the splice
        for row in touched:
            index._tail.pop(int(row), None)
        index._tail_pack = None
    unregister_index(prev_table)
    register_index(new_table, index)
    return index


def _requantize_rows(index: MIPSIndex, rows_j: jax.Array,
                     vecs: np.ndarray) -> None:
    """Splice fresh vectors into the MATERIALIZED quantized view (the
    other views are placeholders — see ``MIPSIndex.quant``). Under PQ
    the codes are bucket-major: each row re-encodes against its
    CURRENT bucket's centroid into its member slot (call after any
    re-assignment); rows not in any device bucket (cold/tail-only) are
    skipped — their exact tail entry serves them."""
    if index.quant == "pq":
        rows_np = np.asarray(rows_j, np.int64)
        changed: set = set()
        for pos, row in enumerate(rows_np):
            b = (int(index.assign[row])
                 if row < len(index.assign) else -1)
            if b < 0:
                continue
            slots = np.nonzero(index.members_np[b] == row)[0]
            if not len(slots):
                continue
            res = (vecs[pos].astype(np.float32)
                   - index.centroids_np[b])
            index.pq_codes_np[b, slots[0]] = _pq_encode(
                res[None, :], index.pq_books_np)[0]
            changed.add(b)
        if changed:
            bids = np.asarray(sorted(changed), np.int32)
            index.pq_codes = index.pq_codes.at[jnp.asarray(bids)].set(
                jnp.asarray(index.pq_codes_np[bids]))
        return
    if index.quant == "bf16":
        index.bf16 = index.bf16.at[rows_j].set(
            jnp.asarray(vecs).astype(jnp.bfloat16))
        return
    codes_t, scales_t = _quantize_int8(vecs)
    index.codes = index.codes.at[rows_j].set(jnp.asarray(codes_t))
    index.scales = index.scales.at[rows_j].set(jnp.asarray(scales_t))


def _reassign_rows(index: MIPSIndex, rows: np.ndarray,
                   vecs: np.ndarray) -> None:
    """Move ``rows`` to their nearest same-shard bucket on the host
    mirrors, then splice ONLY the changed buckets to the device —
    O(delta · cap), never a full repack."""
    shard_rows = index.capacity // index.n_shards
    grown = np.setdiff1d(rows, np.arange(len(index.assign)),
                         assume_unique=False)
    if len(grown):
        index.assign = np.concatenate([
            index.assign,
            np.full(int(rows.max()) + 1 - len(index.assign), -1,
                    np.int32)])
    changed_buckets = set()
    changed_cmax: Dict[int, float] = {}
    changed_crad: Dict[int, float] = {}
    norms = np.linalg.norm(vecs, axis=1)
    cmax_np = np.array(index.cmax)  # np.asarray of a jax array is RO

    def note_radius(bucket: int, pos: int) -> None:
        # widen the bucket's ball to cover the (re-solved / re-homed)
        # row's direction — but only for rows heavy enough to win a
        # query (the same _RADIUS_NORM_FRAC rule as the build)
        if norms[pos] < _RADIUS_NORM_FRAC * cmax_np[bucket]:
            return
        cos = float(vecs[pos] @ index.centroids_np[bucket]
                    / max(norms[pos], 1e-9))
        changed_crad[bucket] = min(changed_crad.get(bucket, 1.0), cos)

    for pos, row in enumerate(np.asarray(rows, np.int64)):
        shard = int(row) // shard_rows
        base = shard * index.c_local
        cent_s = index.centroids_np[base:base + index.c_local]
        new_b = base + int(np.argmax(cent_s @ vecs[pos]))
        old_b = int(index.assign[row]) if row < len(index.assign) else -1
        if norms[pos] > cmax_np[new_b]:
            cmax_np[new_b] = norms[pos]
            changed_cmax[new_b] = float(norms[pos])
        if old_b == new_b:
            note_radius(old_b, pos)
            continue
        if index.counts[new_b] >= index.cap:
            if old_b >= 0:
                # full target: keep the old membership (the fresh codes
                # still score there; widen the old ball accordingly) —
                # the next full rebuild repacks
                note_radius(old_b, pos)
                if norms[pos] > cmax_np[old_b]:
                    cmax_np[old_b] = norms[pos]
                    changed_cmax[old_b] = float(norms[pos])
                continue
            # a NEW row with a full best bucket must live SOMEWHERE:
            # spill to the emptiest bucket of its shard, else (shard
            # totally full) serve it exactly from the tail until the
            # next rebuild
            new_b = base + int(np.argmin(
                index.counts[base:base + index.c_local]))
            if index.counts[new_b] >= index.cap:
                with index._lock:
                    index._tail[int(row)] = np.asarray(
                        vecs[pos], np.float32)
                    index._tail_seq += 1
                    index._tail_seqs[int(row)] = index._tail_seq
                    index._tail_pack = None
                continue
            if norms[pos] > cmax_np[new_b]:
                cmax_np[new_b] = norms[pos]
                changed_cmax[new_b] = float(norms[pos])
        if old_b >= 0:
            slots = index.members_np[old_b]
            hit = np.nonzero(slots == row)[0]
            if len(hit):
                last = int(index.counts[old_b]) - 1
                slots[hit[0]] = slots[last]
                slots[last] = -1
                if index.quant == "pq":
                    # the compaction moved the LAST member into the
                    # vacated slot — its PQ code moves with it (the
                    # slot layouts of members and pq_codes are one)
                    index.pq_codes_np[old_b, hit[0]] = (
                        index.pq_codes_np[old_b, last])
                index.counts[old_b] = last
                changed_buckets.add(old_b)
        index.members_np[new_b, int(index.counts[new_b])] = row
        index.counts[new_b] += 1
        index.assign[row] = new_b
        changed_buckets.add(new_b)
        note_radius(new_b, pos)
    if changed_buckets:
        buckets = np.asarray(sorted(changed_buckets), np.int32)
        index.members = index.members.at[jnp.asarray(buckets)].set(
            jnp.asarray(index.members_np[buckets]))
        if index.quant == "pq":
            index.pq_codes = index.pq_codes.at[
                jnp.asarray(buckets)].set(
                jnp.asarray(index.pq_codes_np[buckets]))
    if changed_cmax:
        # per-bucket .at[] splice (never a fresh jnp.asarray) so a
        # sharded cmax keeps its placement through the update
        bids = np.asarray(sorted(changed_cmax), np.int32)
        vals = np.asarray([changed_cmax[int(b)] for b in bids],
                          np.float32)
        index.cmax = index.cmax.at[jnp.asarray(bids)].set(
            jnp.asarray(vals))
        if index.cmax_np is not None:
            index.cmax_np[bids] = vals
    if changed_crad:
        bids = jnp.asarray(np.asarray(sorted(changed_crad), np.int32))
        vals = jnp.asarray(np.asarray(
            [changed_crad[int(b)] for b in np.asarray(bids)],
            np.float32))
        index.crad_cos = index.crad_cos.at[bids].min(vals)
        cos_b = index.crad_cos[bids]
        index.crad_sin = index.crad_sin.at[bids].set(
            jnp.sqrt(jnp.maximum(1.0 - cos_b * cos_b, 0.0)))
        if index.crad_cos_np is not None:
            bnp = np.asarray(bids)
            index.crad_cos_np[bnp] = np.minimum(
                index.crad_cos_np[bnp], np.asarray(vals))
            index.crad_sin_np[bnp] = np.sqrt(np.maximum(
                1.0 - index.crad_cos_np[bnp] ** 2, 0.0))


def publish_rows(
    table: Any,
    vecs: np.ndarray,               # [T, K] fresh f32 vectors
    rows: Optional[Sequence[int]] = None,   # per-vec base row, -1 = new
) -> Optional[np.ndarray]:
    """Speed-overlay publish seam: fold-in vectors enter serving NOW.

    Known rows (``rows[i] >= 0``) are re-quantized in place (the coarse
    stage sees the fresh vector) AND recorded in the exact tail — the
    published solve, not the stale base row, is what the merged result
    scores. New keys (``rows[i] < 0`` or ``rows=None``) get virtual ids
    (``>= capacity``) in the tail only; the next build/update folds
    them out. Returns the assigned global/virtual ids, or None when no
    index is registered for ``table`` (publishing is always safe to
    call)."""
    index = index_for(table)
    if index is None:
        return None
    vecs = np.asarray(vecs, np.float32)
    if vecs.ndim == 1:
        vecs = vecs[None, :]
    if rows is None:
        rows_arr = np.full(len(vecs), -1, np.int64)
    else:
        rows_arr = np.asarray(rows, np.int64).ravel()
    known = np.nonzero((rows_arr >= 0)
                       & (rows_arr < index.n_items))[0]
    if len(known):
        rj = jnp.asarray(rows_arr[known].astype(np.int32))
        _requantize_rows(index, rj, vecs[known])
    out_ids = np.empty(len(vecs), np.int64)
    known_set = set(known.tolist())
    while True:
        with index._lock:
            successor = index._superseded
            if successor is None:
                for pos in range(len(vecs)):
                    if pos in known_set:
                        gid = int(rows_arr[pos])
                    else:
                        gid = index._next_virtual
                        index._next_virtual += 1
                    index._tail[gid] = vecs[pos]
                    index._tail_seq += 1
                    index._tail_seqs[gid] = index._tail_seq
                    out_ids[pos] = gid
                index._tail_pack = None
                index.churn_rows += len(vecs)
        if successor is None:
            break
        # a daemon swap raced this publish: the successor is already
        # registered, so record the entries there (the swap's tail
        # carry-over only covers entries that existed under the OLD
        # lock — re-routing here closes the window)
        index = successor
    index.built_at = _now()
    return out_ids


# ---------------------------------------------------------------------------
# background rebuild (ops/mips_daemon.py drives this off-path)
# ---------------------------------------------------------------------------

def _tier_mode() -> str:
    """off | auto | on: host-tiering of cold buckets at rebuild time.
    ``auto`` (default) demotes only with enough probe-hit samples;
    ``on`` trusts whatever counters exist (tests plant them)."""
    m = os.environ.get("PIO_MIPS_TIER", "auto").strip().lower()
    return m if m in ("off", "auto", "on") else "auto"


def _tier_min_samples() -> int:
    return _env_int("PIO_MIPS_TIER_MIN_SAMPLES", 32)


def _tier_max_frac() -> float:
    try:
        return min(max(float(os.environ.get(
            "PIO_MIPS_TIER_MAX_FRAC", "") or 0.5), 0.0), 0.9)
    except ValueError:
        return 0.5


def _build_cold(vecs: np.ndarray, ids: np.ndarray,
                seed: int) -> ColdTier:
    """Cluster the demoted rows into their own host mini-index (same
    probe-bound geometry as the device index, numpy arrays only)."""
    n = len(ids)
    cc = min(max(_next_pow2(int(np.sqrt(max(n, 1)))), 16), 1024)
    cent = _spherical_kmeans(vecs, cc, seed + 31)
    assign = _assign_chunked(vecs, cent)
    norms = np.linalg.norm(vecs, axis=1).astype(np.float32)
    cmax = np.zeros(cc, np.float32)
    np.maximum.at(cmax, assign, norms)
    unit = vecs / np.maximum(norms[:, None], 1e-9)
    row_cos = np.einsum("ik,ik->i", unit, cent[assign])
    crad_cos = np.ones(cc, np.float32)
    high = norms >= _RADIUS_NORM_FRAC * cmax[assign]
    np.minimum.at(crad_cos, assign[high],
                  row_cos[high].astype(np.float32))
    crad_cos = np.clip(crad_cos, -1.0, 1.0)
    member_ids = []
    member_vecs = []
    for b in range(cc):
        sel = assign == b
        member_ids.append(ids[sel].astype(np.int64))
        member_vecs.append(vecs[sel].astype(np.float32))
    return ColdTier(
        centroids=cent, cmax=cmax, crad_cos=crad_cos,
        crad_sin=np.sqrt(1.0 - crad_cos * crad_cos).astype(np.float32),
        member_ids=member_ids, member_vecs=member_vecs, rows=n,
        hits=np.zeros(cc, np.int64))


def rebuild_index(table: Any, *, trigger: str = "manual",
                  probe_recall: bool = False) -> Optional[MIPSIndex]:
    """Background rebuild-and-swap for a SINGLE-DEVICE table (the
    rebuild daemon's workhorse — ops/mips_daemon.py books the trigger,
    trace span and metrics around this call).

    Off the serving path it: (1) snapshots the exact tail under a
    sequence watermark, (2) re-clusters the catalogue WITH the
    virtual-id tail folded into a dense **ext block** at its existing
    ids (the overlay's key→id map survives the swap untouched — this
    is the ``adopt_keys`` choreography applied to the index), (3)
    decides bucket tiering from the probe-hit window, then (4)
    atomically replaces the registry entry. Entries published after
    the watermark are carried into the successor's tail under the OLD
    index's lock, and a publisher that raced the swap re-routes via
    ``_superseded`` — a published key is findable at recall 1.0
    before, during and after the swap. The old index object keeps
    serving in-flight queries until their references drop."""
    old = index_for(table)
    if old is None or _maybe_sharded(table):
        return None
    t0 = time.perf_counter()
    i_pad, rank = int(table.shape[0]), int(table.shape[1])
    n_items = old.n_items
    cap_rows = old.capacity
    with old._lock:
        watermark = old._tail_seq
        tail_snap = {g: np.asarray(v, np.float32)
                     for g, v in old._tail.items()}
        next_virtual = old._next_virtual

    # -- assemble the full servable row set -------------------------------
    vf = np.asarray(table[:n_items], np.float32).copy()
    for gid, vec in tail_snap.items():
        if gid < n_items:
            # known-row override: cluster/encode the PUBLISHED solve
            # (the tail entry stays live for the exact final score)
            vf[gid] = vec
    n_ext = max(int(next_virtual) - cap_rows, 0)
    ext_np = np.zeros((n_ext, rank), np.float32)
    have = np.zeros(n_ext, bool)
    if old.ext_np is not None and old.n_ext:
        ext_np[:old.n_ext] = old.ext_np[:old.n_ext]
        have[:old.n_ext] = True
    for gid, vec in tail_snap.items():
        j = gid - cap_rows
        if 0 <= j < n_ext:
            ext_np[j] = vec
            have[j] = True
    ext_ids = cap_rows + np.nonzero(have)[0].astype(np.int64)
    ids_all = np.concatenate(
        [np.arange(n_items, dtype=np.int64), ext_ids])
    rows_all = (np.concatenate([vf, ext_np[have]])
                if len(ext_ids) else vf)

    # -- tier decision from the probe-hit window --------------------------
    cold_mask = np.zeros(len(ids_all), bool)
    tier = _tier_mode()
    enough = (tier == "on"
              or old._probe_samples >= _tier_min_samples())
    if tier != "off" and enough and n_items > 2:
        bucket_cold = old.probe_hits <= 0
        # demotion: rows whose device bucket drew no probes over the
        # window; promotion: rows whose COLD bucket drew probes come
        # back (pressure), quiet cold buckets stay demoted
        row_cold = np.zeros(len(ids_all), bool)
        real = ids_all < n_items
        a_old = np.full(len(ids_all), -1, np.int64)
        in_assign = ids_all[real] < len(old.assign)
        a_idx = ids_all[real][in_assign]
        a_old_real = np.full(int(real.sum()), -1, np.int64)
        a_old_real[in_assign] = old.assign[a_idx]
        a_old[real] = a_old_real
        valid = a_old >= 0
        row_cold[valid] = bucket_cold[a_old[valid]]
        if old.cold is not None:
            still_cold = set()
            for cb in np.nonzero(old.cold.hits <= 0)[0]:
                still_cold.update(
                    int(g) for g in old.cold.member_ids[int(cb)])
            if still_cold:
                row_cold |= np.isin(
                    ids_all, np.fromiter(still_cold, np.int64,
                                         len(still_cold)))
        # published overrides and ext rows are fresh by definition
        fresh = np.fromiter(tail_snap, np.int64, len(tail_snap))
        if len(fresh):
            row_cold &= ~np.isin(ids_all, fresh)
        max_cold = int(_tier_max_frac() * len(ids_all))
        if row_cold.sum() > max_cold:
            keep_hot = np.nonzero(row_cold)[0][max_cold:]
            row_cold[keep_hot] = False
        if row_cold.sum() >= 8:        # below that tiering is noise
            cold_mask = row_cold

    hot_ids = ids_all[~cold_mask]
    hot_vecs = np.ascontiguousarray(rows_all[~cold_mask])
    n_hot = len(hot_ids)

    # -- re-cluster the hot set (single shard) ----------------------------
    seed = old.seed + old.rebuilds
    c_local = default_centroids(max(n_hot, 1))
    cent = _spherical_kmeans(hot_vecs, c_local, seed)
    mean_bucket = -(-max(n_hot, 1) // c_local)
    cap = max(-(-int(mean_bucket * 1.25) // 8) * 8, 8)
    a_hot = _balanced_assign(hot_vecs, cent, cap)
    members_np, counts = _pack_members(a_hot, hot_ids, c_local, cap)
    norms = np.linalg.norm(hot_vecs, axis=1).astype(np.float32)
    cmax = np.zeros(c_local, np.float32)
    np.maximum.at(cmax, a_hot, norms)
    unit = hot_vecs / np.maximum(norms[:, None], 1e-9)
    row_cos = np.einsum("ik,ik->i", unit, cent[a_hot])
    crad_cos = np.ones(c_local, np.float32)
    high = norms >= _RADIUS_NORM_FRAC * cmax[a_hot]
    np.minimum.at(crad_cos, a_hot[high],
                  row_cos[high].astype(np.float32))
    crad_cos = np.clip(crad_cos, -1.0, 1.0)
    crad_sin = np.sqrt(1.0 - crad_cos * crad_cos).astype(np.float32)

    # assign is indexed by GLOBAL id (update/publish splices): size it
    # over the whole id space, -1 for pad/cold/tail-only rows
    e_pad = _next_pow2(max(n_ext, 8))
    assign = np.full(cap_rows + e_pad, -1, np.int32)
    assign[hot_ids] = a_hot

    # -- quantized views over the extended id space -----------------------
    quant = _quant_mode()
    pq_m = 0
    pq_codes_np = np.zeros((1, 1, 1), np.uint8)
    pq_books_np = np.zeros((1, _PQ_CODEBOOK, 1), np.float32)
    codes = np.zeros((1, rank), np.int8)
    scales = np.zeros(1, np.float32)
    bf16_view = _bf16(np.zeros((1, rank), np.float32))
    if quant == "pq":
        pq_m = _pq_m(rank)
        res = hot_vecs - cent[a_hot]
        pq_books_np = _pq_train_books(res, pq_m, seed)
        pq_codes_np = _pq_pack(a_hot, _pq_encode(res, pq_books_np),
                               c_local, cap)
    elif quant == "bf16":
        full = np.zeros((cap_rows + e_pad, rank), np.float32)
        full[hot_ids] = hot_vecs
        bf16_view = _bf16(full)
    else:
        c_h, s_h = _quantize_int8(hot_vecs)
        codes = np.zeros((cap_rows + e_pad, rank), np.int8)
        scales = np.zeros(cap_rows + e_pad, np.float32)
        codes[hot_ids] = c_h
        scales[hot_ids] = s_h

    cold_tier = None
    if cold_mask.any():
        cold_tier = _build_cold(
            np.ascontiguousarray(rows_all[cold_mask]),
            ids_all[cold_mask], seed)

    ext_dev = None
    ext_full = None
    if n_ext:
        ext_full = np.zeros((e_pad, rank), np.float32)
        ext_full[:n_ext] = ext_np
        ext_dev = jax.device_put(ext_full)

    new = MIPSIndex(
        codes=jax.device_put(codes),
        scales=jax.device_put(scales),
        bf16=jax.device_put(bf16_view),
        centroids=jax.device_put(cent),
        cmax=jax.device_put(cmax),
        crad_cos=jax.device_put(crad_cos),
        crad_sin=jax.device_put(crad_sin),
        members=jax.device_put(members_np),
        assign=assign, members_np=members_np, centroids_np=cent,
        counts=counts, n_items=n_items, n_shards=1, c_local=c_local,
        cap=cap, rank=rank, seed=old.seed, quant=quant,
        rebuilds=old.rebuilds + 1, delta_updates=old.delta_updates,
        pq_codes=jax.device_put(pq_codes_np),
        pq_books=jax.device_put(pq_books_np),
        pq_codes_np=pq_codes_np, pq_books_np=pq_books_np, pq_m=pq_m,
        ext=ext_dev, ext_np=ext_full, n_ext=n_ext,
        capacity_rows=cap_rows, cold=cold_tier, engine=old.engine,
        cmax_np=cmax.copy(), crad_cos_np=crad_cos.copy(),
        crad_sin_np=crad_sin.copy(),
    )

    # warm the serving compile BEFORE the swap (ext-block shapes are
    # pow2-rung stable, so consecutive rebuilds usually reuse it): the
    # first post-swap query must not eat a compile
    try:
        if n_items > 1:
            mips_score_and_top_k(vf[0], table, new,
                                 min(10, n_items - 1))
    except Exception:
        logger.exception("mips rebuild warmup failed (serving anyway)")

    # -- the atomic swap --------------------------------------------------
    with old._lock:
        new._next_virtual = old._next_virtual
        for gid, vec in old._tail.items():
            if gid < n_items or old._tail_seqs.get(gid, 0) > watermark:
                # known-row overrides stay (the exact final score);
                # virtual entries published after the watermark carry
                # over — nothing published is ever lost to a swap
                new._tail[gid] = np.asarray(vec, np.float32)
                new._tail_seq += 1
                new._tail_seqs[gid] = new._tail_seq
        new._tail_pack = None
        old._superseded = new
        register_index(table, new)
    _REBUILDS.labels(trigger=trigger).inc()
    if probe_recall:
        try:
            recall_probe(table, new, host_factors=vf)
        except Exception:
            logger.exception("mips recall probe failed at rebuild")
    logger.info(
        "mips index rebuilt (%s): %d items + %d ext, %d centroids "
        "(cap %d), %d cold rows, folded %d tail entries in %.2fs",
        trigger, n_items, n_ext, c_local, cap,
        cold_tier.rows if cold_tier else 0,
        sum(1 for g in tail_snap if g >= cap_rows),
        time.perf_counter() - t0)
    return new


# ---------------------------------------------------------------------------
# the two-stage device kernel
# ---------------------------------------------------------------------------

def _coarse_cut(coarse, cand, n_cand):
    """Top-``n_cand`` coarse survivors. ``lax.top_k``, not argsort: the
    full variadic sort measured 12× slower on CPU XLA at this width,
    and top_k has a native TPU lowering."""
    n_cand = min(n_cand, cand.shape[1])
    _, pos = jax.lax.top_k(coarse, n_cand)
    return jnp.take_along_axis(cand, pos, axis=1)


def _exact_rerank(uv, rows_g, table, exclude, offset, k, ext=None,
                  ext_base=0):
    """Exact f32 rerank of the candidate slice → ([B, kk] scores,
    [B, kk] GLOBAL ids). ``ext`` (daemon-rebuilt indexes) holds the
    folded virtual-id rows at ids ``>= ext_base`` — those never exist
    in ``table``, so the rerank gathers them from the ext block."""
    rows_l = jnp.maximum(rows_g - offset, 0)
    if ext is not None:
        in_ext = rows_g >= ext_base
        tab_v = table[jnp.where(in_ext, 0, rows_l)].astype(jnp.float32)
        ext_v = ext[jnp.clip(rows_g - ext_base, 0, ext.shape[0] - 1)]
        vecs = jnp.where(in_ext[:, :, None], ext_v, tab_v)
    else:
        vecs = table[rows_l].astype(jnp.float32)
    exact = jnp.einsum(
        "bnk,bk->bn", vecs, uv,
        preferred_element_type=jnp.float32)
    exact = jnp.where(rows_g >= 0, exact, NEG_INF)
    if exclude is not None:
        hit = (rows_g[:, :, None] == exclude[None, None, :]).any(-1)
        exact = jnp.where(hit, NEG_INF, exact)
    kk = min(k, rows_g.shape[1])
    top_s, pos2 = jax.lax.top_k(exact, kk)
    top_i = jnp.take_along_axis(rows_g, pos2, axis=1)
    return top_s, top_i


def _probe_bound(uv, centroids, cmax, crad_cos, crad_sin):
    """([B, C] upper bound, [B, C] raw centroid scores). The bound is
    cmax·|q|·cos(θ_qc − r) with r the bucket's ball radius — valid for
    every member, including spilled/off-centroid rows. The raw q·c
    scores ride along because the PQ path reuses them as the exact
    centroid part of its residual decomposition."""
    s = jnp.einsum("bk,ck->bc", uv, centroids,
                   preferred_element_type=jnp.float32)
    qn2 = jnp.sum(uv * uv, axis=1, keepdims=True)
    ortho = jnp.sqrt(jnp.maximum(qn2 - s * s, 0.0))
    return (cmax[None, :] * (s * crad_cos[None, :]
                             + ortho * crad_sin[None, :]), s)


def _pq_coarse(uv, s, probe, pq_codes, pq_books):
    """[B, P, cap] asymmetric PQ scores for the probed buckets' member
    slots: q·v ≈ q·c_b (exact, from the probe stage's raw centroid
    scores) + Σ_m LUT[m, code_m] with LUT = q_sub·codebook — one
    [B, M, 256] einsum per dispatch, then pure integer gathers."""
    B = uv.shape[0]
    m, _cb, d = pq_books.shape
    base = jnp.take_along_axis(s, probe, axis=1)          # [B, P]
    lut = jnp.einsum(
        "bmd,mjd->bmj", uv.reshape(B, m, d), pq_books,
        preferred_element_type=jnp.float32)               # [B, M, 256]
    codes_g = pq_codes[probe].astype(jnp.int32)           # [B,P,cap,M]

    def gather_res(lut_b, codes_b):   # [M, 256], [P, cap, M]
        return lut_b[jnp.arange(m)[None, None, :], codes_b]

    res = jax.vmap(gather_res)(lut, codes_g).sum(-1)      # [B, P, cap]
    return base[:, :, None] + res


def _two_stage(uv, codes, scales, bf16, pq_codes, pq_books, centroids,
               cmax, crad_cos, crad_sin, members, table, exclude,
               offset, *, k, nprobe, n_cand, quant):
    """Fused traced core over (possibly shard-local) slices: [B, K]
    queries → ([B, kk] scores, [B, kk] GLOBAL ids). ``offset`` maps the
    global ids in ``members`` onto this slice's row space. Used by the
    shard_map path, where the whole two-stage must be one program; the
    single-device wrappers run the STAGED pair below instead."""
    B = uv.shape[0]
    cs, s = _probe_bound(uv, centroids, cmax, crad_cos, crad_sin)
    nprobe = min(nprobe, centroids.shape[0])
    _, probe = jax.lax.top_k(cs, nprobe)             # [B, P]
    if quant == "pq":
        # bucket-major codes: gathered by LOCAL probe index, no row
        # offset involved (the slot layout mirrors ``members``)
        cand = members[probe]                        # [B, P, cap]
        coarse = _pq_coarse(uv, s, probe, pq_codes,
                            pq_books).reshape(B, -1)
        cand = cand.reshape(B, -1)
    else:
        cand = members[probe].reshape(B, -1)         # [B, P*cap] global
        safe = jnp.maximum(cand - offset, 0)
        if quant == "bf16":
            coarse = jnp.einsum(
                "bnk,bk->bn", bf16[safe].astype(jnp.float32), uv,
                preferred_element_type=jnp.float32)
        else:
            coarse = jnp.einsum(
                "bnk,bk->bn", codes[safe].astype(jnp.float32), uv,
                preferred_element_type=jnp.float32) * scales[safe]
    coarse = jnp.where(cand >= 0, coarse, NEG_INF)
    rows_g = _coarse_cut(coarse, cand, n_cand)
    return _exact_rerank(uv, rows_g, table, exclude, offset, k)


# -- staged single-device pair ----------------------------------------------
# XLA CPU fuses an int8→f32 convert INTO a downstream dot and emits a
# scalar loop ~8× slower than the BLAS matvec on the same data (measured:
# fused 2.0 ms vs gather+convert 0.55 ms + matvec 0.37 ms at 32k×64);
# a jit boundary after the gather+convert is the only reliable
# materialization point, so the unsharded path runs as TWO dispatches —
# still ONE device→host fetch per query. (A CPU-backend artefact shaping
# device code: ROADMAP Speed 5 re-measures it on the chip.)

@functools.partial(jax.jit, static_argnames=("nprobe", "quant"))
def _mips_probe_jit(uv, centroids, cmax, crad_cos, crad_sin, members,
                    codes, scales, bf16, *, nprobe, quant):
    """Stage 1: centroid scan → probed buckets → candidate ids + the
    MATERIALIZED f32 view of their quantized rows (gather + convert
    only — nothing downstream may fuse into it)."""
    B = uv.shape[0]
    cs, _s = _probe_bound(uv, centroids, cmax, crad_cos, crad_sin)
    _, probe = jax.lax.top_k(cs, min(nprobe, centroids.shape[0]))
    cand = members[probe].reshape(B, -1)
    safe = jnp.maximum(cand, 0).reshape(-1)
    # g is emitted 2-D [B·n, K]: the rank stage feeds it to a plain
    # matmul without slicing (a [0]-slice of a 3-D output forces an
    # 8 MB copy before XLA's BLAS path engages)
    if quant == "bf16":
        g = bf16[safe].astype(jnp.float32)
        sg = jnp.ones((B, cand.shape[1]), jnp.float32)
    else:
        g = codes[safe].astype(jnp.float32)
        sg = scales[safe].reshape(B, -1)
    return cand, g, sg


@functools.partial(jax.jit, static_argnames=("nprobe", "quant"))
def _mips_probe_rows_jit(user_factors, rows, centroids, cmax, crad_cos,
                         crad_sin, members, codes, scales, bf16, *,
                         nprobe, quant):
    """Stage 1 with the user-row gather inside the dispatch (the
    score_user / batched shapes)."""
    uv = user_factors[rows]
    cand, g, sg = _mips_probe_jit(
        uv, centroids, cmax, crad_cos, crad_sin, members, codes,
        scales, bf16, nprobe=nprobe, quant=quant)
    return uv, cand, g, sg


@functools.partial(jax.jit, static_argnames=("k", "n_cand", "quant",
                                             "ext_base"))
def _mips_rank_jit(uv, cand, g, sg, table, ext, exclude, *, k, n_cand,
                   quant, ext_base=0):
    """Stage 2: coarse score over the materialized quantized rows
    (BLAS-shaped), top-k cut, exact f32 rerank, final top-k."""
    B, n = cand.shape
    if B == 1:
        # 2-D matvec on the materialized [n, K] — the BLAS fast path
        coarse = (g @ uv[0])[None, :]
    else:
        coarse = jnp.einsum(
            "bnk,bk->bn", g.reshape(B, n, -1), uv,
            preferred_element_type=jnp.float32)
    if quant != "bf16":
        coarse = coarse * sg
    coarse = jnp.where(cand >= 0, coarse, NEG_INF)
    rows_g = _coarse_cut(coarse, cand, n_cand)
    top_s, top_i = _exact_rerank(uv, rows_g, table, exclude, 0, k,
                                 ext=ext, ext_base=ext_base)
    return jnp.stack([top_s, top_i.astype(jnp.float32)])


# -- staged PQ pair (single-device) ------------------------------------------
# The PQ coarse stage is integer gathers + a LUT einsum — no int8→f32
# convert for XLA CPU to mis-fuse — but the staged split is kept so
# both quant families dispatch identically (two programs, one
# device→host fetch) and share the rank-stage compile ladder shape.

@functools.partial(jax.jit, static_argnames=("nprobe",))
def _mips_pq_probe_jit(uv, centroids, cmax, crad_cos, crad_sin,
                       members, pq_codes, pq_books, *, nprobe):
    """PQ stage 1: centroid scan → probed buckets → candidate ids +
    asymmetric coarse scores (base q·c + residual LUT sums)."""
    B = uv.shape[0]
    cs, s = _probe_bound(uv, centroids, cmax, crad_cos, crad_sin)
    _, probe = jax.lax.top_k(cs, min(nprobe, centroids.shape[0]))
    cand = members[probe]                             # [B, P, cap]
    coarse = _pq_coarse(uv, s, probe, pq_codes,
                        pq_books).reshape(B, -1)
    cand = cand.reshape(B, -1)
    return cand, jnp.where(cand >= 0, coarse, NEG_INF)


@functools.partial(jax.jit, static_argnames=("nprobe",))
def _mips_pq_probe_rows_jit(user_factors, rows, centroids, cmax,
                            crad_cos, crad_sin, members, pq_codes,
                            pq_books, *, nprobe):
    """PQ stage 1 with the user-row gather inside the dispatch."""
    uv = user_factors[rows]
    cand, coarse = _mips_pq_probe_jit(
        uv, centroids, cmax, crad_cos, crad_sin, members, pq_codes,
        pq_books, nprobe=nprobe)
    return uv, cand, coarse


@functools.partial(jax.jit, static_argnames=("k", "n_cand",
                                             "ext_base"))
def _mips_pq_rank_jit(uv, cand, coarse, table, ext, exclude, *, k,
                      n_cand, ext_base=0):
    """PQ stage 2: coarse top-k cut, exact f32 rerank (table + ext
    block), final top-k."""
    rows_g = _coarse_cut(coarse, cand, n_cand)
    top_s, top_i = _exact_rerank(uv, rows_g, table, exclude, 0, k,
                                 ext=ext, ext_base=ext_base)
    return jnp.stack([top_s, top_i.astype(jnp.float32)])


@functools.partial(jax.jit, static_argnames=(
    "k", "nprobe", "n_cand", "quant", "mesh", "gather_user"))
def _mips_sharded_jit(user_vector, codes, scales, bf16, pq_codes,
                      pq_books, centroids, cmax, crad_cos, crad_sin,
                      members, table, exclude, *, k, nprobe, n_cand,
                      quant, mesh, gather_user):
    """Placed tables: per-shard coarse scan + candidate gather + exact
    rerank over the rows the shard owns (everything stays shard-local),
    then the same [n, k_local] all-gather merge as the exhaustive
    ``sharded_top_k``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from incubator_predictionio_tpu.parallel.collectives import (
        all_gather,
        axis_index,
        shard_map,
    )

    axes = tuple(mesh.axis_names)
    n = int(mesh.devices.size)
    local_rows = table.shape[0] // n
    # nprobe/n_cand arrive PRE-SPLIT per shard (one quota rule,
    # ops/mips._quotas, shared with the scan accounting)
    nprobe_l, n_cand_l = nprobe, n_cand
    k_l = min(k, n_cand_l)
    if gather_user:
        uf, rows = user_vector
        uv = uf[rows]
    else:
        uv = user_vector
    uv = jax.lax.with_sharding_constraint(uv, NamedSharding(mesh, P()))
    spec = P(axes)
    args = [uv, codes, scales, bf16, pq_codes, pq_books, centroids,
            cmax, crad_cos, crad_sin, members, table]
    # pq_books is replicated (every shard scores with the full
    # codebook set); everything else row/bucket-shards on axis 0
    specs = [P(), spec, spec, spec, spec, P(), spec, spec, spec, spec,
             spec, spec]
    has_ex = exclude is not None
    if has_ex:
        args.append(exclude)
        specs.append(P())

    def shard(uv_l, codes_l, scales_l, bf_l, pqc_l, pqb_l, cent_l,
              cmax_l, ccos_l, csin_l, mem_l, tab_l, *rest):
        ex_l = rest[0] if has_ex else None
        offset = axis_index(axes) * local_rows
        top_s, top_i = _two_stage(
            uv_l, codes_l, scales_l, bf_l, pqc_l, pqb_l, cent_l,
            cmax_l, ccos_l, csin_l, mem_l, tab_l, ex_l, offset,
            k=k_l, nprobe=nprobe_l, n_cand=n_cand_l, quant=quant)
        merged_s = all_gather(top_s, axes, axis=1, tiled=True)
        merged_i = all_gather(top_i.astype(jnp.int32), axes, axis=1,
                              tiled=True)
        kk = min(k, merged_s.shape[1])
        out_s, pos = jax.lax.top_k(merged_s, kk)
        out_i = jnp.take_along_axis(merged_i, pos, axis=1)
        return jnp.stack([out_s, out_i.astype(jnp.float32)])

    return shard_map(
        shard, mesh=mesh, in_specs=tuple(specs), out_specs=P(),
        check_vma=False,
    )(*args)


def mips_compile_cache_size() -> int:
    """Compiled two-stage variants resident — summed into
    ``ops.topk.serve_compile_cache_size`` so the scheduler's
    zero-steady-state-recompile contract covers the MIPS path too."""
    return sum(
        int(fn._cache_size())
        for fn in (_mips_probe_jit, _mips_probe_rows_jit,
                   _mips_rank_jit, _mips_pq_probe_jit,
                   _mips_pq_probe_rows_jit, _mips_pq_rank_jit,
                   _mips_sharded_jit)
    )


# ---------------------------------------------------------------------------
# serving wrappers (the ops/topk auto-routers land here)
# ---------------------------------------------------------------------------

def _quotas(index: MIPSIndex, k: int) -> Tuple[int, int, int, int]:
    """THE quota rule: (per-shard nprobe, per-shard candidate count,
    total coarse slots, total rerank rows) for one query at the current
    knobs. The sharded path splits the global budgets evenly with a
    small per-shard probe floor (a tiny per-shard index must still
    probe enough buckets to cover a mixed-interest query; the floor is
    cheap precisely because such shards hold few rows). The wrappers
    pass these to the jits as statics AND book them as scan
    accounting, so the measured fraction can never drift from the
    dispatched shapes."""
    n = index.n_shards
    nprobe = _nprobe_for(index)
    n_cand = _candidates_for(index, k)
    if n > 1:
        nprobe_l = min(max(-(-nprobe // n), min(16, index.c_local)),
                       index.c_local)
        n_cand_l = max(-(-n_cand // n), 1)
    else:
        nprobe_l = min(nprobe, index.c_local)
        n_cand_l = n_cand
    return nprobe_l, n_cand_l, nprobe_l * index.cap * n, n_cand_l * n


def _book_scan(index: MIPSIndex, b: int, coarse: int,
               rerank: int) -> None:
    _SCAN_CENTROID.inc(b * index.c_total)
    _SCAN_COARSE.inc(b * coarse)
    _SCAN_RERANK.inc(b * rerank)


def scan_budget(index: MIPSIndex, k: int) -> Tuple[int, int, int]:
    """(global nprobe, coarse slots scanned, rerank rows) per query at
    the current knobs — the analytic candidates-scanned figure, from
    the same quota rule the dispatch uses."""
    nprobe_l, _n_cand_l, coarse, rerank = _quotas(index, k)
    return nprobe_l * index.n_shards, coarse, rerank


def _pad_k(packed: np.ndarray, k: int) -> np.ndarray:
    """[2, ..., kk] → [2, ..., k] (NEG_INF/-1 filled) so the two-stage
    result is shape-compatible with the exhaustive contract even when
    the candidate budget is under k."""
    kk = packed.shape[-1]
    if kk >= k:
        return packed
    pad = np.zeros(packed.shape[:-1] + (k - kk,), np.float32)
    pad[0] = float(NEG_INF)
    pad[1] = -1.0
    return np.concatenate([np.asarray(packed), pad], axis=-1)


def _merge_tail(index: MIPSIndex, packed, uv_host: np.ndarray, k: int,
                exclude) -> np.ndarray:
    """Exact f32 merge of the published tail into a device [2, k] (or
    [2, B, k]) result. Tail entries OVERRIDE device rows with the same
    id (the published solve is fresher than the base row)."""
    tail = index.tail_arrays()
    packed = np.asarray(packed)
    if tail is None:
        return packed
    tids, tvecs = tail
    ex = None
    if exclude is not None:
        ex = np.asarray(exclude).astype(np.int64)
    single = packed.ndim == 2
    if single:
        packed = packed[:, None, :]
        uv_host = np.asarray(uv_host, np.float32)[None, :]
    tscores = uv_host @ tvecs.T                      # [B, T]
    out = np.empty((2, packed.shape[1], k), np.float32)
    for b in range(packed.shape[1]):
        dev_s = packed[0, b]
        dev_i = packed[1, b].astype(np.int64)
        keep = ~np.isin(dev_i, tids)
        ts, ti = tscores[b], tids
        if ex is not None:
            tkeep = ~np.isin(ti, ex)
            ts, ti = ts[tkeep], ti[tkeep]
        all_s = np.concatenate([dev_s[keep], ts])
        all_i = np.concatenate([dev_i[keep], ti])
        order = np.argsort(-all_s, kind="stable")[:k]
        ns = len(order)
        out[0, b, :ns] = all_s[order]
        out[1, b, :ns] = all_i[order].astype(np.float32)
        if ns < k:
            out[0, b, ns:] = float(NEG_INF)
            out[1, b, ns:] = -1.0
    return out[:, 0, :] if single else out


def merge_published_fallback(table: Any, packed: Any, uv_host_fn,
                             k: int,
                             exclude: Optional[Any] = None) -> Any:
    """Exhaustive-fallback parity seam (ops/topk.py): a query routed
    AROUND the two-stage path — oversized exclusion list, top-
    everything k, serving mode off — must still see overlay-published
    rows, which live only in the index's exact tail (virtual ids are
    not table rows, and a known-row override is fresher than the table
    row the exhaustive scan just scored). Cold-tiered rows need no
    help: demotion shrinks the INDEX views, never the table. No-op
    without a registered index or with an empty tail; ``uv_host_fn``
    is only called when there is something to merge."""
    index = index_for(table)
    if index is None or index.tail_size() == 0:
        return packed
    return _merge_tail(index, np.asarray(packed, np.float32),
                       np.asarray(uv_host_fn(), np.float32), k,
                       exclude)


def _maybe_sharded(table: Any) -> bool:
    from incubator_predictionio_tpu.parallel.placement import (
        is_distributed,
    )

    return is_distributed(table)


#: probe-hit sampling period: every Nth dispatch recomputes the probe
#: bound on the host to credit the probed buckets' hit counters (the
#: tiering daemon's demotion signal). 1/8 keeps the [B, C] numpy
#: matmul amortized to noise; when a cold tier is live the bound is
#: computed every dispatch anyway (the cold merge needs it).
_PROBE_SAMPLE_EVERY = 8


def _host_probe_bound(uv: np.ndarray, centroids: np.ndarray,
                      cmax: np.ndarray, crad_cos: np.ndarray,
                      crad_sin: np.ndarray) -> np.ndarray:
    """numpy mirror of :func:`_probe_bound` → [B, C] bound."""
    s = uv @ centroids.T
    qn2 = np.sum(uv * uv, axis=1, keepdims=True)
    ortho = np.sqrt(np.maximum(qn2 - s * s, 0.0))
    return cmax[None, :] * (s * crad_cos[None, :]
                            + ortho * crad_sin[None, :])


def _top_buckets(bound: np.ndarray, nprobe: int) -> np.ndarray:
    """[B, P] host top-nprobe bucket ids per query."""
    nprobe = min(nprobe, bound.shape[1])
    if nprobe >= bound.shape[1]:
        return np.tile(np.arange(bound.shape[1]), (len(bound), 1))
    return np.argpartition(-bound, nprobe - 1, axis=1)[:, :nprobe]


def _merge_cold(index: MIPSIndex, packed: np.ndarray,
                uv_host: np.ndarray, k: int, exclude,
                nprobe: int) -> np.ndarray:
    """Exact host-side serve of the probed COLD buckets, merged into
    the device result like the tail. Cold rows are exact f32 — recall
    for a demoted row is oracle-grade, the trade is host CPU on the
    (by construction rare) queries that probe a cold bucket."""
    cold = index.cold
    single = packed.ndim == 2
    if single:
        packed = packed[:, None, :]
        uv_host = np.asarray(uv_host, np.float32)[None, :]
    ex = (np.asarray(exclude).astype(np.int64)
          if exclude is not None else None)
    bound = _host_probe_bound(uv_host, cold.centroids, cold.cmax,
                              cold.crad_cos, cold.crad_sin)
    top = _top_buckets(bound, min(nprobe, len(cold.cmax)))
    np.add.at(cold.hits, top.ravel(), 1)
    out = np.empty((2, packed.shape[1], k), np.float32)
    for b in range(packed.shape[1]):
        ids_l: List[np.ndarray] = [packed[1, b].astype(np.int64)]
        sc_l: List[np.ndarray] = [packed[0, b].astype(np.float32)]
        for cb in top[b]:
            cids = cold.member_ids[int(cb)]
            if not len(cids):
                continue
            sc = cold.member_vecs[int(cb)] @ uv_host[b]
            if ex is not None:
                keep = ~np.isin(cids, ex)
                cids, sc = cids[keep], sc[keep]
            ids_l.append(cids)
            sc_l.append(sc.astype(np.float32))
        all_i = np.concatenate(ids_l)
        all_s = np.concatenate(sc_l)
        order = np.argsort(-all_s, kind="stable")[:k]
        ns = len(order)
        out[0, b, :ns] = all_s[order]
        out[1, b, :ns] = all_i[order].astype(np.float32)
        if ns < k:
            out[0, b, ns:] = float(NEG_INF)
            out[1, b, ns:] = -1.0
    return out[:, 0, :] if single else out


def _host_stage(index: MIPSIndex, packed, uv_host_fn, k: int, exclude,
                nprobe: int) -> np.ndarray:
    """Post-device host work shared by the serving wrappers: probe-hit
    sampling (demotion signal), the cold-tier exact merge, then the
    exact-tail merge (override semantics — tail last, so a republished
    id always serves its freshest vector). ``uv_host_fn`` defers the
    query fetch: the common no-tail/no-cold steady state pays nothing."""
    packed = _pad_k(np.asarray(packed), k)
    index._dispatches += 1
    cold = index.cold
    sample = (index._dispatches % _PROBE_SAMPLE_EVERY == 0
              and index.cmax_np is not None)
    uv_host = None
    if cold is not None or sample or index.tail_size():
        uv_host = np.asarray(uv_host_fn(), np.float32)
    if sample and uv_host is not None:
        uv2 = uv_host if uv_host.ndim == 2 else uv_host[None, :]
        bound = _host_probe_bound(uv2, index.centroids_np,
                                  index.cmax_np, index.crad_cos_np,
                                  index.crad_sin_np)
        np.add.at(index.probe_hits,
                  _top_buckets(bound, nprobe).ravel(), 1)
        index._probe_samples += 1
    if cold is not None:
        packed = _merge_cold(index, packed, uv_host, k, exclude,
                             nprobe)
    if index.tail_size():
        packed = _merge_tail(index, _pad_k(np.asarray(packed), k),
                             uv_host, k, exclude)
    return _pad_k(np.asarray(packed), k)


def mips_score_and_top_k(
    user_vector: Any,           # [K]
    table: Any,                 # [I_pad, K] (maybe sharded)
    index: MIPSIndex,
    k: int,
    exclude: Optional[Any] = None,
) -> np.ndarray:
    """Two-stage twin of ``ops.topk.score_and_top_k`` → packed [2, k]."""
    from incubator_predictionio_tpu.obs import profile as _profile

    nprobe_l, n_cand_l, coarse, rerank = _quotas(index, k)
    _pt0 = _profile.t0()
    uv = jnp.asarray(user_vector, jnp.float32).reshape(1, -1)
    if _maybe_sharded(table):
        packed = _mips_sharded_jit(
            uv, index.codes, index.scales, index.bf16, index.pq_codes,
            index.pq_books, index.centroids, index.cmax,
            index.crad_cos, index.crad_sin, index.members, table,
            exclude, k=k, nprobe=nprobe_l, n_cand=n_cand_l,
            quant=index.quant, mesh=table.sharding.mesh,
            gather_user=False)[:, 0, :]
    elif index.quant == "pq":
        cand, coarse_s = _mips_pq_probe_jit(
            uv, index.centroids, index.cmax, index.crad_cos,
            index.crad_sin, index.members, index.pq_codes,
            index.pq_books, nprobe=nprobe_l)
        packed = _mips_pq_rank_jit(
            uv, cand, coarse_s, table, index.ext, exclude, k=k,
            n_cand=n_cand_l, ext_base=index.capacity)[:, 0, :]
    else:
        q = index.quant
        cand, g, sg = _mips_probe_jit(
            uv, index.centroids, index.cmax, index.crad_cos,
            index.crad_sin, index.members, index.codes, index.scales,
            index.bf16, nprobe=nprobe_l, quant=q)
        packed = _mips_rank_jit(
            uv, cand, g, sg, table, index.ext, exclude, k=k,
            n_cand=n_cand_l, quant=q,
            ext_base=index.capacity)[:, 0, :]
    _profile.record(_pt0, "serve", "serve_topk_mips",
                    2.0 * (index.c_total + coarse + rerank)
                    * index.rank, packed)
    _book_scan(index, 1, coarse, rerank)
    return _host_stage(
        index, packed,
        lambda: np.asarray(user_vector, np.float32), k, exclude,
        nprobe_l * index.n_shards)


def mips_score_user_and_top_k(
    user_factors: Any,
    table: Any,
    index: MIPSIndex,
    user_idx: int,
    k: int,
    exclude: Optional[Any] = None,
) -> np.ndarray:
    """Two-stage twin of ``ops.topk.score_user_and_top_k`` (user-row
    gather stays inside the single dispatch) → packed [2, k]."""
    from incubator_predictionio_tpu.obs import profile as _profile

    nprobe_l, n_cand_l, coarse, rerank = _quotas(index, k)
    _pt0 = _profile.t0()
    rows = jnp.asarray([int(user_idx)], jnp.int32)
    if _maybe_sharded(table):
        packed = _mips_sharded_jit(
            (user_factors, rows), index.codes, index.scales, index.bf16,
            index.pq_codes, index.pq_books, index.centroids, index.cmax,
            index.crad_cos, index.crad_sin, index.members, table,
            exclude, k=k, nprobe=nprobe_l, n_cand=n_cand_l,
            quant=index.quant, mesh=table.sharding.mesh,
            gather_user=True)[:, 0, :]
    elif index.quant == "pq":
        uv, cand, coarse_s = _mips_pq_probe_rows_jit(
            user_factors, rows, index.centroids, index.cmax,
            index.crad_cos, index.crad_sin, index.members,
            index.pq_codes, index.pq_books, nprobe=nprobe_l)
        packed = _mips_pq_rank_jit(
            uv, cand, coarse_s, table, index.ext, exclude, k=k,
            n_cand=n_cand_l, ext_base=index.capacity)[:, 0, :]
    else:
        q = index.quant
        uv, cand, g, sg = _mips_probe_rows_jit(
            user_factors, rows, index.centroids, index.cmax,
            index.crad_cos, index.crad_sin, index.members, index.codes,
            index.scales, index.bf16, nprobe=nprobe_l, quant=q)
        packed = _mips_rank_jit(
            uv, cand, g, sg, table, index.ext, exclude, k=k,
            n_cand=n_cand_l, quant=q,
            ext_base=index.capacity)[:, 0, :]
    _profile.record(_pt0, "serve", "serve_topk_mips",
                    2.0 * (index.c_total + coarse + rerank)
                    * index.rank, packed)
    _book_scan(index, 1, coarse, rerank)
    return _host_stage(
        index, packed,
        lambda: np.asarray(user_factors[user_idx], np.float32), k,
        exclude, nprobe_l * index.n_shards)


#: batched two-stage dispatch width cap: the [B, nprobe·cap, K]
#: candidate gather is the peak transient; 128 rows keeps it ~100 MB at
#: the default budgets. Larger scheduler batches split into ladder-
#: stable 128-row chunks (one dispatch each — still pow2 shapes).
MIPS_BATCH_CHUNK = 128


def mips_batch_score_top_k(
    user_factors: Any,
    table: Any,
    index: MIPSIndex,
    rows: Any,                  # [B] int array (already pow2-padded)
    k: int,
) -> np.ndarray:
    """Two-stage twin of ``ops.topk.batch_score_top_k`` → [2, B, k]."""
    from incubator_predictionio_tpu.obs import profile as _profile

    nprobe_l, n_cand_l, coarse, rerank = _quotas(index, k)
    rows_np = np.asarray(rows, np.int32).ravel()
    B = len(rows_np)
    _pt0 = _profile.t0()
    chunks = []
    for s in range(0, B, MIPS_BATCH_CHUNK):
        rj = jnp.asarray(rows_np[s:s + MIPS_BATCH_CHUNK])
        if _maybe_sharded(table):
            part = _mips_sharded_jit(
                (user_factors, rj), index.codes, index.scales,
                index.bf16, index.pq_codes, index.pq_books,
                index.centroids, index.cmax, index.crad_cos,
                index.crad_sin, index.members, table, None, k=k,
                nprobe=nprobe_l, n_cand=n_cand_l, quant=index.quant,
                mesh=table.sharding.mesh, gather_user=True)
        elif index.quant == "pq":
            uv, cand, coarse_s = _mips_pq_probe_rows_jit(
                user_factors, rj, index.centroids, index.cmax,
                index.crad_cos, index.crad_sin, index.members,
                index.pq_codes, index.pq_books, nprobe=nprobe_l)
            part = _mips_pq_rank_jit(
                uv, cand, coarse_s, table, index.ext, None, k=k,
                n_cand=n_cand_l, ext_base=index.capacity)
        else:
            q = index.quant
            uv, cand, g, sg = _mips_probe_rows_jit(
                user_factors, rj, index.centroids, index.cmax,
                index.crad_cos, index.crad_sin, index.members,
                index.codes, index.scales, index.bf16,
                nprobe=nprobe_l, quant=q)
            part = _mips_rank_jit(
                uv, cand, g, sg, table, index.ext, None, k=k,
                n_cand=n_cand_l, quant=q, ext_base=index.capacity)
        chunks.append(_pad_k(np.asarray(part), k))
    packed = (chunks[0] if len(chunks) == 1
              else np.concatenate(chunks, axis=1))
    _profile.record(_pt0, "serve", "serve_topk_mips_batch",
                    2.0 * B * (index.c_total + coarse + rerank)
                    * index.rank, packed)
    _book_scan(index, B, coarse, rerank)
    return _host_stage(
        index, packed,
        lambda: np.asarray(user_factors[jnp.asarray(rows_np)],
                           np.float32), k, None,
        nprobe_l * index.n_shards)


# ---------------------------------------------------------------------------
# the planted recall probe (the pio_serve_mips_recall gauge's source)
# ---------------------------------------------------------------------------

def recall_probe(
    table: Any,
    index: Optional[MIPSIndex] = None,
    *,
    host_factors: Optional[np.ndarray] = None,
    k: int = 20,
    n_queries: int = 8,
    seed: int = 0,
) -> Optional[float]:
    """Measure recall@k of the two-stage path against the exhaustive
    host oracle on mixture queries sampled from the catalogue itself,
    and publish it as ``pio_serve_mips_recall``. Cheap enough to run at
    every build/publish (it also warms the serving compile)."""
    from incubator_predictionio_tpu.utils.planted import (
        exhaustive_top_k,
        planted_queries,
        recall_against_oracle,
    )

    index = index if index is not None else index_for(table)
    if index is None:
        return None
    k = min(k, max(index.n_items - 1, 1))
    vf = (np.asarray(host_factors[:index.n_items], np.float32)
          if host_factors is not None
          else np.asarray(table[:index.n_items], np.float32))
    queries = planted_queries(vf, n_queries, seed=seed + 1)
    oracle = exhaustive_top_k(vf, queries, k)
    got = np.stack([
        mips_score_and_top_k(q, table, index, k)[1].astype(np.int64)
        for q in queries
    ])
    recall, _worst = recall_against_oracle(got, oracle, k)
    _RECALL.set(recall)
    return recall
