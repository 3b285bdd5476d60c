"""Host-side sparse → static-shape padded structures.

XLA wants static shapes; ratings matrices are ragged. The bridge is
degree-bucketed padded neighbor lists: rows (users or items) are grouped into
buckets by degree ceiling (powers of two), each bucket padded to its ceiling.
This bounds padding waste at <2× while keeping the number of distinct
compiled shapes at O(log max_degree) — the ALX paper's sharded-batch layout
reduced to its single-host form (PAPERS.md: ALX §4).

Construction is host-side numpy (it runs once per training read, off the
device hot path).
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class PaddedRows:
    """One degree bucket of padded neighbor lists.

    ``row_ids[i]`` is the original row index of padded row ``i``;
    ``cols[i, :]`` / ``vals[i, :]`` are its neighbor column indices and
    values, valid where ``mask[i, :] > 0``. Padding columns point at index 0
    with mask 0 so gathers stay in-bounds.
    """

    row_ids: np.ndarray  # [B] int32
    cols: np.ndarray     # [B, D] int32
    vals: np.ndarray     # [B, D] float32
    mask: np.ndarray     # [B, D] float32

    @property
    def width(self) -> int:
        return int(self.cols.shape[1])

    def pad_rows_to(self, multiple: int) -> "PaddedRows":
        """Pad the batch dimension to a multiple (device-count divisibility).

        Padding rows carry ``row_id = -1`` with zero mask; the ALS scatter
        remaps negatives out of bounds and drops them (ops/als.py
        ``_scatter_rows``)."""
        b = self.row_ids.shape[0]
        target = ((b + multiple - 1) // multiple) * multiple
        if target == b:
            return self
        pad = target - b
        return PaddedRows(
            row_ids=np.concatenate([self.row_ids, np.full(pad, -1, np.int32)]),
            cols=np.concatenate(
                [self.cols, np.zeros((pad, self.width), np.int32)]
            ),
            vals=np.concatenate(
                [self.vals, np.zeros((pad, self.width), np.float32)]
            ),
            mask=np.concatenate(
                [self.mask, np.zeros((pad, self.width), np.float32)]
            ),
        )


#: triplet count above which the C++ builder is worth its call overhead
NATIVE_MIN_NNZ = 100_000


@dataclasses.dataclass
class HeavySegments:
    """Split-row segments extracted from :class:`PaddedRows` buckets.

    Rows whose degree exceeds ``max_width`` are split across several padded
    rows; the solver cannot treat those independently (one scatter-set per
    padded row would keep only one segment's solution). This structure
    groups every split row's segments for the partial-Gram combining solve
    in ops/als.py: per-segment Grams/rhs are computed exactly like a normal
    bucket, then segment-summed by ``seg_ids`` before the single solve per
    heavy row — the ALX sharded-batch reduction in single-host form
    (PAPERS.md: ALX §4).
    """

    seg_ids: np.ndarray  # [S] int32 → index into row_ids (compact)
    row_ids: np.ndarray  # [H] int32 original row indices
    cols: np.ndarray     # [S, W] int32
    vals: np.ndarray     # [S, W] float32
    mask: np.ndarray     # [S, W] float32


def split_heavy(
    buckets: Sequence[PaddedRows],
    row_multiple: int = 8,
) -> Tuple[List[PaddedRows], "HeavySegments | None"]:
    """Separate split rows (duplicated row ids) from the light buckets.

    Returns rebuilt light buckets (split rows removed, re-padded to
    ``row_multiple``) and a :class:`HeavySegments` holding every split
    row's segments, or None when no row was split.
    """
    all_ids = np.concatenate(
        [np.asarray(b.row_ids) for b in buckets]
    ) if buckets else np.empty(0, np.int32)
    live = all_ids[all_ids >= 0]
    uniq, counts = np.unique(live, return_counts=True)
    heavy_ids = set(int(i) for i in uniq[counts > 1])
    if not heavy_ids:
        return list(buckets), None

    light: List[PaddedRows] = []
    seg_rows: List[Tuple[int, np.ndarray, np.ndarray, np.ndarray]] = []
    for b in buckets:
        ids = np.asarray(b.row_ids)
        is_heavy = np.isin(ids, list(heavy_ids)) & (ids >= 0)
        for i in np.nonzero(is_heavy)[0]:
            seg_rows.append((int(ids[i]), b.cols[i], b.vals[i], b.mask[i]))
        keep = ~is_heavy & (ids >= 0)
        if keep.any():
            light.append(
                PaddedRows(
                    row_ids=ids[keep], cols=b.cols[keep],
                    vals=b.vals[keep], mask=b.mask[keep],
                ).pad_rows_to(row_multiple)
            )

    width = max(seg[1].shape[0] for seg in seg_rows)
    s = len(seg_rows)
    cols = np.zeros((s, width), np.int32)
    vals = np.zeros((s, width), np.float32)
    mask = np.zeros((s, width), np.float32)
    row_ids = np.asarray(sorted(heavy_ids), np.int32)
    index = {int(r): i for i, r in enumerate(row_ids)}
    seg_ids = np.empty(s, np.int32)
    for i, (rid, c, v, m) in enumerate(seg_rows):
        w = c.shape[0]
        cols[i, :w], vals[i, :w], mask[i, :w] = c, v, m
        seg_ids[i] = index[rid]
    return light, HeavySegments(
        seg_ids=seg_ids, row_ids=row_ids, cols=cols, vals=vals, mask=mask)


def build_padded_rows(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    min_width: int = 8,
    max_width: int = 4096,
    row_multiple: int = 8,
    impl: str = "auto",
    degrees: "np.ndarray | None" = None,
) -> List[PaddedRows]:
    """COO triplets → degree-bucketed :class:`PaddedRows`.

    Rows with degree > ``max_width`` are *split* across multiple padded rows
    of width ``max_width``, so no data is dropped for power users/items.
    NOTE: the current ALS solver writes one solution per padded row
    (scatter-set) and therefore cannot combine split rows — it validates and
    raises on them (ops/als.py ``assert_no_split``). The split layout exists
    for the future partial-Gram combining solver (the ALX multi-chip path);
    until then keep ``max_width`` above the data's max degree.

    ``impl``: "auto" uses the native C++ builder (native/src/csr_builder.cc)
    for large inputs, "native"/"numpy" force a path. Both produce identical
    buckets.

    ``degrees``: optional precomputed per-row nnz histogram
    (int64[n_rows], sum == nnz) replacing the native plan pass — the
    pipelined ingest path accumulates it per scan shard while the scan is
    still running (see :class:`StreamingPrep`). A wrong histogram is
    detected natively and falls back to the exact plan.
    """
    if impl not in ("auto", "native", "numpy"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl == "native" or (impl == "auto" and len(rows) >= NATIVE_MIN_NNZ):
        from incubator_predictionio_tpu.native.csr import build_buckets_native
        buckets = build_buckets_native(
            np.asarray(rows), np.asarray(cols), np.asarray(vals), n_rows,
            min_width, max_width, degrees=degrees)
        if buckets is not None:
            return [
                PaddedRows(row_ids=r, cols=c, vals=v, mask=m)
                .pad_rows_to(row_multiple)
                for (_w, r, c, v, m) in buckets
            ]
        if impl == "native":
            raise RuntimeError("native csr builder unavailable")
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int32)
    vals = np.asarray(vals, np.float32)
    order = np.argsort(rows, kind="stable")
    rows, cols, vals = rows[order], cols[order], vals[order]

    row_ids_present, starts, counts = np.unique(
        rows, return_index=True, return_counts=True
    )

    # assemble (row_id, start, length) segments, splitting heavy rows
    segments: List[Tuple[int, int, int]] = []
    for rid, start, count in zip(row_ids_present, starts, counts):
        off = 0
        while count - off > 0:
            seg = min(count - off, max_width)
            segments.append((int(rid), int(start + off), int(seg)))
            off += seg

    # bucket segments by power-of-two ceiling
    buckets: dict[int, List[Tuple[int, int, int]]] = {}
    for rid, start, seg in segments:
        width = min_width
        while width < seg:
            width *= 2
        buckets.setdefault(width, []).append((rid, start, seg))

    out: List[PaddedRows] = []
    for width in sorted(buckets):
        segs = buckets[width]
        b = len(segs)
        r_ids = np.empty(b, np.int32)
        c = np.zeros((b, width), np.int32)
        v = np.zeros((b, width), np.float32)
        m = np.zeros((b, width), np.float32)
        for i, (rid, start, seg) in enumerate(segs):
            r_ids[i] = rid
            c[i, :seg] = cols[start:start + seg]
            v[i, :seg] = vals[start:start + seg]
            m[i, :seg] = 1.0
        out.append(
            PaddedRows(row_ids=r_ids, cols=c, vals=v, mask=m).pad_rows_to(
                row_multiple
            )
        )
    return out


def build_both_sides(
    users: np.ndarray,
    items: np.ndarray,
    vals: np.ndarray,
    n_users: int,
    n_items: int,
    max_width: int = 4096,
    row_multiple: int = 8,
    split_row_multiple: int = 8,
    user_degrees: "np.ndarray | None" = None,
    item_degrees: "np.ndarray | None" = None,
):
    """Both training orientations (user-major and item-major) built
    concurrently → ((user_light, user_heavy), (item_light, item_heavy)).

    The two sides are independent and the native builder's ctypes calls
    release the GIL, so a two-thread pool halves the prep wall on hosts
    with ≥2 usable cores (pinned single-core containers degrade to the
    sequential cost — thread spawn is noise at this scale).

    ``user_degrees``/``item_degrees``: optional precomputed per-row
    histograms (see :func:`build_padded_rows`)."""
    from concurrent.futures import ThreadPoolExecutor

    def side(rows, cols, n_rows, degrees):
        return split_heavy(
            build_padded_rows(rows, cols, vals, n_rows, max_width=max_width,
                              row_multiple=row_multiple, degrees=degrees),
            row_multiple=split_row_multiple)

    with ThreadPoolExecutor(max_workers=2) as pool:
        fu = pool.submit(side, users, items, n_users, user_degrees)
        fi = pool.submit(side, items, users, n_items, item_degrees)
        return fu.result(), fi.result()


class StreamingPrep:
    """Scan→prep pipeline sink: consume scan shards as they land.

    The sharded event-log scan (data/storage/cpplog.py ``shard_sink``)
    hands over each completed shard — indices already remapped into the
    global id tables — while later shards are still scanning with the GIL
    released. This sink does the prep work that is per-shard computable
    up front: the per-side degree histograms that replace the native csr
    *plan* pass (:func:`build_padded_rows` ``degrees``). ``overlap_s``
    records how much prep wall was absorbed into the scan.

    ``finish(inter)`` then runs :func:`build_both_sides` on the final
    arrays. Histograms are only used when the scan did NOT have to
    reorder rows (``scan_reordered`` in the scan stats): a reorder
    re-interns ids, so the accumulated histograms index a permuted table
    and are discarded (degrees are recomputed natively — correctness
    never depends on the pipeline)."""

    def __init__(self) -> None:
        self.user_degrees = np.zeros(0, np.int64)
        self.item_degrees = np.zeros(0, np.int64)
        self.overlap_s = 0.0
        self.shards = 0

    def _accumulate(self, hist: np.ndarray, idx: np.ndarray) -> np.ndarray:
        add = np.bincount(idx, minlength=len(hist)).astype(np.int64)
        if len(add) > len(hist):
            add[:len(hist)] += hist
            return add
        hist += add
        return hist

    def add_shard(self, k: int, uidx, iidx, vals, times=None) -> None:
        import time

        t0 = time.perf_counter()
        self.user_degrees = self._accumulate(self.user_degrees, uidx)
        self.item_degrees = self._accumulate(self.item_degrees, iidx)
        self.shards += 1
        self.overlap_s += time.perf_counter() - t0

    def finish(
        self,
        inter,
        max_width: int = 4096,
        row_multiple: int = 8,
        split_row_multiple: int = 8,
        reordered: bool = False,
    ):
        """→ same ((user_light, user_heavy), (item_light, item_heavy))
        tuple as :func:`build_both_sides`, fed the pre-accumulated degree
        histograms when they are still valid for ``inter``."""
        n_users, n_items = len(inter.user_ids), len(inter.item_ids)
        ud = id_ = None
        if not reordered and self.shards:
            mu = min(n_users, len(self.user_degrees))
            ud = np.zeros(n_users, np.int64)
            ud[:mu] = self.user_degrees[:mu]
            mi = min(n_items, len(self.item_degrees))
            id_ = np.zeros(n_items, np.int64)
            id_[:mi] = self.item_degrees[:mi]
        return build_both_sides(
            inter.user_idx, inter.item_idx, inter.values, n_users, n_items,
            max_width=max_width, row_multiple=row_multiple,
            split_row_multiple=split_row_multiple,
            user_degrees=ud, item_degrees=id_)
