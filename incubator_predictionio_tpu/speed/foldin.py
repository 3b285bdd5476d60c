"""Batched device fold-in: solve factor rows against frozen factors.

THE speed-layer compute kernel. For a user (or item) with events newer
than the deployed instance, the exact "what would training have given
this row" answer is one regularized least-squares solve of that row
against the OTHER side's frozen factor table — the same per-row normal
equation ALS solves every sweep, so this module reuses the training
assembly + CG machinery verbatim (ops/als.py ``_gram_rhs_nnz`` /
``_reg_solve``): fold-in numerics cannot drift from training numerics.

Shape discipline: serving traffic produces arbitrary (batch, degree)
pairs, and a naive jit would compile per query. Pending rows are instead
padded onto a small fixed ladder of bucket widths × power-of-two batch
sizes, so the number of compiled variants is bounded by the ladder
(len(widths) × log2(max_batch) + 1) regardless of traffic — steady state
serves entirely from the jit cache (``foldin_compile_cache_size`` is the
counter the tests assert on). Histories longer than the widest bucket
keep their most recent entries (the solve stays O(ladder) per row; a
power user's full history re-enters at the next retrain anyway).
"""

from __future__ import annotations

import functools
import os
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from incubator_predictionio_tpu.obs import profile as _profile
from incubator_predictionio_tpu.ops import als as _als


def foldin_flops(degrees: Sequence[int], rank: int,
                 cg_iters: int) -> float:
    """Analytic useful FLOPs of one fold-in bucket dispatch: per row of
    degree d the Gram assembly is 4·d·K² + rhs 2·d·K, plus the CG solve
    ~iters·2·K² per row — the same counting convention as
    ``ops.als.train_flops`` (padding waste lowers MFU, it never counts
    as work)."""
    k = float(rank)
    d = float(sum(int(x) for x in degrees))
    return 4.0 * d * k * k + 2.0 * d * k \
        + len(degrees) * cg_iters * 2.0 * k * k


def _width_ladder() -> Tuple[int, ...]:
    """Fixed bucket widths (ascending). Read per call so tests/operators
    can override at runtime; the jit cache keys on the resulting shapes
    either way."""
    raw = os.environ.get("PIO_SPEED_WIDTHS", "8,32,128,512")
    widths = sorted({max(int(w), 1) for w in raw.split(",") if w.strip()})
    return tuple(widths) or (8, 32, 128, 512)


def max_batch() -> int:
    """Largest rows-per-dispatch bucket (power of two). Public: the
    overlay's queue-depth-adaptive fold-in budget (speed/overlay.py)
    sizes its per-poll rungs in multiples of this, so every full
    dispatch it requests is a full ladder bucket with zero padding
    waste."""
    try:
        n = int(os.environ.get("PIO_SPEED_MAX_BATCH", "64"))
    except ValueError:
        n = 64
    return 1 << max(n - 1, 0).bit_length()


#: original private name, kept for callers/tests that grew against it
_max_batch = max_batch


@functools.partial(jax.jit, static_argnames=("reg_nnz", "implicit",
                                             "cg_iters"))
def _solve_rows(
    other_factors: jax.Array,   # [M, K] f32 — frozen other-side table
    yty: Optional[jax.Array],   # [K, K] shared Gram (implicit) or None
    cols: jax.Array,            # [B, D] int32, padding cols = 0
    vals: jax.Array,            # [B, D] f32
    mask: jax.Array,            # [B, D] f32 in {0, 1}
    l2: jax.Array,              # scalar f32 (operand — no recompiles)
    alpha: jax.Array,           # scalar f32
    reg_nnz: bool,
    implicit: bool,
    cg_iters: int,
) -> jax.Array:
    """One ladder bucket's fold-in solve → [B, K] f32 (0 for empty rows).

    Exactly the training bucket solve: explicit mode is the MLlib ALS-WR
    λ(·nnz) ridge, implicit mode the Hu-Koren-Volinsky system with the
    batch-shared YᵗY kept out of the matrix (ops/als.py)."""
    gram, rhs, nnz = _als._gram_rhs_nnz(
        other_factors, cols, vals, mask, jnp.float32,
        jax.lax.Precision.HIGHEST, implicit=implicit, alpha=alpha)
    return _als._reg_solve(gram, rhs, nnz, l2, reg_nnz, implicit=implicit,
                           yty=yty, cg_iters=cg_iters)


@functools.partial(jax.jit, static_argnames=("reg_nnz", "implicit",
                                             "cg_iters"))
def _solve_rows_kernel(
    other_factors: jax.Array,   # [M, K] f32 — frozen other-side table
    yty: Optional[jax.Array],   # [K, K] shared Gram (implicit) or None
    cols: jax.Array,
    vals: jax.Array,
    mask: jax.Array,
    l2: jax.Array,
    alpha: jax.Array,
    reg_nnz: bool,
    implicit: bool,
    cg_iters: int,
) -> jax.Array:
    """Kernel-path twin of :func:`_solve_rows`: one ladder bucket through
    the fused gather+Gram+CG Pallas kernel (ops/pallas_kernels
    ``als_fused_solve_cg_pallas``) — the SAME kernel the training sweeps
    dispatch, so fold-in and training share one fused code path end to
    end. Implicit rides the precomputed YᵗY and the training path's
    doubled CG budget. Same jit-cache discipline: one compiled variant
    per ladder bucket, counted by :func:`foldin_compile_cache_size`."""
    from incubator_predictionio_tpu.ops.pallas_kernels import (
        als_fused_solve_cg_pallas,
    )

    return als_fused_solve_cg_pallas(
        other_factors, cols, vals, mask, l2, reg_nnz=reg_nnz,
        iters=cg_iters * (2 if implicit else 1), implicit=implicit,
        alpha=alpha, yty=yty)


def foldin_compile_cache_size() -> int:
    """Number of compiled fold-in variants in this process — the
    no-per-query-recompilation contract's counter. Bounded by the bucket
    ladder (widths × batch sizes × param-flag combinations actually
    used, across BOTH the XLA and the fused-kernel solve paths); tests
    assert it stops growing once the ladder is warm."""
    return int(_solve_rows._cache_size()) \
        + int(_solve_rows_kernel._cache_size())


class FoldInSolver:
    """Batched fold-in against one frozen factor table.

    ``rows`` are (cols, vals) int32/float32 pairs — the key's observed
    interactions indexed into the other side's factor table. ``solve``
    groups them onto the bucket ladder, dispatches one jitted solve per
    occupied (width, batch) bucket, and returns the solved vectors in
    input order.
    """

    def __init__(
        self,
        other_factors: Any,          # [M, K] (host or device)
        l2: float,
        reg_nnz: bool = True,
        implicit: bool = False,
        alpha: float = 1.0,
        cg_iters: Optional[int] = None,
        use_kernel: Optional[bool] = None,
    ) -> None:
        from incubator_predictionio_tpu.parallel.placement import (
            is_distributed,
        )

        # a mesh-sharded frozen table (a placed model's factors) is
        # served AS-IS: jnp.asarray keeps the sharding, the ladder
        # solves run under plain jit and GSPMD routes each history's
        # gathers to the owning shard — no host round trip, no
        # full-table replication on the serving host
        self.other_factors = jnp.asarray(other_factors, jnp.float32)
        self.sharded = is_distributed(self.other_factors)
        self.rank = int(self.other_factors.shape[1])
        self.l2 = float(l2)
        self.reg_nnz = bool(reg_nnz)
        self.implicit = bool(implicit)
        self.alpha = float(alpha)
        self.cg_iters = int(cg_iters if cg_iters is not None
                            else _als._CG_ITERS)
        # fused-kernel routing, resolved ONCE per deploy: the ladder
        # buckets dispatch the SAME fused gather+Gram+CG kernel training
        # uses, when the frozen table fits its VMEM budget. None = auto
        # (PIO_ALS_FUSED_GRAM — off unless forced: the kernel does not
        # lower on the installed TPU compiler, ops/als.py
        # _fused_enabled); tests force True, which serves via interpret
        # on the CPU backend.
        from incubator_predictionio_tpu.ops.pallas_kernels import (
            als_fused_fits,
        )

        fits = als_fused_fits(self.other_factors.shape[0], self.rank,
                              jnp.float32)
        if use_kernel is None:
            use_kernel = fits and _als._fused_enabled(self.implicit,
                                                      warm=False)
        # pallas_call does not auto-partition under GSPMD: a sharded
        # frozen table always serves through the XLA assembly
        self.use_kernel = bool(use_kernel) and fits and not self.sharded
        # the batch-shared YᵗY of implicit ALS: computed ONCE per deploy
        # (it only depends on the frozen table), not once per fold-in
        self._yty = (_als._gram_all(self.other_factors,
                                    jax.lax.Precision.HIGHEST)
                     if self.implicit else None)

    # -- ladder packing -----------------------------------------------------
    @staticmethod
    def _bucket_width(degree: int, widths: Sequence[int]) -> int:
        for w in widths:
            if degree <= w:
                return w
        return widths[-1]

    def solve(
        self, rows: Sequence[Tuple[np.ndarray, np.ndarray]]
    ) -> np.ndarray:
        """Fold in a batch of keys → [len(rows), K] f32 (in input order).

        Empty histories solve to the zero vector (the cold-start fixed
        point); histories wider than the ladder keep their most RECENT
        ``widths[-1]`` interactions (callers pass history oldest-first).
        """
        n = len(rows)
        out = np.zeros((n, self.rank), np.float32)
        if n == 0:
            return out
        widths = _width_ladder()
        max_b = _max_batch()
        by_width: dict = {}
        for slot, (cols, vals) in enumerate(rows):
            cols = np.asarray(cols, np.int32).reshape(-1)
            vals = np.asarray(vals, np.float32).reshape(-1)
            d = int(cols.shape[0])
            if d == 0:
                continue
            cap = widths[-1]
            if d > cap:  # keep the newest interactions
                cols, vals, d = cols[-cap:], vals[-cap:], cap
            by_width.setdefault(self._bucket_width(d, widths), []).append(
                (slot, cols, vals))
        for width, members in sorted(by_width.items()):
            for s in range(0, len(members), max_b):
                chunk = members[s:s + max_b]
                b = len(chunk)
                b_pad = min(1 << max(b - 1, 0).bit_length(), max_b)
                cols = np.zeros((b_pad, width), np.int32)
                vals = np.zeros((b_pad, width), np.float32)
                mask = np.zeros((b_pad, width), np.float32)
                for r, (_slot, c, v) in enumerate(chunk):
                    cols[r, :len(c)] = c
                    vals[r, :len(v)] = v
                    mask[r, :len(c)] = 1.0
                _pt0 = _profile.t0()
                solve_fn = (_solve_rows_kernel if self.use_kernel
                            else _solve_rows)
                sol = np.asarray(solve_fn(
                    self.other_factors, self._yty,
                    jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(mask),
                    jnp.float32(self.l2), jnp.float32(self.alpha),
                    reg_nnz=self.reg_nnz, implicit=self.implicit,
                    cg_iters=self.cg_iters))
                # np.asarray already synced the dispatch: result=None
                _profile.record(
                    _pt0, "foldin", "foldin_solve",
                    foldin_flops([len(c) for _s, c, _v in chunk],
                                 self.rank, self.cg_iters)
                    if _pt0 is not None else 0.0)
                for r, (slot, _c, _v) in enumerate(chunk):
                    out[slot] = sol[r]
        return out

    def warmup(self) -> None:
        """Pre-compile every ladder width at batch size 1 (the common
        trickle shape) so the first live fold-in never pays an XLA
        compile. Larger batch shapes compile on first use — bounded by
        the ladder either way."""
        for width in _width_ladder():
            # degree == width so each solve lands in ITS bucket (a
            # shorter row would all fall into the smallest bucket)
            self.solve([(np.zeros(width, np.int32),
                         np.ones(width, np.float32))])


def dense_reference_solve(
    other_factors: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    l2: float,
    reg_nnz: bool = True,
    implicit: bool = False,
    alpha: float = 1.0,
) -> np.ndarray:
    """Dense numpy least-squares reference for ONE row — the differential
    oracle the fold-in tests compare every ladder bucket against.

    Explicit: (XᵀX + λ·nnz·I) w = Xᵀy. Implicit (Hu-Koren-Volinsky with
    binary preference): (YᵗY + Yᵤᵗ(Cᵤ−I)Yᵤ + λI) w = Yᵤᵗcᵤ, c = 1+αr.
    """
    other = np.asarray(other_factors, np.float64)
    x = other[np.asarray(cols, np.int64)]
    y = np.asarray(vals, np.float64)
    k = other.shape[1]
    if implicit:
        conf = 1.0 + alpha * y
        a = other.T @ other + x.T @ np.diag(conf - 1.0) @ x \
            + l2 * np.eye(k)
        b = x.T @ conf
    else:
        lam = l2 * (max(len(y), 1) if reg_nnz else 1.0)
        a = x.T @ x + lam * np.eye(k)
        b = x.T @ y
    return np.linalg.solve(a, b).astype(np.float32)
