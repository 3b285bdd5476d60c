"""Alternating Least Squares on TPU — the MLlib-ALS replacement.

The reference's recommendation templates call Spark MLlib's shuffle-based ALS
(examples/scala-parallel-recommendation/custom-query/src/main/scala/
ALSAlgorithm.scala:25-31). This is the TPU-first redesign (ALX-style,
PAPERS.md): factors live in dense device arrays; each half-sweep is

  1. gather the *other* side's factors for every observed interaction
     (degree-bucketed padded rows, see ops.sparse),
  2. one big batched einsum builds all K×K normal-equation Grams at once
     (bf16 inputs, f32 accumulation — MXU-shaped work),
  3. a batched Cholesky-backed solve produces the new factors,
  4. a masked scatter writes them back.

Sharding: the padded-row batches shard across the whole mesh on the batch
axis; factor tables are replicated (they are MBs even at ML-20M scale:
270k×128 ≈ 138 MB total) so gathers are local and XLA inserts exactly one
all-gather per half-sweep when the scatter output needs replication again.
Model-parallel sharded factor tables (the full ALX layout for >100M-row
embedding tables) ride the same bucket structure and are the designated
extension on the ``mp`` mesh axis.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from incubator_predictionio_tpu.ops.sparse import (
    PaddedRows,
    build_both_sides,
    build_padded_rows,
    split_heavy,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ALSState:
    """Factor matrices (a pytree — checkpoints via workflow.checkpoint).

    ``placement`` (STATIC pytree metadata, never a leaf) carries the
    mesh-sharded layout when the tables are distributed — a
    :class:`~incubator_predictionio_tpu.parallel.placement.FactorPlacement`
    recording the mesh, per-table shardings and the padded sizes. None
    (the default) is the single-chip layout; every existing constructor
    site is unchanged. Being static, a placement change is a different
    jit cache key: resharded programs recompile, same-placement
    steady-state retrains never do."""

    user_factors: Any  # [n_users, rank] f32 (padded when placed)
    item_factors: Any  # [n_items, rank] f32 (padded when placed)
    placement: Optional[Any] = dataclasses.field(
        default=None, metadata=dict(static=True))


def als_init(
    key: jax.Array, n_users: int, n_items: int, rank: int, scale: float = 0.1
) -> ALSState:
    ku, ki = jax.random.split(key)
    return ALSState(
        user_factors=scale * jax.random.normal(ku, (n_users, rank), jnp.float32),
        item_factors=scale * jax.random.normal(ki, (n_items, rank), jnp.float32),
    )


@functools.partial(jax.jit, static_argnames=("n_rows",))
def _grow_factors(prev: jax.Array, key: jax.Array, n_rows: int,
                  scale: float) -> jax.Array:
    """Prefix-copy a factor table into a larger index space → [n_rows, K].

    The traincache tail fold interns ids in stable first-seen order, so a
    previous model's rows map onto the new index space as an EXACT prefix
    — no gather, no remap (and therefore none of the negative-padding
    wraparound `_gather_x0` clamps against): the old table is copied
    row-for-row device-side and only the NEW ids get ``als_init``-scale
    random rows appended. Not donated: checkpointed prev factors arrive
    as host numpy (never donatable — the annotation would only warn)."""
    pu, rank = prev.shape
    if n_rows == pu:
        return prev.astype(jnp.float32)
    fresh = scale * jax.random.normal(key, (n_rows - pu, rank), jnp.float32)
    return jnp.concatenate([prev.astype(jnp.float32), fresh])


def continue_state(
    prev_user: Any,            # [U0, K] prior user factors (host or device)
    prev_item: Any,            # [I0, K] prior item factors
    n_users: int,
    n_items: int,
    seed: int = 0,
    scale: float = 0.1,
) -> Optional[ALSState]:
    """Seed a retrain from a previous model's factors (the cross-retrain
    continuation of the O(delta) steady-state path).

    Returns None when the prior tables cannot be a prefix of the new
    index space (more rows than the new table — ids were deleted or the
    index space was rebuilt, so row i no longer names the same entity);
    the caller then falls back to ``als_init``. The caller is
    responsible for verifying the id-space prefix property itself (the
    engines check the BiMap prefix; see models/*/engine.py)."""
    prev_user = jnp.asarray(prev_user)
    prev_item = jnp.asarray(prev_item)
    if (prev_user.ndim != 2 or prev_item.ndim != 2
            or prev_user.shape[1] != prev_item.shape[1]
            or prev_user.shape[0] > n_users
            or prev_item.shape[0] > n_items):
        return None
    ku, ki = jax.random.split(jax.random.key(seed))
    return ALSState(
        user_factors=_grow_factors(prev_user, ku, n_users, scale),
        item_factors=_grow_factors(prev_item, ki, n_items, scale),
    )


def _gram_rhs_nnz(
    other_factors: jax.Array,  # [M, K]
    cols: jax.Array,           # [..., D] int32
    vals: jax.Array,           # [..., D] f32
    mask: jax.Array,           # [..., D] f32 in {0, 1}
    compute_dtype: Any,
    precision: Any,
    implicit: bool,
    alpha: float,
    gram_dtype: Any = jnp.float32,
):
    """Normal-equation pieces for a batch of padded rows → (gram, rhs, nnz).

    THE single copy of the numerically delicate assembly — explicit mode
    relies on mask² == mask to apply the mask once per side; implicit mode
    builds Yᵤᵗ(Cᵤ−I)Yᵤ with c = 1 + α·r (Hu-Koren-Volinsky). Everything
    accumulates in f32 at the given matmul precision (see the note on
    :func:`_solve_bucket`). Used by the bucket solvers AND the split-row
    partial-Gram path so their numerics cannot drift apart.

    ``gram_dtype=bfloat16`` casts the Gram batch in the einsum epilogue
    (accumulation stays f32): the [rows, K, K] Gram is the largest tensor
    of a sweep (~9 GB f32 on the ML-20M user side), so emitting it bf16
    halves both the write and every CG re-read without a separate
    materialized cast. Only the bf16 bucket path opts in — the split-row
    path must segment-sum partial Grams in f32 first."""
    # The gather is the dominant HBM stream at scale ([..., D, K] ≈
    # nnz·K elements per half-sweep): casting the SOURCE table to
    # compute_dtype first halves that traffic in bf16 mode AND hands the
    # MXU single-pass bf16 operands (vs the 6-pass f32 HIGHEST schedule).
    # Implicit mode NEVER casts — its bucket solver is hardcoded f32, and
    # the heavy (split-row) path must match it exactly (the "numerics
    # cannot drift apart" contract above).
    src = (other_factors
           if implicit or other_factors.dtype == compute_dtype
           else other_factors.astype(compute_dtype))
    gathered = src[cols]                                # [..., D, K]
    masked = gathered * mask[..., None].astype(gathered.dtype)
    if implicit:
        conf_minus1 = alpha * vals * mask               # (c-1), 0 on padding
        gram = jnp.einsum(
            "...d,...dk,...dl->...kl", conf_minus1, masked, gathered,
            preferred_element_type=jnp.float32, precision=precision,
        )
        rhs = jnp.einsum(
            "...d,...dk->...k", (1.0 + conf_minus1) * mask, masked,
            preferred_element_type=jnp.float32, precision=precision,
        )
    else:
        gram = jnp.einsum(
            "...dk,...dl->...kl", masked, gathered,
            preferred_element_type=jnp.float32, precision=precision,
        )
        rhs = jnp.einsum(
            "...d,...dk->...k", (vals * mask).astype(gathered.dtype), masked,
            preferred_element_type=jnp.float32, precision=precision,
        )
    return gram.astype(gram_dtype), rhs, mask.sum(axis=-1)


#: batched SPD solver: "cg" (Jacobi-preconditioned conjugate gradient) or
#: "cholesky" (XLA's batched factorization). CG is the TPU default: XLA's
#: batched Cholesky serializes K dependent steps of thin vector work
#: (measured ~25 µs per 128×128 system on v5e — it would dominate the whole
#: training run at ML-20M scale), while CG is nothing but batched matvecs,
#: ~16× faster in-trace at ≤1e-5 relative error on λ·nnz-regularized grams
#: (the diagonal regularizer is exactly what makes Jacobi preconditioning
#: effective here).
#: 16 iterations reach ≤3e-6 relative solve error on λ·nnz-regularized
#: grams (measured; 32 and 16 produce bit-identical training RMSE at
#: ML-20M-shape workloads, and the solve cost is linear in the budget)
_SOLVER = os.environ.get("PIO_ALS_SOLVER", "cg")
_CG_ITERS = int(os.environ.get("PIO_ALS_CG_ITERS", "16"))
#: fused Pallas bucket solve (ops/pallas_kernels.als_solve_cg_pallas):
#: "auto" uses the kernel for explicit CG buckets when the backend is a
#: TPU; "on" forces it (tests use interpret mode); "off" pins the XLA
#: path. The kernel removes the (1+iters)·rows·K² Gram HBM stream —
#: the dominant bf16-sweep traffic at ML-20M shape — by keeping each
#: row's Gram and the whole CG solve in VMEM.
_ALS_KERNEL = os.environ.get("PIO_ALS_KERNEL", "auto")
#: minimum bucket width D for kernel routing when the kernel is enabled.
#: Small-D buckets are where the fused solve loses: the kernel pads every
#: row's gather to a full 128 lane tile ((dp−d)·K wasted read per row)
#: and solves each row's CG serially, while its Gram-stream saving —
#: (1+iters)·K² per row on the XLA path — is the same for every bucket,
#: so it is RELATIVELY thinnest exactly where the padding tax is highest
#: (measured on-chip at 2M nnz, D̄≈14: kernel 1.50 s vs XLA 1.15 s).
#: Bucket widths are static at trace time, so routing is free.
_KERNEL_MIN_D = int(os.environ.get("PIO_ALS_KERNEL_MIN_D", "64"))
#: warm-start every bucket CG from the previous sweep's factors. At a
#: fixed iteration budget this only improves the residual (the start
#: point is closer); its real payoff is a LOWER budget for the same
#: RMSE — each saved CG iteration saves a full [rows, K, K] Gram-batch
#: re-read, the dominant bf16-sweep HBM stream. Measured convergence
#: curves: see docs/performance.md (warm@N vs cold@N on the planted
#: bench workload — convergence is platform-independent).
_CG_WARMSTART = os.environ.get("PIO_ALS_CG_WARMSTART", "1") not in (
    "0", "off", "false")


def _kernel_rows_default() -> int:
    """Current rows-per-program default (PIO_ALS_KERNEL_ROWS, owned by
    pallas_kernels). Read at CALL time so sweeps/monkeypatches see it;
    the resolved value is threaded as a static jit arg — never read
    mid-trace."""
    from incubator_predictionio_tpu.ops import pallas_kernels

    return pallas_kernels._ALS_ROWS


def _fused_gram_mode() -> str:
    """`PIO_ALS_FUSED_GRAM` — the fused gather+Gram+CG kernel selector
    ("on" forces it — the CPU interpret-mode test hook; anything else,
    "auto" included, keeps the two-stage kernel / XLA assembly: see
    :func:`_fused_enabled`). Read per call, never frozen at import (the
    env-import lint contract)."""
    return os.environ.get("PIO_ALS_FUSED_GRAM", "auto")


def _cg_tol_env() -> float:
    """`PIO_ALS_CG_TOL` — device-side CG residual early-exit tolerance
    (relative preconditioned residual; 0 = fixed budget, the default:
    the budget is already tuned, and a data-dependent iteration count
    would blur the analytic FLOP attribution). Read per call."""
    try:
        return float(os.environ.get("PIO_ALS_CG_TOL", "0") or 0.0)
    except ValueError:
        return 0.0


def _kernel_enabled(implicit: bool, warm: bool = False) -> bool:
    """Resolve the bucket-kernel selector OUTSIDE any jit trace (the
    result is a static jit argument). Explicit CG routes through either
    kernel generation; the implicit path needs the batch-shared YᵗY
    term, which only the fused-gather kernel carries — implicit is
    therefore kernel-eligible exactly when the fused generation is.
    ``auto`` is the backend test (``als_kernel_available``): on a TPU the
    two-stage kernel is selected and runs compiled or raises — there is
    no probe and no reroute. ``warm`` names the variant (x0 operand or
    not) the caller will dispatch."""
    if _SOLVER != "cg" or _ALS_KERNEL == "off":
        return False
    if implicit:
        return _fused_enabled(True, warm)
    if _ALS_KERNEL == "on":
        return True
    from incubator_predictionio_tpu.ops.pallas_kernels import (
        als_kernel_available,
    )

    return als_kernel_available()


def _fused_enabled(implicit: bool, warm: bool) -> bool:
    """Resolve the fused-gather generation selector OUTSIDE any trace.

    ``auto`` NEVER selects it: ``als_fused_solve_cg_pallas`` does not
    lower on the installed TPU compiler (jax 0.9.0 / libtpu 0.0.34). Its
    in-kernel ``jnp.take`` row gather is refused at lowering, at ML-20M
    widths and at a 60×64 table alike, by
    ``jax/_src/pallas/mosaic/lowering.py`` ``_gather_lowering_rule``:
    ``ValueError: Shape mismatch in input, indices and output`` — Mosaic
    only has same-shape ``take_along_axis`` gathers, and those stop at
    one source vreg ("Not implemented: Multiple source vregs along gather
    dimension"). It has never run on a chip and has never been measured.
    tests/test_tpu_aot_compile.py holds the strict xfail that flips when
    a compiler accepts the kernel.

    `PIO_ALS_FUSED_GRAM=on` remains as the CPU interpret-mode test hook
    (tests/test_fused_gram.py); on a TPU it raises the error above.
    `PIO_ALS_KERNEL=on` deliberately does NOT turn it on."""
    if _SOLVER != "cg" or _ALS_KERNEL == "off":
        return False
    return _fused_gram_mode() == "on"


def _fused_sides(n_users: int, n_items: int, implicit: bool, warm: bool,
                 compute_dtype: Any, rank: int) -> Tuple[bool, bool]:
    """Per-half-sweep fused-gather routing → (user_sweep, item_sweep).

    The fused kernel pins the OTHER side's factor table in VMEM, so the
    decision is per gather source: the user half-sweep gathers from the
    item table (small — fits at ML-20M shape), the item half-sweep from
    the user table (usually does not). Resolved HERE, outside the trace,
    from static shapes + the VMEM budget (`PIO_ALS_FUSED_VMEM_MB`), and
    threaded as a static jit arg — a mid-trace read would bake a stale
    budget into the cache."""
    dt = jnp.float32 if implicit else compute_dtype
    return (_fused_one(True, implicit, warm, n_items, rank, dt),
            _fused_one(True, implicit, warm, n_users, rank, dt))


def _fused_one(use_kernel: bool, implicit: bool, warm: bool,
               table_rows: int, rank: int, dtype: Any) -> bool:
    """THE single-side fused-routing conjunction: kernel selected AND
    the fused generation enabled for this exact (implicit, warm)
    variant AND the gather table inside the VMEM budget. Every call
    site — the per-sweep tuple above, the one-shot `_update_side`
    entries, retrain's per-leg closure via `_fused_sides` — resolves
    through here so the rule cannot drift between files."""
    if not use_kernel or not _fused_enabled(implicit, warm):
        return False
    from incubator_predictionio_tpu.ops.pallas_kernels import (
        als_fused_fits,
    )

    return als_fused_fits(table_rows, rank, dtype)
#: CG budget for the bf16 early sweeps of the mixed schedule. Each CG
#: iteration re-reads the whole [rows, K, K] Gram batch (~9 GB at
#: ML-20M scale on the user side) — the dominant HBM stream once gathers
#: run bf16 — and early sweeps are re-solved next sweep anyway, so a
#: loose solve costs nothing in final quality (the f32 polish runs the
#: full budget; guarded by the planted-recovery test). With warm start
#: the default drops 6 → 3: measured on the planted workload (10
#: sweeps, λ=0.03), warm@3 reaches the same fit RMSE as cold@6 (0.162
#: vs 0.162; docs/performance.md has the full curve), and
#: warm-start's +1 initial-residual matvec still nets 5 Gram
#: reads/row vs cold@6's 7 — a ~29% cut of the dominant stream.
_CG_ITERS_BF16 = int(os.environ.get("PIO_ALS_CG_ITERS_BF16") or
                     ("3" if _CG_WARMSTART else "6"))


def _cg_solve_spd(a: jax.Array, b: jax.Array, iters: int,
                  matvec_dtype: Any = jnp.float32,
                  lam: Optional[jax.Array] = None,
                  shared: Optional[jax.Array] = None,
                  x0: Optional[jax.Array] = None,
                  tol: float = 0.0,
                  return_iters: bool = False):
    """Batched Jacobi-PCG for SPD systems → x ≈ (a [+ diag(lam)])⁻¹ b, [B, K].

    Division guards make converged (and all-zero) systems fixed points
    instead of NaN factories: a zero-nnz explicit row has a = λI, b = 0,
    so r = 0 → every α/β guard holds it at x = 0.

    ``matvec_dtype=bfloat16`` halves the dominant HBM stream (every
    iteration re-reads the whole [B, K, K] Gram batch — ~9 GB at ML-20M
    scale) by running the matvec on a bf16 Gram with f32 accumulation;
    x/r/p and all reductions stay f32. Used by the mixed schedule's bf16
    sweeps only — the f32 polish runs full-precision CG.

    ``lam`` ([B] f32) applies the λ(+λ·nnz) ridge INSIDE the matvec in
    f32, so the caller can hand over a bare bf16 Gram (half the write and
    every re-read) while the regularizer — the part conditioning depends
    on — never rounds through bf16.

    ``shared`` ([K, K] f32) adds a batch-shared SPD term (implicit ALS's
    YᵗY) inside the matvec as one thin einsum — the [B, K, K] broadcast
    ``yty[None] + gram`` never materializes, which at training scale is a
    whole extra Gram-batch write + read per half-sweep.

    ``x0`` ([B, K] f32) warm-starts the iteration (one extra matvec for
    the initial residual). ALS re-solves every factor row from scratch
    each sweep while the true solution moves less and less — warm
    starting from the previous sweep's factors buys the same residual in
    roughly half the iterations once the alternation settles, and each
    saved iteration saves a full re-read of the Gram batch.

    ``tol`` > 0 adds a DEVICE-SIDE residual early exit
    (``lax.while_loop`` with ``iters`` as the ceiling): the loop stops
    once every row's preconditioned residual rᵗz has fallen to
    tol²·(r₀ᵗz₀) — well-conditioned batches (warm starts on settled
    alternations, small fold-in systems) stop paying the full budget,
    and each saved iteration saves a full Gram-batch re-read. No host
    sync: the criterion is evaluated in-trace (the host-sync lint
    contract). ``tol == 0`` keeps the fixed-budget ``fori_loop`` —
    bit-identical to the historical path. ``return_iters`` additionally
    returns the iteration count actually run (a device scalar; tests
    pin the early exit with it)."""
    diag = jnp.diagonal(a, axis1=-2, axis2=-1).astype(jnp.float32)
    if shared is not None:
        diag = diag + jnp.diagonal(shared)[None, :]
    if lam is not None:
        diag = diag + lam[:, None]
    minv = jnp.where(diag > 0, 1.0 / diag, 0.0)
    hp = jax.lax.Precision.HIGHEST
    a_mv = a if a.dtype == matvec_dtype else a.astype(matvec_dtype)

    def matvec(p):
        ap = jnp.einsum(
            "bkl,bl->bk", a_mv, p.astype(a_mv.dtype),
            preferred_element_type=jnp.float32,
            precision=hp if a_mv.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
        if shared is not None:
            ap = ap + jnp.einsum(
                "kl,bl->bk", shared, p,
                preferred_element_type=jnp.float32, precision=hp)
        if lam is not None:
            ap = ap + lam[:, None] * p
        return ap

    def body(_, carry):
        x, r, p, rz = carry
        ap = matvec(p)
        pap = jnp.sum(p * ap, -1)
        alpha = jnp.where(pap > 0, rz / pap, 0.0)
        x = x + alpha[:, None] * p
        r = r - alpha[:, None] * ap
        z = minv * r
        rz2 = jnp.sum(r * z, -1)
        beta = jnp.where(rz > 0, rz2 / rz, 0.0)
        p = z + beta[:, None] * p
        return x, r, p, rz2

    if x0 is None:
        x, r = jnp.zeros_like(b), b
    else:
        x = x0.astype(jnp.float32)
        r = b - matvec(x)
    z = minv * r
    rz0 = jnp.sum(r * z, -1)
    if tol and tol > 0.0:
        tol2 = jnp.float32(tol) ** 2

        def cond(carry):
            i, _x, _r, _p, rz = carry
            return jnp.logical_and(i < iters, jnp.any(rz > tol2 * rz0))

        def wbody(carry):
            i, x, r, p, rz = carry
            x, r, p, rz = body(0, (x, r, p, rz))
            return i + 1, x, r, p, rz

        i, x, _r, _p, _rz = jax.lax.while_loop(
            cond, wbody, (jnp.int32(0), x, r, z, rz0))
    else:
        x, _r, _p, _rz = jax.lax.fori_loop(
            0, iters, body, (x, r, z, rz0))
        i = jnp.int32(iters)
    return (x, i) if return_iters else x


def _reg_solve(
    gram: jax.Array,           # [B, K, K]
    rhs: jax.Array,            # [B, K]
    nnz: jax.Array,            # [B]
    l2: float,
    reg_nnz: bool,
    implicit: bool,
    yty: Optional[jax.Array],
    cg_iters: int = _CG_ITERS,
    cg_matvec_dtype: Any = jnp.float32,
    x0: Optional[jax.Array] = None,
    cg_tol: float = 0.0,
) -> jax.Array:
    """Regularize + batched SPD solve; zero factors for empty rows."""
    rank = gram.shape[-1]
    eye = jnp.eye(rank, dtype=jnp.float32)
    if implicit:
        # CG keeps the batch-shared YᵗY OUT of the matrix (one thin einsum
        # in the matvec) — the [B, K, K] broadcast sum never materializes
        lam = jnp.full(nnz.shape, l2, jnp.float32)
        shared = yty
        a = gram
    else:
        # MLlib-style ALS-WR: lambda scaled by row nnz (reg_nnz=True).
        # For CG the ridge stays OUT of the matrix — applied in f32 inside
        # the matvec — so a bf16 Gram batch can be solved directly.
        lam = l2 * jnp.where(reg_nnz, jnp.maximum(nnz, 1.0), 1.0)
        shared = None
        a = gram
    if _SOLVER == "cg":
        # implicit grams are dominated by the shared YᵗY with only λ (not
        # λ·nnz) on the diagonal — worse conditioned, so double the budget
        sol = _cg_solve_spd(a, rhs, cg_iters * (2 if implicit else 1),
                            matvec_dtype=cg_matvec_dtype, lam=lam,
                            shared=shared, x0=x0, tol=cg_tol)
    else:
        a = a.astype(jnp.float32) + lam[:, None, None] * eye
        if shared is not None:
            a = a + shared[None]
        chol = jax.scipy.linalg.cho_factor(a)
        sol = jax.scipy.linalg.cho_solve(chol, rhs[..., None])[..., 0]
    return jnp.where(nnz[:, None] > 0, sol, 0.0)


@functools.partial(
    jax.jit,
    static_argnames=("reg_nnz", "compute_dtype", "precision", "cg_iters",
                     "cg_tol"),
)
def _solve_bucket(
    other_factors: jax.Array,  # [M, K] f32
    cols: jax.Array,           # [B, D] int32
    vals: jax.Array,           # [B, D] f32
    mask: jax.Array,           # [B, D] f32 in {0, 1}
    l2: float,
    reg_nnz: bool = True,
    compute_dtype: Any = jnp.float32,
    precision: Any = jax.lax.Precision.HIGHEST,
    cg_iters: int = _CG_ITERS,
    x0: Optional[jax.Array] = None,
    cg_tol: float = 0.0,
) -> jax.Array:
    """Batched normal-equation solve for one degree bucket → [B, K].

    Precision note: DEFAULT matmul precision truncates f32 einsum inputs to
    bf16 passes, which stalls ALS convergence (the Gram matrices pick up
    ~1e-2 error and the alternation stops improving around RMSE 0.6 on data
    it should fit to <0.1). The Gram/rhs assembly therefore defaults to
    HIGHEST (multi-pass f32 on the MXU); ``compute_dtype=bfloat16`` with
    DEFAULT precision remains available as the fast low-precision mode for
    early sweeps.
    """
    # the bf16 bucket path emits the Gram batch directly in bf16 (CG takes
    # it as-is, with the ridge applied in f32 — see _cg_solve_spd); the
    # cholesky solver needs the f32 matrix to factor
    gram_dtype = compute_dtype if _SOLVER == "cg" else jnp.float32
    gram, rhs, nnz = _gram_rhs_nnz(
        other_factors, cols, vals, mask, compute_dtype, precision,
        implicit=False, alpha=0.0, gram_dtype=gram_dtype)
    return _reg_solve(gram, rhs, nnz, l2, reg_nnz, implicit=False, yty=None,
                      cg_iters=cg_iters, cg_matvec_dtype=compute_dtype,
                      x0=x0, cg_tol=cg_tol)


def _solve_bucket_kernel(
    gsrc: jax.Array,           # [M, K] gather source, ALREADY compute-dtype
    cols: jax.Array,
    vals: jax.Array,
    mask: jax.Array,
    l2: float,
    reg_nnz: bool,
    cg_iters: int,
    kernel_rows: int = 1,
    x0: Optional[jax.Array] = None,
) -> jax.Array:
    """Explicit-CG bucket solve via the fused Pallas kernel.

    Same contract as :func:`_solve_bucket` (CG leg): λ(+λ·nnz) ridge,
    empty rows → 0. The [B, K, K] Gram batch lives only in VMEM — see
    ops/pallas_kernels.als_solve_cg_pallas. (Interpret-mode selection
    happens inside the kernel wrapper: no Mosaic backend → interpret,
    which is how PIO_ALS_KERNEL=on works on the CPU test mesh.)
    ``kernel_rows`` selects the one-row or row-grouped kernel layout
    (resolved by the caller via :func:`_kernel_rows_default`)."""
    from incubator_predictionio_tpu.ops.pallas_kernels import (
        als_solve_cg_pallas,
    )

    return als_solve_cg_pallas(
        gsrc, cols, vals, mask, l2, reg_nnz=reg_nnz, iters=cg_iters,
        rows_per_program=max(kernel_rows, 1), x0=x0)


def _solve_bucket_fused(
    gsrc: jax.Array,           # [M, K] gather source, ALREADY compute-dtype
    yty: Optional[jax.Array],  # [K, K] shared implicit term, or None
    cols: jax.Array,
    vals: jax.Array,
    mask: jax.Array,
    l2: float,
    reg_nnz: bool,
    cg_iters: int,
    implicit: bool = False,
    alpha: float = 0.0,
    x0: Optional[jax.Array] = None,
) -> jax.Array:
    """Bucket solve via the fused gather+Gram+CG Pallas kernel — the
    table-resident generation of :func:`_solve_bucket_kernel`: the
    [B, D, K] gather never materializes in HBM either. Covers BOTH
    feedback modes (implicit rides the precomputed YᵗY as one shared
    operand); callers gate on ``als_fused_fits`` for the table shape
    and pass the implicit path's doubled CG budget themselves."""
    from incubator_predictionio_tpu.ops.pallas_kernels import (
        als_fused_solve_cg_pallas,
    )

    return als_fused_solve_cg_pallas(
        gsrc, cols, vals, mask, l2, reg_nnz=reg_nnz, iters=cg_iters,
        implicit=implicit, alpha=alpha, yty=yty, x0=x0)


#: f32-element budget for one bucket chunk's gather intermediate
#: ([chunk, D, K]); 2^24 elements = 64 MB. Buckets whose full gather would
#: exceed this are solved in row chunks under lax.map, keeping peak HBM for
#: the normal-equation assembly flat regardless of dataset size (the
#: ML-20M-scale requirement: 20M nnz × rank 128 would otherwise gather
#: multi-GB [B, D, K] tensors per bucket). Tunable: bigger chunks = fewer
#: sequential lax.map steps at more peak HBM.
_CHUNK_ELEMS = int(os.environ.get("PIO_ALS_CHUNK_ELEMS", str(1 << 24)))


def _solve_bucket_chunked(solver_fn, cols, vals, mask, rank: int,
                          row_elems: Optional[int] = None,
                          x0: Optional[jax.Array] = None):
    """Apply ``solver_fn((cols, vals, mask[, x0])) -> sol`` in bounded row
    chunks.

    Zero-mask padding rows solve to 0 and are sliced off, so chunk padding
    never leaks into the scatter. ``row_elems`` overrides the per-row
    gather footprint used for chunk sizing (the Pallas path pads D and K
    to lane multiples, so its materialized gather is larger than D·rank
    for narrow buckets). ``x0`` rides along row-aligned when present
    (CG warm start)."""
    B, D = cols.shape
    rank_x = x0.shape[1] if x0 is not None else rank
    chunk = max(8, _CHUNK_ELEMS // max(row_elems or (D * rank), 1))
    if B <= chunk:
        t = (cols, vals, mask) + ((x0,) if x0 is not None else ())
        return solver_fn(t)
    n = -(-B // chunk)
    pad = n * chunk - B
    if pad:
        cols = jnp.pad(cols, ((0, pad), (0, 0)))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
        if x0 is not None:
            x0 = jnp.pad(x0, ((0, pad), (0, 0)))
    parts = (cols.reshape(n, chunk, D), vals.reshape(n, chunk, D),
             mask.reshape(n, chunk, D))
    if x0 is not None:
        parts = parts + (x0.reshape(n, chunk, rank_x),)
    sols = jax.lax.map(solver_fn, parts)
    return sols.reshape(n * chunk, rank)[:B]


def _gram_rhs_nnz_chunked(other_factors, cols, vals, mask, compute_dtype,
                          precision, implicit, alpha):
    """Apply :func:`_gram_rhs_nnz` in bounded row chunks (lax.map).

    The heavy-segment path's equivalent of :func:`_solve_bucket_chunked`:
    split segments are max_width wide, so even a few hundred of them would
    gather a multi-GB [S, D, K] tensor at once. Chunk padding rows carry
    zero masks → zero partials, sliced off before the segment sum."""
    S, D = cols.shape
    rank = other_factors.shape[1]
    chunk = max(1, _CHUNK_ELEMS // max(D * rank, 1))
    if S <= chunk:
        return _gram_rhs_nnz(other_factors, cols, vals, mask, compute_dtype,
                             precision, implicit, alpha)
    n = -(-S // chunk)
    pad = n * chunk - S
    if pad:
        cols = jnp.pad(cols, ((0, pad), (0, 0)))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
    pg, prhs, pnnz = jax.lax.map(
        lambda t: _gram_rhs_nnz(other_factors, t[0], t[1], t[2],
                                compute_dtype, precision, implicit, alpha),
        (cols.reshape(n, chunk, D), vals.reshape(n, chunk, D),
         mask.reshape(n, chunk, D)),
    )
    return (pg.reshape(n * chunk, rank, rank)[:S],
            prhs.reshape(n * chunk, rank)[:S],
            pnnz.reshape(n * chunk)[:S])


def _gather_x0(prev_factors: jax.Array, row_ids: jax.Array) -> jax.Array:
    """Warm-start factors for a padded row batch → [rows, K] f32.

    Padding rows carry row_id -1, and a bare ``prev_factors[row_ids]``
    wraps numpy-style to the LAST row — padding rows would warm-start
    from a real row's factors. Their solutions are dropped at scatter
    (``_scatter_rows_impl``), but the wraparound still feeds garbage
    into the padded CG lanes, so clamp the gather and zero the padding
    rows (a zero start is the exact cold-start fixed point)."""
    safe = prev_factors[jnp.maximum(row_ids, 0)].astype(jnp.float32)
    return jnp.where(row_ids[:, None] >= 0, safe, 0.0)


def _scatter_rows_impl(out: jax.Array, row_ids: jax.Array,
                       sol: jax.Array) -> jax.Array:
    # Padding rows carry row_id -1. JAX scatter wraps negative indices
    # numpy-style (-1 = last row!), so remap them to n (out of bounds) where
    # mode="drop" genuinely drops them.
    safe_ids = jnp.where(row_ids < 0, out.shape[0], row_ids)
    return out.at[safe_ids].set(sol, mode="drop")


@functools.partial(jax.jit, donate_argnames=("out",),
                   static_argnames=())
def _scatter_rows(out: jax.Array, row_ids: jax.Array, sol: jax.Array) -> jax.Array:
    return _scatter_rows_impl(out, row_ids, sol)


def _sweep_side(
    n_rows: int,
    other_factors: jax.Array,
    tree,                      # ((row_ids, cols, vals, mask), ...)
    heavy,                     # (seg_ids, row_ids, cols, vals, mask) | None
    l2: float,
    alpha: float,
    reg_nnz: bool,
    compute_dtype: Any,
    precision: Any,
    implicit: bool,
    cg_iters: int = _CG_ITERS,
    use_kernel: bool = False,
    kernel_min_d: int = 0,
    kernel_rows: int = 1,
    prev_factors: Optional[jax.Array] = None,
    use_fused: bool = False,
    cg_tol: float = 0.0,
) -> jax.Array:
    """One half-sweep (traced): solve every bucket + split rows, scatter.

    THE single sweep implementation — the fused trainer, als_sweep and
    als_sweep_implicit all trace through here, so the paths cannot
    diverge. ``use_kernel``, ``kernel_min_d`` and ``use_fused``
    (resolved by the caller, outside the trace, and part of every jit
    cache key — a mid-trace global read would silently survive a
    runtime override) route CG buckets of width ≥ min-D through the
    Pallas solves: ``use_fused`` selects the gather+Gram+CG generation
    (the caller has already checked the gather table fits the VMEM
    budget for THIS side — see ``_fused_sides``), otherwise the
    two-stage Gram+CG kernel serves explicit buckets. Narrower buckets
    and the heavy split-row path always use the XLA assembly; implicit
    buckets are kernel-eligible only in the fused generation (the
    shared-YᵗY operand)."""
    rank = other_factors.shape[1]
    out = jnp.zeros((n_rows, rank), jnp.float32)
    yty = _gram_all(other_factors, precision) if implicit else None
    # Hoist the compute-dtype cast of the gather source to once per
    # half-sweep — inside the chunked lax.map it would re-cast the whole
    # table per chunk (~150 chunks/half-sweep at ML-20M), swamping the
    # bf16 traffic saving it exists to provide. Implicit mode stays f32.
    gsrc = other_factors
    if not implicit and other_factors.dtype != compute_dtype:
        gsrc = other_factors.astype(compute_dtype)
    if use_fused and use_kernel:
        # the fused kernel's table block needs a sublane-aligned row
        # count; pad ONCE per half-sweep (padding rows are never
        # gathered — every col id < M — so the XLA buckets and the
        # heavy path can share the padded source unchanged)
        mp = -(-gsrc.shape[0] // 8) * 8
        if mp != gsrc.shape[0]:
            gsrc = jnp.pad(gsrc, ((0, mp - gsrc.shape[0]), (0, 0)))
    for row_ids, cols, vals, mask in tree:
        row_elems = None
        x0 = (_gather_x0(prev_factors, row_ids)
              if prev_factors is not None else None)
        if use_kernel and use_fused and cols.shape[1] >= kernel_min_d:
            from incubator_predictionio_tpu.ops.pallas_kernels import (
                als_fused_row_elems,
            )

            row_elems = als_fused_row_elems(cols.shape[1], rank)

            def solver(t, _yty=yty):
                return _solve_bucket_fused(
                    gsrc, _yty, t[0], t[1], t[2], l2, reg_nnz=reg_nnz,
                    cg_iters=cg_iters * (2 if implicit else 1),
                    implicit=implicit, alpha=alpha,
                    x0=t[3] if len(t) > 3 else None)
        elif implicit:
            def solver(t, _yty=yty):
                return _solve_bucket_implicit(
                    other_factors, _yty, t[0], t[1], t[2], l2, alpha,
                    precision=precision, cg_iters=cg_iters,
                    x0=t[3] if len(t) > 3 else None, cg_tol=cg_tol)
        elif use_kernel and cols.shape[1] >= kernel_min_d:
            # chunk by the PADDED gather footprint the kernel actually
            # materializes (single source of truth in pallas_kernels)
            from incubator_predictionio_tpu.ops.pallas_kernels import (
                als_padded_row_elems,
            )

            row_elems = als_padded_row_elems(cols.shape[1], rank)

            def solver(t):
                return _solve_bucket_kernel(
                    gsrc, t[0], t[1], t[2], l2, reg_nnz=reg_nnz,
                    cg_iters=cg_iters, kernel_rows=kernel_rows,
                    x0=t[3] if len(t) > 3 else None)
        else:
            def solver(t):
                return _solve_bucket(
                    gsrc, t[0], t[1], t[2], l2, reg_nnz=reg_nnz,
                    compute_dtype=compute_dtype, precision=precision,
                    cg_iters=cg_iters, x0=t[3] if len(t) > 3 else None,
                    cg_tol=cg_tol)
        # large buckets solve in bounded row chunks (lax.map) so the
        # [B, D, K] gather / [B, K, K] gram temps never exceed the chunk
        # budget — the ML-20M-scale HBM requirement
        sol = _solve_bucket_chunked(solver, cols, vals, mask, rank,
                                    row_elems=row_elems, x0=x0)
        out = _scatter_rows_impl(out, row_ids, sol)
    if heavy is not None:
        h_ids, h_sol = _solve_heavy(
            gsrc, heavy, l2, alpha, reg_nnz, compute_dtype,
            precision, implicit, yty, cg_iters=cg_iters,
            prev_factors=prev_factors, cg_tol=cg_tol)
        out = _scatter_rows_impl(out, h_ids, h_sol)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "reg_nnz", "compute_dtype", "precision",
                     "implicit", "cg_iters", "use_kernel", "kernel_min_d",
                     "kernel_rows", "use_fused", "cg_tol"),
)
def _sweep_side_jit(n_rows, other_factors, tree, heavy, l2, alpha, reg_nnz,
                    compute_dtype, precision, implicit,
                    cg_iters=_CG_ITERS, use_kernel=False, kernel_min_d=0,
                    kernel_rows=1, prev_factors=None, use_fused=False,
                    cg_tol=0.0):
    return _sweep_side(n_rows, other_factors, tree, heavy, l2, alpha,
                       reg_nnz, compute_dtype, precision, implicit,
                       cg_iters=cg_iters, use_kernel=use_kernel,
                       kernel_min_d=kernel_min_d, kernel_rows=kernel_rows,
                       prev_factors=prev_factors, use_fused=use_fused,
                       cg_tol=cg_tol)


def _update_side(
    n_rows: int,
    other_factors: jax.Array,
    buckets: Sequence[PaddedRows],
    l2: float,
    reg_nnz: bool,
    compute_dtype: Any,
    precision: Any,
) -> jax.Array:
    use_kernel = _kernel_enabled(False, warm=False)
    return _sweep_side_jit(
        n_rows, other_factors, _buckets_tree(buckets), None, l2, 0.0,
        reg_nnz, compute_dtype, precision, implicit=False,
        # this path never passes prev_factors: the cold variant
        use_kernel=use_kernel,
        kernel_min_d=_KERNEL_MIN_D,
        kernel_rows=_kernel_rows_default(),
        use_fused=_fused_one(use_kernel, False, False,
                             other_factors.shape[0],
                             other_factors.shape[1], compute_dtype),
        cg_tol=_cg_tol_env())


def assert_no_split(buckets: Sequence[PaddedRows], side: str = "row") -> None:
    """Raise if any row was split across padded rows (degree > max_width).

    The scatter-set in the sweep keeps one arbitrary segment's solution for
    a duplicated row id, which would be silently wrong. The ``als_sweep``
    API therefore rejects split rows; ``als_train``/``als_train_implicit``
    route them through the partial-Gram combining solve instead
    (``split_heavy`` + ``_solve_heavy``)."""
    ids = np.concatenate(
        [np.asarray(b.row_ids)[np.asarray(b.row_ids) >= 0] for b in buckets]
    ) if buckets else np.empty(0, np.int32)
    if len(ids) != len(np.unique(ids)):
        raise NotImplementedError(
            f"a {side} exceeds the bucket max_width (its interactions were "
            "split across solve rows); raise max_width or wait for the "
            "sharded-split solver"
        )


def als_sweep(
    state: ALSState,
    user_buckets: Sequence[PaddedRows],
    item_buckets: Sequence[PaddedRows],
    l2: float = 0.1,
    reg_nnz: bool = True,
    compute_dtype: Any = jnp.float32,
    precision: Any = jax.lax.Precision.HIGHEST,
    validate: bool = True,
) -> ALSState:
    """One full ALS iteration: solve users against items, then items against
    the *new* user factors (the classic alternation order).

    ``validate`` checks the buckets contain no split rows (see
    :func:`assert_no_split`); pass False when the caller has already
    validated (als_train does, once, outside the sweep loop)."""
    if validate:
        assert_no_split(user_buckets, "user")
        assert_no_split(item_buckets, "item")
    new_users = _update_side(
        state.user_factors.shape[0], state.item_factors, user_buckets,
        l2, reg_nnz, compute_dtype, precision,
    )
    new_items = _update_side(
        state.item_factors.shape[0], new_users, item_buckets,
        l2, reg_nnz, compute_dtype, precision,
    )
    return ALSState(user_factors=new_users, item_factors=new_items)


# ---------------------------------------------------------------------------
# Implicit-feedback ALS (Hu-Koren-Volinsky), the MLlib ALS.trainImplicit
# replacement used by the similarproduct/ecommerce templates
# (examples/scala-parallel-similarproduct/multi/src/main/scala/
# ALSAlgorithm.scala:147).
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("precision", "cg_iters", "cg_tol")
)
def _solve_bucket_implicit(
    other_factors: jax.Array,  # [M, K]
    yty: jax.Array,            # [K, K] — Gram of ALL other-side factors
    cols: jax.Array,           # [B, D]
    vals: jax.Array,           # [B, D] raw confidence weights r
    mask: jax.Array,           # [B, D]
    l2: float,
    alpha: float,
    precision: Any = jax.lax.Precision.HIGHEST,
    cg_iters: int = _CG_ITERS,
    x0: Optional[jax.Array] = None,
    cg_tol: float = 0.0,
) -> jax.Array:
    """Per-row system: (YᵗY + Yᵤᵗ(Cᵤ−I)Yᵤ + λI) x = Yᵤᵗ cᵤ with
    c = 1 + α·r and binary preference — YᵗY is shared across the whole
    batch (the classic implicit-ALS trick), so per-row work stays
    proportional to the row's observations. The implicit CG runs a
    DOUBLED budget (worse conditioning, see _reg_solve), so a closer
    starting point helps it most; the budget itself is unchanged until
    an implicit-specific convergence study justifies cutting it."""
    gram, rhs, nnz = _gram_rhs_nnz(
        other_factors, cols, vals, mask, jnp.float32, precision,
        implicit=True, alpha=alpha)
    return _reg_solve(gram, rhs, nnz, l2, True, implicit=True, yty=yty,
                      cg_iters=cg_iters, x0=x0, cg_tol=cg_tol)


@functools.partial(jax.jit, static_argnames=("precision",))
def _gram_all(factors: jax.Array, precision: Any) -> jax.Array:
    return jnp.einsum(
        "ik,il->kl", factors, factors,
        preferred_element_type=jnp.float32, precision=precision,
    )


def _update_side_implicit(
    n_rows: int,
    other_factors: jax.Array,
    buckets: Sequence[PaddedRows],
    l2: float,
    alpha: float,
    precision: Any,
) -> jax.Array:
    use_kernel = _kernel_enabled(True, warm=False)
    return _sweep_side_jit(
        n_rows, other_factors, _buckets_tree(buckets), None, l2, alpha,
        True, jnp.float32, precision, implicit=True,
        use_kernel=use_kernel, kernel_min_d=_KERNEL_MIN_D,
        use_fused=_fused_one(use_kernel, True, False,
                             other_factors.shape[0],
                             other_factors.shape[1], jnp.float32),
        cg_tol=_cg_tol_env())


def als_sweep_implicit(
    state: ALSState,
    user_buckets: Sequence[PaddedRows],
    item_buckets: Sequence[PaddedRows],
    l2: float = 0.1,
    alpha: float = 1.0,
    precision: Any = jax.lax.Precision.HIGHEST,
    validate: bool = True,
) -> ALSState:
    if validate:
        assert_no_split(user_buckets, "user")
        assert_no_split(item_buckets, "item")
    new_users = _update_side_implicit(
        state.user_factors.shape[0], state.item_factors, user_buckets,
        l2, alpha, precision,
    )
    new_items = _update_side_implicit(
        state.item_factors.shape[0], new_users, item_buckets,
        l2, alpha, precision,
    )
    return ALSState(user_factors=new_users, item_factors=new_items)


def als_train_implicit(
    users: np.ndarray,
    items: np.ndarray,
    weights: np.ndarray,
    n_users: int,
    n_items: int,
    rank: int = 64,
    iterations: int = 10,
    l2: float = 0.1,
    alpha: float = 1.0,
    seed: int = 0,
    precision: Any = jax.lax.Precision.HIGHEST,
    max_width: int = 1 << 16,
) -> ALSState:
    """Implicit-feedback training over (user, item, weight) observations."""
    (user_light, user_heavy), (item_light, item_heavy) = build_both_sides(
        users, items, weights, n_users, n_items, max_width=max_width)
    state = als_init(jax.random.key(seed), n_users, n_items, rank)
    # resolve the kernel/fused selectors HERE, outside the trace (they
    # are static jit arguments) — implicit is kernel-eligible only in
    # the fused-gather generation (shared YᵗY operand)
    warm = _CG_WARMSTART
    use_kernel = _kernel_enabled(True, warm=warm)
    out = _als_run_fused(
        state, _buckets_tree(user_light), _buckets_tree(item_light),
        l2, alpha, iterations, True, jnp.float32, precision, implicit=True,
        user_heavy=_heavy_tree(user_heavy), item_heavy=_heavy_tree(item_heavy),
        warmstart=warm, use_kernel=use_kernel, kernel_min_d=_KERNEL_MIN_D,
        use_fused=(_fused_sides(n_users, n_items, True, warm,
                                jnp.float32, rank)
                   if use_kernel else (False, False)),
        cg_tol=_cg_tol_env(),
    )
    from incubator_predictionio_tpu.ops.retrain import _book_sweeps

    _book_sweeps("fresh", iterations)
    return out


# ---------------------------------------------------------------------------
# Mesh-sharded (placed) training — the full ALX layout (PAPERS.md: ALX §4).
#
# A FactorPlacement (parallel/placement.py) shards BOTH factor tables on
# rows over the flattened mesh; interaction buckets are shard-blocked so
# each device solves exactly the rows it owns; the other side's factors
# move by explicit collectives inside shard_map (parallel/collectives.py):
# an all-gather for tables narrow enough to replicate transiently, a
# ppermute ring over table SLICES for wide ones — each device only ever
# holds one slice of the wide table, which is what re-enables the fused
# Gram+solve kernel's VMEM residency at big-table shapes. Updates are
# shard-local by construction (each device scatters only its own rows:
# the cross-replica weight-update-sharding pattern, arxiv 2004.13336).
# The whole multi-sweep run is ONE dispatch; nothing crosses to the host.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _ShardCfg:
    """Hashable static config of one placed run (jit cache key)."""

    u_mode: str                 # gather strategy of the USER half-sweep
    i_mode: str                 # ... and the item half-sweep
    implicit: bool
    reg_nnz: bool
    l2: float
    alpha: float
    compute_dtype: Any
    precision: Any
    cg_iters: int
    cg_tol: float
    use_kernel: bool
    kernel_min_d: int
    kernel_rows: int
    warmstart: bool
    fused_u: bool
    fused_i: bool


def _allgather_cap_bytes(placement) -> float:
    """Widest table the auto gather strategy will transiently all-gather
    per half-sweep: `PIO_SHARD_ALLGATHER_MB` when set, else 1/16 of one
    device's memory as the backend reports it (≈1 GB on a 16 GB v5e
    chip), else — a backend that reports none, the CPU test mesh — 64
    MB."""
    raw = os.environ.get("PIO_SHARD_ALLGATHER_MB", "").strip()
    if raw:
        try:
            return float(raw) * (1 << 20)
        except ValueError:
            pass
    stats = placement.mesh.devices.flat[0].memory_stats()
    limit = (stats or {}).get("bytes_limit")
    return limit / 16.0 if limit else 64.0 * (1 << 20)


def _shard_gather_modes(placement, rank: int, dtype: Any,
                        implicit: bool) -> Tuple[str, str]:
    """Per-half-sweep gather strategy → (user_sweep, item_sweep).

    `PIO_SHARD_GATHER` = allgather | ring | auto (default). Auto keeps
    the transient full-table all-gather while the gathered table stays
    under :func:`_allgather_cap_bytes`, and switches to the
    slice-resident ring only above it — the catalogue scale where a
    device cannot hold the whole other table. The ring's layout splits
    every row whose interactions span slices into padded per-slice
    segments, so on data without locality (ML-20M shape on four chips:
    every row spans every slice) it pads ~100× and its partial-Gram
    gather alone is a 34 GB allocation the chip's compiler refuses —
    while the all-gathered 134 MB user table is nothing to a 16 GB chip.

    When — and only when — the fused-gather kernel is enabled
    (`PIO_ALS_FUSED_GRAM=on`, the CPU interpret-test hook: it does not
    lower on the installed TPU compiler, see :func:`_fused_enabled`),
    auto also picks the ring where the full table would not fit that
    kernel's VMEM table budget but one slice does. The decision is per
    gather SOURCE (user sweep gathers the item table and vice versa),
    resolved here outside any trace."""
    mode = os.environ.get("PIO_SHARD_GATHER", "auto")
    if mode in ("allgather", "ring"):
        return mode, mode
    cap = _allgather_cap_bytes(placement)
    item = jnp.dtype(jnp.float32 if implicit else dtype).itemsize
    n = placement.n_shards
    fused = _fused_enabled(implicit, _CG_WARMSTART)

    def one(table_rows: int) -> str:
        from incubator_predictionio_tpu.ops.pallas_kernels import (
            als_fused_fits,
        )

        dt = jnp.float32 if implicit else dtype
        if table_rows * rank * item > cap:
            return "ring"
        if (fused and n > 1 and not als_fused_fits(table_rows, rank, dt)
                and als_fused_fits(-(-table_rows // n), rank, dt)):
            return "ring"
        return "allgather"

    return one(placement.n_items_padded), one(placement.n_users_padded)


def gather_source_rows(placement, side_gathered: str, mode: str) -> int:
    """Rows of the array a half-sweep's gather hands the solve — the
    FULL padded table under allgather, ONE slice under ring. This is
    the shape the fused kernel pins in VMEM, and the ONE rule shared by
    :func:`_fused_sides_placed` and the tests' fit arithmetic (a second
    copy of this math could silently drift from what the trainer
    actually routes)."""
    full = (placement.n_users_padded if side_gathered == "user"
            else placement.n_items_padded)
    return (placement.shard_rows(side_gathered) if mode == "ring"
            else full)


def _fused_sides_placed(placement, modes: Tuple[str, str], implicit: bool,
                        warm: bool, dtype: Any,
                        rank: int) -> Tuple[bool, bool]:
    """Sharded twin of :func:`_fused_sides`: the fused kernel pins the
    gather source in VMEM, and under a placement that source is either
    the transiently gathered FULL table (allgather mode) or one SLICE of
    it (ring mode) — so `als_fused_fits` is checked against the
    shard-local shape the kernel will actually pin (see
    :func:`gather_source_rows`). Sharding is the MFU unlock: a table
    over budget on one chip routes fused again once its slice fits."""
    use_kernel = _kernel_enabled(implicit, warm=warm)
    if not use_kernel:
        return False, False
    dt = jnp.float32 if implicit else dtype
    return (
        _fused_one(True, implicit, warm,
                   gather_source_rows(placement, "item", modes[0]),
                   rank, dt),
        _fused_one(True, implicit, warm,
                   gather_source_rows(placement, "user", modes[1]),
                   rank, dt),
    )


def build_placed_sides(
    users: np.ndarray,
    items: np.ndarray,
    vals: np.ndarray,
    placement,
    modes: Tuple[str, str],
    max_width: int = 1 << 16,
    ring_layouts: Tuple[Any, Any] = (None, None),
    ring_host_out: Optional[dict] = None,
):
    """Host-side prep of both orientations in their placed layouts →
    (u_data, i_data), every leaf device-put sharded on axis 0.

    allgather sides are shard-blocked single-chip buckets (cols global,
    row ids localized per device; heavy split rows partitioned to their
    owner so the partial-Gram reduction stays shard-local); ring sides
    are the per-step pure/mixed layout of
    :func:`~...parallel.sharding.build_ring_side`.

    ``ring_layouts`` lets the ring-plan cache (ops/retrain.py
    ``_ring_sides_with_reuse``) hand in an already-merged HOST
    (pure, mixed) layout per side — the side then skips the full-COO
    build and only pays the device put. ``ring_host_out`` (a dict)
    receives each ring side's host layout under its side name, so the
    cache can adopt what was built without a second construction."""
    from incubator_predictionio_tpu.parallel.sharding import (
        build_ring_side,
        localize_tree,
        shard_block_buckets,
        shard_block_heavy,
    )

    n = placement.n_shards
    sharding = placement.table_sharding()

    def put(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a), sharding), tree)

    def one_side(side, rows, cols, other_side, mode, prebuilt):
        sr_self = placement.shard_rows(side)
        sr_other = placement.shard_rows(other_side)
        if mode == "ring":
            if prebuilt is not None:
                pure, mixed = prebuilt
            else:
                pure, mixed = build_ring_side(
                    rows, cols, vals, n, sr_self, sr_other,
                    max_width=max_width)
            if ring_host_out is not None:
                ring_host_out[side] = (pure, mixed)
            return put((pure, mixed))
        light, heavy = split_heavy(build_padded_rows(
            rows, cols, vals, sr_self * n, max_width=max_width))
        tree = localize_tree(
            shard_block_buckets(light, n, sr_self), n, sr_self)
        return put((tree, shard_block_heavy(heavy, n, sr_self)))

    return (one_side("user", users, items, "item", modes[0],
                     ring_layouts[0]),
            one_side("item", items, users, "user", modes[1],
                     ring_layouts[1]))


def _ring_sweep_side(
    shard_rows_self: int,
    other_local: jax.Array,     # [rows_other/n, K] — this device's slice
    data,                       # (pure, mixed) local views
    cfg: _ShardCfg,
    placement,
    prev_local: Optional[jax.Array],
    fused: bool,
) -> jax.Array:
    """One placed half-sweep in ring mode (traced, inside shard_map).

    The other table's slices rotate around the mesh ring (``ppermute``,
    n−1 hops); at each step this device solves the PURE rows whose cols
    all live in the currently held slice — complete systems, so the
    fused gather+Gram+CG kernel applies with only the slice resident —
    and accumulates partial Gram/RHS for MIXED rows (cols spanning
    slices), which solve once after the ring via the same
    partial-Gram-combining path as split rows (`_reg_solve` over the
    segment sums). Peak residency is exactly two slices (current +
    in-flight), never the full table."""
    from incubator_predictionio_tpu.parallel.collectives import (
        all_reduce_sum,
        ppermute_next,
    )

    axes = placement.axes
    n = placement.n_shards
    pure, mixed = data
    rank = other_local.shape[1]
    out = jnp.zeros((shard_rows_self, rank), jnp.float32)
    implicit = cfg.implicit
    yty = (all_reduce_sum(_gram_all(other_local, cfg.precision), axes)
           if implicit else None)
    gsrc = other_local
    if not implicit and other_local.dtype != cfg.compute_dtype:
        gsrc = other_local.astype(cfg.compute_dtype)
    if fused and cfg.use_kernel:
        mp8 = -(-gsrc.shape[0] // 8) * 8
        if mp8 != gsrc.shape[0]:
            gsrc = jnp.pad(gsrc, ((0, mp8 - gsrc.shape[0]), (0, 0)))
    h = mixed[0].shape[0] if mixed is not None else 0
    mg = jnp.zeros((h + 1, rank, rank), jnp.float32)
    mr = jnp.zeros((h + 1, rank), jnp.float32)
    mn = jnp.zeros(h + 1, jnp.float32)
    cur = gsrc
    for s in range(n):
        for rid_a, col_a, val_a, msk_a in pure:
            rid, c, v, m = rid_a[s], col_a[s], val_a[s], msk_a[s]
            x0 = (_gather_x0(prev_local, rid)
                  if prev_local is not None else None)
            # same solver dispatch as _sweep_side, and the same
            # _solve_bucket_chunked streaming: ring mode exists for the
            # catalog scale where a one-shot [B, D, K] gather temp would
            # OOM, so pure buckets must keep the bounded-chunk guarantee
            row_elems = None
            if cfg.use_kernel and fused and c.shape[1] >= cfg.kernel_min_d:
                from incubator_predictionio_tpu.ops.pallas_kernels import (
                    als_fused_row_elems,
                )

                row_elems = als_fused_row_elems(c.shape[1], rank)

                def solver(t, _cur=cur, _yty=yty):
                    return _solve_bucket_fused(
                        _cur, _yty, t[0], t[1], t[2], cfg.l2,
                        reg_nnz=cfg.reg_nnz,
                        cg_iters=cfg.cg_iters * (2 if implicit else 1),
                        implicit=implicit, alpha=cfg.alpha,
                        x0=t[3] if len(t) > 3 else None)
            elif implicit:
                def solver(t, _cur=cur, _yty=yty):
                    return _solve_bucket_implicit(
                        _cur, _yty, t[0], t[1], t[2], cfg.l2, cfg.alpha,
                        precision=cfg.precision, cg_iters=cfg.cg_iters,
                        x0=t[3] if len(t) > 3 else None,
                        cg_tol=cfg.cg_tol)
            elif cfg.use_kernel and c.shape[1] >= cfg.kernel_min_d:
                from incubator_predictionio_tpu.ops.pallas_kernels import (
                    als_padded_row_elems,
                )

                row_elems = als_padded_row_elems(c.shape[1], rank)

                def solver(t, _cur=cur):
                    return _solve_bucket_kernel(
                        _cur, t[0], t[1], t[2], cfg.l2,
                        reg_nnz=cfg.reg_nnz, cg_iters=cfg.cg_iters,
                        kernel_rows=cfg.kernel_rows,
                        x0=t[3] if len(t) > 3 else None)
            else:
                def solver(t, _cur=cur):
                    return _solve_bucket(
                        _cur, t[0], t[1], t[2], cfg.l2,
                        reg_nnz=cfg.reg_nnz,
                        compute_dtype=cfg.compute_dtype,
                        precision=cfg.precision, cg_iters=cfg.cg_iters,
                        x0=t[3] if len(t) > 3 else None,
                        cg_tol=cfg.cg_tol)
            sol = _solve_bucket_chunked(solver, c, v, m, rank,
                                        row_elems=row_elems, x0=x0)
            out = _scatter_rows_impl(out, rid, sol)
        if mixed is not None:
            _rid_m, sid_a, mc_a, mv_a, mm_a = mixed
            pg, pr, pn = _gram_rhs_nnz(
                cur, mc_a[s], mv_a[s], mm_a[s], cfg.compute_dtype,
                cfg.precision, implicit, cfg.alpha)
            sid = sid_a[s]
            mg = mg + jax.ops.segment_sum(pg, sid, num_segments=h + 1)
            mr = mr + jax.ops.segment_sum(pr, sid, num_segments=h + 1)
            mn = mn + jax.ops.segment_sum(pn, sid, num_segments=h + 1)
        if s < n - 1:
            cur = ppermute_next(cur, axes)
    if mixed is not None:
        rid_m = mixed[0]
        x0 = (_gather_x0(prev_local, rid_m)
              if prev_local is not None else None)
        sol = _reg_solve(
            mg[:h], mr[:h], mn[:h], cfg.l2, cfg.reg_nnz, implicit, yty,
            cg_iters=cfg.cg_iters,
            cg_matvec_dtype=(jnp.float32 if implicit
                             else cfg.compute_dtype),
            x0=x0, cg_tol=cfg.cg_tol)
        out = _scatter_rows_impl(out, rid_m, sol)
    return out


def _placed_half_sweep(side: str, other_local: jax.Array, data,
                       cfg: _ShardCfg, placement,
                       prev_local: Optional[jax.Array]) -> jax.Array:
    """One half-sweep of the placed program (traced, inside shard_map):
    solve the rows THIS device owns on ``side`` against the other
    side's factors, moved by the side's gather strategy."""
    from incubator_predictionio_tpu.parallel.collectives import all_gather

    mode = cfg.u_mode if side == "user" else cfg.i_mode
    fused = cfg.fused_u if side == "user" else cfg.fused_i
    rows_local = placement.shard_rows(side)
    if mode == "ring":
        return _ring_sweep_side(rows_local, other_local, data, cfg,
                                placement, prev_local, fused)
    others = all_gather(other_local, placement.axes, axis=0, tiled=True)
    tree, heavy = data
    return _sweep_side(
        rows_local, others, tree, heavy, cfg.l2, cfg.alpha, cfg.reg_nnz,
        cfg.compute_dtype, cfg.precision, cfg.implicit,
        cg_iters=cfg.cg_iters, use_kernel=cfg.use_kernel,
        kernel_min_d=cfg.kernel_min_d, kernel_rows=cfg.kernel_rows,
        prev_factors=prev_local, use_fused=fused, cg_tol=cfg.cg_tol)


def _squeeze_ring(data, mode: str):
    """Drop the sharded leading axis of a ring side's local views (the
    allgather layout is flat — each device already sees its block)."""
    if mode != "ring" or data is None:
        return data
    return jax.tree_util.tree_map(lambda a: a[0], data)


def _placed_specs(placement, u_data, i_data):
    from jax.sharding import PartitionSpec as P

    spec = P(placement.axes)
    mk = functools.partial(jax.tree_util.tree_map, lambda _: spec)
    return mk(u_data), mk(i_data)


def _placed_sweep_pair(u_loc, i_loc, u_d, i_d, cfg, placement):
    nu = _placed_half_sweep(
        "user", i_loc, u_d, cfg, placement,
        u_loc if cfg.warmstart else None)
    nv = _placed_half_sweep(
        "item", nu, i_d, cfg, placement,
        i_loc if cfg.warmstart else None)
    return nu, nv


@functools.partial(
    jax.jit, static_argnames=("placement", "cfg", "iterations"))
def _als_run_placed(uf, vf, u_data, i_data, *, placement, cfg,
                    iterations: int):
    """Fixed-budget placed training: every sweep of every shard in ONE
    dispatch (shard_map inside jit; collectives only, no host)."""
    from jax.sharding import PartitionSpec as P

    from incubator_predictionio_tpu.parallel.collectives import shard_map

    spec = P(placement.axes)

    def run(u_loc, i_loc, u_d, i_d):
        u_d = _squeeze_ring(u_d, cfg.u_mode)
        i_d = _squeeze_ring(i_d, cfg.i_mode)

        def body(_, st):
            return _placed_sweep_pair(st[0], st[1], u_d, i_d, cfg,
                                      placement)

        return jax.lax.fori_loop(0, iterations, body, (u_loc, i_loc))

    specs_u, specs_i = _placed_specs(placement, u_data, i_data)
    return shard_map(
        run, mesh=placement.mesh,
        in_specs=(spec, spec, specs_u, specs_i),
        out_specs=(spec, spec), check_vma=False,
    )(uf, vf, u_data, i_data)


def _converge_placed_impl(uf, vf, u_data, i_data, tol, placement, cfg,
                          max_sweeps: int, min_sweeps: int):
    """Traceable early-stopping placed run → (uf, vf, sweeps, delta).

    The plateau criterion is evaluated DEVICE-SIDE per sweep with the
    partial factor-delta sums reduced across shards by one psum — the
    sharded twin of :func:`_converge_impl`, still zero host syncs. Split
    out un-jitted so ops/retrain.py can fuse the O(delta) splice
    scatters into the SAME dispatch (`_converge_spliced_placed`)."""
    from jax.sharding import PartitionSpec as P

    from incubator_predictionio_tpu.parallel.collectives import (
        all_reduce_sum,
        shard_map,
    )

    spec = P(placement.axes)

    def run(u_loc, i_loc, u_d, i_d):
        u_d = _squeeze_ring(u_d, cfg.u_mode)
        i_d = _squeeze_ring(i_d, cfg.i_mode)

        def cond(carry):
            i, _u, _v, d = carry
            return jnp.logical_and(
                i < max_sweeps,
                jnp.logical_or(i < max(min_sweeps, 1), d >= tol))

        def body(carry):
            i, u, v, _d = carry
            nu, nv = _placed_sweep_pair(u, v, u_d, i_d, cfg, placement)
            num = (jnp.sum((nu - u) ** 2) + jnp.sum((nv - v) ** 2))
            den = jnp.sum(u ** 2) + jnp.sum(v ** 2)
            num = all_reduce_sum(num, placement.axes)
            den = all_reduce_sum(den, placement.axes)
            d = jnp.sqrt(num / jnp.maximum(den, 1e-30))
            return i + 1, nu, nv, d

        i, u, v, d = jax.lax.while_loop(
            cond, body, (jnp.int32(0), u_loc, i_loc, jnp.float32(jnp.inf)))
        return u, v, i, d

    specs_u, specs_i = _placed_specs(placement, u_data, i_data)
    return shard_map(
        run, mesh=placement.mesh,
        in_specs=(spec, spec, specs_u, specs_i),
        out_specs=(spec, spec, P(), P()), check_vma=False,
    )(uf, vf, u_data, i_data)


@functools.partial(
    jax.jit,
    static_argnames=("placement", "cfg", "max_sweeps", "min_sweeps"))
def _als_converge_placed(uf, vf, u_data, i_data, tol, *, placement, cfg,
                         max_sweeps: int, min_sweeps: int):
    return _converge_placed_impl(uf, vf, u_data, i_data, tol, placement,
                                 cfg, max_sweeps, min_sweeps)


def _placed_cfg(placement, rank: int, implicit: bool, reg_nnz: bool,
                l2: float, alpha: float, compute_dtype: Any,
                precision: Any, cg_iters: int,
                modes: Optional[Tuple[str, str]] = None) -> _ShardCfg:
    """Resolve every env-dependent selector OUTSIDE the trace (kernel
    route, fused routing vs shard-local shapes, gather strategy) into
    the hashable static config of one placed run."""
    warm = _CG_WARMSTART
    if modes is None:
        modes = _shard_gather_modes(placement, rank, compute_dtype,
                                    implicit)
    fused_u, fused_i = _fused_sides_placed(
        placement, modes, implicit, warm, compute_dtype, rank)
    return _ShardCfg(
        u_mode=modes[0], i_mode=modes[1], implicit=implicit,
        reg_nnz=reg_nnz, l2=float(l2), alpha=float(alpha),
        compute_dtype=compute_dtype, precision=precision,
        cg_iters=int(cg_iters), cg_tol=_cg_tol_env(),
        use_kernel=_kernel_enabled(implicit, warm=warm),
        kernel_min_d=_KERNEL_MIN_D, kernel_rows=_kernel_rows_default(),
        warmstart=warm, fused_u=fused_u, fused_i=fused_i)


@functools.lru_cache(maxsize=32)
def _replicate_jit(sharding):
    """One compiled gather-to-replicated program per target sharding —
    cached so the profiler's collective sample never re-traces."""
    return jax.jit(
        lambda a: jax.lax.with_sharding_constraint(a, sharding))


def _profile_placed_collectives(placement, uf, vf,
                                modes: Tuple[str, str]) -> None:
    """PIO_PROFILE=1: sample the factor-gather collective under its own
    op label ``als_allgather``. The sweep's gathers execute inside the
    ONE training dispatch and cannot be timed there without breaking the
    zero-host-sync contract; this times one standalone all-gather of
    each gathered table on the same mesh (block-until-ready) — the
    per-half-sweep unit collective cost, separable in /metrics next to
    ``als_fused``/``als_sharded``. Off (the default) costs one enabled()
    check."""
    from incubator_predictionio_tpu.obs import profile as _profile

    if placement.n_shards <= 1 or not _profile.enabled():
        return
    gather = _replicate_jit(placement.replicated())
    for arr in (vf, uf):  # user sweep gathers items, item sweep users
        # untimed warm run: compile/trace cost must not book as the
        # collective's device time
        jax.block_until_ready(gather(arr))
        t0 = _profile.t0()
        out = gather(arr)
        _profile.record(t0, "train", "als_allgather", result=out)


def _book_shard_metrics(placement, cfg: _ShardCfg, rank: int,
                        sweeps: int) -> None:
    """pio_shard_* observability (booked OUTSIDE any trace)."""
    try:
        from incubator_predictionio_tpu.obs import metrics as obs_metrics

        reg = obs_metrics.REGISTRY
        reg.gauge(
            "pio_shard_mesh_devices",
            "devices in the active factor-table mesh",
        ).set(placement.n_shards)
        rows = reg.gauge(
            "pio_shard_rows", "factor-table rows per shard", labels=("side",))
        rows.labels(side="user").set(placement.shard_rows("user"))
        rows.labels(side="item").set(placement.shard_rows("item"))
        gb = reg.counter(
            "pio_shard_gather_bytes_total",
            "bytes moved by factor-shard collectives, by strategy",
            labels=("strategy",))
        for side, mode in (("item", cfg.u_mode), ("user", cfg.i_mode)):
            n = placement.n_shards
            if n <= 1 or not sweeps:
                continue
            if mode == "allgather":
                gb.labels(strategy="allgather").inc(
                    placement.allgather_bytes(side, sweeps, rank))
            else:
                # ring: every slice visits every device once per sweep,
                # rotated at the sweep's compute dtype (bf16 slices move
                # half the bytes of f32; implicit always rotates f32)
                rows_p = (placement.n_users_padded if side == "user"
                          else placement.n_items_padded)
                item = jnp.dtype(jnp.float32 if cfg.implicit
                                 else cfg.compute_dtype).itemsize
                gb.labels(strategy="ring").inc(
                    rows_p * rank * item * (n - 1) * sweeps)
    except Exception:  # pragma: no cover — telemetry must never fail a train
        pass


def als_train_placed(
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    n_users: int,
    n_items: int,
    mesh=None,
    placement=None,
    rank: int = 64,
    iterations: int = 10,
    l2: float = 0.1,
    alpha: float = 1.0,
    seed: int = 0,
    reg_nnz: bool = True,
    implicit: bool = False,
    compute_dtype: Any = jnp.float32,
    precision: Any = jax.lax.Precision.HIGHEST,
    max_width: int = 1 << 16,
    bf16_sweeps: int = 0,
) -> ALSState:
    """Placement-aware training over the mesh → a PLACED ALSState
    (padded, tables sharded, ``state.placement`` set).

    The returned tables stay distributed for sharded serving
    (ops/topk.py per-shard merge) and sharded retrain; slice with
    ``placement.unplace_state`` when a host-shaped model is needed."""
    from incubator_predictionio_tpu.obs import profile as _profile
    from incubator_predictionio_tpu.parallel.placement import (
        make_placement,
    )

    if placement is None:
        placement = make_placement(mesh, n_users, n_items)
    modes = _shard_gather_modes(placement, rank, compute_dtype, implicit)
    u_data, i_data = build_placed_sides(
        users, items, ratings, placement, modes, max_width=max_width)
    state0 = als_init(jax.random.key(seed), n_users, n_items, rank)
    state = placement.place_state(state0)

    _prof_t0 = _profile.t0()
    lo = 0 if implicit else min(max(bf16_sweeps, 0), iterations)
    uf, vf = state.user_factors, state.item_factors
    if lo:
        cfg_lo = _placed_cfg(
            placement, rank, False, reg_nnz, l2, 0.0, jnp.bfloat16,
            jax.lax.Precision.DEFAULT,
            min(_CG_ITERS_BF16, _CG_ITERS), modes=modes)
        uf, vf = _als_run_placed(uf, vf, u_data, i_data,
                                 placement=placement, cfg=cfg_lo,
                                 iterations=lo)
    cfg = _placed_cfg(placement, rank, implicit, reg_nnz, l2, alpha,
                      compute_dtype, precision, _CG_ITERS, modes=modes)
    if iterations - lo:
        uf, vf = _als_run_placed(uf, vf, u_data, i_data,
                                 placement=placement, cfg=cfg,
                                 iterations=iterations - lo)
    out = ALSState(user_factors=uf, item_factors=vf, placement=placement)
    if _prof_t0 is not None:
        _profile.record(
            _prof_t0, "train", "als_sharded", result=out,
            flops_fn=lambda: train_flops(
                len(ratings), n_users, n_items, rank, iterations, lo))
    _profile_placed_collectives(placement, uf, vf, modes)
    # book each leg at ITS dtype: bf16 sweeps rotate bf16 ring slices
    # (half the bytes of the f32 leg)
    if lo:
        _book_shard_metrics(placement, cfg_lo, rank, lo)
    _book_shard_metrics(placement, cfg, rank, iterations - lo)
    from incubator_predictionio_tpu.ops.retrain import _book_sweeps

    _book_sweeps("fresh", iterations)
    return out


def als_train_sharded(
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    n_users: int,
    n_items: int,
    mesh,                       # jax.sharding.Mesh with (dp, mp) axes
    rank: int = 64,
    iterations: int = 10,
    l2: float = 0.1,
    alpha: float = 1.0,
    seed: int = 0,
    reg_nnz: bool = True,
    implicit: bool = False,
    compute_dtype: Any = jnp.float32,
    precision: Any = jax.lax.Precision.HIGHEST,
    max_width: int = 1 << 16,
    bf16_sweeps: int = 0,
    keep_placed: bool = False,
) -> ALSState:
    """Mesh-sharded training (the ALX layout) — the historical entry,
    now a thin wrapper over :func:`als_train_placed`.

    Both factor tables shard on rows over the flattened mesh via a
    :class:`~...parallel.placement.FactorPlacement`; half-sweeps run
    under shard_map with each device solving the row buckets it owns.
    Numerics match the unsharded run up to floating-point reduction
    order. ``keep_placed=False`` (the historical contract) slices the
    result back to the true sizes; ``keep_placed=True`` returns the
    distributed state for sharded serving/retrain."""
    from incubator_predictionio_tpu.parallel.placement import (
        make_placement,
    )

    placement = make_placement(mesh, n_users, n_items)
    out = als_train_placed(
        users, items, ratings, n_users, n_items, placement=placement,
        rank=rank, iterations=iterations, l2=l2, alpha=alpha, seed=seed,
        reg_nnz=reg_nnz, implicit=implicit, compute_dtype=compute_dtype,
        precision=precision, max_width=max_width, bf16_sweeps=bf16_sweeps)
    return out if keep_placed else placement.unplace_state(out)


@jax.jit
def _predict_coo(
    user_factors: jax.Array, item_factors: jax.Array,
    users: jax.Array, items: jax.Array,
) -> jax.Array:
    return jnp.sum(user_factors[users] * item_factors[items], axis=-1)


def rmse(
    state: ALSState,
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    chunk: int = 1 << 20,
) -> float:
    """Root-mean-square error over COO ratings (evaluation metric parity with
    the reference recommendation template's eval)."""
    users = np.asarray(users, np.int32)
    items = np.asarray(items, np.int32)
    ratings = np.asarray(ratings, np.float32)
    total, n = 0.0, len(ratings)
    for s in range(0, n, chunk):
        pred = _predict_coo(
            state.user_factors, state.item_factors,
            jnp.asarray(users[s:s + chunk]), jnp.asarray(items[s:s + chunk]),
        )
        total += float(jnp.sum((pred - jnp.asarray(ratings[s:s + chunk])) ** 2))
    return float(np.sqrt(total / max(n, 1)))


# ---------------------------------------------------------------------------
# Fused whole-run training: every sweep of every bucket inside ONE jit.
#
# The per-bucket python loop above costs one device dispatch per
# solve/scatter — ~2·sweeps·buckets dispatches per training run, and at
# ML-100K scale the dispatches, not the solves, are the wall. The fused
# path traces the full alternation (lax.fori_loop over sweeps; buckets unrolled
# inside the body, their shapes are static) so the whole `pio train` compute
# is ONE dispatch.
# ---------------------------------------------------------------------------

def _buckets_tree(buckets: Sequence[PaddedRows]):
    return tuple(
        (jnp.asarray(b.row_ids), jnp.asarray(b.cols), jnp.asarray(b.vals),
         jnp.asarray(b.mask))
        for b in buckets
    )


def _heavy_tree(heavy):
    if heavy is None:
        return None
    return (jnp.asarray(heavy.seg_ids), jnp.asarray(heavy.row_ids),
            jnp.asarray(heavy.cols), jnp.asarray(heavy.vals),
            jnp.asarray(heavy.mask))


def _solve_heavy(
    other_factors: jax.Array,
    heavy,                      # (seg_ids[S], row_ids[H], cols, vals, mask)
    l2: float,
    alpha: float,
    reg_nnz: bool,
    compute_dtype: Any,
    precision: Any,
    implicit: bool,
    yty: Optional[jax.Array],
    cg_iters: int = _CG_ITERS,
    prev_factors: Optional[jax.Array] = None,
    cg_tol: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Partial-Gram combining solve for split rows → (row_ids, sol[H, K]).

    Per-segment normal-equation pieces are computed exactly like a regular
    bucket, then segment-summed per original row before ONE solve per row —
    the reduction ALX does across shards, here across split segments.
    ``prev_factors`` warm-starts the combining CG exactly like the bucket
    path — the heaviest rows share the reduced bf16 budget, so they need
    the warm start most."""
    seg_ids, row_ids, cols, vals, mask = heavy
    n_heavy = row_ids.shape[0]
    pg, prhs, pnnz = _gram_rhs_nnz_chunked(
        other_factors, cols, vals, mask, compute_dtype, precision,
        implicit, alpha)
    gram = jax.ops.segment_sum(pg, seg_ids, num_segments=n_heavy)
    rhs = jax.ops.segment_sum(prhs, seg_ids, num_segments=n_heavy)
    nnz = jax.ops.segment_sum(pnnz, seg_ids, num_segments=n_heavy)
    x0 = (_gather_x0(prev_factors, row_ids)
          if prev_factors is not None else None)
    return row_ids, _reg_solve(
        gram, rhs, nnz, l2, reg_nnz, implicit, yty, cg_iters=cg_iters,
        cg_matvec_dtype=jnp.float32 if implicit else compute_dtype,
        x0=x0, cg_tol=cg_tol)


@functools.partial(
    jax.jit,
    static_argnames=("iterations", "reg_nnz", "compute_dtype", "precision",
                     "implicit", "cg_iters", "use_kernel", "kernel_min_d",
                     "kernel_rows", "warmstart", "use_fused", "cg_tol"),
    donate_argnames=("state",),
)
def _als_run_fused(
    state: ALSState,
    user_tree,
    item_tree,
    l2: float,
    alpha: float,
    iterations: int,
    reg_nnz: bool,
    compute_dtype: Any,
    precision: Any,
    implicit: bool,
    user_heavy=None,
    item_heavy=None,
    cg_iters: int = _CG_ITERS,
    use_kernel: bool = False,
    kernel_min_d: int = 0,
    kernel_rows: int = 1,
    warmstart: bool = False,
    use_fused: Tuple[bool, bool] = (False, False),
    cg_tol: float = 0.0,
) -> ALSState:
    def body(_, st):
        new_users = _sweep_side(
            st.user_factors.shape[0], st.item_factors, user_tree, user_heavy,
            l2, alpha, reg_nnz, compute_dtype, precision, implicit,
            cg_iters=cg_iters, use_kernel=use_kernel,
            kernel_min_d=kernel_min_d, kernel_rows=kernel_rows,
            prev_factors=st.user_factors if warmstart else None,
            use_fused=use_fused[0], cg_tol=cg_tol)
        new_items = _sweep_side(
            st.item_factors.shape[0], new_users, item_tree, item_heavy,
            l2, alpha, reg_nnz, compute_dtype, precision, implicit,
            cg_iters=cg_iters, use_kernel=use_kernel,
            kernel_min_d=kernel_min_d, kernel_rows=kernel_rows,
            prev_factors=st.item_factors if warmstart else None,
            use_fused=use_fused[1], cg_tol=cg_tol)
        return ALSState(user_factors=new_users, item_factors=new_items)

    return jax.lax.fori_loop(0, iterations, body, state)


def _rel_delta(prev: ALSState, new: ALSState) -> jax.Array:
    """Relative Frobenius factor movement of one sweep → f32 scalar.

    THE plateau criterion of the convergence early-stop: ‖new − prev‖_F
    over ‖prev‖_F across both sides. Scale-free, so one tolerance serves
    every rank/λ/dataset, and an O(rows·K) reduction — noise next to a
    sweep's Gram streams."""
    num = (jnp.sum((new.user_factors - prev.user_factors) ** 2)
           + jnp.sum((new.item_factors - prev.item_factors) ** 2))
    den = (jnp.sum(prev.user_factors ** 2)
           + jnp.sum(prev.item_factors ** 2))
    return jnp.sqrt(num / jnp.maximum(den, 1e-30))


def _converge_impl(
    state: ALSState,
    user_tree,
    item_tree,
    l2: float,
    alpha: float,
    tol,                        # f32 operand — NOT static (no recompiles)
    max_sweeps: int,
    min_sweeps: int,
    reg_nnz: bool,
    compute_dtype: Any,
    precision: Any,
    implicit: bool,
    user_heavy=None,
    item_heavy=None,
    cg_iters: int = _CG_ITERS,
    use_kernel: bool = False,
    kernel_min_d: int = 0,
    kernel_rows: int = 1,
    warmstart: bool = False,
    use_fused: Tuple[bool, bool] = (False, False),
    cg_tol: float = 0.0,
) -> Tuple[ALSState, jax.Array, jax.Array]:
    """Traced body of :func:`_als_run_converge` — split out so
    ops/retrain.py can fuse the O(delta) plan splice into the SAME
    dispatch (`_converge_spliced`: scatter the tail entries into the
    resident trees, then run this loop, all inside one jit)."""
    def sweep(st):
        new_users = _sweep_side(
            st.user_factors.shape[0], st.item_factors, user_tree, user_heavy,
            l2, alpha, reg_nnz, compute_dtype, precision, implicit,
            cg_iters=cg_iters, use_kernel=use_kernel,
            kernel_min_d=kernel_min_d, kernel_rows=kernel_rows,
            prev_factors=st.user_factors if warmstart else None,
            use_fused=use_fused[0], cg_tol=cg_tol)
        new_items = _sweep_side(
            st.item_factors.shape[0], new_users, item_tree, item_heavy,
            l2, alpha, reg_nnz, compute_dtype, precision, implicit,
            cg_iters=cg_iters, use_kernel=use_kernel,
            kernel_min_d=kernel_min_d, kernel_rows=kernel_rows,
            prev_factors=st.item_factors if warmstart else None,
            use_fused=use_fused[1], cg_tol=cg_tol)
        return ALSState(user_factors=new_users, item_factors=new_items)

    def cond(carry):
        i, _st, d = carry
        return jnp.logical_and(
            i < max_sweeps,
            jnp.logical_or(i < max(min_sweeps, 1), d >= tol))

    def body(carry):
        i, st, _d = carry
        new = sweep(st)
        return i + 1, new, _rel_delta(st, new)

    i, st, d = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), state, jnp.float32(jnp.inf)))
    return st, i, d


@functools.partial(
    jax.jit,
    static_argnames=("max_sweeps", "min_sweeps", "reg_nnz", "compute_dtype",
                     "precision", "implicit", "cg_iters", "use_kernel",
                     "kernel_min_d", "kernel_rows", "warmstart", "use_fused",
                     "cg_tol"),
    donate_argnames=("state",),
)
def _als_run_converge(
    state: ALSState,
    user_tree,
    item_tree,
    l2: float,
    alpha: float,
    tol,                        # f32 operand — NOT static (no recompiles)
    max_sweeps: int,
    min_sweeps: int,
    reg_nnz: bool,
    compute_dtype: Any,
    precision: Any,
    implicit: bool,
    user_heavy=None,
    item_heavy=None,
    cg_iters: int = _CG_ITERS,
    use_kernel: bool = False,
    kernel_min_d: int = 0,
    kernel_rows: int = 1,
    warmstart: bool = False,
    use_fused: Tuple[bool, bool] = (False, False),
    cg_tol: float = 0.0,
) -> Tuple[ALSState, jax.Array, jax.Array]:
    """Early-stopping fused run → (state, sweeps_run, last_delta).

    ``lax.while_loop`` evaluates the plateau criterion (:func:`_rel_delta`
    below ``tol``) DEVICE-SIDE every sweep, so the whole run is still one
    dispatch and no per-sweep host sync exists (the `host-sync` lint
    rule's contract). Floor: at least ``min_sweeps`` full sweep pairs
    (and always ≥ 1 — the loop must produce a delta before it can judge
    one); ceiling: the fixed ``max_sweeps`` budget. The returned
    ``sweeps_run``/``last_delta`` are device scalars — callers fetch them
    ONCE after the run (one sync per train, not per sweep). Calling with
    ``min_sweeps == max_sweeps`` runs exactly that many sweeps and hands
    back the last delta: the chunked-probe building block of the unfused
    path (ops/retrain.py)."""
    return _converge_impl(
        state, user_tree, item_tree, l2, alpha, tol, max_sweeps,
        min_sweeps, reg_nnz, compute_dtype, precision, implicit,
        user_heavy=user_heavy, item_heavy=item_heavy, cg_iters=cg_iters,
        use_kernel=use_kernel, kernel_min_d=kernel_min_d,
        kernel_rows=kernel_rows, warmstart=warmstart, use_fused=use_fused,
        cg_tol=cg_tol)


def train_flops(
    nnz: int,
    n_users: int,
    n_items: int,
    rank: int,
    iterations: int,
    bf16_sweeps: int = 0,
    solver: Optional[str] = None,
    cg_iters: Optional[int] = None,
    cg_iters_bf16: Optional[int] = None,
    warmstart: Optional[bool] = None,
) -> float:
    """THE analytic FLOP count of one training run — the single formula
    an offline MFU and the live ``pio_mfu{phase="train"}`` gauge
    (obs/profile.py) both divide by, so the two figures agree by
    construction when the measured walls agree.

    Per half-sweep over ``nnz`` observations at rank K: the Gram batch
    is 2·nnz·K² MACs = 4·nnz·K² FLOPs at HIGHEST precision (the f32
    multi-pass costs ~3× a bf16 pass; counted at face value —
    conservative), the rhs 2·nnz·K, and each row's CG solve
    ~iters·2·K² FLOPs (about the same count as a direct K³/3 Cholesky
    at K=128, iters=32; bf16 sweeps run the loose ``_CG_ITERS_BF16``
    budget, polish sweeps the full one, warm starts pay one extra
    matvec). Both sides per sweep, ``iterations`` sweeps. Counts USEFUL
    work only — padding waste shows up as lower MFU, not higher FLOPs.
    """
    k = float(rank)
    nnz = float(nnz)
    solver = _SOLVER if solver is None else solver
    cg_iters = _CG_ITERS if cg_iters is None else int(cg_iters)
    cg_iters_bf16 = (_CG_ITERS_BF16 if cg_iters_bf16 is None
                     else int(cg_iters_bf16))
    warmstart = _CG_WARMSTART if warmstart is None else bool(warmstart)
    per_side_gram = 2.0 * nnz * k * k * 2.0   # multiply+add
    per_side_rhs = 2.0 * nnz * k
    if solver == "cg":
        bf16 = min(max(int(bf16_sweeps), 0), int(iterations))
        iters = (bf16 * min(cg_iters_bf16, cg_iters)
                 + (int(iterations) - bf16) * cg_iters) / max(
                     int(iterations), 1)
        if warmstart:
            iters += 1.0  # the warm start's initial-residual matvec
        per_solve = iters * 2.0 * k * k
    else:
        per_solve = k ** 3 / 3.0 + 2.0 * k * k
    solves = (int(n_users) + int(n_items)) * per_solve
    per_sweep = 2.0 * per_side_gram + 2.0 * per_side_rhs + solves
    return per_sweep * int(iterations)


def tree_nnz(tree, heavy=None) -> int:
    """Observed interaction count of one side's device trees — mask
    sums, so it costs a few device reduces + fetches. Only the
    PIO_PROFILE=1 path calls this (the profiler is already blocking on
    walls); production training never pays it."""
    total = 0.0
    for _row_ids, _cols, _vals, mask in tree:
        total += float(jnp.sum(mask))
    if heavy is not None:
        total += float(jnp.sum(heavy[4]))
    return int(total)


def _mixed_run(
    state: ALSState,
    u_tree,
    i_tree,
    l2: float,
    iterations: int,
    bf16_sweeps: int,
    reg_nnz: bool,
    compute_dtype: Any,
    precision: Any,
    user_heavy,
    item_heavy,
    use_kernel: Optional[bool] = None,
    kernel_min_d: Optional[int] = None,
    kernel_rows: Optional[int] = None,
    warmstart: Optional[bool] = None,
    use_fused: "Optional[Tuple[bool, bool]]" = None,
) -> ALSState:
    """Mixed-precision schedule: ``bf16_sweeps`` early sweeps with bf16
    gathers + single-pass MXU matmuls (DEFAULT precision), then the
    remaining sweeps at (compute_dtype, precision) — the f32 HIGHEST
    polish that restores full convergence. Two fused dispatches instead
    of one; explicit feedback only (implicit confidences stay f32).

    Why this is safe: ALS re-solves every factor row from scratch each
    half-sweep (the state is not incrementally perturbed), so low-precision
    early sweeps only affect the *starting point* of the f32 polish — the
    polish sweeps land on the same fixed point (validated by the planted
    low-rank recovery test, tests/test_als.py)."""
    from incubator_predictionio_tpu.obs import profile as _profile

    _prof_t0 = _profile.t0()
    lo = min(max(bf16_sweeps, 0), iterations)
    # resolve the Pallas selector HERE (python level, outside any trace —
    # it is a static jit argument). Callers pass False explicitly
    # on the mesh-sharded path: pallas_call does not auto-partition under
    # GSPMD, so the sharded program keeps the XLA assembly.
    if warmstart is None:
        warmstart = _CG_WARMSTART
    if use_kernel is None:
        # name the exact variant this run dispatches (warm adds the x0
        # operand — a different kernel), honoring per-call overrides
        use_kernel = _kernel_enabled(False, warm=bool(warmstart))
    if kernel_min_d is None:
        kernel_min_d = _KERNEL_MIN_D
    if kernel_rows is None:
        kernel_rows = _kernel_rows_default()
    n_u = state.user_factors.shape[0]
    n_i = state.item_factors.shape[0]
    rank = state.user_factors.shape[1]
    cg_tol = _cg_tol_env()

    def fused_for(dtype):
        # per-leg: the VMEM fit depends on the gather table's dtype
        # (a bf16 table is half the f32 footprint)
        if use_fused is not None:
            return use_fused
        if not use_kernel:
            return (False, False)
        return _fused_sides(n_u, n_i, False, bool(warmstart), dtype, rank)

    if lo:
        state = _als_run_fused(
            state, u_tree, i_tree, l2, 0.0, lo, reg_nnz,
            jnp.bfloat16, jax.lax.Precision.DEFAULT, implicit=False,
            user_heavy=user_heavy, item_heavy=item_heavy,
            cg_iters=min(_CG_ITERS_BF16, _CG_ITERS),
            use_kernel=use_kernel, kernel_min_d=kernel_min_d,
            kernel_rows=kernel_rows, warmstart=warmstart,
            use_fused=fused_for(jnp.bfloat16), cg_tol=cg_tol,
        )
    if iterations - lo:
        state = _als_run_fused(
            state, u_tree, i_tree, l2, 0.0, iterations - lo, reg_nnz,
            compute_dtype, precision, implicit=False,
            user_heavy=user_heavy, item_heavy=item_heavy,
            use_kernel=use_kernel, kernel_min_d=kernel_min_d,
            kernel_rows=kernel_rows, warmstart=warmstart,
            use_fused=fused_for(compute_dtype), cg_tol=cg_tol,
        )
    if _prof_t0 is not None:
        # PIO_PROFILE=1: attribute the device wall + analytic FLOPs of
        # this run (blocks on the final state — the profiler's
        # contract). flops_fn defers the tree mask sums until AFTER the
        # wall is captured, so their dispatches/fetches never
        # contaminate the measured device time. Kernel-path runs book
        # under their own op label (`als_fused`) so /metrics separates
        # the fused Gram+solve trajectory from the XLA assembly —
        # `als.train_flops` stays the ONE FLOP formula for both, so
        # pio_mfu{phase="train"} is comparable across the op split.
        _profile.record(
            _prof_t0, "train", "als_fused" if use_kernel else "als_train",
            result=state,
            flops_fn=lambda: train_flops(
                tree_nnz(u_tree, user_heavy),
                state.user_factors.shape[0], state.item_factors.shape[0],
                state.user_factors.shape[1], iterations, lo,
                warmstart=warmstart))
    return state


def als_train(
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    n_users: int,
    n_items: int,
    rank: int = 64,
    iterations: int = 10,
    l2: float = 0.1,
    seed: int = 0,
    reg_nnz: bool = True,
    compute_dtype: Any = jnp.float32,
    precision: Any = jax.lax.Precision.HIGHEST,
    max_width: int = 1 << 16,
    track_rmse: bool = False,
    bf16_sweeps: int = 0,
) -> Tuple[ALSState, List[float]]:
    """Full training: build padded buckets once, run ``iterations`` sweeps.

    Rows whose degree exceeds ``max_width`` are split into segments and
    solved via the partial-Gram combining path (ops/sparse.py
    ``split_heavy`` + ``_solve_heavy``), so power users/items of any degree
    train correctly."""
    (user_light, user_heavy), (item_light, item_heavy) = build_both_sides(
        users, items, ratings, n_users, n_items, max_width=max_width)
    u_tree, i_tree = _buckets_tree(user_light), _buckets_tree(item_light)
    u_hv, i_hv = _heavy_tree(user_heavy), _heavy_tree(item_heavy)

    state = als_init(jax.random.key(seed), n_users, n_items, rank)
    history: List[float] = []
    if track_rmse:
        # per-sweep metric needs per-sweep dispatches
        for sweep in range(iterations):
            state = _mixed_run(
                state, u_tree, i_tree, l2, 1,
                1 if sweep < bf16_sweeps else 0,
                reg_nnz, compute_dtype, precision,
                user_heavy=u_hv, item_heavy=i_hv,
            )
            history.append(rmse(state, users, items, ratings))
    else:
        state = _mixed_run(
            state, u_tree, i_tree, l2, iterations, bf16_sweeps,
            reg_nnz, compute_dtype, precision,
            user_heavy=u_hv, item_heavy=i_hv,
        )
    # obs bridge: the sweep counter books for fresh trains too, so
    # /metrics' fresh-vs-continue split stays meaningful (lazy import —
    # ops.retrain imports this module)
    from incubator_predictionio_tpu.ops.retrain import _book_sweeps

    _book_sweeps("fresh", iterations)
    return state, history
