"""Hand-written Pallas TPU kernels for the serving/training hot ops.

The reference delegates all compute to Spark MLlib and serves predictions
with driver-side Scala loops (examples/.../ALSAlgorithm.scala predict,
core/.../workflow/CreateServer.scala:498-650 query path); it has no custom
kernels of any kind. This module is the TPU-native analogue of "the code the
hot loop actually runs": Mosaic kernels that keep the MXU busy and cut HBM
traffic where XLA's default lowering leaves bandwidth on the table.

Two kernels:

- :func:`score_and_top_k_pallas` — full-catalog recommendation scoring.
  Grid over item blocks; each program computes a [B, block] score tile on
  the MXU, applies the serve-time allow/deny mask in-register, and reduces
  the tile to its block-local top-k **before** touching HBM. Only
  ``num_blocks × 128`` candidates are ever written back instead of the full
  ``[B, n_items]`` score matrix — for catalogs ≥100k items the HBM write
  traffic drops by >100× and the final merge is a tiny ``lax.top_k``.
- :func:`flash_attention` — FlashAttention-style fused attention for the
  sequence model family (models/sequence). One kernel program per
  (batch·head, query-block, KV-block) grid cell; K/V stream through VMEM
  one tile at a time with the online-softmax state in VMEM scratch, so
  VMEM use is S-independent and the [S, S] logit matrix never
  materializes. Numerics are kept bit-compatible with
  ops/attention.py (same MASK_VALUE, same zero-for-fully-masked-row rule)
  so the single-chip path and the ring-attention path agree.

Every kernel runs under ``interpret=True`` on CPU for the test suite and
compiles with Mosaic on a TPU. Callers route on the per-family predicates
— :func:`topk_kernel_available` / :func:`flash_available` /
:func:`als_kernel_available` — which are the backend test and nothing
more: on a TPU a selected kernel runs compiled or raises the compiler's
error, it is never swapped for an XLA path behind the caller's back.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from incubator_predictionio_tpu.ops.attention import MASK_VALUE  # noqa: E402
# (imported, not duplicated: flash numerics must stay bit-identical to the
# dense/blockwise/ring paths in ops/attention.py)

NEG_INF = -3.4e38   # python float: pallas kernels may not close over arrays
_LANES = 128


def pallas_available() -> bool:
    """True when the default backend is a TPU — the ONE routing predicate
    of every kernel family. There the kernels compile with Mosaic; on any
    other backend they only run in interpret mode (the CPU test suite).

    Deliberately not a probe: a kernel the route selects on a TPU either
    runs compiled or raises the compiler's own error (the traceback names
    the kernel body, e.g. ``_als_cg_kernel``). Nothing here catches a
    compile or run failure and reroutes to an XLA path — a reroute would
    make a measurement of "the kernel path" silently measure another one.
    Whether each kernel compiles for the chip at real widths is pinned
    ahead of time by tests/test_tpu_aot_compile.py."""
    return jax.default_backend() == "tpu"


def topk_kernel_available() -> bool:
    """Routing predicate of the serving top-k family (ops/topk.py)."""
    return pallas_available()


def flash_available() -> bool:
    """Routing predicate of the attention family (ops/transformer.py)."""
    return pallas_available()


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """The kernel entries' ``interpret`` argument → a bool.

    ``None`` means interpret mode exactly when the backend is not a TPU.
    On a TPU it can never be true: an interpreted kernel there is a slow
    XLA program wearing the kernel's name."""
    on_tpu = pallas_available()
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU backend: Pallas kernels run compiled "
            "there (interpret mode is the CPU test hook)")
    return bool(interpret)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# Kernel 1: blocked full-catalog top-K scoring
# ---------------------------------------------------------------------------


def _topk_tile_kernel(q_ref, it_ref, al_ref, out_s_ref, out_i_ref,
                      *, k: int, block_items: int):
    """Score one item block and keep its local top-k.

    q_ref:  [B, Kp]      query factors (replicated across the grid)
    it_ref: [blk, Kp]    this block's item factors
    al_ref: [1, blk]     allow mask (0 = excluded / padding)
    out_*:  [1, B, 128]  this block's candidate slots (first k valid)
    """
    i = pl.program_id(0)
    scores = jax.lax.dot_general(
        q_ref[:], it_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )                                                    # [B, blk]
    b = scores.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    gid = i * block_items + col                          # global item ids
    allowed = al_ref[:] > 0.0                            # [1, blk] → bcast
    scores = jnp.where(allowed, scores, NEG_INF)

    cand_s = jnp.full((b, _LANES), NEG_INF, jnp.float32)
    cand_i = jnp.full((b, _LANES), -1, jnp.int32)
    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (b, _LANES), 1)
    big = jnp.int32(2**31 - 1)
    # k is small and static: unrolled iterative max-select, all VPU work on
    # an in-register [B, blk] tile — no HBM traffic until the final store
    for j in range(k):
        m = jnp.max(scores, axis=1, keepdims=True)       # [B, 1]
        at_max = scores == m
        sel = jnp.min(jnp.where(at_max, gid, big), axis=1, keepdims=True)
        slot = slot_iota == j
        cand_s = jnp.where(slot, m, cand_s)
        cand_i = jnp.where(slot, sel, cand_i)
        scores = jnp.where(gid == sel, NEG_INF, scores)
    out_s_ref[0] = cand_s
    out_i_ref[0] = cand_i


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_items", "interpret"),
)
def _score_topk_pallas(
    queries: jax.Array,             # [B, K] f32
    item_factors: jax.Array,        # [I, K] f32
    allowed: jax.Array,             # [I] f32, 1 = allowed
    k: int,
    block_items: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    b, rank = queries.shape
    n_items = item_factors.shape[0]
    blk = block_items
    i_pad = _round_up(max(n_items, blk), blk)
    k_pad = _round_up(max(rank, _LANES), _LANES)
    b_pad = _round_up(max(b, 8), 8)

    # shapes are static at trace time: skip the pad-copy entirely when the
    # caller's arrays are already tile-aligned (the serving path stores
    # factors pre-aligned, so the hot path is copy-free)
    q = queries.astype(jnp.float32)
    if (b_pad, k_pad) != q.shape:
        q = jnp.zeros((b_pad, k_pad), jnp.float32).at[:b, :rank].set(q)
    it = item_factors.astype(jnp.float32)
    if (i_pad, k_pad) != it.shape:
        it = jnp.zeros((i_pad, k_pad), jnp.float32).at[:n_items, :rank].set(it)
    al = allowed.astype(jnp.float32)[None]
    if i_pad != n_items:
        al = jnp.zeros((1, i_pad), jnp.float32).at[0, :n_items].set(al[0])

    n_blocks = i_pad // blk
    cand_s, cand_i = pl.pallas_call(
        functools.partial(_topk_tile_kernel, k=k, block_items=blk),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((b_pad, k_pad), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((blk, k_pad), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, b_pad, _LANES), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b_pad, _LANES), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, b_pad, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, b_pad, _LANES), jnp.int32),
        ],
        interpret=interpret,
        name="pio_topk_tile",
    )(q, it, al)

    # merge: [n_blocks, B, 128] → per-query candidate row → exact top-k.
    # Correctness: every global top-k item is, within its own block, among
    # that block's top-k (k ≤ 128 slots kept), so the union of block
    # candidates always contains the exact answer.
    flat_s = cand_s.transpose(1, 0, 2).reshape(b_pad, n_blocks * _LANES)
    flat_i = cand_i.transpose(1, 0, 2).reshape(b_pad, n_blocks * _LANES)
    top_s, pos = jax.lax.top_k(flat_s, k)
    top_i = jnp.take_along_axis(flat_i, pos, axis=1)
    # when fewer than k items are allowed, exhausted blocks select padding
    # columns (gid >= n_items); mark those slots -1 so no out-of-range item
    # id ever escapes to the caller
    top_i = jnp.where(top_s <= NEG_INF / 2, -1, top_i)
    return top_s[:b], top_i[:b]


@functools.partial(
    jax.jit, static_argnames=("k", "block_items", "interpret"))
def _score_and_top_k_pallas_jit(
    user_vector, item_factors, k, exclude, allowed_mask, block_items,
    interpret,
):
    n_items = item_factors.shape[0]
    allowed = (jnp.ones((n_items,), jnp.float32) if allowed_mask is None
               else allowed_mask.astype(jnp.float32))
    if exclude is not None:
        safe = jnp.where(exclude < 0, n_items, exclude)
        allowed = allowed.at[safe].set(0.0, mode="drop")
    top_s, top_i = _score_topk_pallas(
        user_vector[None, :], item_factors, allowed,
        k=k, block_items=block_items, interpret=interpret,
    )
    return jnp.stack([top_s[0], top_i[0].astype(jnp.float32)])


def score_and_top_k_pallas(
    user_vector: jax.Array,         # [K]
    item_factors: jax.Array,        # [I, K]
    k: int,
    exclude: Optional[jax.Array] = None,       # [E] int32, -1 = no-op
    allowed_mask: Optional[jax.Array] = None,  # [I] bool
    block_items: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Drop-in Pallas variant of ops.topk.score_and_top_k.

    Returns the same packed [2, k] array (row 0 = scores, row 1 = indices as
    f32) so serving still pays exactly one device→host fetch per query.
    Exclusions are folded into a dense allow-mask (a [n_items] vector is
    bytes even at million-item scale) applied inside the kernel, so an
    excluded item can never displace a real candidate.
    """
    interpret = _resolve_interpret(interpret)
    k = min(k, item_factors.shape[0], _LANES)
    # one fully-jitted dispatch per query: un-jitted, the mask build and
    # the packing would each be a separate dispatch
    return _score_and_top_k_pallas_jit(
        user_vector, item_factors, k, exclude, allowed_mask, block_items,
        bool(interpret),
    )


# ---------------------------------------------------------------------------
# Kernel 2: fused flash attention
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, val_ref, o_ref,
                  m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, q_block: int,
                  kv_block: int, n_kv_blocks: int):
    """One (batch·head, q-block, kv-block) program — the KV scan is the
    grid's MINOR dimension, so VMEM holds only one [kb, D] K/V tile at a
    time (the full-KV-resident layout capped sequence length at ~6k before
    scoped-VMEM OOM; this scales to any S). The online-softmax state
    (m, l, acc) lives in VMEM scratch, which Mosaic persists across grid
    steps that revisit the same output block.

    q_ref:   [1, qb, D]   this q block (constant across the kv dim)
    k_ref:   [1, kb, D]   this kv block
    v_ref:   [1, kb, D]
    val_ref: [1, 1, kb]   key validity (padding/ragged mask)
    o_ref:   [1, qb, D]   revisited; written on the last kv step
    """
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qb = q_ref.shape[1]
    # causal: kv blocks fully in this q block's future contribute nothing
    live = (not causal) or (j * kv_block <= qi * q_block + qb - 1)

    @pl.when(live)
    def _step():
        q_tile = q_ref[0].astype(jnp.float32) * scale    # [qb, D]
        q_pos = qi * q_block + jax.lax.broadcasted_iota(
            jnp.int32, (qb, 1), 0)                       # [qb, 1]
        k_blk = k_ref[0].astype(jnp.float32)             # [kb, D]
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q_tile, k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [qb, kb]
        kv_pos = j * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_block), 1)
        mask = val_ref[0, 0, :][None, :] > 0.0
        if causal:
            mask = mask & (q_pos >= kv_pos)
        s = jnp.where(mask, s, MASK_VALUE)
        # online softmax — identical update rule to ops/attention.py
        # _online_block so sharded and single-chip numerics agree
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_kv_blocks - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)             # fully masked → 0
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "q_block", "kv_block", "interpret",
                     "n_heads"),
)
def _flash_bhsd(
    q: jax.Array,                   # [BH, Sq, D]
    k: jax.Array,                   # [BH, Skv, D]
    v: jax.Array,
    valid: jax.Array,               # [B, 1, Skv] f32
    n_heads: int,
    causal: bool,
    scale: float,
    q_block: int,
    kv_block: int,
    interpret: bool,
) -> jax.Array:
    bh, s_q, d = q.shape
    s_kv = k.shape[1]
    qb = min(q_block, _round_up(s_q, 8))
    kb = min(kv_block, _round_up(s_kv, 8))
    sq_pad = _round_up(s_q, qb)
    skv_pad = _round_up(s_kv, kb)
    qp = jnp.pad(q, ((0, 0), (0, sq_pad - s_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, skv_pad - s_kv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, skv_pad - s_kv), (0, 0)))
    valp = jnp.pad(valid, ((0, 0), (0, 0), (0, skv_pad - s_kv)))  # pads invalid
    n_q_blocks = sq_pad // qb
    n_kv_blocks = skv_pad // kb

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, q_block=qb,
            kv_block=kb, n_kv_blocks=n_kv_blocks),
        # kv is the MINOR grid dim: programs revisiting one (bh, q-block)
        # output run consecutively, carrying the softmax state in scratch
        grid=(bh, n_q_blocks, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, qb, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, kb, d), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, kb, d), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            # [B, 1, S] so the trailing block dims satisfy Mosaic's
            # (sublane, lane) tiling rule for any batch size
            pl.BlockSpec((1, 1, kb), lambda b, i, j: (b // n_heads, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, qb, d), lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bh, sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),   # running max m
            pltpu.VMEM((qb, 1), jnp.float32),   # running sum l
            pltpu.VMEM((qb, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="pio_flash_fwd",
    )(qp, kp, vp, valp)
    return out[:, :s_q, :]


@functools.lru_cache(maxsize=None)
def _flash_with_vjp(causal: bool, scale: float, q_block: int, kv_block: int,
                    interpret: bool):
    """custom_vjp closure over the static config.

    Mosaic kernels are not reverse-differentiable, but the sequence engines
    train through their attention op (ops/transformer.py _fit_scan), so the
    fused kernel must be usable under ``value_and_grad``. Forward runs the
    Pallas kernel; backward differentiates the XLA blockwise path
    (ops/attention.py), which implements the *same* online-softmax update
    rule — a recompute-based backward with O(S·block) memory, no [S, S]
    residuals."""
    from incubator_predictionio_tpu.ops.attention import blockwise_attention

    def forward(q, k, v, valid):
        b, s_q, h, d = q.shape

        def to_bhsd(x):
            return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

        out = _flash_bhsd(
            to_bhsd(q), to_bhsd(k), to_bhsd(v), valid[:, None, :],
            n_heads=h, causal=causal, scale=scale,
            q_block=q_block, kv_block=kv_block, interpret=interpret,
        )
        return out.reshape(b, h, s_q, d).transpose(0, 2, 1, 3)

    @jax.custom_vjp
    def f(q, k, v, valid):
        return forward(q, k, v, valid)

    def fwd(q, k, v, valid):
        return forward(q, k, v, valid), (q, k, v, valid)

    def bwd(res, g):
        q, k, v, valid = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: blockwise_attention(
                q_, k_, v_, causal=causal, block_size=kv_block, scale=scale,
                kv_valid=valid > 0.0),
            q, k, v,
        )
        dq, dk, dv = vjp(g)
        return dq, dk, dv, jnp.zeros_like(valid)

    f.defvjp(fwd, bwd)
    return f


#: measured per-length block optima on v5e (scripts/flash_tune.py,
#: dispatch-amortized, jitted both sides; re-run after kernel/toolchain
#: changes). Keys are the smallest sweep length ≥ S; larger S reuse the
#: longest entry. Override per deployment:
#: PIO_FLASH_BLOCKS="8192:2048x512,16384:1024x1024,32768:1024x1024"
_FLASH_BLOCK_TABLE: "tuple" = (
    # (max_seq, q_block, kv_block)
    (8192, 2048, 512),      # 3.99 ms vs 13.10 ms XLA blockwise (3.3×)
    (16384, 1024, 1024),    # 9.06 ms vs 38.51 ms (4.3×)
    (1 << 62, 1024, 1024),  # 27.97 ms vs 161 ms at 32k (5.8×)
)


def _parse_block_env() -> "Optional[tuple]":
    raw = os.environ.get("PIO_FLASH_BLOCKS", "").strip()
    if not raw:
        return None
    try:
        entries = []
        for part in raw.split(","):
            s, _, qk = part.partition(":")
            qb, _, kb = qk.partition("x")
            entry = (int(s), int(qb), int(kb))
            if min(entry) <= 0:
                raise ValueError("block sizes must be positive")
            entries.append(entry)
        entries.sort()
        # the last entry also covers every longer sequence
        entries[-1] = (1 << 62, entries[-1][1], entries[-1][2])
        return tuple(entries)
    except ValueError:
        import logging

        logging.getLogger(__name__).warning(
            "ignoring malformed PIO_FLASH_BLOCKS=%r "
            "(want e.g. 8192:2048x512,16384:1024x1024)", raw)
        return None


_FLASH_BLOCKS_ACTIVE = _parse_block_env() or _FLASH_BLOCK_TABLE


def default_flash_blocks(s_q: int) -> "tuple":
    """(q_block, kv_block) for sequence length ``s_q`` from the measured
    table (or the PIO_FLASH_BLOCKS override)."""
    for max_s, qb, kb in _FLASH_BLOCKS_ACTIVE:
        if s_q <= max_s:
            return qb, kb
    return 1024, 1024


def flash_attention(
    q: jax.Array,                   # [B, S, H, D]
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    kv_valid: Optional[jax.Array] = None,   # [S] or [B, S] bool
    q_block: Optional[int] = None,
    kv_block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused attention on BSHD arrays; same contract as
    ops.attention.dot_product_attention / blockwise_attention.

    K/V stream through VMEM one [kv_block, D] tile at a time (the kv scan
    is a grid dimension; the online-softmax state rides in VMEM scratch),
    so VMEM use is S-independent — any sequence length fits, and causal
    query blocks skip their strictly-future KV blocks. The [S, S] logit
    matrix never exists in HBM. Block defaults come from the measured
    per-length table (:data:`_FLASH_BLOCK_TABLE`, scripts/flash_tune.py
    sweep on v5e; PIO_FLASH_BLOCKS overrides): with them flash beats the
    XLA blockwise scan 3.3× at S=8k, 4.3× at 16k and 5.8× at 32k —
    transformer._default_attn routes to flash above FLASH_MIN_SEQ.
    Differentiable: backward runs through the XLA blockwise reference
    (see :func:`_flash_with_vjp`).
    """
    interpret = _resolve_interpret(interpret)
    b, _s_q, _h, d = q.shape
    s_kv = k.shape[1]
    sc = scale if scale is not None else d ** -0.5
    if q_block is None or kv_block is None:
        dq, dk = default_flash_blocks(_s_q)
        q_block = dq if q_block is None else q_block
        kv_block = dk if kv_block is None else kv_block

    if kv_valid is None:
        valid = jnp.ones((b, s_kv), jnp.float32)
    elif kv_valid.ndim == 1:
        valid = jnp.broadcast_to(
            kv_valid.astype(jnp.float32)[None, :], (b, s_kv))
    else:
        valid = kv_valid.astype(jnp.float32)

    fn = _flash_with_vjp(bool(causal), float(sc), int(q_block),
                         int(kv_block), bool(interpret))
    return fn(q, k, v, valid)


# ---------------------------------------------------------------------------
# Kernel 3: fused ALS bucket solve (Gram + CG entirely in VMEM)
# ---------------------------------------------------------------------------
#
# The ALS half-sweep's HBM profile under the XLA path (ops/als.py) is
# dominated by the [rows, K, K] Gram batch: one write at assembly plus one
# full re-read per CG iteration — (1 + iters)·rows·K² elements per side
# (~32 GB of the ~42 GB user-side stream at ML-20M/bf16). This kernel
# removes that stream entirely: each program streams one row's gathered
# factor blocks [dt, K] through VMEM, accumulates the K×K Gram and the rhs
# in VMEM scratch, then runs ALL Jacobi-PCG iterations against the
# VMEM-resident Gram and writes only the [K] solution back to HBM. Per-row
# HBM traffic drops from (1+iters)·K² + D·K to D·K — the gathered blocks,
# read exactly once.
#
# (The verdict-suggested alternative — Gram-free CG as two thin einsums
# per iteration — RAISES traffic at ML-20M shapes: its per-iteration stream
# is 2·nnz·K vs the Gram re-read's rows·K², a ratio of 2·D̄/K ≈ 2.3× on
# the user side and ≈ 11.7× on the item side. Keeping the Gram but
# pinning it in VMEM beats both.)


def _als_cg_kernel(g_ref, wv_ref, lam_ref, x0_ref, o_ref, gram_ref,
                   rhs_ref, *, iters: int, n_d_blocks: int, precise: bool,
                   warm: bool):
    """One (row, d-block) program of the fused bucket solve.

    Mosaic block-shape note: the TPU lowering requires each of the last
    two block dims to be sublane/lane aligned (8/128) OR equal to the
    array dim. A [B, dt]-shaped aux with block (1, dt) violates the
    sublane rule, so every per-row aux rides as [B, 1, x] with block
    (1, 1, x) — last-two dims (1, x) equal the array dims exactly.

    g_ref:   [1, dt, Kp]  this row's masked gathered factors, one d tile
                          (bf16 on the fast schedule; mask already applied,
                          so gram = gᵗg and rhs = wvᵗg need no masking here
                          — mask² == mask)
    wv_ref:  [1, 1, dt]   vals·mask d tile, f32 (legal under the rule
                          above: sublane dim 1 equals the array dim 1,
                          lane dim dt is a 128 multiple)
    lam_ref: [1, 1, Kp]   per-row ridge λ(+λ·nnz), broadcast across K
                          (f32; applied INSIDE the matvec so the Gram can
                          stay in its compute dtype without rounding the
                          regularizer)
    x0_ref:  [1, 1, Kp]   CG warm start (zeros + ``warm=False`` → cold)
    o_ref:   [1, 1, Kp]   solution, written on the last d step
    gram/rhs scratch persist across the d-minor grid steps (flash-kernel
    accumulator pattern).
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)
        rhs_ref[...] = jnp.zeros_like(rhs_ref)

    g = g_ref[0]                                         # [dt, Kp]
    wv = wv_ref[0]                                       # [1, dt]
    # bf16 inputs take the MXU single-pass (DEFAULT); the f32 polish path
    # pins HIGHEST so its Gram never silently truncates to bf16 passes —
    # the exact failure mode the XLA path documents (_solve_bucket:
    # "DEFAULT precision stalls ALS convergence around RMSE 0.6")
    prec = (jax.lax.Precision.HIGHEST if precise
            else jax.lax.Precision.DEFAULT)
    gram_ref[...] += jax.lax.dot_general(
        g, g, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    )
    rhs_ref[...] += jax.lax.dot_general(
        wv.astype(g.dtype), g,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    )

    @pl.when(j == n_d_blocks - 1)
    def _solve():
        gram = gram_ref[...]                             # [Kp, Kp] f32
        lam = lam_ref[0]                                 # [1, Kp]
        kp = gram.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (kp, kp), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (kp, kp), 1)
        diag = jnp.sum(jnp.where(row == col, gram, 0.0), axis=0,
                       keepdims=True) + lam              # [1, Kp]
        minv = jnp.where(diag > 0, 1.0 / diag, 0.0)
        b = rhs_ref[...]                                 # [1, Kp]

        # Jacobi-PCG, numerics matching ops/als.py _cg_solve_spd:
        # cold x = 0 start or warm start from the previous sweep
        # (one extra matvec for the initial residual); division guards
        # make converged/empty systems fixed points (rank-padding coords
        # have b = 0, gram row 0 → they stay exactly 0: a zero x0 row
        # keeps the cold fixed point)
        def matvec(p):
            return jax.lax.dot_general(
                p, gram, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            ) + lam * p                                  # [1, Kp]

        def body(_, carry):
            x, r, p, rz = carry
            ap = matvec(p)
            pap = jnp.sum(p * ap, keepdims=True)[..., :1]   # [1, 1]
            alpha = jnp.where(pap > 0, rz / pap, 0.0)
            x = x + alpha * p
            r = r - alpha * ap
            z = minv * r
            rz2 = jnp.sum(r * z, keepdims=True)[..., :1]
            beta = jnp.where(rz > 0, rz2 / rz, 0.0)
            p = z + beta * p
            return x, r, p, rz2

        if warm:
            x0 = x0_ref[0]                               # [1, Kp]
            r0 = b - matvec(x0)
        else:
            x0 = jnp.zeros_like(b)
            r0 = b
        z0 = minv * r0
        rz0 = jnp.sum(r0 * z0, keepdims=True)[..., :1]
        x, _r, _p, _rz = jax.lax.fori_loop(
            0, iters, body, (x0, r0, z0, rz0))
        o_ref[0] = x


def _als_cg_kernel_rows(g_ref, wv_ref, lam_ref, x0_ref, o_ref, gram_ref,
                        rhs_ref, *, iters: int, n_d_blocks: int,
                        precise: bool, warm: bool):
    """Row-grouped variant of :func:`_als_cg_kernel`: R rows per program.

    The one-row kernel is per-program-overhead-bound at ML-20M shape
    (~165k programs per half-sweep, each with ~0.1 µs of real work);
    grouping R=8 sublane-aligned rows cuts the program count 8× and
    batches the CG across the group. Aux arrays are plain 2-D here —
    an R-row block satisfies Mosaic's sublane rule directly.

    g_ref:   [R, dt, Kp]  row group's masked gathered factors, one d tile
    wv_ref:  [R, dt]      vals·mask tile, f32
    lam_ref: [R, Kp]      per-row ridge, broadcast across K
    x0_ref:  [R, Kp]      CG warm start (zeros + ``warm=False`` → cold)
    o_ref:   [R, Kp]      solutions, written on the last d step
    gram/rhs scratch: [R, Kp, Kp] / [R, Kp], persist across d steps.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)
        rhs_ref[...] = jnp.zeros_like(rhs_ref)

    g = g_ref[...]                                       # [R, dt, Kp]
    wv = wv_ref[...].astype(g.dtype)                     # [R, dt]
    prec = (jax.lax.Precision.HIGHEST if precise
            else jax.lax.Precision.DEFAULT)
    # Mosaic's dot lowering is 2-D only (batched dot_general fails to
    # parse) — unroll the static R rows; each Gram update stays one
    # [dt,Kp]ᵗ[dt,Kp] MXU pass
    for r in range(g.shape[0]):
        g_r = g[r]                                       # [dt, Kp]
        gram_ref[r] += jax.lax.dot_general(
            g_r, g_r, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        rhs_ref[r:r + 1] += jax.lax.dot_general(
            wv[r:r + 1], g_r, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )

    @pl.when(j == n_d_blocks - 1)
    def _solve():
        gram = gram_ref[...]                             # [R, Kp, Kp] f32
        lam = lam_ref[...]                               # [R, Kp]
        r_n, kp = gram.shape[0], gram.shape[1]
        row = jax.lax.broadcasted_iota(jnp.int32, (r_n, kp, kp), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (r_n, kp, kp), 2)
        diag = jnp.sum(jnp.where(row == col, gram, 0.0), axis=1) + lam
        minv = jnp.where(diag > 0, 1.0 / diag, 0.0)      # [R, Kp]
        b = rhs_ref[...]                                 # [R, Kp]

        def matvec(p):
            # gram is symmetric; [R,Kp,Kp]·[R,Kp] as a VPU
            # broadcast-reduce (8·128² f32 — tiny), sidestepping
            # Mosaic's 2-D-only dots for the batched case
            return jnp.sum(gram * p[:, :, None], axis=1) + lam * p

        # batched Jacobi-PCG, numerics per ops/als.py _cg_solve_spd;
        # every reduction is per-row so groups never mix. Cold x = 0 or
        # warm start from the previous sweep (one extra matvec); zero
        # padding rows keep the cold fixed point either way
        def body(_, carry):
            x, r, p, rz = carry
            ap = matvec(p)
            pap = jnp.sum(p * ap, axis=1, keepdims=True)    # [R, 1]
            alpha = jnp.where(pap > 0, rz / pap, 0.0)
            x = x + alpha * p
            r = r - alpha * ap
            z = minv * r
            rz2 = jnp.sum(r * z, axis=1, keepdims=True)
            beta = jnp.where(rz > 0, rz2 / rz, 0.0)
            p = z + beta * p
            return x, r, p, rz2

        if warm:
            x0 = x0_ref[...]                             # [R, Kp]
            r0 = b - matvec(x0)
        else:
            x0 = jnp.zeros_like(b)
            r0 = b
        z0 = minv * r0
        rz0 = jnp.sum(r0 * z0, axis=1, keepdims=True)
        x, _r, _p, _rz = jax.lax.fori_loop(
            0, iters, body, (x0, r0, z0, rz0))
        o_ref[...] = x


def als_padded_dims(d: int, k: int) -> Tuple[int, int]:
    """(dp, kp) padding of :func:`als_solve_cg_pallas` — THE single copy
    of its padding math; the kernel and its chunk-sizing callers both
    derive from this so they can never drift."""
    return max(_LANES, _round_up(d, _LANES)), _round_up(k, _LANES)


def als_padded_row_elems(d: int, k: int) -> int:
    """Per-row element footprint of the [B, dp, kp] gather the kernel
    materializes (ops/als.py _solve_bucket_chunked sizes HBM chunks with
    this)."""
    dp, kp = als_padded_dims(d, k)
    return dp * kp


#: rows per program for the fused ALS solve. 1 = the proven one-program-
#: per-row layout; 8 = sublane-aligned row groups (8× fewer programs,
#: batched CG) — the per-program-overhead lever. Sweep on chip with
#: scripts/als_kernel_bench.py (PIO_TUNE_ROWS) before changing the
#: default.
_ALS_ROWS = int(os.environ.get("PIO_ALS_KERNEL_ROWS", "1"))


def als_solve_cg_pallas(
    table: jax.Array,              # [M, K] factor table (bf16 fast path)
    cols: jax.Array,               # [B, D] int32
    vals: jax.Array,               # [B, D] f32
    mask: jax.Array,               # [B, D] f32 in {0, 1}
    l2: float,
    reg_nnz: bool = True,
    iters: int = 16,
    interpret: Optional[bool] = None,
    rows_per_program: Optional[int] = None,
    x0: Optional[jax.Array] = None,   # [B, K] f32 CG warm start
) -> jax.Array:
    """Fused normal-equation solve for one bucket chunk → [B, K] f32.

    Drop-in for the explicit-feedback CG leg of ops/als.py _solve_bucket
    (same regularization semantics: λ·max(nnz,1) ridge when ``reg_nnz``,
    plain λ otherwise; empty rows solve to 0). The gather stays in XLA —
    one [B, D, K] masked-gather pass — and this kernel consumes it in one
    streamed read; the [B, K, K] Gram batch never touches HBM.

    D is padded to a lane multiple (min 128) and K to a 128 multiple;
    padding columns carry zero mask/vals and padding rank coordinates
    solve to exactly 0 (see kernel docstring), so the slice-back is
    exact. ``rows_per_program`` > 1 (sublane multiples only) pads the row
    count and runs the row-grouped kernel; padding rows carry zero
    mask/vals and solve to exactly 0, sliced away on return. ``x0``
    warm-starts the in-VMEM CG from the previous sweep's factors (rank
    padding rides as zero columns, which stay exact fixed points).
    """
    interpret = _resolve_interpret(interpret)
    rows = _ALS_ROWS if rows_per_program is None else int(rows_per_program)
    # group sizes must satisfy Mosaic's sublane rule: 1 (the [B,1,x] aux
    # layout) or a multiple of 8 (a (rows, dt) block). Anything else is
    # rounded UP to the next legal group instead of crashing the
    # lowering mid-training.
    rows = 1 if rows <= 1 else _round_up(rows, 8)
    B, d = cols.shape
    k = table.shape[1]
    dp, kp = als_padded_dims(d, k)
    # dt must DIVIDE dp or the floored grid would silently skip the
    # remainder tile (dp is always a multiple of 128, so 128 divides)
    dt = next(t for t in (512, 256, 128) if dp % t == 0)

    gathered = table[cols]                               # [B, D, K]
    g = gathered * mask[..., None].astype(gathered.dtype)
    wv2 = jnp.pad((vals * mask).astype(jnp.float32),
                  ((0, 0), (0, dp - d)))
    nnz = jnp.sum(mask, axis=-1)
    lam = l2 * (jnp.maximum(nnz, 1.0) if reg_nnz
                else jnp.ones_like(nnz))
    warm = x0 is not None
    x0p = (jnp.pad(x0.astype(jnp.float32), ((0, 0), (0, kp - k)))
           if warm else None)
    n_d = dp // dt

    if rows > 1:
        bp = _round_up(B, rows)
        g = jnp.pad(g, ((0, bp - B), (0, dp - d), (0, kp - k)))
        wv2 = jnp.pad(wv2, ((0, bp - B), (0, 0)))
        # padding rows get λ of an empty system (b = 0, gram = 0 → x = 0)
        lam_b = jnp.pad(jnp.broadcast_to(lam[:, None], (B, kp)),
                        ((0, bp - B), (0, 0)), constant_values=1.0)
        # the x0 operand exists only on the warm path — cold kernels
        # never read it, so a zeros buffer would be pure padding traffic
        ops = [g, wv2, lam_b]
        in_specs = [
            pl.BlockSpec((rows, dt, kp), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, dt), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, kp), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ]
        if warm:
            ops.append(jnp.pad(x0p, ((0, bp - B), (0, 0))))
            in_specs.append(pl.BlockSpec((rows, kp), lambda i, j: (i, 0),
                                         memory_space=pltpu.VMEM))
        body = functools.partial(_als_cg_kernel_rows, iters=int(iters),
                                 n_d_blocks=n_d,
                                 precise=table.dtype == jnp.float32,
                                 warm=warm)
        if warm:
            kfn = body
        else:
            # positional ref alignment: without the x0 operand the
            # kernel signature's x0_ref slot must not swallow o_ref
            def kfn(g_ref, wv_ref, lam_ref, o_ref, gram_ref, rhs_ref):
                return body(g_ref, wv_ref, lam_ref, None, o_ref,
                            gram_ref, rhs_ref)
        out = pl.pallas_call(
            kfn,
            grid=(bp // rows, n_d),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((rows, kp), lambda i, j: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((bp, kp), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((rows, kp, kp), jnp.float32),  # gram acc
                pltpu.VMEM((rows, kp), jnp.float32),      # rhs acc
            ],
            interpret=interpret,
            name="pio_als_cg_rows",
        )(*ops)
        return out[:B, :k]

    g = jnp.pad(g, ((0, 0), (0, dp - d), (0, kp - k)))
    # per-row auxes ride as [B, 1, x] — see kernel docstring block note
    wv = wv2[:, None, :]
    lam_b = jnp.broadcast_to(lam[:, None, None], (B, 1, kp))

    ops = [g, wv, lam_b]
    in_specs = [
        pl.BlockSpec((1, dt, kp), lambda i, j: (i, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, dt), lambda i, j: (i, 0, j),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, kp), lambda i, j: (i, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    if warm:
        # cold kernels never read x0 — the operand only exists warm
        ops.append(x0p[:, None, :])
        in_specs.append(pl.BlockSpec((1, 1, kp), lambda i, j: (i, 0, 0),
                                     memory_space=pltpu.VMEM))
    body1 = functools.partial(_als_cg_kernel, iters=int(iters),
                              n_d_blocks=n_d,
                              precise=table.dtype == jnp.float32,
                              warm=warm)
    if warm:
        kfn1 = body1
    else:
        def kfn1(g_ref, wv_ref, lam_ref, o_ref, gram_ref, rhs_ref):
            return body1(g_ref, wv_ref, lam_ref, None, o_ref, gram_ref,
                         rhs_ref)
    out = pl.pallas_call(
        kfn1,
        # d is the MINOR grid dim: programs revisiting one row's output
        # run consecutively, carrying gram/rhs in scratch
        grid=(B, n_d),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, kp), lambda i, j: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, 1, kp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((kp, kp), jnp.float32),   # gram accumulator
            pltpu.VMEM((1, kp), jnp.float32),    # rhs accumulator
        ],
        interpret=interpret,
        name="pio_als_cg",
    )(*ops)
    return out[:, 0, :k]


# ---------------------------------------------------------------------------
# Kernel 4: fully fused ALS bucket solve (gather + Gram + CG in VMEM)
# ---------------------------------------------------------------------------
#
# Kernel 3 removed the [rows, K, K] Gram stream but still consumes an
# XLA-materialized [B, D, K] gather — one full HBM write + read of
# nnz·K elements per half-sweep. When the OTHER side's factor table fits
# VMEM (the ML-20M item table: 26.7k × 128 bf16 ≈ 6.9 MB), this kernel
# removes that stream too: the whole table rides into VMEM once per
# program chain, each program gathers its row's factor blocks directly
# from the VMEM-resident table (jnp.take on the loaded block), weights
# them, accumulates the K×K Gram and rhs in scratch, and runs every CG
# iteration in VMEM. Per-row HBM traffic drops from dp·K (the gather
# read) + 3·dp (cols/vals/mask) to just 3·dp + K — the interaction
# triplets and the solution.
#
# One kernel covers all three production variants: explicit ALS-WR
# (λ(·nnz) ridge), implicit Hu-Koren-Volinsky (the batch-shared YᵗY term
# rides as one [K, K] operand added inside the matvec — never
# materialized per row), and CG warm start (``x0``). The per-entry
# weights are folded host/XLA-side into two [B, D] vectors so the kernel
# body is variant-free:
#
#   gram_w  = mask            (explicit)   | α·r·mask        (implicit)
#   rhs_w   = vals·mask       (explicit)   | (1 + α·r)·mask  (implicit)
#   gram   += Σ_d gram_w_d · t_d t_dᵀ ;  rhs += Σ_d rhs_w_d · t_d
#
# (identical to ops/als._gram_rhs_nnz term-for-term: mask² == mask and
# the implicit confidences already carry the mask factor).


def _als_fused_kernel(tab_ref, cols_ref, gw_ref, rw_ref, lam_ref, yty_ref,
                      x0_ref, o_ref, gram_ref, rhs_ref, *, iters: int,
                      n_d_blocks: int, precise: bool, warm: bool,
                      shared: bool):
    """One (row, d-block) program of the fused gather+Gram+CG solve.

    tab_ref:  [Mp, Kp]    the WHOLE other-side factor table (block == array
                          → trivially Mosaic-legal; the index map is
                          constant so the pipeline keeps it VMEM-resident
                          across grid steps)
    cols_ref: [1, 1, dt]  this row's interaction column ids, one d tile
    gw_ref:   [1, 1, dt]  per-entry Gram weight (see module comment)
    rw_ref:   [1, 1, dt]  per-entry rhs weight, f32
    lam_ref:  [1, 1, Kp]  per-row ridge, broadcast across K
    yty_ref:  [Kp, Kp]    batch-shared implicit term (``shared`` only)
    x0_ref:   [1, 1, Kp]  CG warm start (``warm`` only)
    o_ref:    [1, 1, Kp]  solution, written on the last d step
    gram/rhs scratch persist across the d-minor grid steps."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)
        rhs_ref[...] = jnp.zeros_like(rhs_ref)

    idx = cols_ref[0, 0]                                 # [dt] int32
    tab = tab_ref[...]                                   # [Mp, Kp]
    g = jnp.take(tab, idx, axis=0)                       # [dt, Kp] in VMEM
    # weights ∈ {0,1}·stuff with the mask already folded in, so padding
    # entries (idx 0) contribute exactly 0 to gram AND rhs
    gw = gw_ref[0, 0].astype(g.dtype)                    # [dt]
    rw = rw_ref[0]                                       # [1, dt] f32
    prec = (jax.lax.Precision.HIGHEST if precise
            else jax.lax.Precision.DEFAULT)
    gram_ref[...] += jax.lax.dot_general(
        g * gw[:, None], g, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    )
    rhs_ref[...] += jax.lax.dot_general(
        rw.astype(g.dtype), g, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    )

    @pl.when(j == n_d_blocks - 1)
    def _solve():
        gram = gram_ref[...]                             # [Kp, Kp] f32
        lam = lam_ref[0]                                 # [1, Kp]
        kp = gram.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (kp, kp), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (kp, kp), 1)
        diag = jnp.sum(jnp.where(row == col, gram, 0.0), axis=0,
                       keepdims=True) + lam              # [1, Kp]
        if shared:
            yty = yty_ref[...]                           # [Kp, Kp] f32
            diag = diag + jnp.sum(jnp.where(row == col, yty, 0.0),
                                  axis=0, keepdims=True)
        minv = jnp.where(diag > 0, 1.0 / diag, 0.0)
        b = rhs_ref[...]                                 # [1, Kp]

        # Jacobi-PCG, numerics matching ops/als.py _cg_solve_spd: the
        # ridge (and the shared YᵗY) stay OUT of the matrix, applied
        # inside the matvec in f32; division guards make converged/empty
        # systems fixed points (zero rows/rank padding stay exactly 0)
        def matvec(p):
            ap = jax.lax.dot_general(
                p, gram, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            ) + lam * p                                  # [1, Kp]
            if shared:
                ap = ap + jax.lax.dot_general(
                    p, yty, dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                )
            return ap

        def body(_, carry):
            x, r, p, rz = carry
            ap = matvec(p)
            pap = jnp.sum(p * ap, keepdims=True)[..., :1]   # [1, 1]
            alpha = jnp.where(pap > 0, rz / pap, 0.0)
            x = x + alpha * p
            r = r - alpha * ap
            z = minv * r
            rz2 = jnp.sum(r * z, keepdims=True)[..., :1]
            beta = jnp.where(rz > 0, rz2 / rz, 0.0)
            p = z + beta * p
            return x, r, p, rz2

        if warm:
            x0 = x0_ref[0]                               # [1, Kp]
            r0 = b - matvec(x0)
        else:
            x0 = jnp.zeros_like(b)
            r0 = b
        z0 = minv * r0
        rz0 = jnp.sum(r0 * z0, keepdims=True)[..., :1]
        x, _r, _p, _rz = jax.lax.fori_loop(
            0, iters, body, (x0, r0, z0, rz0))
        o_ref[0] = x


def als_fused_row_elems(d: int, k: int) -> int:
    """Per-row HBM element footprint of the fused-gather path: the
    cols/gram-weight/rhs-weight tiles plus the lam/x0/out vectors — the
    [B, dp, kp] gather of the two-stage path never materializes, so
    chunk sizing (ops/als.py _solve_bucket_chunked) keys on this much
    smaller figure."""
    dp, kp = als_padded_dims(d, k)
    return 3 * dp + 3 * kp


def als_fused_table_bytes(m_rows: int, rank: int, dtype=jnp.float32) -> int:
    """VMEM bytes of the padded gather table the fused kernel pins."""
    kp = _round_up(max(rank, 1), _LANES)
    mp = _round_up(max(m_rows, 8), 8)
    return mp * kp * jnp.dtype(dtype).itemsize


def als_fused_vmem_budget_bytes() -> int:
    """Table budget for the fused-gather kernel (``PIO_ALS_FUSED_VMEM_MB``,
    default 10 MB). VMEM is ~16 MB/core on current TPUs; the budget
    covers the resident table only — the double-buffered [dt, Kp] tiles,
    the [Kp, Kp] Gram scratch and the CG vectors ride in the remainder
    (≲ 0.5 MB at dt=512, K=128). Read per call, never frozen at import."""
    try:
        mb = float(os.environ.get("PIO_ALS_FUSED_VMEM_MB", "") or 10.0)
    except ValueError:
        mb = 10.0
    return int(mb * (1 << 20))


def als_fused_fits(m_rows: int, rank: int, dtype=jnp.float32) -> bool:
    """True when the other-side table fits the fused kernel's VMEM
    budget. At ML-20M shape: the item table (26.7k × 128 bf16 ≈ 6.9 MB)
    fits — the USER half-sweep (the heavy side) runs fully fused; the
    user table (138k × 128 ≈ 35 MB bf16) does not — the item half-sweep
    keeps the two-stage kernel. The check is pure host arithmetic on
    static shapes, resolved OUTSIDE any trace."""
    return als_fused_table_bytes(m_rows, rank, dtype) \
        <= als_fused_vmem_budget_bytes()


def als_fused_solve_cg_pallas(
    table: jax.Array,              # [M, K] gather source (bf16 fast path)
    cols: jax.Array,               # [B, D] int32
    vals: jax.Array,               # [B, D] f32
    mask: jax.Array,               # [B, D] f32 in {0, 1}
    l2,
    reg_nnz: bool = True,
    iters: int = 16,
    implicit: bool = False,
    alpha: float = 1.0,
    yty: Optional[jax.Array] = None,   # [K, K] f32 — implicit only
    x0: Optional[jax.Array] = None,    # [B, K] f32 CG warm start
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused gather+normal-equation solve for one bucket chunk → [B, K].

    Same contract as the explicit-CG leg of ops/als.py ``_solve_bucket``
    (and, with ``implicit=True`` + ``yty``, as ``_solve_bucket_implicit``
    at the caller's doubled budget): λ·max(nnz,1) / λ ridge, empty rows
    solve to exactly 0. Unlike :func:`als_solve_cg_pallas`, the gather
    happens INSIDE the kernel against the VMEM-resident table — callers
    must gate on :func:`als_fused_fits` for the table's shape/dtype.
    Padding (D → lane multiple, K → 128 multiple, padding cols id 0 with
    zero weights) is exact: padded coordinates stay fixed at 0.

    The in-kernel gather is a ``jnp.take`` on the loaded table block —
    exact in interpret mode. It does NOT lower on the installed TPU
    compiler (jax 0.9.0 Mosaic ``_gather_lowering_rule``: "Shape mismatch
    in input, indices and output" — only same-shape, single-vreg
    ``take_along_axis`` gathers exist there), so ops/als.py
    ``_fused_enabled`` keeps it off the ``auto`` route; it runs only
    under ``PIO_ALS_FUSED_GRAM=on`` in interpret mode (CPU tests).
    tests/test_tpu_aot_compile.py carries the strict xfail that turns
    green when a compiler accepts it."""
    interpret = _resolve_interpret(interpret)
    B, d = cols.shape
    m, k = table.shape
    dp, kp = als_padded_dims(d, k)
    mp = _round_up(max(m, 8), 8)
    # dt must DIVIDE dp (dp is always a 128 multiple, so 128 divides)
    dt = next(t for t in (512, 256, 128) if dp % t == 0)
    n_d = dp // dt

    tab = table
    if (mp, kp) != tab.shape:
        tab = jnp.zeros((mp, kp), table.dtype).at[:m, :k].set(tab)
    maskf = mask.astype(jnp.float32)
    if implicit:
        gw = alpha * vals * maskf          # (c − 1), 0 on padding
        rw = maskf + gw                    # (1 + α·r)·mask
    else:
        gw = maskf
        rw = vals * maskf
    colsp = jnp.pad(cols, ((0, 0), (0, dp - d)))[:, None, :]
    gw = jnp.pad(gw, ((0, 0), (0, dp - d)))[:, None, :]
    rw = jnp.pad(rw, ((0, 0), (0, dp - d)))[:, None, :]
    nnz = jnp.sum(maskf, axis=-1)
    if implicit:
        lam = jnp.full_like(nnz, l2)
    else:
        lam = l2 * (jnp.maximum(nnz, 1.0) if reg_nnz
                    else jnp.ones_like(nnz))
    lam_b = jnp.broadcast_to(lam[:, None, None], (B, 1, kp))
    shared = implicit
    warm = x0 is not None

    ops = [tab, colsp, gw, rw, lam_b]
    in_specs = [
        pl.BlockSpec((mp, kp), lambda i, j: (0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, dt), lambda i, j: (i, 0, j),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, dt), lambda i, j: (i, 0, j),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, dt), lambda i, j: (i, 0, j),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, kp), lambda i, j: (i, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    if shared:
        ytyp = yty.astype(jnp.float32)
        if (kp, kp) != ytyp.shape:
            ytyp = jnp.zeros((kp, kp), jnp.float32).at[:k, :k].set(ytyp)
        ops.append(ytyp)
        in_specs.append(pl.BlockSpec((kp, kp), lambda i, j: (0, 0),
                                     memory_space=pltpu.VMEM))
    if warm:
        ops.append(jnp.pad(x0.astype(jnp.float32),
                           ((0, 0), (0, kp - k)))[:, None, :])
        in_specs.append(pl.BlockSpec((1, 1, kp), lambda i, j: (i, 0, 0),
                                     memory_space=pltpu.VMEM))
    body = functools.partial(_als_fused_kernel, iters=int(iters),
                             n_d_blocks=n_d,
                             precise=table.dtype == jnp.float32,
                             warm=warm, shared=shared)
    # positional ref alignment: absent optional operands must not let a
    # later ref slot swallow o_ref (same pattern as als_solve_cg_pallas)
    if shared and warm:
        kfn = body
    elif shared:
        def kfn(t, c, g, r, l, y, o, gr, rh):
            return body(t, c, g, r, l, y, None, o, gr, rh)
    elif warm:
        def kfn(t, c, g, r, l, x, o, gr, rh):
            return body(t, c, g, r, l, None, x, o, gr, rh)
    else:
        def kfn(t, c, g, r, l, o, gr, rh):
            return body(t, c, g, r, l, None, None, o, gr, rh)
    out = pl.pallas_call(
        kfn,
        # d is the MINOR grid dim: programs revisiting one row's output
        # run consecutively, carrying gram/rhs in scratch
        grid=(B, n_d),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, kp), lambda i, j: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, 1, kp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((kp, kp), jnp.float32),   # gram accumulator
            pltpu.VMEM((1, kp), jnp.float32),    # rhs accumulator
        ],
        interpret=interpret,
        name="pio_als_fused",
    )(*ops)
    # empty rows solve to EXACTLY 0 (the _reg_solve where-guard): the
    # cold kernel holds that fixed point by construction, but a warm
    # start on a zero-nnz row would leave a converging-to-zero residue
    return jnp.where(nnz[:, None] > 0, out[:, 0, :k], 0.0)


def als_kernel_available() -> bool:
    """Routing predicate of the two-stage ALS bucket solve
    (:func:`als_solve_cg_pallas`; ops/als.py ``_kernel_enabled``). Every
    variant the route can dispatch — 1-row and 8-row layouts, warm and
    cold, bf16 and f32 tables — is compiled for the chip at ML-20M widths
    by tests/test_tpu_aot_compile.py. The fused-gather generation
    (:func:`als_fused_solve_cg_pallas`) is NOT covered by this predicate:
    it does not lower on the installed compiler and ``auto`` never selects
    it (ops/als.py ``_fused_enabled``)."""
    return pallas_available()
