"""Attention kernels for the sequence model family.

The reference has no attention anywhere (SURVEY.md §5 "Long-context");
sequence behavior tops out at MarkovChain transitions. This framework's
sequence engines (models/sequence/) are transformer-based, so attention is a
first-class hot op designed for the MXU:

- :func:`dot_product_attention` — dense reference implementation (and the
  fast path for short sequences: one fused softmax(QKᵀ)V per head).
- :func:`blockwise_attention` — FlashAttention-style online-softmax over KV
  blocks via ``lax.scan``: O(S) memory in sequence length, static shapes,
  MXU-sized [block × head_dim] matmuls. This is the single-device
  long-context path; the distributed path wraps it per-shard
  (parallel/ring.py ring attention).
- :func:`kernel_attention` — causal attention on a TPU through the Pallas
  kernel that ships with jax (splash attention): grouped-query heads read
  their key-value head in place (nothing is repeated), a sliding window
  skips the key blocks behind it, and no score block ever reaches HBM.
  The XLA scan above writes and re-reads a float32 ``[B, H, 512, 512]``
  score block a step: 48 of 185 ms of a 16,384-token dispatch of the
  sequence cell (PERF.md §6, PR 33).

All functions take [batch, seq, heads, head_dim] ("BSHD") arrays.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

#: scores at masked positions — large-negative instead of -inf so a fully
#: masked row exps to exactly 0 without NaNs from (-inf) - (-inf)
MASK_VALUE = -1e30


def _scale(q, scale: Optional[float]) -> float:
    return scale if scale is not None else q.shape[-1] ** -0.5


def _combine_masks(causal, q_pos, kv_pos, kv_valid, window=None):
    """Broadcastable [B|1, 1, Q, K] boolean mask, or None if unmasked.

    ``kv_valid`` is a per-key padding mask, [K] or [B, K]. ``window``
    (sliding-window layers) keeps the keys at distance ``t − s < window``
    from the query: the token itself and the ``window − 1`` before it.
    """
    mask = None
    if causal:
        mask = (q_pos[:, None] >= kv_pos[None, :])[None, None]
    if window is not None:
        near = (q_pos[:, None] - kv_pos[None, :] < window)[None, None]
        mask = near if mask is None else (mask & near)
    if kv_valid is not None:
        vm = kv_valid if kv_valid.ndim == 2 else kv_valid[None]
        vm = vm[:, None, None, :]
        mask = vm if mask is None else (mask & vm)
    return mask


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_offset: int = 0,
    kv_valid: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Dense softmax(QKᵀ)V on [B, S, H, D] inputs.

    ``q_offset``/``kv_offset`` are the global positions of the first query /
    key row — this is what lets sequence-sharded callers (ring attention)
    reuse the same masking rule on local blocks. ``kv_valid`` ([K] or
    [B, K]) masks padding keys; ``window`` is a sliding-window layer's
    reach (see :func:`_combine_masks`).
    """
    s = _scale(q, scale)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * s
    q_pos = q_offset + jnp.arange(q.shape[1])
    kv_pos = kv_offset + jnp.arange(k.shape[1])
    mask = _combine_masks(causal, q_pos, kv_pos, kv_valid, window)
    if mask is not None:
        logits = jnp.where(mask, logits, MASK_VALUE)
    p = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    if mask is not None:
        # zero (not softmax-uniform) output for fully masked rows — the
        # invariant the sequence-sharded kernels rely on when a shard's
        # whole KV block is in the future
        p = jnp.where(mask, p, 0.0)
    l = p.sum(axis=-1, keepdims=True)
    probs = (p / jnp.where(l == 0.0, 1.0, l)).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _online_block(q, k_blk, v_blk, m, l, o, scale, causal, q_pos, kv_pos,
                  kv_valid=None, window=None):
    """One online-softmax accumulation step against a single KV block.

    Carries (m, l, o) = running rowmax, normalizer, unnormalized output in
    f32. Shared by blockwise_attention and ring attention so the numerics
    are identical on one chip and on a sequence-sharded mesh. ``kv_valid``
    masks padded tail keys independently of causality.
    """
    s_blk = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32
    ) * scale
    mask = _combine_masks(causal, q_pos, kv_pos, kv_valid, window)
    if mask is not None:
        s_blk = jnp.where(mask, s_blk, MASK_VALUE)
    # m_new is always finite (masked scores are MASK_VALUE), so the exps
    # below never see (-inf) - (-inf); the initial m = -inf just makes the
    # first block's correction factor exp(-inf - m_new) = 0
    m_new = jnp.maximum(m, s_blk.max(axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s_blk - m_new[..., None])
    if mask is not None:
        # zero masked probabilities so a fully-masked block adds no mass
        # (exp(MASK_VALUE - MASK_VALUE) would otherwise be 1)
        p = jnp.where(mask, p, 0.0)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
    )
    return m_new, l_new, o_new


def _finalize(m, l, o, dtype):
    # fully-masked rows (l == 0) produce 0 output rather than NaN
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return jnp.einsum("bhqd->bqhd", o / l_safe[..., None]).astype(dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_size", "scale",
                                             "window"))
def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_size: int = 512,
    scale: Optional[float] = None,
    kv_valid: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Online-softmax attention scanning KV in blocks ([B, S, H, D] in/out).

    Memory is O(S·block) instead of O(S²); the scan is a static-length
    ``lax.scan`` so XLA pipelines the per-block matmuls on the MXU.
    ``kv_valid`` ([K] or [B, K]) masks padding keys. Under a causal or
    ``window`` mask the queries go block by block too, and each query
    block scans only the key blocks it can see (static ranges): a block
    wholly in the future, or wholly behind the window, is never computed.
    """
    b, s_q, h, d = q.shape
    s_kv = k.shape[1]
    blk = min(block_size, s_kv)
    n_blocks = -(-s_kv // blk)
    pad = n_blocks * blk - s_kv
    if pad or kv_valid is not None:
        # fold ragged-tail padding into one per-key validity mask
        if kv_valid is None:
            valid = jnp.ones((1, s_kv), bool)
        else:
            valid = jnp.broadcast_to(
                kv_valid if kv_valid.ndim == 2 else kv_valid[None],
                (kv_valid.shape[0] if kv_valid.ndim == 2 else 1, s_kv),
            )
        valid = jnp.pad(valid, ((0, 0), (0, pad)))  # pads with False
    else:
        valid = None
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sc = _scale(q, scale)

    k_blocks = k.reshape(b, n_blocks, blk, h, d).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, n_blocks, blk, h, d).transpose(1, 0, 2, 3, 4)
    valid_blocks = (
        None if valid is None
        else valid.reshape(valid.shape[0], n_blocks, blk).transpose(1, 0, 2)
    )

    def attend(q_part, q_pos, lo, hi):
        """``q_part`` against key blocks ``lo`` .. ``hi − 1``."""
        n_q = q_part.shape[1]

        def step(carry, xs):
            m, l, o = carry
            i, k_blk, v_blk = xs[:3]
            kv_pos = i * blk + jnp.arange(blk)
            m, l, o = _online_block(
                q_part, k_blk, v_blk, m, l, o, sc, causal, q_pos, kv_pos,
                kv_valid=xs[3] if len(xs) > 3 else None, window=window,
            )
            return (m, l, o), None

        init = (
            jnp.full((b, h, n_q), -jnp.inf, jnp.float32),
            jnp.zeros((b, h, n_q), jnp.float32),
            jnp.zeros((b, h, n_q, d), jnp.float32),
        )
        xs = (jnp.arange(lo, hi), k_blocks[lo:hi], v_blocks[lo:hi])
        if valid_blocks is not None:
            xs += (valid_blocks[lo:hi],)
        (m, l, o), _ = lax.scan(step, init, xs)
        return _finalize(m, l, o, q.dtype)

    if not causal and window is None:
        return attend(q, jnp.arange(s_q), 0, n_blocks)
    outs = []
    for qs in range(0, s_q, blk):
        qe = min(qs + blk, s_q) - 1
        hi = min(qe // blk + 1, n_blocks) if causal else n_blocks
        lo = 0 if window is None else max(qs - window + 1, 0) // blk
        if hi <= lo:    # every key this block could see lies past the end
            outs.append(jnp.zeros((b, qe + 1 - qs, h, d), q.dtype))
        else:
            outs.append(attend(q[:, qs:qe + 1], jnp.arange(qs, qe + 1),
                               lo, hi))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


#: the attention kernel's tile (query rows a program, key rows a step)
#: and the rotary pass's (rows a program)
KERNEL_BLOCK = 512
ROTATE_ROWS = 1024


def kernel_attention_fits(dtype, length: int, head_dim: int) -> bool:
    """True when :func:`rotate_heads_first` and :func:`kernel_attention`
    serve a block of these shapes on this backend: a TPU, bfloat16, more
    than the dense path's 1,024 positions in whole tiles of both, heads
    of one lane tile."""
    from incubator_predictionio_tpu.ops.pallas_kernels import (
        pallas_available,
    )

    return (pallas_available() and jnp.dtype(dtype) == jnp.bfloat16
            and length > 1024 and length % KERNEL_BLOCK == 0
            and length % ROTATE_ROWS == 0 and head_dim == 128)


def _rotate_kernel(x_ref, cos_ref, sin_ref, o_ref, *, scale: float):
    from jax.experimental.pallas import tpu as pltpu

    x = x_ref[0].astype(jnp.float32)                        # [rows, dh]
    # rotate-half: (−x₂, x₁) is x turned half a tile round, the sign
    # carried by the sine table
    turned = pltpu.roll(x, x.shape[1] // 2, axis=1)
    o_ref[0, 0] = ((x * cos_ref[...] + turned * sin_ref[...])
                   * scale).astype(o_ref.dtype)


def rotate_heads_first(x: jax.Array, cos: jax.Array, sin: jax.Array,
                       n_heads: int, scale: float = 1.0,
                       interpret: bool = False) -> jax.Array:
    """[B, H, L, dh]: a projection's output ``x`` [B, L, H·dh] turned by
    its rotary positions (``cos``, ``sin`` [L, dh] float32, the halves
    repeated), times ``scale``, laid out heads first as the attention
    kernel reads it. One pass: XLA's own lowering of rotate-half leaves a
    float32 copy of the heads, two layout copies and two half-width slices
    in HBM (PERF.md §6, PR 33)."""
    from jax.experimental import pallas as pl

    b, l, width = x.shape
    dh = width // n_heads
    rows = min(l, ROTATE_ROWS)
    half = jnp.arange(dh) < dh // 2
    return pl.pallas_call(
        functools.partial(_rotate_kernel, scale=scale),
        grid=(b, l // rows, n_heads),
        in_specs=[pl.BlockSpec((1, rows, dh), lambda i, r, h: (i, r, h)),
                  pl.BlockSpec((rows, dh), lambda i, r, h: (r, 0)),
                  pl.BlockSpec((rows, dh), lambda i, r, h: (r, 0))],
        out_specs=pl.BlockSpec((1, 1, rows, dh),
                               lambda i, r, h: (i, h, r, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_heads, l, dh), x.dtype),
        interpret=interpret,
        name="pio_rotate_heads_first",
    )(x, cos, jnp.where(half, -sin, sin))


def kernel_attention(
    q: jax.Array,                   # [B, H, S, D], the scale already in it
    k: jax.Array,                   # [B, Hkv, S, D], H a multiple of Hkv
    v: jax.Array,
    kv_valid: Optional[jax.Array] = None,   # [B, S] bool
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal softmax(QKᵀ)V, heads first in and out, through jax's
    splash-attention kernel.

    Query head ``j`` reads key-value head ``j // (H / Hkv)``. ``window``
    keeps the token itself and the ``window − 1`` before it; key blocks
    wholly outside the mask are never loaded. ``kv_valid`` masks padding
    keys for the valid queries (the kernel's segment ids: a query sees
    the keys of its own kind, so a padding query reads the padding keys
    before it and never divides by nothing; what it computes is unused).
    """
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as masks,
    )

    _, h, s, _ = q.shape
    blk = min(KERNEL_BLOCK, s)
    one = (masks.CausalMask((s, s)) if window is None
           else masks.LocalMask((s, s), (window - 1, 0), 0))
    kernel = splash.make_splash_mha(
        masks.MultiHeadMask([one] * h),
        block_sizes=splash.BlockSizes(
            block_q=blk, block_kv=blk, block_kv_compute=blk),
        head_shards=1, q_seq_shards=1, interpret=interpret)
    if kv_valid is None:
        return jax.vmap(kernel)(q, k, v)
    kind = kv_valid.astype(jnp.int32)
    return jax.vmap(lambda q_, k_, v_, kind_: kernel(
        q_, k_, v_, segment_ids=splash.SegmentIds(q=kind_, kv=kind_)))(
            q, k, v, kind)
