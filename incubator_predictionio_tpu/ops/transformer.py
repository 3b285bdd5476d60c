"""Causal transformer for next-item prediction (the sequence engines).

No reference counterpart exists — the reference's only sequence behavior is
MarkovChain top-N transitions (e2/.../MarkovChain.scala:33); this is the
TPU-native upgrade of that capability: a SASRec-style self-attentive
session model over event-store item sequences.

One description, one forward: a block is a :class:`BlockSpec` (per-layer
attention kind and window, grouped-query heads with ``head_dim`` free of
``d_model``, learned or rotary positions — plain or YaRN-scaled —, a dense
gelu or a routed swiglu feed-forward, a tied or an untied head, a dtype)
and :func:`block_apply` runs it. The SASRec block ``transformer_init``
makes is one instance (:func:`sasrec_block`); a published language-model
block with sliding-window and full layers over routed experts is another.

TPU design notes:
- Layers are *stacked* pytrees scanned with ``lax.scan`` over whole
  periods of the layer pattern, the period's layers unrolled inside — one
  compiled body per period whatever the depth.
- Attention is pluggable: dense/blockwise on one chip
  (ops/attention.py), ring or Ulysses sequence parallelism on an ``sp``
  mesh axis (parallel/ring.py) for long sessions.
- The full fit loop (epochs × minibatches) runs inside one jit via a
  nested ``lax.scan`` over a pre-batched [steps, B, L] tensor; weights are
  donated so optimizer state lives on device across the whole run.
- Embedding/projection matmuls accumulate in f32 via
  ``preferred_element_type`` and are MXU-shaped ([B·L, D] × [D, V]).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import optax

from incubator_predictionio_tpu.ops.topk import (
    count_serve_program,
    top_k_with_exclusions,
)

#: attention callable: (q, k, v, causal) -> out, all [B, S, H, Dh]
AttnFn = Callable[..., jax.Array]

PAD = 0  # padding token; real items are 1..n_items


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TransformerWeights:
    item_emb: Any    # [V, D]  (tied output projection)
    pos_emb: Any     # [L, D]
    # stacked per-layer weights, leading axis = layer
    ln1_scale: Any   # [N, D]
    ln2_scale: Any   # [N, D]
    wq: Any          # [N, D, D]
    wk: Any          # [N, D, D]
    wv: Any          # [N, D, D]
    wo: Any          # [N, D, D]
    w_up: Any        # [N, D, 4D]
    w_down: Any      # [N, 4D, D]
    lnf_scale: Any   # [D]


def transformer_init(
    key: jax.Array,
    n_items: int,
    max_len: int,
    d_model: int = 64,
    n_layers: int = 2,
) -> TransformerWeights:
    ks = jax.random.split(key, 8)
    v = n_items + 1  # + PAD
    d, h = d_model, 4 * d_model

    def init(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    return TransformerWeights(
        item_emb=init(ks[0], (v, d), d ** -0.5),
        pos_emb=init(ks[1], (max_len, d), 0.02),
        ln1_scale=jnp.ones((n_layers, d)),
        ln2_scale=jnp.ones((n_layers, d)),
        wq=init(ks[2], (n_layers, d, d), d ** -0.5),
        wk=init(ks[3], (n_layers, d, d), d ** -0.5),
        wv=init(ks[4], (n_layers, d, d), d ** -0.5),
        wo=init(ks[5], (n_layers, d, d), d ** -0.5),
        w_up=init(ks[6], (n_layers, d, h), d ** -0.5),
        w_down=init(ks[7], (n_layers, h, d), h ** -0.5),
        lnf_scale=jnp.ones((d,)),
    )


@dataclasses.dataclass(frozen=True)
class Rotary:
    """Rotary positions of one layer kind. ``factor`` None is the plain
    kind (``inv_freq_i = theta^(−2i/dim)``); with a factor it is YaRN:
    frequencies above the ramp keep their wavelength, those below are
    interpolated by ``factor``, and cos/sin carry ``attention_factor``."""

    __camel_case__ = True

    theta: float
    factor: Optional[float] = None
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer of the period: causal attention over everything before
    (``window`` None) or over the token and the ``window − 1`` before it;
    ``rotary`` None leaves q and k as projected (learned positions)."""

    __camel_case__ = True

    window: Optional[int] = None
    rotary: Optional[Rotary] = None


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """A transformer block, hashable (a jit-static argument)."""

    __camel_case__ = True

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    #: the layers of one period of the pattern, in order
    period: Tuple[LayerSpec, ...]
    n_periods: int
    #: feed-forward: "dense-gelu" (one up/down pair of ``ffn_width``) or
    #: "routed-swiglu" (``n_experts`` of ``ffn_width``, ``experts_per_token``
    #: of them a token, ops/moe.py)
    ffn: str = "dense-gelu"
    ffn_width: int = 0
    n_experts: int = 0
    experts_per_token: int = 0
    #: a learned position table added to the embedding
    learned_positions: bool = False
    #: the output projection is the embedding table
    tied_head: bool = True
    dtype: str = "float32"
    norm_eps: float = 1e-6
    #: positions the block is run at: the rows of a learned position
    #: table, the length of a served user's window
    max_len: int = 0

    @property
    def n_layers(self) -> int:
        return len(self.period) * self.n_periods


def block_spec_from_json(doc: Any) -> BlockSpec:
    """The description as engine parameters carry it (camelCase keys)."""
    from incubator_predictionio_tpu.utils import json_codec

    spec = json_codec.extract(BlockSpec, doc)
    if spec.ffn not in ("dense-gelu", "routed-swiglu"):
        raise ValueError(f"unknown feed-forward kind {spec.ffn!r}")
    if spec.n_heads % spec.n_kv_heads:
        raise ValueError(f"{spec.n_heads} query heads do not divide over "
                         f"{spec.n_kv_heads} key-value heads")
    return spec


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DenseWeights:
    w_up: Any        # [P, D, F]
    w_down: Any      # [P, F, D]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LayerWeights:
    """One period position's weights, leading axis = period."""

    ln1_scale: Any   # [P, D]
    ln2_scale: Any   # [P, D]
    wq: Any          # [P, D, H·dh]
    wk: Any          # [P, D, Hkv·dh]
    wv: Any          # [P, D, Hkv·dh]
    wo: Any          # [P, H·dh, D]
    ffn: Any         # DenseWeights | ops.moe.ExpertWeights, stacked alike


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BlockWeights:
    item_emb: Any    # [V, D]
    pos_emb: Any     # [L, D] | None
    layers: Any      # tuple of LayerWeights, one per layer of the period
    lnf_scale: Any   # [D]
    head: Any        # [V, D] | None (tied)


def block_init(key: jax.Array, spec: BlockSpec, vocab: int,
               max_len: int) -> BlockWeights:
    """Seeded normal weights of a description (tests, small models)."""
    from incubator_predictionio_tpu.ops.moe import ExpertWeights

    dtype = jnp.dtype(spec.dtype)
    d, p = spec.d_model, spec.n_periods
    hq = spec.n_heads * spec.head_dim
    hkv = spec.n_kv_heads * spec.head_dim
    f, e = spec.ffn_width, spec.n_experts

    def init(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32)
                * scale).astype(dtype)

    def layer(k):
        ks = jax.random.split(k, 8)
        if spec.ffn == "routed-swiglu":
            ffn = ExpertWeights(
                router=init(ks[4], (p, d, e), d ** -0.5),
                w_gate=init(ks[5], (p, e, d, f), d ** -0.5),
                w_up=init(ks[6], (p, e, d, f), d ** -0.5),
                w_down=init(ks[7], (p, e, f, d), f ** -0.5))
        else:
            ffn = DenseWeights(w_up=init(ks[4], (p, d, f), d ** -0.5),
                               w_down=init(ks[5], (p, f, d), f ** -0.5))
        return LayerWeights(
            ln1_scale=jnp.ones((p, d), dtype),
            ln2_scale=jnp.ones((p, d), dtype),
            wq=init(ks[0], (p, d, hq), d ** -0.5),
            wk=init(ks[1], (p, d, hkv), d ** -0.5),
            wv=init(ks[2], (p, d, hkv), d ** -0.5),
            wo=init(ks[3], (p, hq, d), hq ** -0.5), ffn=ffn)

    ks = jax.random.split(key, 3 + len(spec.period))
    return BlockWeights(
        item_emb=init(ks[0], (vocab, d), 1.0),
        pos_emb=(init(ks[1], (max_len, d), 0.02)
                 if spec.learned_positions else None),
        layers=tuple(layer(k) for k in ks[3:]),
        lnf_scale=jnp.ones((d,), dtype),
        head=None if spec.tied_head else init(ks[2], (vocab, d), d ** -0.5))


def sasrec_block(w: TransformerWeights, n_heads: int
                 ) -> Tuple[BlockSpec, BlockWeights]:
    """The SASRec block as an instance of the description: a period of
    one full causal layer, as many periods as layers, learned positions,
    a dense gelu feed-forward, the head tied, float32."""
    d = w.item_emb.shape[1]
    spec = BlockSpec(
        d_model=d, n_heads=n_heads, n_kv_heads=n_heads,
        head_dim=d // n_heads, period=(LayerSpec(),),
        n_periods=w.wq.shape[0], ffn="dense-gelu",
        ffn_width=w.w_up.shape[2], learned_positions=True, tied_head=True,
        dtype=str(w.item_emb.dtype), max_len=w.pos_emb.shape[0])
    layer = LayerWeights(
        ln1_scale=w.ln1_scale, ln2_scale=w.ln2_scale, wq=w.wq, wk=w.wk,
        wv=w.wv, wo=w.wo, ffn=DenseWeights(w_up=w.w_up, w_down=w.w_down))
    return spec, BlockWeights(item_emb=w.item_emb, pos_emb=w.pos_emb,
                              layers=(layer,), lnf_scale=w.lnf_scale,
                              head=None)


def _rms_norm(x, scale, eps: float = 1e-6):
    # statistics in float32 whatever the activations' dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _mm(a, b):
    """``a @ b`` accumulated in float32, in the activations' dtype."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def rotary_inv_freq(rot: Rotary, dim: int) -> np.ndarray:
    """[dim/2] float64 inverse frequencies of one layer kind."""
    pos = rot.theta ** (2.0 * np.arange(dim // 2) / dim)
    if rot.factor is None:
        return 1.0 / pos

    def turns_at(n):   # the dimension whose wavelength makes n turns
        return (dim * np.log(rot.original_max_position / (2 * np.pi * n))
                / (2 * np.log(rot.theta)))

    low = max(int(np.floor(turns_at(rot.beta_fast))), 0)
    high = min(int(np.ceil(turns_at(rot.beta_slow))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high - low) or 0.001), 0.0, 1.0)
    return ramp / (rot.factor * pos) + (1.0 - ramp) / pos


def _rotary_tables(rot: Optional[Rotary], dim: int, length: int):
    """(cos, sin) [L, dim] float32, the halves repeated (rotate-half);
    no rotary turns nothing: (1, 0)."""
    if rot is None:
        return (jnp.ones((length, dim), jnp.float32),
                jnp.zeros((length, dim), jnp.float32))
    angles = (jnp.arange(length, dtype=jnp.float32)[:, None]
              * jnp.asarray(rotary_inv_freq(rot, dim), jnp.float32)[None])
    angles = jnp.concatenate([angles, angles], axis=-1)
    return (jnp.cos(angles) * rot.attention_factor,
            jnp.sin(angles) * rot.attention_factor)


def _apply_rotary(x, cos, sin):
    """[B, L, H, dh] rotated by its position, in float32."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[None, :, None, :]
            + turned * sin[None, :, None, :]).astype(x.dtype)


#: sequence length from which (inclusive) the Pallas flash kernel serves
#: instead of the XLA blockwise scan. Measured on v5e with dispatch
#: amortized (scripts/flash_tune.py sweeps block shapes and re-measures
#: this): with the per-length block table (pallas_kernels.py) flash wins
#: 3.3x at 8k, 4.3x at 16k, 5.8x at 32k. Below 8k is unmeasured on
#: chip, so the scan keeps it for now. Re-run the sweep after
#: kernel/toolchain changes and update here (or override via env).
def _flash_min_seq() -> int:
    raw = os.environ.get("PIO_FLASH_MIN_SEQ", "")
    try:
        return int(raw) if raw.strip() else 8192
    except ValueError:
        import logging

        logging.getLogger(__name__).warning(
            "ignoring malformed PIO_FLASH_MIN_SEQ=%r; using 8192", raw)
        return 8192


FLASH_MIN_SEQ = _flash_min_seq()


def _default_attn(q, k, v, causal=True, kv_valid=None, window=None):
    from incubator_predictionio_tpu.ops.attention import (
        blockwise_attention, dot_product_attention,
    )
    # flash streams KV block-by-block (kv is a grid dimension), so VMEM use
    # is S-independent — no length cap; the crossover constant above picks
    # the faster implementation per length. The kernel has no window
    # mask: a sliding-window layer stays on the scan, which skips the key
    # blocks behind the window.
    if FLASH_MIN_SEQ <= q.shape[1] and window is None:
        from incubator_predictionio_tpu.ops.pallas_kernels import (
            flash_attention, flash_available)
        if flash_available():
            return flash_attention(q, k, v, causal=causal, kv_valid=kv_valid)
    if q.shape[1] > 1024:
        return blockwise_attention(q, k, v, causal=causal, kv_valid=kv_valid,
                                   window=window)
    return dot_product_attention(q, k, v, causal=causal, kv_valid=kv_valid,
                                 window=window)


def block_apply(
    spec: BlockSpec,
    w: BlockWeights,
    tokens: jax.Array,          # [B, L] int32
    attn_fn: Optional[AttnFn] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(hidden states [B, L, D] after the final norm, tokens routed to
    each expert [n_layers, n_experts] int32 — no columns for a dense
    feed-forward)."""
    from incubator_predictionio_tpu.ops.attention import (
        kernel_attention, kernel_attention_fits, rotate_heads_first,
    )
    from incubator_predictionio_tpu.ops.moe import moe_apply

    attn = attn_fn or _default_attn
    b, l = tokens.shape
    h_q, h_kv, dh = spec.n_heads, spec.n_kv_heads, spec.head_dim
    x = w.item_emb[tokens]
    if spec.learned_positions:
        x = x + w.pos_emb[:l]
    # padding keys are masked out of every attention softmax
    kv_valid = tokens != PAD
    tables = {rot: _rotary_tables(rot, dh, l)
              for rot in {ls.rotary for ls in spec.period}}
    # the default backend on a TPU, for bfloat16 heads of one lane tile
    # over more positions than the dense path takes: the attention kernel
    # that ships with jax, fed by one rotary pass a projection
    on_kernel = attn_fn is None and kernel_attention_fits(spec.dtype, l, dh)

    def layer(x, ls: LayerSpec, lw: LayerWeights):
        with jax.named_scope("seq.attn.full" if ls.window is None
                             else "seq.attn.sliding"):
            h = _rms_norm(x, lw.ln1_scale, spec.norm_eps)
            q, k, v = _mm(h, lw.wq), _mm(h, lw.wk), _mm(h, lw.wv)
            cos, sin = tables[ls.rotary]
            if on_kernel:
                # one pass a projection: rotary, the scores' scale and the
                # kernel's layout; grouped heads stay as they are
                o = kernel_attention(
                    rotate_heads_first(q, cos, sin, h_q, dh ** -0.5),
                    rotate_heads_first(k, cos, sin, h_kv),
                    v.reshape(b, l, h_kv, dh).transpose(0, 2, 1, 3),
                    kv_valid=kv_valid, window=ls.window,
                ).transpose(0, 2, 1, 3)
            else:
                q = q.reshape(b, l, h_q, dh)
                k = k.reshape(b, l, h_kv, dh)
                v = v.reshape(b, l, h_kv, dh)
                if ls.rotary is not None:
                    q = _apply_rotary(q, cos, sin)
                    k = _apply_rotary(k, cos, sin)
                if h_kv != h_q:  # query head j reads key-value head j // group
                    k = jnp.repeat(k, h_q // h_kv, axis=2)
                    v = jnp.repeat(v, h_q // h_kv, axis=2)
                # a pluggable backend without a window argument (ring,
                # ulysses) still serves the layers that have none
                kw = {} if ls.window is None else {"window": ls.window}
                o = attn(q, k, v, causal=True, kv_valid=kv_valid, **kw)
            x = x + _mm(o.reshape(b, l, h_q * dh), lw.wo)
        h = _rms_norm(x, lw.ln2_scale, spec.norm_eps)
        if spec.ffn == "routed-swiglu":
            y, counts = moe_apply(h.reshape(b * l, spec.d_model), lw.ffn,
                                  spec.experts_per_token)
            return x + y.reshape(b, l, spec.d_model), counts
        return (x + _mm(jax.nn.gelu(_mm(h, lw.ffn.w_up)), lw.ffn.w_down),
                jnp.zeros((0,), jnp.int32))

    def period(x, layers):
        counts = []
        for ls, lw in zip(spec.period, layers):
            x, c = layer(x, ls, lw)
            counts.append(c)
        return x, jnp.stack(counts)

    x, counts = jax.lax.scan(period, x, w.layers)
    return (_rms_norm(x, w.lnf_scale, spec.norm_eps),
            counts.reshape(spec.n_layers, -1))


def head_logits(spec: BlockSpec, w: BlockWeights, hidden: jax.Array
                ) -> jax.Array:
    """[..., V] float32 logits of final-normed hidden states [..., D]."""
    table = w.item_emb if spec.tied_head else w.head
    return jnp.einsum("...d,vd->...v", hidden, table,
                      preferred_element_type=jnp.float32)


def transformer_apply(
    w: TransformerWeights,
    tokens: jax.Array,          # [B, L] int32
    n_heads: int,
    attn_fn: Optional[AttnFn] = None,
) -> jax.Array:
    """Hidden states [B, L, D] after the final norm."""
    spec, block = sasrec_block(w, n_heads)
    return block_apply(spec, block, tokens, attn_fn)[0]


def next_item_logits(
    w: TransformerWeights, tokens: jax.Array, n_heads: int,
    attn_fn: Optional[AttnFn] = None,
) -> jax.Array:
    """[B, L, V] logits with the output projection tied to item_emb."""
    h = transformer_apply(w, tokens, n_heads, attn_fn)
    return jnp.einsum(
        "bld,vd->blv", h, w.item_emb, preferred_element_type=jnp.float32
    )


@functools.partial(
    jax.jit,
    static_argnames=("n_heads", "learning_rate", "epochs", "attn_fn"),
    donate_argnames=("w", "tx_state"),
)
def _fit_scan(w, batches, tx_state, n_heads, learning_rate, epochs,
              attn_fn=None):
    tx = optax.adamw(learning_rate)

    def loss_fn(w, batch):
        logits = next_item_logits(w, batch[:, :-1], n_heads, attn_fn)
        targets = batch[:, 1:]
        mask = (targets != PAD) & (batch[:, :-1] != PAD)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        return jnp.sum(ce * mask) / jnp.maximum(mask.sum(), 1)

    def step(carry, batch):
        w, s = carry
        loss, grads = jax.value_and_grad(loss_fn)(w, batch)
        updates, s = tx.update(grads, s, w)
        return (optax.apply_updates(w, updates), s), loss

    def epoch(carry, _):
        carry, losses = jax.lax.scan(step, carry, batches)
        return carry, losses.mean()

    (w, tx_state), losses = jax.lax.scan(
        epoch, (w, tx_state), None, length=epochs
    )
    return w, losses


def sasrec_fit(
    sequences: np.ndarray,      # [N, L] int32, PAD-padded, items 1..n_items
    n_items: int,
    d_model: int = 64,
    n_heads: int = 2,
    n_layers: int = 2,
    epochs: int = 20,
    batch_size: int = 128,
    learning_rate: float = 1e-3,
    seed: int = 0,
    attn_fn: Optional[AttnFn] = None,
) -> tuple[TransformerWeights, np.ndarray]:
    """Train on next-item prediction; returns (weights, per-epoch loss).

    ``attn_fn`` selects the attention backend — e.g. a
    ``functools.partial(ring_attention, mesh=mesh)`` for sequence-parallel
    training of long sessions. It must be hashable (jit-static).
    """
    seqs = np.asarray(sequences, np.int32)
    n, max_len = seqs.shape
    if d_model % n_heads:
        raise ValueError(f"d_model {d_model} not divisible by {n_heads} heads")
    w = transformer_init(
        jax.random.key(seed), n_items, max_len, d_model, n_layers
    )
    # pre-batch into [steps, B, L]; ragged tail is padded with PAD-only rows
    # (masked out of the loss)
    bs = min(batch_size, n)
    steps = -(-n // bs)
    pad_rows = steps * bs - n
    if pad_rows:
        seqs = np.concatenate(
            [seqs, np.zeros((pad_rows, max_len), np.int32)]
        )
    rng = np.random.default_rng(seed)
    seqs = seqs[rng.permutation(len(seqs))]
    batches = jnp.asarray(seqs.reshape(steps, bs, max_len))
    tx_state = optax.adamw(learning_rate).init(w)
    w, losses = _fit_scan(w, batches, tx_state, n_heads,
                          learning_rate, epochs, attn_fn)
    return w, np.asarray(losses)


@functools.partial(jax.jit, static_argnames=("n_heads", "k"))
def sasrec_topk(
    w: TransformerWeights,
    tokens: jax.Array,          # [B, L] recent history, PAD-padded LEFT
    n_heads: int,
    k: int = 10,
) -> tuple[jax.Array, jax.Array]:
    """Top-k next items from the last position's hidden state.

    Returns (scores [B, k], item ids [B, k]); PAD is never returned.
    """
    h = transformer_apply(w, tokens, n_heads)
    last = h[:, -1]                                       # [B, D]
    scores = jnp.einsum(
        "bd,vd->bv", last, w.item_emb, preferred_element_type=jnp.float32
    )
    # never recommend PAD or items already in the history (PAD ∈ history
    # columns, so the vmap covers it)
    scores = jax.vmap(lambda s, t: s.at[t].set(-jnp.inf))(scores, tokens)
    scores = scores.at[:, PAD].set(-jnp.inf)
    return jax.lax.top_k(scores, k)


def _block_top_k(spec: BlockSpec, w: BlockWeights, tokens: jax.Array,
                 k: int) -> jax.Array:
    """The serving readout, packed for ONE fetch: ``[B·k scores | B·k item
    tokens | B PAD positions a row | n_layers·n_experts tokens routed]``
    float32 (every count is exact below 2**24). PAD and the row's own
    items are never returned; a masked slot scores ``ops/topk.NEG_INF``."""
    hidden, counts = block_apply(spec, w, tokens)
    with jax.named_scope("seq.head"):
        logits = head_logits(spec, w, hidden[:, -1])          # [B, V]
        struck = jnp.concatenate(
            [tokens, jnp.full((tokens.shape[0], 1), PAD, tokens.dtype)], 1)
        top_s, top_i = jax.vmap(
            lambda s, t: top_k_with_exclusions(s, k, exclude=t))(
                logits, struck)
    f32 = jnp.float32
    return jnp.concatenate([
        top_s.reshape(-1), top_i.astype(f32).reshape(-1),
        (tokens == PAD).sum(axis=1).astype(f32),
        counts.astype(f32).reshape(-1)])


@count_serve_program
@functools.partial(jax.jit, static_argnames=("spec", "k"))
def block_top_k_tokens(spec: BlockSpec, w: BlockWeights,
                       tokens: jax.Array, k: int) -> jax.Array:
    """Top-k next items for explicit windows ``tokens`` [B, L] (PAD-padded
    LEFT); see :func:`_block_top_k` for the packed result."""
    return _block_top_k(spec, w, tokens, k)


@count_serve_program
@functools.partial(jax.jit, static_argnames=("spec", "k"))
def block_top_k_rows(spec: BlockSpec, w: BlockWeights, windows: jax.Array,
                     rows: jax.Array, k: int) -> jax.Array:
    """Top-k next items for the resident users ``rows`` [B]: their windows
    are gathered from ``windows`` [n_users, L] on the device, so a
    dispatch is one launch (the rows ride up as this program's own
    argument) and one fetch."""
    return _block_top_k(spec, w, windows[rows], k)


def unpack_top_k(packed: np.ndarray, batch: int, k: int, spec: BlockSpec):
    """(scores [B, k], item tokens [B, k] int64, PAD positions [B], tokens
    routed [n_layers, n_experts]) of a fetched :func:`_block_top_k`."""
    a, b, c = batch * k, 2 * batch * k, 2 * batch * k + batch
    return (packed[:a].reshape(batch, k),
            packed[a:b].reshape(batch, k).astype(np.int64),
            packed[b:c].astype(np.int64),
            packed[c:].reshape(spec.n_layers, -1).astype(np.int64))
