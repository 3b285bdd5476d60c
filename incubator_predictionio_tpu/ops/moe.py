"""Routed expert feed-forward layer (sparse mixture of experts).

The reference has no expert layer anywhere; the sequence engine's block
description (ops/transformer.py ``BlockSpec``) asks for one when a
layer's feed-forward is ``routed-swiglu``.

One token's layer: ``p = softmax(h·Wr)`` over all experts in float32,
``S`` = the ``top_k`` largest, ``w_e = p_e / Σ_S p`` (renormalised), and
``y = Σ_{e∈S} w_e · Wdown_e(silu(Wgate_e·h) ⊙ Wup_e·h)``.

TPU design notes:
- No capacity factor and no dropped token: the dispatch's ``T × top_k``
  assignments are SORTED by expert and the three expert matmuls are
  grouped matrix products over the sorted rows (row block ``g`` of the
  left operand meets table ``g`` of the right), so every shape is static
  whatever the routing.
- On a TPU the experts' feed-forward is ONE Pallas kernel a tile of 256
  sorted rows (:func:`_experts_pallas`): both up products, ``silu·up``
  and the down product stay in VMEM, an expert's three tables are loaded
  once for all its tiles, and HBM sees the rows once in and once out;
  the tiles' bookkeeping is that of the grouped matmul that ships with
  jax (``jax.experimental.pallas.ops.tpu.megablox``). Elsewhere, and for
  row counts the tile does not divide, three ``jax.lax.ragged_dot``. On
  a v5e ``ragged_dot`` ran at 25–28% of its roofline at 131,072 rows,
  jax's grouped matmul at 77% with a pass between the products, this
  kernel at 84% of the whole feed-forward's (48% at 16,384 rows, where
  the tables' load is most of it; PERF.md §6, PR 33).
- The router runs in float32 (logits, softmax, top-k): a routed expert
  flips on rounding, and bf16 logits flip many.
- The un-sort is a gather by the inverse permutation, not a scatter-add:
  deterministic, and each token's ``top_k`` partial results are summed in
  a fixed order.
- The per-expert token counts the grouped products need anyway are
  returned with the result, for the load counters.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ExpertWeights:
    router: Any     # [D, E]
    w_gate: Any     # [E, D, F]
    w_up: Any       # [E, D, F]
    w_down: Any     # [E, F, D]


def route(h: jax.Array, router: jax.Array, top_k: int
          ) -> Tuple[jax.Array, jax.Array]:
    """(weights [T, top_k] float32 renormalised over the chosen, experts
    [T, top_k] int32) for tokens ``h`` [T, D]."""
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    return top_p / top_p.sum(axis=-1, keepdims=True), top_e.astype(jnp.int32)


#: rows of sorted assignments a program of the TPU kernel takes
KERNEL_ROWS = 256
#: VMEM the kernel may use: three tables of one expert, twice (the next
#: expert's load under this one's products), beside the row tiles
KERNEL_VMEM_BYTES = 64 << 20


def _experts_kernel(offsets_ref, groups_ref, tiles_ref, xs_ref, gate_ref,
                    up_ref, down_ref, out_ref, *, rows: int):
    """One visit: the rows of tile ``tiles[i]`` that belong to expert
    ``groups[i]`` through that expert's feed-forward. A tile two experts
    share is visited once by each, one after the other, and each stores
    its own rows."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    i = pl.program_id(0)
    xs = xs_ref[...]
    gate = jnp.dot(xs, gate_ref[...], preferred_element_type=f32)
    up = jnp.dot(xs, up_ref[...], preferred_element_type=f32)
    act = (jax.nn.silu(gate) * up).astype(xs.dtype)
    ys = jnp.dot(act, down_ref[...], preferred_element_type=f32)
    row = tiles_ref[i] * rows + jax.lax.broadcasted_iota(
        jnp.int32, ys.shape, 0)
    mine = ((row >= offsets_ref[groups_ref[i]])
            & (row < offsets_ref[groups_ref[i] + 1]))
    out_ref[...] = jnp.where(mine, ys, out_ref[...].astype(f32)
                             ).astype(out_ref.dtype)


def _experts_pallas(xs: jax.Array, w: ExpertWeights, group_sizes: jax.Array,
                    rows: int, interpret: bool = False) -> jax.Array:
    """The experts' feed-forward as ONE kernel a tile of sorted rows: both
    up products, ``silu·up`` and the down product stay in VMEM, so HBM
    sees the rows once in and once out (three kernels and a pass between
    them moved ``[rows, F]`` five times; PERF.md §6, PR 33). The tiles'
    bookkeeping is jax's own grouped matmul's (megablox)."""
    import importlib

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    m, d = xs.shape
    f = w.w_gate.shape[2]
    (offsets, groups, tiles), visits = megablox.make_group_metadata(
        group_sizes=group_sizes, m=m, tm=rows,
        start_group=jnp.int32(0), num_nonzero_groups=group_sizes.shape[0],
        visit_empty_groups=False)
    tile = lambda i, offsets, groups, tiles: (tiles[i], 0)
    table = lambda i, offsets, groups, tiles: (groups[i], 0, 0)
    return pl.pallas_call(
        functools.partial(_experts_kernel, rows=rows),
        out_shape=jax.ShapeDtypeStruct((m, d), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((rows, d), tile),
                      pl.BlockSpec((None, d, f), table),
                      pl.BlockSpec((None, d, f), table),
                      pl.BlockSpec((None, f, d), table)],
            out_specs=pl.BlockSpec((rows, d), tile),
            grid=(visits,)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=KERNEL_VMEM_BYTES),
        interpret=interpret,
        name="pio_moe_experts",
    )(offsets, groups, tiles, xs, w.w_gate, w.w_up, w.w_down)


def grouped_swiglu(xs: jax.Array, w: ExpertWeights,
                   group_sizes: jax.Array) -> jax.Array:
    """The experts' feed-forward over rows sorted by expert: rows
    ``[Σ group_sizes[:g], Σ group_sizes[:g+1])`` go through expert ``g``.
    Returns [rows, D] in ``xs.dtype``."""
    from incubator_predictionio_tpu.ops.pallas_kernels import (
        pallas_available,
    )

    if pallas_available() and xs.shape[0] % KERNEL_ROWS == 0 \
            and xs.dtype == jnp.bfloat16:
        return _experts_pallas(xs, w, group_sizes, KERNEL_ROWS)
    f32 = jnp.float32
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes)
    gate = dot(xs, w.w_gate, preferred_element_type=f32)
    up = dot(xs, w.w_up, preferred_element_type=f32)
    act = (jax.nn.silu(gate) * up).astype(xs.dtype)
    # the MXU accumulates in float32 whatever it stores: asking for the
    # activations' dtype saves writing and re-reading a float32 [rows, D]
    return dot(act, w.w_down, preferred_element_type=xs.dtype)


def moe_apply(h: jax.Array, w: ExpertWeights, top_k: int
              ) -> Tuple[jax.Array, jax.Array]:
    """(y [T, D] in ``h.dtype``, tokens routed to each expert [E] int32)
    for normed tokens ``h`` [T, D]."""
    t, d = h.shape
    n_experts = w.router.shape[1]
    with jax.named_scope("seq.moe.route"):
        weights, experts = route(h, w.router, top_k)
        flat = experts.reshape(t * top_k)
        order = jnp.argsort(flat, stable=True)          # assignment ids
        # a compare and a sum: a scatter-add of T·k ones (bincount) took
        # 1.1 ms a layer at 131,072 assignments on a v5e
        group_sizes = jnp.sum(
            flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype),
            axis=0, dtype=jnp.int32)
        xs = h[order // top_k]                          # [T·k, D] sorted
    with jax.named_scope("seq.moe.experts"):
        ys = grouped_swiglu(xs, w, group_sizes)
    with jax.named_scope("seq.moe.route"):
        inverse = jnp.argsort(order)    # half a scatter's time on a v5e
        per_choice = ys[inverse].reshape(t, top_k, d).astype(jnp.float32)
        y = jnp.einsum("tkd,tk->td", per_choice, weights)
    return y.astype(h.dtype), group_sizes
