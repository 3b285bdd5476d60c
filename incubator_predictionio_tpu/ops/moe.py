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
- On a TPU the experts' feed-forward is ONE Pallas kernel
  (:func:`_experts_pallas`): a visit brings a tile of 256 sorted rows
  into VMEM and takes only the blocks of it that hold rows of its expert
  (:func:`kernel_rows` rows each) through both up products, ``silu·up``
  and the down product, so HBM sees the rows once in and once out and
  the MXU multiplies few rows it then masks. An expert's three tables
  are copied in once for all its visits, by the kernel itself and a
  whole expert ahead (the grid's own pipeline looks one visit ahead,
  which is shorter than the copy once a visit skips blocks). The tiles'
  bookkeeping is that of the grouped matmul that ships with jax
  (``jax.experimental.pallas.ops.tpu.megablox``). Elsewhere, and for row
  counts the tile does not divide, three ``jax.lax.ragged_dot``. On a
  v5e ``ragged_dot`` ran at 25–28% of its roofline at 131,072 rows and
  jax's grouped matmul at 77% with a pass between the products (PERF.md
  §6, PR 33); this kernel's readings by width are in PERF.md §6, PR 34.
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
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


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


#: rows of sorted assignments a visit of the TPU kernel brings into VMEM
KERNEL_TILE_ROWS = 256
#: VMEM the kernel may use: three tables of one expert, twice (the next
#: expert's copy under this one's products), beside the row tiles
KERNEL_VMEM_BYTES = 64 << 20


def kernel_rows(m: int, n_experts: int) -> int:
    """Rows of a block of the TPU kernel, for ``m`` sorted rows over
    ``n_experts``. A visit multiplies the blocks of its tile that hold
    rows of its expert, so a group pays about one block more than the
    ``m / n_experts`` rows it holds on average: the smaller block wins
    until the MXU's rate on so few rows costs more than the rows saved.
    On a v5e, 64 experts of 2,304 × 896: where a group is at most a tile,
    32 rows (1.385 ms against 1.424 at 64 rows a block, 16,384 rows);
    above, 64 (2.468 against 2.480 at 32,768 rows, 8.93 against 9.31 at
    131,072); 16 rows run at half the MXU's rate (PERF.md §6, PR 34)."""
    return 32 if m <= n_experts * KERNEL_TILE_ROWS else 64


def rows_multiplied(group_sizes, rows: Optional[int]) -> int:
    """Rows the experts' products multiplied for ``group_sizes`` ([...,
    experts]: a layer's, or a row a layer; on the host): in blocks of
    ``rows`` — the kernel's, every block a group touches — or, with
    ``rows`` None, the routed rows themselves (three ``ragged_dot``). The
    routed rows over it is the share of the MXU's products that were
    kept."""
    sizes = np.asarray(group_sizes, np.int64)
    if rows is None:
        return int(sizes.sum())
    ends = np.cumsum(sizes, axis=-1)
    starts = ends - sizes
    blocks = np.where(sizes > 0, -(-ends // rows) - starts // rows, 0)
    return int(blocks.sum()) * rows


def _experts_kernel(offsets_ref, groups_ref, tiles_ref, slots_ref, nexts_ref,
                    xs_ref, gate_hbm, up_hbm, down_hbm, out_ref,
                    gate_buf, up_buf, down_buf, sems, *, rows: int):
    """One visit: the rows of tile ``tiles[i]`` that belong to expert
    ``groups[i]`` through that expert's feed-forward, a block of ``rows``
    at a time and only the blocks that hold such rows. A tile two experts
    share is visited once by each, one after the other, and each stores
    its own rows. The expert's tables lie in slot ``slots[expert]`` of the
    buffers: its first visit waits for their copy and starts the copy of
    ``nexts[expert]``'s, the next expert with rows (past the last: none),
    into the other slot."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    tile = xs_ref.shape[0]
    i = pl.program_id(0)
    expert = groups_ref[i]
    slot = slots_ref[expert]

    def copies(e, s):
        return [pltpu.make_async_copy(hbm.at[e], buf.at[s], sems.at[s, j])
                for j, (hbm, buf) in enumerate(((gate_hbm, gate_buf),
                                                (up_hbm, up_buf),
                                                (down_hbm, down_buf)))]

    @pl.when(i == 0)
    def _():
        for copy in copies(expert, slot):
            copy.start()

    @pl.when((i == 0) | (groups_ref[jnp.maximum(i - 1, 0)] != expert))
    def _():
        for copy in copies(expert, slot):
            copy.wait()
        following = nexts_ref[expert]

        @pl.when(following < gate_hbm.shape[0])
        def _():
            for copy in copies(following, 1 - slot):
                copy.start()

    # the expert's rows as they lie in this tile, and the blocks they touch
    at = tiles_ref[i] * tile
    first = jnp.maximum(offsets_ref[expert] - at, 0)
    last = jnp.minimum(offsets_ref[expert + 1] - at, tile)

    def block(b, carry):
        start = pl.multiple_of(b * rows, rows)
        xs = xs_ref[pl.ds(start, rows), :]
        gate = jnp.dot(xs, gate_buf[slot], preferred_element_type=f32)
        up = jnp.dot(xs, up_buf[slot], preferred_element_type=f32)
        act = (jax.nn.silu(gate) * up).astype(xs.dtype)
        ys = jnp.dot(act, down_buf[slot], preferred_element_type=f32)
        row = start + jax.lax.broadcasted_iota(jnp.int32, ys.shape, 0)
        mine = (row >= first) & (row < last)
        out_ref[pl.ds(start, rows), :] = jnp.where(
            mine, ys, out_ref[pl.ds(start, rows), :].astype(f32)
        ).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(first // rows, pl.cdiv(last, rows), block, None)


def _experts_pallas(xs: jax.Array, w: ExpertWeights, group_sizes: jax.Array,
                    rows: int, interpret: bool = False) -> jax.Array:
    """The experts' feed-forward as ONE kernel over tiles of sorted rows,
    multiplied in blocks of ``rows``: both up products, ``silu·up`` and
    the down product stay in VMEM, so HBM sees the rows once in and once
    out (three kernels and a pass between them moved ``[rows, F]`` five
    times; PERF.md §6, PR 33). The tiles' bookkeeping is jax's own grouped
    matmul's (megablox)."""
    import importlib

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    m, d = xs.shape
    e, _, f = w.w_gate.shape
    tile = KERNEL_TILE_ROWS
    (offsets, groups, tiles), visits = megablox.make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tile,
        start_group=jnp.int32(0), num_nonzero_groups=e,
        visit_empty_groups=False)
    # the experts with rows take the two slots in turn; the one after
    # expert g is the first with rows past it (e: none)
    some = group_sizes > 0
    slots = (jnp.cumsum(some, dtype=jnp.int32) - 1) % 2
    ids = jnp.arange(e, dtype=jnp.int32)
    at_or_after = jax.lax.cummin(jnp.where(some, ids, e), reverse=True)
    nexts = jnp.concatenate([at_or_after[1:], jnp.full((1,), e, jnp.int32)])
    rows_of = lambda i, offsets, groups, tiles, slots, nexts: (tiles[i], 0)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_experts_kernel, rows=rows),
        out_shape=jax.ShapeDtypeStruct((m, d), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=[pl.BlockSpec((tile, d), rows_of),
                      in_hbm, in_hbm, in_hbm],
            out_specs=pl.BlockSpec((tile, d), rows_of),
            grid=(visits,),
            scratch_shapes=[pltpu.VMEM((2, d, f), xs.dtype),
                            pltpu.VMEM((2, d, f), xs.dtype),
                            pltpu.VMEM((2, f, d), xs.dtype),
                            pltpu.SemaphoreType.DMA((2, 3))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=KERNEL_VMEM_BYTES),
        interpret=interpret,
        name="pio_moe_experts",
    )(offsets, groups, tiles, slots, nexts, xs, w.w_gate, w.w_up, w.w_down)


def kernel_serves(m: int, dtype) -> bool:
    """Whether :func:`grouped_swiglu` takes the TPU kernel for ``m`` rows
    of ``dtype``: on a TPU, bfloat16, whole tiles."""
    from incubator_predictionio_tpu.ops.pallas_kernels import (
        pallas_available,
    )

    return (pallas_available() and m % KERNEL_TILE_ROWS == 0
            and jnp.dtype(dtype) == jnp.bfloat16)


def grouped_swiglu(xs: jax.Array, w: ExpertWeights,
                   group_sizes: jax.Array) -> jax.Array:
    """The experts' feed-forward over rows sorted by expert: rows
    ``[Σ group_sizes[:g], Σ group_sizes[:g+1])`` go through expert ``g``.
    Returns [rows, D] in ``xs.dtype``."""
    if kernel_serves(xs.shape[0], xs.dtype):
        return _experts_pallas(
            xs, w, group_sizes,
            kernel_rows(xs.shape[0], group_sizes.shape[0]))
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
