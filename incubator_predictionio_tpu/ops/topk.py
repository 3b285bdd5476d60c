"""Device-side top-K scoring with exclusions.

Serving a recommendation query in the reference is a driver-side loop over
an in-memory factor map (examples/.../ALSModel.scala recommendProducts). On
TPU the whole catalog is scored in one [1, K] × [K, I] matmul and ranked
with ``lax.top_k`` without leaving the device; seen/blocked items are masked
to -inf before ranking (business-rule filtering at serve time, parity with
the ecommerce template's filtering serve step).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from incubator_predictionio_tpu.obs import profile as _profile
from incubator_predictionio_tpu.ops import mips as _mips

NEG_INF = jnp.float32(-3.4e38)

#: precision of every exhaustive scoring matmul here. The factors are
#: f32 and the answers are compared with — and must rank like — plain f32
#: arithmetic (the host mirror, the byte-identity contract between the
#: serving paths). A TPU's DEFAULT precision multiplies f32 operands in
#: ONE bf16 pass: measured on a v5e at ML-20M shape (PERF.md, PR 21),
#: the batched [B, K]·[K, I] dispatch then returned a different top-10
#: for 16 of 64 users and scores off by up to 2.4e-3 relative; HIGHEST
#: gave 64 of 64 and 4.5e-7. (The matvec paths were already exact there;
#: pinned all the same so the paths cannot drift apart. On the CPU
#: backend the argument changes nothing.)
_EXACT = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("k",))
def top_k_with_exclusions(
    scores: jax.Array,              # [I] f32
    k: int,
    exclude: Optional[jax.Array] = None,   # [E] int32 item ids, -1 = no-op
    allowed_mask: Optional[jax.Array] = None,  # [I] bool — serve-time filter
) -> Tuple[jax.Array, jax.Array]:
    """Returns (top_scores[k], top_indices[k])."""
    if allowed_mask is not None:
        scores = jnp.where(allowed_mask, scores, NEG_INF)
    if exclude is not None:
        # negative ids would wrap numpy-style; remap to n so "drop" drops them
        safe = jnp.where(exclude < 0, scores.shape[-1], exclude)
        scores = scores.at[safe].set(NEG_INF, mode="drop")
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _score_and_top_k_xla(
    user_vector: jax.Array,
    item_factors: jax.Array,
    k: int,
    exclude: Optional[jax.Array] = None,
    allowed_mask: Optional[jax.Array] = None,
) -> jax.Array:
    scores = jnp.dot(item_factors, user_vector, precision=_EXACT)
    top_s, top_i = top_k_with_exclusions(scores, k, exclude, allowed_mask)
    return jnp.stack([top_s, top_i.astype(jnp.float32)])


#: catalogs below this use the fused XLA matvec+top_k (lower fixed cost);
#: above it the Pallas blocked kernel's HBM-write savings win (measured
#: crossover on v5e: XLA ahead at 131k items, Pallas ahead at 1M)
PALLAS_MIN_ITEMS = 500_000


# ---------------------------------------------------------------------------
# Sharded serving: per-shard partial top-k + all-gather merge.
#
# With the item table row-sharded over the mesh (FactorPlacement), each
# device scores ONLY its slice and ranks a local top-k; one [n, k]
# all-gather then merges — the collective moves k rows per shard instead
# of the full score vector, and the full [I] score vector never exists
# anywhere. Serving routes here automatically when the table is actually
# distributed (parallel/placement.py is_distributed).
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("k", "valid_items", "mesh", "gather_user"))
def _sharded_topk_jit(
    user_vector,                # [K] or (user_factors, user_idx)
    item_factors: jax.Array,    # [I_pad, K] row-sharded over mesh
    exclude,                    # [E] int32 global ids or None
    allowed_mask,               # [I_pad] bool or None
    *,
    k: int,
    valid_items: int,
    mesh,
    gather_user: bool,
):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from incubator_predictionio_tpu.parallel.collectives import (
        all_gather,
        axis_index,
        shard_map,
    )

    axes = tuple(mesh.axis_names)
    n = int(mesh.devices.size)
    i_pad = item_factors.shape[0]
    local_rows = i_pad // n
    k_local = min(k, local_rows)
    if gather_user:
        uf, user_idx = user_vector
        # one GSPMD gather from the sharded user table — the owning
        # shard serves the row; no host crossing
        uv = uf[user_idx]
    else:
        uv = user_vector
    uv = jax.lax.with_sharding_constraint(
        uv, NamedSharding(mesh, P()))

    spec = P(axes)
    args = [uv, item_factors]
    specs = [P(), spec]
    has_ex = exclude is not None
    has_mask = allowed_mask is not None
    if has_ex:
        args.append(exclude)
        specs.append(P())
    if has_mask:
        args.append(allowed_mask)
        specs.append(spec)

    def shard(uv_l, items_l, *rest):
        rest = list(rest)
        ex_l = rest.pop(0) if has_ex else None
        mask_l = rest.pop(0) if has_mask else None
        offset = axis_index(axes) * local_rows
        scores = jnp.dot(items_l, uv_l, precision=_EXACT)  # [local_rows]
        rows_g = offset + jnp.arange(local_rows)
        scores = jnp.where(rows_g < valid_items, scores, NEG_INF)
        if mask_l is not None:
            scores = jnp.where(mask_l, scores, NEG_INF)
        if ex_l is not None:
            loc = ex_l - offset
            safe = jnp.where(
                (loc >= 0) & (loc < local_rows), loc, local_rows)
            scores = scores.at[safe].set(NEG_INF, mode="drop")
        s_l, i_l = jax.lax.top_k(scores, k_local)    # partial top-k
        merged_s = all_gather(s_l, axes, axis=0, tiled=True)
        merged_i = all_gather(
            (i_l + offset).astype(jnp.int32), axes, axis=0, tiled=True)
        top_s, pos = jax.lax.top_k(merged_s, k)      # merge n·k → k
        top_i = merged_i[pos]
        return jnp.stack([top_s, top_i.astype(jnp.float32)])

    return shard_map(
        shard, mesh=mesh, in_specs=tuple(specs),
        out_specs=P(), check_vma=False,
    )(*args)


def _fold_valid_mask(
    allowed_mask: Optional[jax.Array],
    item_factors: jax.Array,
    valid_items: Optional[int],
) -> Optional[jax.Array]:
    """Fold a ``valid_items`` bound into the allowed mask for the
    single-device paths (the sharded path masks by row offset instead,
    without materializing an [I] array)."""
    if valid_items is None or valid_items >= item_factors.shape[0]:
        return allowed_mask
    vm = jnp.arange(item_factors.shape[0]) < valid_items
    if allowed_mask is None:
        return vm
    return jnp.asarray(allowed_mask, bool) & vm


def sharded_top_k(
    user_vector,                 # [K] vector OR (user_factors, user_idx)
    item_factors: jax.Array,     # row-sharded [I_pad, K]
    k: int,
    exclude: Optional[jax.Array] = None,
    allowed_mask: Optional[jax.Array] = None,
    valid_items: Optional[int] = None,
) -> jax.Array:
    """Top-k over a mesh-sharded item table → packed [2, k] (replicated).

    ``valid_items`` masks the placement's padding rows (zero factors
    would otherwise outrank negative real scores); default = the full
    padded table. ``allowed_mask`` shorter than the padded table is
    padded False (padding is never servable)."""
    _pt0 = _profile.t0()  # None on the PIO_PROFILE=0 default hot path
    mesh = item_factors.sharding.mesh
    i_pad = int(item_factors.shape[0])
    valid = int(valid_items) if valid_items is not None else i_pad
    gather_user = isinstance(user_vector, tuple)
    if allowed_mask is not None and allowed_mask.shape[0] < i_pad:
        allowed_mask = jnp.pad(
            jnp.asarray(allowed_mask, bool),
            (0, i_pad - allowed_mask.shape[0]))
    kk = min(int(k), i_pad)
    out = _sharded_topk_jit(
        user_vector, item_factors, exclude, allowed_mask,
        k=kk, valid_items=valid, mesh=mesh, gather_user=gather_user)
    _profile.record(_pt0, "serve", "serve_topk_sharded",
                    2.0 * i_pad * item_factors.shape[1], out)
    return out


@functools.partial(jax.jit, static_argnames=("k",))
def _score_user_top_k_xla(
    user_factors: jax.Array,        # [U, K]
    item_factors: jax.Array,        # [I, K]
    user_idx,                       # scalar int
    k: int,
    exclude: Optional[jax.Array] = None,
    allowed_mask: Optional[jax.Array] = None,
) -> jax.Array:
    scores = jnp.dot(item_factors, user_factors[user_idx],
                     precision=_EXACT)
    top_s, top_i = top_k_with_exclusions(scores, k, exclude, allowed_mask)
    return jnp.stack([top_s, top_i.astype(jnp.float32)])


def score_user_and_top_k(
    user_factors: jax.Array,        # [U, K] (device-resident)
    item_factors: jax.Array,        # [I, K] (device-resident)
    user_idx: int,
    k: int,
    exclude: Optional[jax.Array] = None,
    allowed_mask: Optional[jax.Array] = None,
    valid_items: Optional[int] = None,
) -> jax.Array:
    """Serving fast path: user-row gather + full-catalog scoring + top-k in
    ONE device call, packed [2, k].

    Indexing ``user_factors[user_idx]`` outside the jit would be a second
    dispatch per query. Callers fetch the packed result with one
    ``np.asarray``. ``valid_items`` masks trailing padding rows — a
    PLACED table's pow2 capacity tail has zero factors, and score 0
    would outrank genuinely negative real items — so any caller serving
    a padded table directly must pass the true item count."""
    from incubator_predictionio_tpu.parallel.placement import (
        is_distributed,
    )

    # auto-route: a registered MIPS index serves the query two-stage
    # (coarse bucket scan + exact rerank, ops/mips.py) unless the mode,
    # a filter mask, or the catalogue size says exhaustive; exhaustive
    # stays the fallback AND the recall oracle (valid_items is moot on
    # the MIPS path — buckets only ever hold true rows)
    mips_index = _mips.route(item_factors, k=k,
                             allowed_mask=allowed_mask, exclude=exclude)
    if mips_index is not None:
        return _mips.mips_score_user_and_top_k(
            user_factors, item_factors, mips_index, user_idx, k,
            exclude=exclude)
    _mips.book_exhaustive(int(item_factors.shape[0]))
    # fallback parity with the published tail — see score_and_top_k
    masked = allowed_mask is not None

    def _fold(out):
        if masked:
            return out
        return _mips.merge_published_fallback(
            item_factors, out,
            lambda: np.asarray(user_factors[user_idx], np.float32), k,
            exclude)

    if is_distributed(item_factors):
        return _fold(sharded_top_k((user_factors, user_idx),
                                   item_factors, k, exclude=exclude,
                                   allowed_mask=allowed_mask,
                                   valid_items=valid_items))
    allowed_mask = _fold_valid_mask(allowed_mask, item_factors,
                                    valid_items)
    _pt0 = _profile.t0()
    if item_factors.shape[0] >= PALLAS_MIN_ITEMS and k <= 128:
        from incubator_predictionio_tpu.ops.pallas_kernels import (
            score_and_top_k_pallas, topk_kernel_available)
        if topk_kernel_available():
            # huge catalogs: compute dominates, the extra gather dispatch
            # is noise next to the blocked kernel's win
            out = score_and_top_k_pallas(
                user_factors[user_idx], item_factors, k,
                exclude=exclude, allowed_mask=allowed_mask,
                block_items=8192,
            )
            _profile.record(
                _pt0, "serve", "serve_topk",
                2.0 * item_factors.shape[0] * item_factors.shape[1], out)
            return _fold(out)
    out = _score_user_top_k_xla(user_factors, item_factors, user_idx, k,
                                exclude, allowed_mask)
    _profile.record(_pt0, "serve", "serve_topk",
                    2.0 * item_factors.shape[0] * item_factors.shape[1],
                    out)
    return _fold(out)


@functools.partial(jax.jit, static_argnames=("k", "valid_items"))
def _batch_score_top_k_xla(
    user_factors: jax.Array,        # [U, K]
    item_factors: jax.Array,        # [I, K]
    rows: jax.Array,                # [B] int32 user indices
    k: int,
    valid_items: Optional[int] = None,
) -> jax.Array:
    scores = jnp.dot(user_factors[rows], item_factors.T,
                     precision=_EXACT)                    # [B, I] — MXU
    if valid_items is not None and valid_items < item_factors.shape[0]:
        # placed tables carry zero-factor padding rows; mask them out
        # (score 0 would outrank genuinely negative real items). Under
        # sharded operands GSPMD partitions the matmul + mask + top_k.
        cols = jnp.arange(item_factors.shape[0])
        scores = jnp.where(cols[None, :] < valid_items, scores, NEG_INF)
    top_s, top_i = jax.lax.top_k(scores, k)
    return jnp.stack([top_s, top_i.astype(jnp.float32)])  # [2, B, k]


def next_pow2(n: int) -> int:
    """Smallest power of two ≥ n (≥1) — THE padding policy of the batched
    serving dispatch. Warmup hooks compile per-shape against this exact
    function, so any change here automatically changes what they warm."""
    return 1 << max(int(n) - 1, 0).bit_length()


def pad_exclude(ids) -> Optional[jax.Array]:
    """Exclusion ids → pow2-padded int32 device array (-1 = no-op
    slots), or None for an empty list — THE serve-time exclusion
    shape. One copy of the padding policy: it bounds the jitted serve
    variants to O(log max-seen) compiles, so every call site must pad
    by the same rule."""
    ids = list(ids)
    if not ids:
        return None
    width = next_pow2(len(ids))
    out = np.full(width, -1, np.int32)
    out[:len(ids)] = ids
    return jnp.asarray(out)


def ladder_rungs(cap: int) -> Tuple[int, ...]:
    """The pow2 batch-width ladder up to ``cap`` — exactly the shapes
    :func:`batch_score_top_k` can dispatch (its ``B`` pads to the next
    power of two) and therefore exactly what the continuous-batching
    scheduler (serving/scheduler.py) may pick. Deploy-time warmup
    (``ALSAlgorithm.warmup``) and the zero-recompile test walk THIS
    ladder, so warmed shapes track dispatchable shapes through one
    rule."""
    cap = next_pow2(max(int(cap), 1))
    return tuple(1 << i for i in range(cap.bit_length()))


#: further jitted serving programs (another engine's whole forward) that
#: count towards :func:`serve_compile_cache_size`
_COUNTED_PROGRAMS: list = []


def count_serve_program(fn):
    """Have ``serve_compile_cache_size`` count the jitted ``fn`` too: an
    engine whose serving dispatch is a program of its own (the sequence
    engine's forward, ops/transformer.py) registers it here, so that the
    zero-recompile contract sees its ladder as it sees this module's."""
    _COUNTED_PROGRAMS.append(fn)
    return fn


def serve_compile_cache_size() -> int:
    """Compiled serving-dispatch variants resident in this process —
    the scheduler's zero-steady-state-recompile contract counter (the
    serving twin of ``speed.foldin.foldin_compile_cache_size``).
    Bounded by the pow2 ladder × the distinct (k, catalog) shapes
    served; tests pin that a warm ladder stops growing it."""
    return sum(
        int(fn._cache_size())
        for fn in (top_k_with_exclusions, _score_and_top_k_xla,
                   _score_user_top_k_xla, _batch_score_top_k_xla,
                   _sharded_topk_jit, *_COUNTED_PROGRAMS)
    ) + _mips.mips_compile_cache_size()


def batch_score_top_k(
    user_factors: jax.Array,
    item_factors: jax.Array,
    rows,                           # [B] int array of user indices
    k: int,
    valid_items: Optional[int] = None,
) -> jax.Array:
    """Score B users against the whole catalog and rank, in ONE dispatch.

    The serving micro-batcher's compute path (the reference leaves this as
    "TODO: Parallelize", CreateServer.scala:523): one [B, K] × [K, I] matmul
    amortizes the device round trip over the whole batch. BOTH static shape
    inputs are padded to the next power of two — ``rows`` with row 0
    repeated, ``k`` capped at the catalog — so live traffic with varying
    batch sizes AND varying ``num`` compiles O(log max-batch · log catalog)
    variants total instead of one per distinct (B, num) pair. Callers slice
    row b of the packed [2, B_pad, k_pad] result to their own ``num``."""
    import numpy as np

    B = len(rows)
    n_items = item_factors.shape[0]
    k_pad = min(next_pow2(int(k)), n_items)
    if B == 0:
        # an empty batch would otherwise index rows[0] below (and
        # next_pow2(0) still pads to 1) — hand back an empty packed
        # result without touching the device
        return jnp.zeros((2, 0, k_pad), jnp.float32)
    pad = next_pow2(B)
    # vectorized pad (row 0 repeated), not a per-call Python list — this
    # runs on the serving hot path for every fused micro-batch
    rows_np = np.asarray(rows, np.int32).reshape(B)
    if pad > B:
        rows_np = np.concatenate(
            [rows_np, np.full(pad - B, rows_np[0], np.int32)])
    # the scheduler's fused dispatch rides the same MIPS auto-route as
    # the per-query paths (padded rows keep the pow2 ladder; the
    # two-stage stage widths are static, so steady state still never
    # recompiles)
    mips_index = _mips.route(item_factors, k=k_pad)
    if mips_index is not None:
        return _mips.mips_batch_score_top_k(
            user_factors, item_factors, mips_index, rows_np, k_pad)
    _mips.book_exhaustive(int(pad) * int(item_factors.shape[0]))
    _pt0 = _profile.t0()  # None on the PIO_PROFILE=0 default hot path
    # ONE call into the runtime launches the dispatch: the padded int32
    # rows go in as a host array and ride up on the jitted call's own
    # argument path (a device array made first is a second dispatch
    # through Python and a transfer waited for before the program is
    # called: 0.27 of a 0.49 ms launch on the chip's host, PERF.md PR 27).
    # The call returns once the program is enqueued, rows included.
    out = _batch_score_top_k_xla(user_factors, item_factors,
                                 rows_np, k_pad,
                                 valid_items=valid_items)
    _profile.record(
        _pt0, "serve", "serve_topk_batch",
        2.0 * B * user_factors.shape[1] * item_factors.shape[0], out)
    # fallback parity with the published tail — see score_and_top_k
    return _mips.merge_published_fallback(
        item_factors, out,
        lambda: np.asarray(user_factors[jnp.asarray(rows_np)],
                           np.float32), k_pad, None)


def score_and_top_k(
    user_vector: jax.Array,         # [K]
    item_factors: jax.Array,        # [I, K]
    k: int,
    exclude: Optional[jax.Array] = None,
    allowed_mask: Optional[jax.Array] = None,
    valid_items: Optional[int] = None,
) -> jax.Array:
    """Full-catalog scoring + ranking in one fused device call.

    Returns a single packed [2, k] f32 array (row 0 = scores, row 1 =
    indices): serving pays exactly ONE device→host fetch per query — at
    27k items the fetch count, not the FLOPs, sets the query latency.
    Large catalogs on a TPU route to the
    Pallas blocked-candidate kernel (ops/pallas_kernels.py), which never
    writes the full score vector to HBM. ``valid_items`` masks a placed
    table's zero-factor padding tail (see :func:`score_user_and_top_k`).
    """
    from incubator_predictionio_tpu.parallel.placement import (
        is_distributed,
    )

    # auto-route to the two-stage MIPS path (ops/mips.py) when an index
    # is registered for this table; filters/off/small catalogues keep
    # the exhaustive path below, which is also the recall oracle
    mips_index = _mips.route(item_factors, k=k,
                             allowed_mask=allowed_mask, exclude=exclude)
    if mips_index is not None:
        return _mips.mips_score_and_top_k(
            user_vector, item_factors, mips_index, k, exclude=exclude)
    _mips.book_exhaustive(int(item_factors.shape[0]))
    # fallback parity: overlay-published rows live only in the index's
    # exact tail (virtual ids are NOT table rows), so a query routed
    # around the two-stage path — oversized exclusion list, mode off —
    # must merge them or published keys silently vanish. Filtered
    # queries skip the merge (a virtual id cannot honor an item mask);
    # no-op without a registered index or with an empty tail.
    masked = allowed_mask is not None

    def _fold(out):
        if masked:
            return out
        return _mips.merge_published_fallback(
            item_factors, out,
            lambda: np.asarray(user_vector, np.float32), k, exclude)

    if is_distributed(item_factors):
        # placed serving: per-shard partial top-k + all-gather merge
        return _fold(sharded_top_k(user_vector, item_factors, k,
                                   exclude=exclude,
                                   allowed_mask=allowed_mask,
                                   valid_items=valid_items))
    allowed_mask = _fold_valid_mask(allowed_mask, item_factors,
                                    valid_items)
    _pt0 = _profile.t0()  # None on the PIO_PROFILE=0 default hot path
    if item_factors.shape[0] >= PALLAS_MIN_ITEMS and k <= 128:
        from incubator_predictionio_tpu.ops.pallas_kernels import (
            score_and_top_k_pallas, topk_kernel_available)
        if topk_kernel_available():
            out = score_and_top_k_pallas(
                user_vector, item_factors, k,
                exclude=exclude, allowed_mask=allowed_mask,
                block_items=8192,
            )
            _profile.record(
                _pt0, "serve", "serve_topk",
                2.0 * item_factors.shape[0] * item_factors.shape[1], out)
            return _fold(out)
    out = _score_and_top_k_xla(user_vector, item_factors, k,
                               exclude, allowed_mask)
    _profile.record(_pt0, "serve", "serve_topk",
                    2.0 * item_factors.shape[0] * item_factors.shape[1],
                    out)
    return _fold(out)
