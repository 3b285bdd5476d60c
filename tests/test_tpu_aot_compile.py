"""Compile for the chip, without the chip.

The TPU's compiler is installed in the sandbox and compiles for a chip
that is DESCRIBED, not attached (on-chip-measurement guide §2). These
tests lower every Pallas kernel a route can select, and the jitted
ML-20M-shape programs of the main path, for one chip of a described
``v5e:2x2`` at real widths — what interpret mode cannot show: a slice
the tiling refuses, a kernel over its VMEM, a lowering rule that does
not exist, a program over 16 GB. A compile that passes is a compile,
never a chip run: nothing here executes.

All of it lives in THIS file on purpose. Only one process may load the
TPU's library, so the topology is described inside a module-scoped
fixture (never at import, in a skipif, a parametrize argument or
conftest.py) and everything compiles in the test's own process: under
pytest-xdist the worker that is handed this file loads the library and
every other worker collects the same tests without touching it.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_predictionio_tpu.ops import als, topk
from incubator_predictionio_tpu.ops import pallas_kernels as pk

N_USERS, N_ITEMS, RANK = 138_493, 26_744, 128
ROUTED_ITEMS = 524_288  # a catalogue past ops/topk.py PALLAS_MIN_ITEMS
HBM_BYTES = 16e9        # one v5e chip
F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32

#: the degree-bucket geometry `ops.sparse.build_both_sides` produces for
#: chip_smoke.py's seed-7 ML-20M-shape ratings (19,765,361 after the
#: preparator's dedup): (rows, width) per light bucket, and the item
#: side's split-row segments
USER_BUCKETS = ((8, 64), (75304, 128), (57192, 256), (5440, 512),
                (520, 1024), (56, 2048), (8, 4096))
ITEM_BUCKETS = ((14144, 512), (9048, 1024), (2568, 2048), (720, 4096))
ITEM_HEAVY = (734, 4096, 280)       # segments, width, split rows


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with jax's persistent compilation cache off
    around the compiles (an entry written for a described chip cannot be
    read back without one: the next compile would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    def make(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)

    return make


def assert_fits_one_chip(compiled) -> None:
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES


def compile_for_chip(fn, *shapes, kernel: bool = True):
    """Lower + compile ``fn`` for the described chip → the executable;
    raises whatever the chip's compiler would raise."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    if kernel:
        assert "tpu_custom_call" in compiled.as_text(), \
            "no Mosaic kernel in the compiled program"
    assert_fits_one_chip(compiled)
    return compiled


# -- serving top-k kernel ----------------------------------------------------

@pytest.mark.parametrize("n_items", [ROUTED_ITEMS, N_ITEMS])
def test_topk_kernel_compiles(shape, n_items):
    """At a catalogue ops/topk.py routes to the kernel (with the call
    sites' block_items=8192), and at the ML-20M catalogue."""
    assert ROUTED_ITEMS >= topk.PALLAS_MIN_ITEMS > N_ITEMS
    compile_for_chip(
        lambda q, items: pk.score_and_top_k_pallas(
            q, items, 10, block_items=8192, interpret=False),
        shape((RANK,), F32), shape((n_items, RANK), F32))


# -- flash attention ---------------------------------------------------------

def test_flash_forward_compiles_at_8k(shape):
    qb, kb = pk.default_flash_blocks(8192)
    q = shape((1, 8192, 8, 64), F32)
    compile_for_chip(
        lambda a, b, c: pk.flash_attention(
            a, b, c, q_block=qb, kv_block=kb, interpret=False), q, q, q)


def test_flash_grad_compiles_at_8k(shape):
    """The sequence engines train through the kernel's custom VJP (XLA
    blockwise backward): the gradient program must compile and fit."""
    qb, kb = pk.default_flash_blocks(8192)
    q = shape((1, 8192, 8, 64), F32)
    compile_for_chip(
        jax.grad(lambda a, b, c: jnp.sum(pk.flash_attention(
            a, b, c, q_block=qb, kv_block=kb, interpret=False)),
            argnums=(0, 1, 2)),
        q, q, q, kernel=False)  # sum's cotangent needs no forward output


# -- two-stage ALS bucket solve ----------------------------------------------

def _als_args(shape, table_dtype, d, warm, rows_b=2048):
    args = [shape((N_ITEMS, RANK), table_dtype), shape((rows_b, d), I32),
            shape((rows_b, d), F32), shape((rows_b, d), F32)]
    if warm:
        args.append(shape((rows_b, RANK), F32))
    return args


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
@pytest.mark.parametrize("rows", [1, 8])
@pytest.mark.parametrize("d", [128, 1024])
def test_als_two_stage_kernel_compiles_bf16(shape, d, rows, warm):
    """Every variant the auto route can dispatch in the bf16 sweeps."""
    compile_for_chip(
        lambda t, c, v, m, x0=None: pk.als_solve_cg_pallas(
            t, c, v, m, 0.03, True, 3, interpret=False,
            rows_per_program=rows, x0=x0),
        *_als_args(shape, BF16, d, warm))


def test_als_two_stage_kernel_compiles_f32_polish(shape):
    """The f32 HIGHEST polish sweep's kernel (precise Gram, 16 CG)."""
    compile_for_chip(
        lambda t, c, v, m, x0: pk.als_solve_cg_pallas(
            t, c, v, m, 0.03, True, 16, interpret=False,
            rows_per_program=1, x0=x0),
        *_als_args(shape, F32, 1024, warm=True))


# -- fused gather+Gram+CG kernel: does NOT lower -----------------------------

@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="jax 0.9.0 Mosaic jax/_src/pallas/mosaic/lowering.py "
           "_gather_lowering_rule: 'ValueError: Shape mismatch in input, "
           "indices and output' — the in-kernel jnp.take row gather has "
           "no TPU lowering (ops/als.py _fused_enabled keeps the kernel "
           "off the auto route). When this XPASSes, a compiler accepts "
           "it: measure it on the chip before routing it.")
@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit-bf16", "implicit-f32-yty"])
def test_als_fused_kernel_lowers(shape, implicit, warm):
    rows_b, d = 512, 1024
    args = _als_args(shape, F32 if implicit else BF16, d, warm=False,
                     rows_b=rows_b)
    extra = {}
    if implicit:
        extra["yty"] = shape((RANK, RANK), F32)
    if warm:
        extra["x0"] = shape((rows_b, RANK), F32)
    names = list(extra)
    compile_for_chip(
        lambda t, c, v, m, *rest: pk.als_fused_solve_cg_pallas(
            t, c, v, m, 0.03, True, 6 if implicit else 3,
            implicit=implicit, alpha=1.0, interpret=False,
            **dict(zip(names, rest))),
        *args, *extra.values())


# -- the sequence block's kernels at published widths -------------------------

@pytest.mark.parametrize("rows", [16_384, 32_768, 49_152, 65_536,
                                  131_072])
def test_expert_layer_kernel_compiles_at_published_widths(shape, rows,
                                                          monkeypatch):
    """ops/moe.grouped_swiglu on the route a TPU backend resolves — one
    Pallas kernel over tiles of 256 sorted rows in blocks of the height
    ``kernel_rows`` gives that width, two experts' three tables in VMEM —
    for 64 experts of [2304, 896], [2304, 896], [896, 2304] in bfloat16:
    the routed rows (2,048 tokens × 8 a window) of one window, of two,
    three (a batch runs at its own width), four and eight. The compiled
    text holds an op named ``pio_moe_experts``: what the benchmark's
    ``moe_ops`` reads the kernel's device time by."""
    from incubator_predictionio_tpu.ops import moe

    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    weights = moe.ExpertWeights(
        router=shape((2304, 64), BF16), w_gate=shape((64, 2304, 896), BF16),
        w_up=shape((64, 2304, 896), BF16),
        w_down=shape((64, 896, 2304), BF16))
    compiled = compile_for_chip(moe.grouped_swiglu,
                                shape((rows, 2304), BF16), weights,
                                shape((64,), I32))
    assert compiled.as_text().count("pio_moe_experts") >= 1


@pytest.mark.parametrize("window", [1024, None])
def test_attention_kernel_route_compiles_at_published_widths(shape, window):
    """The sequence block's attention on a TPU: a projection's output
    through the rotary pass into the kernel's layout, then jax's
    splash-attention kernel over 32 query heads on 4 key-value heads of
    128 at 2,048 positions, with padding keys' segment ids and a window."""
    import functools

    from incubator_predictionio_tpu.ops import attention

    def route(q, k, v, cos, sin, valid):
        return attention.kernel_attention(
            attention.rotate_heads_first(q, cos, sin, 32, 128 ** -0.5),
            attention.rotate_heads_first(k, cos, sin, 4), v,
            kv_valid=valid, window=window)

    compiled = compile_for_chip(
        route, shape((2, 2048, 4096), BF16), shape((2, 2048, 512), BF16),
        shape((2, 4, 2048, 128), BF16), shape((2048, 128), jnp.float32),
        shape((2048, 128), jnp.float32), shape((2, 2048), jnp.bool_))
    text = compiled.as_text()
    assert "pio_rotate_heads_first" in text and "splash" in text


# -- the main path's jitted programs at ML-20M shape -------------------------

def test_als_train_program_compiles_at_ml20m_shape(shape, monkeypatch):
    """The fused whole-run train program `pio train` dispatches for the
    bf16 sweeps, on the route a TPU backend resolves: the two-stage
    kernel in every bucket of width >= 64, fused-gather off."""
    # the program asks the backend which route to take; this process's
    # backend is the CPU, so steer it here, in the test
    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    assert als._kernel_enabled(False, warm=True)
    assert als._fused_sides(N_USERS, N_ITEMS, False, True, BF16,
                            RANK) == (False, False)

    def tree(buckets):
        return tuple((shape((b,), I32), shape((b, d), I32),
                      shape((b, d), F32), shape((b, d), F32))
                     for b, d in buckets)

    segs, width, heavy_rows = ITEM_HEAVY
    item_heavy = (shape((segs,), I32), shape((heavy_rows,), I32),
                  shape((segs, width), I32), shape((segs, width), F32),
                  shape((segs, width), F32))
    state = als.ALSState(user_factors=shape((N_USERS, RANK), F32),
                         item_factors=shape((N_ITEMS, RANK), F32))
    compiled = als._als_run_fused.lower(
        state, tree(USER_BUCKETS), tree(ITEM_BUCKETS), 0.03, 0.0, 2, True,
        BF16, jax.lax.Precision.DEFAULT, implicit=False, user_heavy=None,
        item_heavy=item_heavy,
        cg_iters=min(als._CG_ITERS_BF16, als._CG_ITERS), use_kernel=True,
        kernel_min_d=als._KERNEL_MIN_D, kernel_rows=1, warmstart=True,
        use_fused=(False, False), cg_tol=0.0).compile()
    # one kernel call per kernel-routed bucket, both sides
    assert compiled.as_text().count("tpu_custom_call") >= len(
        USER_BUCKETS) + len(ITEM_BUCKETS)
    assert_fits_one_chip(compiled)


def test_singleton_serving_program_compiles_at_ml20m_shape(shape):
    compile_for_chip(
        lambda uf, vf, row: topk._score_user_top_k_xla(uf, vf, row, 10),
        shape((N_USERS, RANK), F32), shape((N_ITEMS, RANK), F32),
        shape((), I32), kernel=False)


@pytest.mark.parametrize("batch", [64, 512])
def test_batched_serving_program_compiles_at_ml20m_shape(shape, batch):
    compile_for_chip(
        lambda uf, vf, rows: topk._batch_score_top_k_xla(uf, vf, rows, 16),
        shape((N_USERS, RANK), F32), shape((N_ITEMS, RANK), F32),
        shape((batch,), I32), kernel=False)


def test_sharded_top_k_compiles_for_four_chips(topo):
    """The per-shard partial top-k + all-gather merge over a 2x2 mesh of
    the described chips, on a row-sharded ML-20M item table."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
    rows = NamedSharding(mesh, P(("dp", "mp")))
    n_users_pad, n_items_pad = 4 * 34_624, 4 * 6_686
    uf = jax.ShapeDtypeStruct((n_users_pad, RANK), F32, sharding=rows)
    vf = jax.ShapeDtypeStruct((n_items_pad, RANK), F32, sharding=rows)
    row = jax.ShapeDtypeStruct((), I32, sharding=NamedSharding(mesh, P()))
    compiled = topk._sharded_topk_jit.lower(
        (uf, row), vf, None, None, k=10, valid_items=N_ITEMS, mesh=mesh,
        gather_user=True).compile()
    assert "all-gather" in compiled.as_text()
