"""Pallas kernel correctness vs the dense XLA references.

Runs in interpret mode on the CPU test mesh (conftest pins JAX_PLATFORMS=cpu);
the same kernels compile with Mosaic on real TPU — mirroring how the
reference validates distributed behavior on local[4] Spark before a real
cluster (reference: core/src/test/.../workflow/BaseTest.scala:71-88).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_predictionio_tpu.ops.attention import dot_product_attention
from incubator_predictionio_tpu.ops.pallas_kernels import (
    flash_attention,
    score_and_top_k_pallas,
)
from incubator_predictionio_tpu.ops.topk import score_and_top_k


def _rand(key, *shape):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32)


class TestPallasTopK:
    def test_matches_xla_reference(self):
        items = _rand(0, 500, 24)
        user = _rand(1, 24)
        ref = np.asarray(score_and_top_k(user, items, k=7))
        got = np.asarray(score_and_top_k_pallas(
            user, items, k=7, interpret=True, block_items=128))
        np.testing.assert_allclose(got[0], ref[0], rtol=1e-5)
        np.testing.assert_array_equal(got[1], ref[1])

    def test_exclusions_cannot_displace_candidates(self):
        # exclude more items than one block's candidate budget — the dense
        # in-kernel mask must keep results exact anyway
        items = _rand(2, 300, 16)
        user = _rand(3, 16)
        exclude = jnp.arange(250, dtype=jnp.int32)  # only 50 items remain
        ref = np.asarray(score_and_top_k(user, items, k=5, exclude=exclude))
        got = np.asarray(score_and_top_k_pallas(
            user, items, k=5, exclude=exclude, interpret=True,
            block_items=128))
        np.testing.assert_allclose(got[0], ref[0], rtol=1e-5)
        np.testing.assert_array_equal(got[1], ref[1])

    def test_allowed_mask_and_negative_exclude(self):
        items = _rand(4, 260, 8)
        user = _rand(5, 8)
        mask = np.ones(260, bool)
        mask[::3] = False
        exclude = jnp.asarray([-1, 7, -1, 11], jnp.int32)
        ref = np.asarray(score_and_top_k(
            user, items, k=4, exclude=exclude,
            allowed_mask=jnp.asarray(mask)))
        got = np.asarray(score_and_top_k_pallas(
            user, items, k=4, exclude=exclude,
            allowed_mask=jnp.asarray(mask), interpret=True,
            block_items=128))
        np.testing.assert_allclose(got[0], ref[0], rtol=1e-5)
        np.testing.assert_array_equal(got[1], ref[1])

    def test_k_exceeding_allowed_returns_neg_inf_fillers(self):
        items = _rand(6, 40, 8)
        user = _rand(7, 8)
        mask = np.zeros(40, bool)
        mask[:3] = True  # only 3 allowed, ask for 6
        got = np.asarray(score_and_top_k_pallas(
            user, items, k=6, allowed_mask=jnp.asarray(mask),
            interpret=True, block_items=128))
        assert (got[0][3:] <= -1e37).all()
        # filler slots must never leak padding item ids (>= n_items)
        np.testing.assert_array_equal(got[1][3:], -1)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q = _rand(10, 2, 100, 2, 32)
        k = _rand(11, 2, 100, 2, 32)
        v = _rand(12, 2, 100, 2, 32)
        ref = dot_product_attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal=causal, interpret=True,
                              q_block=32, kv_block=32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)

    def test_ragged_kv_valid(self):
        q = _rand(13, 2, 40, 2, 16)
        k = _rand(14, 2, 40, 2, 16)
        v = _rand(15, 2, 40, 2, 16)
        valid = np.zeros((2, 40), bool)
        valid[0, :17] = True
        valid[1, :33] = True
        ref = dot_product_attention(q, k, v, causal=True,
                                    kv_valid=jnp.asarray(valid))
        got = flash_attention(q, k, v, causal=True,
                              kv_valid=jnp.asarray(valid), interpret=True,
                              q_block=16, kv_block=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)

    def test_fully_masked_rows_are_zero(self):
        # with causal + all keys invalid, output must be exactly 0 (the
        # invariant ring attention relies on), not NaN
        q = _rand(16, 1, 8, 1, 16)
        k = _rand(17, 1, 8, 1, 16)
        v = _rand(18, 1, 8, 1, 16)
        valid = jnp.zeros((1, 8), bool)
        got = np.asarray(flash_attention(
            q, k, v, causal=True, kv_valid=valid, interpret=True,
            q_block=8, kv_block=8))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, np.zeros_like(got))

    def test_decode_single_query_row(self):
        q = _rand(19, 1, 1, 2, 32)
        k = _rand(20, 1, 64, 2, 32)
        v = _rand(21, 1, 64, 2, 32)
        # a length-1 query attending over a 64-long KV cache, non-causal
        ref = dot_product_attention(q, k, v, causal=False)
        got = flash_attention(q, k, v, causal=False, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)


class TestFlashAttentionGrad:
    def test_grad_matches_dense_reference(self):
        # sequence engines train THROUGH the attention op — the fused
        # kernel must be differentiable (custom VJP via the blockwise path)
        q = _rand(30, 1, 24, 2, 16)
        k = _rand(31, 1, 24, 2, 16)
        v = _rand(32, 1, 24, 2, 16)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, interpret=True,
                q_block=8, kv_block=8) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

        gq, gk, gv = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        rq, rk, rv = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), atol=3e-5)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(rk), atol=3e-5)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), atol=3e-5)


class TestAlsCgKernel:
    """Fused bucket solve (Gram + CG in VMEM) vs the XLA assembly path."""

    def _problem(self, seed=0, M=400, K=64, B=24, D=48):
        rng = np.random.default_rng(seed)
        table = rng.normal(0, 0.3, (M, K)).astype(np.float32)
        cols = rng.integers(0, M, (B, D)).astype(np.int32)
        vals = rng.normal(3.5, 1.0, (B, D)).astype(np.float32)
        mask = (rng.random((B, D)) < 0.8).astype(np.float32)
        mask[3] = 0.0  # empty row must solve to exactly 0
        return table, cols, vals, mask

    @pytest.mark.parametrize("rows", [1, 8])
    @pytest.mark.parametrize("dtype,prec,tol", [
        (jnp.float32, jax.lax.Precision.HIGHEST, 1e-4),
        (jnp.bfloat16, jax.lax.Precision.DEFAULT, 2e-2),
    ])
    def test_matches_solve_bucket(self, dtype, prec, tol, rows):
        from incubator_predictionio_tpu.ops import als
        from incubator_predictionio_tpu.ops.pallas_kernels import (
            als_solve_cg_pallas,
        )

        table, cols, vals, mask = self._problem()
        src = jnp.asarray(table).astype(dtype)
        ref = als._solve_bucket(
            src, jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(mask),
            0.1, reg_nnz=True, compute_dtype=dtype, precision=prec,
            cg_iters=16)
        got = als_solve_cg_pallas(
            src, jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(mask),
            0.1, reg_nnz=True, iters=16, interpret=True,
            rows_per_program=rows)
        rel = float(jnp.max(jnp.abs(ref - got))
                    / (jnp.max(jnp.abs(ref)) + 1e-9))
        assert rel < tol, rel
        assert bool(jnp.all(got[3] == 0.0))

    @pytest.mark.parametrize("rows", [1, 8])
    def test_multi_tile_d_and_no_reg_nnz(self, rows):
        """D=1024 streams two 512-wide tiles through the accumulator;
        B=13 forces row-group padding in the grouped variant."""
        from incubator_predictionio_tpu.ops import als
        from incubator_predictionio_tpu.ops.pallas_kernels import (
            als_solve_cg_pallas,
        )

        table, cols, vals, mask = self._problem(seed=1, M=600, K=32, B=13,
                                                D=1024)
        src = jnp.asarray(table)
        ref = als._solve_bucket(
            src, jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(mask),
            0.05, reg_nnz=False, cg_iters=16)
        got = als_solve_cg_pallas(
            src, jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(mask),
            0.05, reg_nnz=False, iters=16, interpret=True,
            rows_per_program=rows)
        rel = float(jnp.max(jnp.abs(ref - got))
                    / (jnp.max(jnp.abs(ref)) + 1e-9))
        assert rel < 1e-4, rel

    _PARITY_CACHE: dict = {}

    def _parity_problem(self, als):
        """Planted problem + the rows-independent XLA reference, computed
        once and shared by both layout params (the baseline runs with the
        kernel off, so re-training it per param would be pure waste)."""
        if not self._PARITY_CACHE:
            rng = np.random.default_rng(7)
            n_u, n_i, k_true, nnz = 120, 60, 4, 4000
            u = rng.normal(0, 1, (n_u, k_true)).astype(np.float32)
            v = rng.normal(0, 1, (n_i, k_true)).astype(np.float32)
            users = rng.integers(0, n_u, nnz).astype(np.int32)
            items = rng.integers(0, n_i, nnz).astype(np.int32)
            ratings = np.einsum("nk,nk->n", u[users], v[items]).astype(
                np.float32)
            kw = dict(n_users=n_u, n_items=n_i, rank=16, iterations=8,
                      l2=0.02, bf16_sweeps=4, max_width=64)
            old = als._ALS_KERNEL
            als._ALS_KERNEL = "off"
            try:
                st_xla, _ = als.als_train(users, items, ratings, **kw)
            finally:
                als._ALS_KERNEL = old
            self._PARITY_CACHE.update(
                users=users, items=items, ratings=ratings, kw=kw,
                st_xla=st_xla)
        c = self._PARITY_CACHE
        return c["users"], c["items"], c["ratings"], c["kw"], c["st_xla"]

    @pytest.mark.parametrize("rows", [1, 8])
    def test_full_training_parity(self, monkeypatch, rows):
        """als_train with the kernel forced on (interpret on CPU) reaches
        the same fit as the XLA path — the planted-recovery guarantee
        holds through the fused solve, including the mixed bf16+f32
        schedule and the split-row heavy path (max_width forces splits),
        in BOTH program layouts."""
        from incubator_predictionio_tpu.ops import als
        from incubator_predictionio_tpu.ops import pallas_kernels as pk
        monkeypatch.setattr(pk, "_ALS_ROWS", rows)

        users, items, ratings, kw, st_xla = self._parity_problem(als)
        monkeypatch.setattr(als, "_ALS_KERNEL", "on")
        # this problem's buckets are narrower than the default min-D
        # routing cut — force every bucket through the kernel
        monkeypatch.setattr(als, "_KERNEL_MIN_D", 0)
        st_krn, _ = als.als_train(users, items, ratings, **kw)
        r_xla = als.rmse(st_xla, users, items, ratings)
        r_krn = als.rmse(st_krn, users, items, ratings)
        # both fit the planted structure; the kernel keeps its Gram in f32
        # so it may be (slightly) more accurate than the bf16 XLA path
        assert r_krn < max(1.15 * r_xla, r_xla + 0.02), (r_krn, r_xla)
        assert r_krn < 0.1, r_krn

    @pytest.mark.parametrize("fused_mode", ["on", "off"])
    def test_min_d_routing(self, monkeypatch, fused_mode):
        """With the kernel enabled, buckets narrower than _KERNEL_MIN_D
        stay on the XLA path (the padding tax region) while wide buckets
        route through the fused solve — decided per bucket at trace
        time, in BOTH kernel generations (fused gather vs two-stage)."""
        from incubator_predictionio_tpu.ops import als

        monkeypatch.setenv("PIO_ALS_FUSED_GRAM", fused_mode)
        widths = []
        real = als._solve_bucket_kernel
        real_fused = als._solve_bucket_fused

        def spy(gsrc, cols, vals, mask, l2, reg_nnz, cg_iters,
                kernel_rows=1, x0=None):
            assert fused_mode == "off", "two-stage kernel ran in fused mode"
            widths.append(cols.shape[1])
            return real(gsrc, cols, vals, mask, l2, reg_nnz=reg_nnz,
                        cg_iters=cg_iters, kernel_rows=kernel_rows, x0=x0)

        def spy_fused(gsrc, yty, cols, vals, mask, l2, reg_nnz, cg_iters,
                      implicit=False, alpha=0.0, x0=None):
            assert fused_mode == "on", "fused kernel ran while forced off"
            widths.append(cols.shape[1])
            return real_fused(gsrc, yty, cols, vals, mask, l2,
                              reg_nnz=reg_nnz, cg_iters=cg_iters,
                              implicit=implicit, alpha=alpha, x0=x0)

        monkeypatch.setattr(als, "_solve_bucket_kernel", spy)
        monkeypatch.setattr(als, "_solve_bucket_fused", spy_fused)
        monkeypatch.setattr(als, "_ALS_KERNEL", "on")
        monkeypatch.setattr(als, "_KERNEL_MIN_D", 64)

        rng = np.random.default_rng(3)
        n_u, n_i = 300, 40
        # ~85% of users rate <16 items (narrow buckets), a few rate 100+
        # (wide buckets) — both routing branches must appear
        degs = np.where(rng.random(n_u) < 0.85, rng.integers(2, 12, n_u),
                        rng.integers(100, 160, n_u)).astype(np.int64)
        users = np.repeat(np.arange(n_u, dtype=np.int32), degs)
        items = rng.integers(0, n_i, len(users)).astype(np.int32)
        ratings = rng.normal(3.5, 1.0, len(users)).astype(np.float32)
        als.als_train(users, items, ratings, n_users=n_u, n_items=n_i,
                      rank=8, iterations=1, l2=0.05)
        assert widths, "no bucket routed through the kernel"
        assert all(w >= 64 for w in widths), widths


def test_flash_block_table_selection(monkeypatch):
    """default_flash_blocks picks the measured per-length optimum and the
    PIO_FLASH_BLOCKS override parses (malformed values fall back)."""
    from incubator_predictionio_tpu.ops import pallas_kernels as pk

    assert pk.default_flash_blocks(1024) == (2048, 512)
    assert pk.default_flash_blocks(8192) == (2048, 512)
    assert pk.default_flash_blocks(8193) == (1024, 1024)
    assert pk.default_flash_blocks(16384) == (1024, 1024)
    assert pk.default_flash_blocks(1 << 20) == (1024, 1024)

    monkeypatch.setenv("PIO_FLASH_BLOCKS", "4096:256x512,16384:512x1024")
    parsed = pk._parse_block_env()
    assert parsed == ((4096, 256, 512), (1 << 62, 512, 1024))

    monkeypatch.setenv("PIO_FLASH_BLOCKS", "garbage")
    assert pk._parse_block_env() is None


def test_availability_is_the_backend_test_and_never_a_probe(monkeypatch):
    """Every *_available() routing predicate is `backend == tpu` and
    nothing more: no kernel is compiled or run to decide a route, so no
    compile or run error can be converted into False (and into a quiet
    reroute to an XLA path)."""
    from incubator_predictionio_tpu.ops import pallas_kernels as pk

    predicates = (pk.pallas_available, pk.topk_kernel_available,
                  pk.flash_available, pk.als_kernel_available)
    assert not any(p() for p in predicates)     # the CPU test backend

    def boom(*a, **kw):
        raise AssertionError("a routing predicate ran a kernel")

    for entry in ("score_and_top_k_pallas", "flash_attention",
                  "als_solve_cg_pallas", "als_fused_solve_cg_pallas"):
        monkeypatch.setattr(pk, entry, boom)
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    assert all(p() for p in predicates)
    assert not hasattr(pk, "_probe_kernel_runs")
    assert not hasattr(pk, "_probe_mosaic")


def test_interpret_never_resolves_true_on_a_tpu_backend(monkeypatch):
    """interpret=None → interpret mode exactly when the backend is not a
    TPU; on a TPU backend no entry can resolve interpret=True."""
    from incubator_predictionio_tpu.ops import pallas_kernels as pk

    assert pk._resolve_interpret(None) is True      # CPU backend
    assert pk._resolve_interpret(False) is False
    assert pk._resolve_interpret(True) is True
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    assert pk._resolve_interpret(None) is False
    assert pk._resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        pk._resolve_interpret(True)


def test_auto_route_on_tpu_selects_two_stage_never_the_fused_kernel(
        monkeypatch):
    """ops/als.py routing on a TPU backend: `auto` selects the two-stage
    kernel (which compiles — tests/test_tpu_aot_compile.py) and never the
    fused-gather kernel (which does not lower); only the explicit
    PIO_ALS_FUSED_GRAM=on test hook turns that one on."""
    from incubator_predictionio_tpu.ops import als
    from incubator_predictionio_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(als, "_ALS_KERNEL", "auto")
    monkeypatch.delenv("PIO_ALS_FUSED_GRAM", raising=False)
    assert als._kernel_enabled(False, warm=True) is False    # CPU
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    for warm in (False, True):
        assert als._kernel_enabled(False, warm=warm) is True
        assert als._fused_enabled(False, warm) is False
        assert als._fused_enabled(True, warm) is False
        assert als._kernel_enabled(True, warm=warm) is False  # implicit
    assert als._fused_sides(138_493, 26_744, False, True,
                            jnp.bfloat16, 128) == (False, False)
    monkeypatch.setenv("PIO_ALS_FUSED_GRAM", "on")
    assert als._fused_enabled(False, True) is True
    assert als._fused_sides(138_493, 26_744, False, True,
                            jnp.bfloat16, 128) == (True, False)
