"""ALS solver correctness on the CPU mesh."""

import numpy as np
import pytest

from incubator_predictionio_tpu.ops import (
    als_train,
    build_padded_rows,
    rmse,
    top_k_with_exclusions,
)


def synthetic_ratings(n_users=60, n_items=40, rank=4, density=0.3, seed=0):
    rng = np.random.default_rng(seed)
    u = rng.normal(size=(n_users, rank)) / np.sqrt(rank)
    v = rng.normal(size=(n_items, rank)) / np.sqrt(rank)
    full = u @ v.T + 3.0
    mask = rng.random((n_users, n_items)) < density
    users, items = np.nonzero(mask)
    return users, items, full[users, items].astype(np.float32)


def test_build_padded_rows_round_trip():
    users = np.array([0, 0, 0, 1, 2, 2, 2, 2, 2])
    items = np.array([5, 6, 7, 1, 0, 1, 2, 3, 4])
    vals = np.arange(9, dtype=np.float32)
    buckets = build_padded_rows(users, items, vals, n_rows=3, min_width=2,
                                row_multiple=1)
    # reconstruct
    seen = {}
    for b in buckets:
        for i, rid in enumerate(b.row_ids):
            if rid < 0:
                continue
            cols = b.cols[i][b.mask[i] > 0]
            vs = b.vals[i][b.mask[i] > 0]
            seen.setdefault(int(rid), []).extend(zip(cols.tolist(), vs.tolist()))
    assert sorted(seen[0]) == [(5, 0.0), (6, 1.0), (7, 2.0)]
    assert seen[1] == [(1, 3.0)]
    assert len(seen[2]) == 5


def test_build_padded_rows_splits_heavy_rows():
    users = np.zeros(10, dtype=np.int64)
    items = np.arange(10)
    vals = np.ones(10, np.float32)
    buckets = build_padded_rows(users, items, vals, 1, min_width=2,
                                max_width=4, row_multiple=1)
    total = sum(int(b.mask.sum()) for b in buckets)
    assert total == 10  # nothing dropped
    widths = sorted(b.width for b in buckets)
    assert max(widths) <= 4


def test_als_fits_synthetic_low_rank():
    users, items, ratings = synthetic_ratings()
    state, history = als_train(
        users, items, ratings, n_users=60, n_items=40,
        rank=8, iterations=8, l2=0.01, track_rmse=True,
    )
    assert history[-1] < 0.15  # near-exact recovery of a rank-4 matrix
    assert history[-1] <= history[0]  # monotone-ish improvement end to end
    assert rmse(state, users, items, ratings) == pytest.approx(history[-1])


def test_als_mixed_bf16_schedule_recovers_planted_rank():
    """bf16 early sweeps + f32 polish land on the same fixed point as the
    all-f32 run: ALS re-solves every row from scratch each half-sweep, so
    low-precision sweeps only change the polish's starting point. Guards
    the mixed schedule (``bf16_sweeps``)."""
    users, items, ratings = synthetic_ratings(
        n_users=80, n_items=50, rank=4, density=0.4, seed=3)
    f32, _ = als_train(users, items, ratings, 80, 50, rank=8,
                       iterations=8, l2=0.01, seed=5)
    mixed, _ = als_train(users, items, ratings, 80, 50, rank=8,
                         iterations=8, l2=0.01, seed=5, bf16_sweeps=6)
    r_f32 = rmse(f32, users, items, ratings)
    r_mixed = rmse(mixed, users, items, ratings)
    # near-exact recovery of the planted rank-4 structure, both schedules
    assert r_f32 < 0.15
    assert r_mixed < r_f32 + 0.02  # parity: polish restores convergence
    # all-bf16 (no polish) is the documented degraded mode — it must still
    # produce finite factors, but is NOT required to reach parity
    nopolish, _ = als_train(users, items, ratings, 80, 50, rank=8,
                            iterations=8, l2=0.01, seed=5, bf16_sweeps=8)
    assert np.isfinite(np.asarray(nopolish.user_factors)).all()


def test_als_f32_path_and_reg_modes():
    import jax.numpy as jnp

    users, items, ratings = synthetic_ratings(seed=1)
    state, _ = als_train(
        users, items, ratings, 60, 40, rank=8, iterations=4,
        compute_dtype=jnp.float32, reg_nnz=False,
    )
    assert rmse(state, users, items, ratings) < 0.5


def test_als_cold_rows_stay_zero():
    # user 59 and item 39 have no ratings
    users = np.array([0, 1, 2])
    items = np.array([0, 1, 2])
    ratings = np.array([4.0, 3.0, 5.0], np.float32)
    state, _ = als_train(users, items, ratings, 60, 40, rank=4, iterations=2)
    assert np.allclose(np.asarray(state.user_factors)[59], 0.0)
    assert np.allclose(np.asarray(state.item_factors)[39], 0.0)


def test_als_heavy_row_trains_and_sweep_api_still_rejects():
    users = np.zeros(10, dtype=np.int64)
    items = np.arange(10)
    ratings = np.ones(10, np.float32)
    # als_train routes split rows through the partial-Gram combining solver
    state, _ = als_train(users, items, ratings, 1, 10, rank=2, iterations=1,
                         max_width=4)
    assert np.isfinite(np.asarray(state.user_factors)).all()
    # the raw sweep API cannot combine split rows and must keep rejecting
    from incubator_predictionio_tpu.ops.als import als_init, als_sweep
    from incubator_predictionio_tpu.ops.sparse import build_padded_rows
    import jax
    buckets = build_padded_rows(users, items, ratings, 1, max_width=4)
    with pytest.raises(NotImplementedError):
        als_sweep(als_init(jax.random.key(0), 1, 10, 2), buckets, buckets)


def test_top_k_with_exclusions():
    import jax.numpy as jnp

    scores = jnp.asarray([1.0, 5.0, 3.0, 4.0, 2.0])
    top_s, top_i = top_k_with_exclusions(scores, 2)
    assert top_i.tolist() == [1, 3]
    top_s, top_i = top_k_with_exclusions(
        scores, 2, exclude=jnp.asarray([1, 3], jnp.int32)
    )
    assert top_i.tolist() == [2, 4]
    allowed = jnp.asarray([True, False, True, True, True])
    top_s, top_i = top_k_with_exclusions(scores, 2, allowed_mask=allowed)
    assert top_i.tolist() == [3, 2]
    # -1 exclude ids are inert (drop mode)
    _s, top_i = top_k_with_exclusions(scores, 1, exclude=jnp.asarray([-1]))
    assert top_i.tolist() == [1]


class TestSplitRowSolver:
    """Rows with degree > max_width: partial-Gram combining (ALX-style)."""

    def test_explicit_matches_unsplit(self):
        import numpy as np
        from incubator_predictionio_tpu.ops.als import als_train, rmse
        rng = np.random.default_rng(0)
        # user 0 rates 60 items; max_width=16 forces 4-way splitting
        users = np.concatenate([np.zeros(60, np.int64),
                                rng.integers(1, 20, 200)])
        items = np.concatenate([np.arange(60) % 30,
                                rng.integers(0, 30, 200)]).astype(np.int64)
        ratings = rng.integers(1, 6, 260).astype(np.float32)
        split, _ = als_train(users, items, ratings, 20, 30, rank=8,
                             iterations=5, seed=1, max_width=16)
        whole, _ = als_train(users, items, ratings, 20, 30, rank=8,
                             iterations=5, seed=1, max_width=1 << 12)
        np.testing.assert_allclose(
            np.asarray(split.user_factors), np.asarray(whole.user_factors),
            atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(split.item_factors), np.asarray(whole.item_factors),
            atol=1e-4)
        assert rmse(split, users, items, ratings) < 1.0

    def test_implicit_matches_unsplit(self):
        import numpy as np
        from incubator_predictionio_tpu.ops.als import als_train_implicit
        rng = np.random.default_rng(2)
        users = np.concatenate([np.full(40, 3, np.int64),
                                rng.integers(0, 10, 100)])
        items = np.concatenate([np.arange(40) % 25,
                                rng.integers(0, 25, 100)]).astype(np.int64)
        w = rng.random(140).astype(np.float32) + 0.5
        split = als_train_implicit(users, items, w, 10, 25, rank=8,
                                   iterations=4, seed=3, max_width=8)
        whole = als_train_implicit(users, items, w, 10, 25, rank=8,
                                   iterations=4, seed=3, max_width=1 << 12)
        np.testing.assert_allclose(
            np.asarray(split.user_factors), np.asarray(whole.user_factors),
            atol=1e-4)

    def test_split_heavy_structure(self):
        import numpy as np
        from incubator_predictionio_tpu.ops.sparse import (
            build_padded_rows, split_heavy)
        rows = np.concatenate([np.zeros(20, np.int64), [1, 2, 2]])
        cols = np.arange(23, dtype=np.int32)
        vals = np.ones(23, np.float32)
        buckets = build_padded_rows(rows, cols, vals, 3, max_width=8)
        light, heavy = split_heavy(buckets)
        assert heavy is not None
        # row 0 split into ceil(20/8)=3 segments; rows 1, 2 stay light
        assert list(heavy.row_ids) == [0]
        assert heavy.seg_ids.shape[0] == 3
        assert heavy.mask.sum() == 20
        light_ids = np.concatenate([b.row_ids for b in light])
        assert set(light_ids[light_ids >= 0]) == {1, 2}
        # no-split input passes through untouched
        l2, h2 = split_heavy(build_padded_rows(
            rows[20:], cols[20:], vals[20:], 3))
        assert h2 is None and len(l2) == 1
