"""Real-data RMSE regression bound (VERDICT r4 item 4).

Trains on the reference's bundled MovieLens sample — the only real
interaction data in this egress-free environment — read at run time from
the read-only reference tree (never copied into the repo; provenance:
/root/reference/examples/experimental/data/movielens.txt, the file the
reference's own movielens tutorials consume). Skips when the reference
tree is not mounted."""

import os

import numpy as np
import pytest

#: user::item::rating, 1.5k real ratings: 30 users × 100 items, std 1.19
MOVIELENS_SAMPLE = "/root/reference/examples/experimental/data/movielens.txt"
#: 1.2k training ratings cannot support a wide rank (it would fit the
#: noise): rank 8, λ=0.1 measured best of a small grid on this sample
MOVIELENS_RANK = 8
MOVIELENS_L2 = 0.1
#: measured 1.076/1.058/1.024 across seeds 0..2 (10 sweeps, 80/20
#: split). 1.20 is ~11% headroom over the worst seed and below the 1.31
#: a mis-regularized run measures.
MOVIELENS_RMSE_BOUND = 1.20

pytestmark = pytest.mark.skipif(
    not os.path.exists(MOVIELENS_SAMPLE),
    reason="reference movielens sample not available")


def load_movielens_sample():
    """→ (users, items, vals, n_users, n_items) from the sample file."""
    with open(MOVIELENS_SAMPLE) as f:
        rows = [line.strip().split("::") for line in f if line.strip()]
    users = np.asarray([int(r[0]) for r in rows], np.int32)
    items = np.asarray([int(r[1]) for r in rows], np.int32)
    vals = np.asarray([float(r[2]) for r in rows], np.float32)
    # dense reindex (ids in the file are sparse)
    uu, users = np.unique(users, return_inverse=True)
    ii, items = np.unique(items, return_inverse=True)
    return (users.astype(np.int32), items.astype(np.int32), vals,
            len(uu), len(ii))


def test_movielens_model_keeps_its_heldout_rmse():
    """The solver stays healthy on real human ratings: under the pinned
    bound, and a real model — better than predicting the train-mean on
    the same 80/20 split."""
    from incubator_predictionio_tpu.ops import als

    users, items, vals, n_u, n_i = load_movielens_sample()
    rng = np.random.default_rng(0)
    perm = rng.permutation(len(vals))
    cut = int(0.8 * len(vals))
    tr, te = perm[:cut], perm[cut:]
    state, _ = als.als_train(
        users[tr], items[tr], vals[tr], n_u, n_i,
        rank=MOVIELENS_RANK, iterations=10, l2=MOVIELENS_L2, seed=0)
    rmse = als.rmse(state, users[te], items[te], vals[te])
    const = float(np.sqrt(np.mean((vals[te] - vals[tr].mean()) ** 2)))
    assert rmse < const, (rmse, const)
    assert rmse <= MOVIELENS_RMSE_BOUND, rmse
