"""Seeded planted-factor catalogue generator — the shared fixture of the
two-stage MIPS serving path (``ops/mips.recall_probe`` and the tests
import it).

ML-20M tops out at ~27k items, far too small to measure an
approximate-MIPS win; real embedding catalogues are 10-100× larger. This
module PLANTS a factor table with the geometry trained factor tables
actually have — cluster structure (genres/categories), bounded relative
within-cluster noise, and a log-normal popularity (norm) profile — at
any item count, so the candidate-stage recall and the exhaustive-vs-
two-stage device walls are measurable without new data.

The geometry matters: an isotropic-noise table (per-dim noise comparable
to the cluster radius) is ~75% noise at rank 64 and NO index structure
can beat a linear scan on it — which is a statement about the fixture,
not about serving. Here ``noise`` is the RELATIVE within-cluster radius
(noise vector norm over center norm), matching the spectral decay of
trained MF factors, and the recall gate (tests/test_mips.py) is honest
because the exhaustive oracle runs on the same table.

Everything is a pure function of the seed — the determinism tests
compare runs byte-for-byte.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def planted_item_factors(
    n_items: int,
    rank: int,
    seed: int = 0,
    n_genres: int = 64,
    noise: float = 0.6,
    pop_sigma: float = 0.35,
) -> np.ndarray:
    """[n_items, rank] f32 planted item factor table.

    item = (unit genre center + relative-noise) × log-normal popularity.
    ``noise`` is the within-cluster radius relative to the unit center
    (per-dim sigma = noise/sqrt(rank)); ``pop_sigma`` the log-normal
    sigma of the row norms (the MIPS-relevant norm spread — top-k by
    inner product is popularity-weighted, so the coarse stage must
    survive it)."""
    rng = np.random.default_rng(seed)
    genres = rng.normal(0.0, 1.0, (n_genres, rank))
    genres /= np.maximum(
        np.linalg.norm(genres, axis=1, keepdims=True), 1e-9)
    which = rng.integers(0, n_genres, n_items)
    v = genres[which] + rng.normal(
        0.0, noise / np.sqrt(rank), (n_items, rank))
    v *= rng.lognormal(0.0, pop_sigma, n_items)[:, None]
    return np.ascontiguousarray(v, dtype=np.float32)


def planted_queries(
    item_factors: np.ndarray,
    n_queries: int,
    seed: int = 1,
    mix: int = 3,
) -> np.ndarray:
    """[n_queries, rank] f32 user-like query vectors: each the mean of
    ``mix`` random item rows — the blended-interest shape ALS user
    vectors converge to, and the harder case for a bucketed coarse
    stage than single-item queries."""
    rng = np.random.default_rng(seed)
    picks = rng.integers(0, item_factors.shape[0], (n_queries, mix))
    return np.ascontiguousarray(
        item_factors[picks].mean(axis=1), dtype=np.float32)


def exhaustive_top_k(
    item_factors: np.ndarray,
    queries: np.ndarray,
    k: int,
) -> np.ndarray:
    """[n_queries, k] exact oracle ids (descending score) — the recall
    gate's ground truth, computed on the host so it cannot share a bug
    with the device path under test."""
    scores = queries @ item_factors.T
    part = np.argpartition(scores, -k, axis=1)[:, -k:]
    ps = np.take_along_axis(scores, part, axis=1)
    order = np.argsort(-ps, axis=1, kind="stable")
    return np.take_along_axis(part, order, axis=1)


def recall_against_oracle(
    approx_ids: np.ndarray,   # [Q, >=k] approximate ids (any order)
    oracle_ids: np.ndarray,   # [Q, k] exact ids
    k: int,
) -> Tuple[float, float]:
    """(mean recall@k, min per-query recall@k)."""
    recalls = []
    for row in range(oracle_ids.shape[0]):
        got = set(int(i) for i in approx_ids[row] if i >= 0)
        want = set(int(i) for i in oracle_ids[row][:k])
        recalls.append(len(got & want) / max(len(want), 1))
    return float(np.mean(recalls)), float(np.min(recalls))
