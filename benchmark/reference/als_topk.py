"""The plain reference of ALS top-k serving, and its control.

What `pio deploy` has to answer for ``{"user": u, "num": k}``: the k
items with the largest inner product of u's factor row with the item
rows, in descending order, with those products as scores. Here it is
written straight down: the sampled users' rows and the whole item table
are made again from the seed (``benchmark.factors`` — nothing the
program held is taken), one float32 matmul at HIGHEST precision in
blocks of users, one ``top_k``. It imports nothing of the program.

Numbers compared, per run, over a seeded sample of the answers the timed
window got (each relative to that user's best reference score):

- ``score_err``: the widest gap between a served score and the
  reference's score of the SAME item;
- ``rank_gap``: the widest gap by which the reference score of the item
  served at rank j lies below the reference's own j-th best (0 when the
  order is the reference's; near-ties may swap within rounding);
- ``malformed``: sampled answers that are not k distinct known items in
  descending order (limit 0).

The control is the same reference at the precision below the one the
configuration states (float32 at HIGHEST → ``high``, three bf16 passes),
put in the program's place: its top-k is what the program would have
served. ``emulated`` splits the operands into bf16 parts by hand, so it
reads the same on any backend (the test uses it on the CPU); ``device``
asks the backend for ``Precision.HIGH`` (the step that would tempt a
later PR), which only a TPU honours.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark import factors

BLOCK = 128  # users scored at a time: a [128, n_items] f32 block


def _tables(config: dict, seed: int, user_rows):
    p = config["planted"]
    args = (config["rank"], p["rank"], p["noise"])
    items = factors.make_table(seed, "item", config["n_items"], *args)
    users = factors.make_rows(seed, "user", np.asarray(user_rows), *args)
    return users, items


def _bf16_parts(x, n: int):
    import jax.numpy as jnp

    parts, rest = [], x
    for _ in range(n):
        p = rest.astype(jnp.bfloat16).astype(jnp.float32)
        parts.append(p)
        rest = rest - p
    return parts


def scores_of(users, items, precision: str):
    """[S, n_items] float32 scores at the named precision."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.dot(users, items.T, precision=hi)
    if precision == "high":
        return jnp.dot(users, items.T, precision=jax.lax.Precision.HIGH)
    if precision == "high_emulated":
        # bf16_3x: the three largest cross terms of the bf16 splits,
        # each product exact, accumulated in float32
        u0, u1 = _bf16_parts(users, 2)
        v0, v1 = _bf16_parts(items, 2)
        return (jnp.dot(u0, v0.T, precision=hi)
                + (jnp.dot(u0, v1.T, precision=hi)
                   + jnp.dot(u1, v0.T, precision=hi)))
    raise ValueError(f"unknown precision {precision!r}")


def top_k(config: dict, seed: int, user_rows, k: int,
          precision: str = "highest"):
    """(scores [S, k], item rows [S, k], reference score matrix blocks
    are not kept) for the users, block by block."""
    import jax

    user_rows = np.asarray(user_rows)
    users, items = _tables(config, seed, user_rows)
    out_s, out_i = [], []
    for at in range(0, len(user_rows), BLOCK):
        s = scores_of(users[at:at + BLOCK], items, precision)
        top_s, top_i = jax.lax.top_k(s, k)
        out_s.append(np.asarray(top_s))
        out_i.append(np.asarray(top_i))
    return np.concatenate(out_s), np.concatenate(out_i)


def parse_answer(body: bytes):
    """(item rows, scores) of one served body, or None if it is not an
    answer of the expected form."""
    try:
        rows = json.loads(body)["itemScores"]
        items = [int(r["item"][1:]) for r in rows]
        if any(r["item"][0] != "i" for r in rows):
            return None
        scores = [float(r["score"]) for r in rows]
    except (ValueError, KeyError, TypeError, IndexError):
        return None
    return items, scores


def compare(config: dict, seed: int, user_rows, answers, k: int) -> dict:
    """The numbers compared, for answers = [(item rows, scores) | None]
    aligned with ``user_rows``."""
    import jax
    import jax.numpy as jnp

    user_rows = np.asarray(user_rows)
    n_items = config["n_items"]
    formed = np.array([
        a is not None and len(a[0]) == k and len(set(a[0])) == k
        and all(0 <= i < n_items for i in a[0])
        and all(x >= y for x, y in zip(a[1], a[1][1:]))
        for a in answers], bool)
    numbers = {"compared": int(formed.sum()),
               "malformed": int((~formed).sum()),
               "score_err": 0.0, "rank_gap": 0.0}
    if not formed.any():
        return numbers
    rows = user_rows[formed]
    got_i = np.array([a[0] for a, f in zip(answers, formed) if f], np.int32)
    got_s = np.array([a[1] for a, f in zip(answers, formed) if f],
                     np.float64)
    users, items = _tables(config, seed, rows)
    for at in range(0, len(rows), BLOCK):
        s = scores_of(users[at:at + BLOCK], items, "highest")
        best, _ = jax.lax.top_k(s, k)
        of_served = jnp.take_along_axis(
            s, jnp.asarray(got_i[at:at + BLOCK]), axis=1)
        best = np.asarray(best, np.float64)
        of_served = np.asarray(of_served, np.float64)
        scale = np.abs(best[:, :1])
        numbers["score_err"] = max(numbers["score_err"], float(np.max(
            np.abs(got_s[at:at + BLOCK] - of_served) / scale)))
        numbers["rank_gap"] = max(numbers["rank_gap"], float(np.max(
            (best - of_served) / scale)))
    return numbers


def control(config: dict, seed: int, user_rows, k: int,
            precision: str) -> dict:
    """The numbers the control reads: the reference at ``precision`` in
    the program's place, compared as a run's answers are."""
    top_s, top_i = top_k(config, seed, user_rows, k, precision)
    answers = [(i.tolist(), s.astype(np.float64).tolist())
               for s, i in zip(top_s, top_i)]
    return compare(config, seed, user_rows, answers, k)


def judge(numbers: dict, limits: dict) -> bool:
    """``correct``: every number within its limit, and something
    compared at all."""
    return (numbers["compared"] > 0 and numbers["malformed"] == 0
            and numbers["score_err"] <= limits["score_err"]
            and numbers["rank_gap"] <= limits["rank_gap"])
