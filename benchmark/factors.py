"""Seeded ALS factor tables, and the loader `pio deploy` restores them by.

The benchmark makes its weights from ``--seed``: planted low rank plus
noise (the first ``plant_rank`` columns carry unit-variance signal, the
rest ``noise``-scaled normal noise), float32, one jitted call per table
on the device. Every ROW is drawn from its own key (``fold_in(key,
row)``), so the plain reference regenerates exactly the rows it needs
without taking anything the program has held.

``SeededALSModel`` is a PredictionIO ``PersistentModel``: the engine
instance the harness writes where `pio deploy` looks holds a manifest
naming this class (core/persistent_model.py), and `pio deploy` calls
``load`` through ``Engine.prepare_deploy`` exactly as it would restore
any model that manages its own persistence. No 5 GB file is written:
the contract's "writes little to disk" and "weights made on the device
from the seed" both hold, and the msgpack checkpoint's 4 GiB ``bin``
limit (MSD's 4.68 GB user table) is never met. The tables are handed
over as host numpy arrays, which is what a checkpoint restore yields.
"""

from __future__ import annotations

import functools
import json
import os
import time

#: filled by ``SeededALSModel.load`` so the harness can say where set-up
#: went (seconds by stage) — read by run.py, never by the program
LOAD_WALLS: dict = {}


def seed_key(seed: int):
    """A jax PRNG key from any whole number (the driver's seeds pass
    2**31): the low and high parts are folded in separately."""
    import jax

    seed = int(seed)
    lo, hi = seed % (2**31 - 1), seed // (2**31 - 1)
    return jax.random.fold_in(jax.random.key(lo), hi)


def table_key(seed: int, side: str):
    import jax

    return jax.random.fold_in(seed_key(seed), {"user": 1, "item": 2}[side])


@functools.lru_cache(maxsize=None)
def _rows_fn(rank: int, plant_rank: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rows_of(key, noise, rows):
        scale = jnp.where(jnp.arange(rank) < plant_rank, 1.0,
                          noise).astype(jnp.float32)

        def one(r):
            return jax.random.normal(jax.random.fold_in(key, r), (rank,),
                                     jnp.float32) * scale

        return jax.vmap(one)(rows)

    return rows_of


def make_rows(seed: int, side: str, rows, rank: int, plant_rank: int,
              noise: float):
    """Rows ``rows`` (any int array) of one side's table, on the device."""
    import jax.numpy as jnp

    return _rows_fn(int(rank), int(plant_rank))(
        table_key(seed, side), jnp.float32(noise),
        jnp.asarray(rows, jnp.uint32))


def make_table(seed: int, side: str, n_rows: int, rank: int,
               plant_rank: int, noise: float):
    """The whole [n_rows, rank] float32 table of one side, on the device,
    in one jitted call."""
    import jax.numpy as jnp

    return make_rows(seed, side, jnp.arange(n_rows, dtype=jnp.uint32),
                     rank, plant_rank, noise)


def user_id(row: int) -> str:
    return f"u{row}"


def item_id(row: int) -> str:
    return f"i{row}"


def spec_path(instance_id: str) -> str:
    home = os.environ["PIO_HOME"]
    return os.path.join(home, "pmodels", f"seeded-{instance_id}.json")


def write_spec(instance_id: str, spec: dict) -> None:
    path = spec_path(instance_id)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(spec, f)


class SeededALSModel:
    """PersistentModel loader: the recommendation template's ALSModel
    with both factor tables made from the seed in the spec file."""

    @classmethod
    def load(cls, instance_id, params, ctx):
        import numpy as np

        from incubator_predictionio_tpu.data.bimap import BiMap
        from incubator_predictionio_tpu.models.recommendation.engine import (
            ALSModel,
        )

        with open(spec_path(instance_id)) as f:
            spec = json.load(f)
        t0 = time.perf_counter()
        args = (spec["rank"], spec["plant_rank"], spec["noise"])
        # device → host one table at a time: the device never holds more
        # than one table of the benchmark's beside what the program put
        uf = np.asarray(make_table(spec["seed"], "user", spec["n_users"],
                                   *args))
        vf = np.asarray(make_table(spec["seed"], "item", spec["n_items"],
                                   *args))
        t1 = time.perf_counter()
        model = ALSModel(
            user_factors=uf, item_factors=vf,
            user_bimap=BiMap({user_id(k): k
                              for k in range(spec["n_users"])}),
            item_bimap=BiMap({item_id(k): k
                              for k in range(spec["n_items"])}),
            item_years={}, item_categories={}, user_seen={})
        LOAD_WALLS["generate_s"] = t1 - t0
        LOAD_WALLS["bimaps_s"] = time.perf_counter() - t1
        LOAD_WALLS["loaded_at"] = time.time()
        return model
