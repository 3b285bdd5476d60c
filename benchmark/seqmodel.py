"""Seeded weights and user windows of a sequence block, and the loader
`pio deploy` restores them by.

The benchmark makes its weights from ``--seed``: every tensor from its
own key (``fold_in(fold_in(seed key, tensor), layer)``), normal, scaled
so that activations stay of order one, rounded to the configuration's
dtype — on the device, one jitted call a tensor. Every user's WINDOW
(``window`` item tokens in 1 .. vocab − 1, no PAD: every history is full)
is drawn from its own key (``fold_in(window key, row)``), so the plain
reference remakes exactly the sampled users' windows without taking
anything the program has held.

``SeededSeqModel`` is a PredictionIO ``PersistentModel`` loader, as
``benchmark.factors.SeededALSModel`` is: the engine instance the harness
writes holds a manifest naming this class, and `pio deploy` calls
``load``. The block's description comes from the algorithm's parameters
(``block``, what the engine itself reads); of the spec file
``benchmark.deploy.write_instance`` writes, ``seed`` and ``n_users`` are
used, ``n_items`` and ``rank`` are checked against the description (the
head's rows and width), and ``plant_rank`` / ``noise`` are ignored.

The package's block modules are imported at the top: on a program that
lacks them this module fails at import, and `pio deploy` with it.
"""

from __future__ import annotations

import functools
import json
import time

from incubator_predictionio_tpu.ops import moe, transformer

from benchmark import factors

#: one id a tensor, folded into the seed's key
TENSORS = {"embed": 1, "head": 2, "final_norm": 3, "ln1": 4, "ln2": 5,
           "wq": 6, "wk": 7, "wv": 8, "wo": 9, "router": 10, "w_gate": 11,
           "w_up": 12, "w_down": 13, "windows": 14}
#: user rows made in one call: a [32768, 2048] int32 block is 268 MB
WINDOW_ROWS = 32768


@functools.lru_cache(maxsize=None)
def _normal_fn(shape: tuple, dtype: str, centre: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, scale):
        return (centre + jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    return make


def tensor(seed: int, name: str, layer: int, shape, scale: float,
           dtype: str = "bfloat16", centre: float = 0.0):
    """One seeded tensor on the device: ``centre + scale · normal``."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(
        jax.random.fold_in(factors.seed_key(seed), TENSORS[name]), layer)
    return _normal_fn(tuple(int(n) for n in shape), dtype, float(centre))(
        key, jnp.float32(scale))


def layer_tensors(seed: int, layer: int, d: int, hq: int, hkv: int,
                  n_experts: int, width: int, dtype: str = "bfloat16"
                  ) -> dict:
    """One layer's tensors by name, in the shapes the equations use:
    ``wq`` [D, H·dh], ``wk``/``wv`` [D, Hkv·dh], ``wo`` [H·dh, D],
    ``router`` [D, E], ``w_gate``/``w_up`` [E, D, F], ``w_down``
    [E, F, D], the two norm gains [D] (1 + 0.1 · normal)."""
    def t(name, shape, scale, centre=0.0):
        return tensor(seed, name, layer, shape, scale, dtype, centre)

    return {
        "ln1": t("ln1", (d,), 0.1, 1.0), "ln2": t("ln2", (d,), 0.1, 1.0),
        "wq": t("wq", (d, hq), d ** -0.5),
        "wk": t("wk", (d, hkv), d ** -0.5),
        "wv": t("wv", (d, hkv), d ** -0.5),
        "wo": t("wo", (hq, d), hq ** -0.5),
        "router": t("router", (d, n_experts), d ** -0.5),
        "w_gate": t("w_gate", (n_experts, d, width), d ** -0.5),
        "w_up": t("w_up", (n_experts, d, width), d ** -0.5),
        "w_down": t("w_down", (n_experts, width, d), width ** -0.5),
    }


def table_tensors(seed: int, vocab: int, d: int, dtype: str = "bfloat16"
                  ) -> dict:
    """The embedding, the untied head and the final norm's gain."""
    return {
        "embed": tensor(seed, "embed", 0, (vocab, d), 1.0, dtype),
        "head": tensor(seed, "head", 0, (vocab, d), d ** -0.5, dtype),
        "final_norm": tensor(seed, "final_norm", 0, (d,), 0.1, dtype, 1.0),
    }


@functools.lru_cache(maxsize=None)
def _windows_fn(length: int, vocab: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def windows_of(key, rows):
        def one(r):
            return jax.random.randint(jax.random.fold_in(key, r),
                                      (length,), 1, vocab, jnp.int32)

        return jax.vmap(one)(rows)

    return windows_of


def make_windows(seed: int, rows, length: int, vocab: int):
    """[len(rows), length] int32 windows of the user rows, on the device."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(factors.seed_key(seed), TENSORS["windows"])
    return _windows_fn(int(length), int(vocab))(
        key, jnp.asarray(rows, jnp.uint32))


def make_all_windows(seed: int, n_users: int, length: int, vocab: int):
    import jax.numpy as jnp
    import numpy as np

    blocks = [make_windows(seed, np.arange(at, min(at + WINDOW_ROWS,
                                                   n_users)), length, vocab)
              for at in range(0, n_users, WINDOW_ROWS)]
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks)


def block_weights(seed: int, spec: "transformer.BlockSpec", vocab: int
                  ) -> "transformer.BlockWeights":
    """The seeded tensors in the program's own layout: a period
    position's tensors stacked over the periods."""
    import jax.numpy as jnp

    if spec.ffn != "routed-swiglu" or spec.tied_head \
            or spec.learned_positions:
        raise SystemExit("benchmark.seqmodel: the seeded loader makes a "
                         "routed block with rotary positions and its own "
                         "head")
    hq = spec.n_heads * spec.head_dim
    hkv = spec.n_kv_heads * spec.head_dim
    n_in = len(spec.period)
    layers = []
    for at in range(n_in):
        per = [layer_tensors(seed, p * n_in + at, spec.d_model, hq, hkv,
                             spec.n_experts, spec.ffn_width, spec.dtype)
               for p in range(spec.n_periods)]
        st = {k: jnp.stack([t[k] for t in per]) for k in per[0]}
        del per
        layers.append(transformer.LayerWeights(
            ln1_scale=st["ln1"], ln2_scale=st["ln2"], wq=st["wq"],
            wk=st["wk"], wv=st["wv"], wo=st["wo"],
            ffn=moe.ExpertWeights(router=st["router"], w_gate=st["w_gate"],
                                  w_up=st["w_up"], w_down=st["w_down"])))
    tables = table_tensors(seed, vocab, spec.d_model, spec.dtype)
    return transformer.BlockWeights(
        item_emb=tables["embed"], pos_emb=None, layers=tuple(layers),
        lnf_scale=tables["final_norm"], head=tables["head"])


class SeededSeqModel:
    """PersistentModel loader: the sequence template's SeqRecModel with
    the block's weights and every user's window made from the seed."""

    @classmethod
    def load(cls, instance_id, params, ctx):
        from incubator_predictionio_tpu.data.bimap import BiMap
        from incubator_predictionio_tpu.models.sequence.engine import (
            SeqRecModel,
        )

        with open(factors.spec_path(instance_id)) as f:
            spec_file = json.load(f)
        block = transformer.block_spec_from_json(params.block)
        vocab = int(spec_file["n_items"])
        if int(spec_file["rank"]) != block.d_model:
            raise SystemExit(
                f"benchmark.seqmodel: the configuration's rank "
                f"{spec_file['rank']} is not the block's width "
                f"{block.d_model}")
        t0 = time.perf_counter()
        seed, n_users = spec_file["seed"], int(spec_file["n_users"])
        windows = make_all_windows(seed, n_users, block.max_len, vocab)
        weights = block_weights(seed, block, vocab)
        weights.lnf_scale.block_until_ready()
        t1 = time.perf_counter()
        model = SeqRecModel(
            weights=weights,
            # token t is item "i<t>"; token 0 is PAD and has no name
            item_bimap=BiMap({factors.item_id(t + 1): t
                              for t in range(vocab - 1)}),
            n_heads=block.n_heads, max_len=block.max_len, final_loss=0.0,
            spec=block, windows=windows,
            user_bimap=BiMap({factors.user_id(k): k
                              for k in range(n_users)}))
        factors.LOAD_WALLS["generate_s"] = t1 - t0
        factors.LOAD_WALLS["bimaps_s"] = time.perf_counter() - t1
        factors.LOAD_WALLS["loaded_at"] = time.time()
        return model
