"""The plain reference of the routed, windowed block: the package's copy.

The forward of a published language-model block — sliding-window and
full grouped-query layers over softmax-routed experts — written straight
down in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: a Python loop over layers
and over experts, a dense [T, T] mask, no kernels, no cache,
no batching, one window at a time. It uses nothing of ``ops/``; the
tests hold ``ops/transformer.block_apply`` to it at logit level, and it
agrees with the benchmark's copy (``benchmark/reference/
mellum2_topk.py``, which decides a run's ``correct`` on the chip).

The equations (sizes in ``sz``, see :func:`sizes_of`):

- ``x = E[tokens]`` (no positional embedding, no scaling).
- Each layer: ``h = RMSNorm(x)·g1``; ``q, k, v = h·Wq, h·Wk, h·Wv`` (no
  bias); rotary positions on q and k (rotate-half, all ``head_dim``
  dims); query head j reads key-value head ⌊j / (heads / kv heads)⌋;
  scores ``q·k/√head_dim``; mask: causal, in a sliding layer also
  ``t − s < window`` (the token itself and the window − 1 before it),
  PAD keys masked; softmax; ``x = x + concat(heads)·Wo``. Then
  ``h = RMSNorm(x)·g2``; ``p = softmax(h·Wr)`` over the experts; ``S`` =
  the ``top`` largest; ``w_e = p_e / Σ_S p``; ``x = x + Σ_{e∈S} w_e ·
  Wdown_e(silu(Wgate_e·h) ⊙ Wup_e·h)``.
- Rotary, plain: ``inv_freq_i = theta^(−2i/dim)``, angles
  ``pos·inv_freq``.
- Rotary, YaRN: ``pos_i = theta^(2i/dim)``; ``d(n) = dim·ln(original_max
  /(2π·n)) / (2·ln theta)``; ``low = ⌊d(beta_fast)⌋``, ``high =
  ⌈d(beta_slow)⌉``, clipped to [0, dim − 1]; ``ramp_i = clip((i − low)/
  (high − low), 0, 1)``; ``inv_freq_i = ramp_i/(factor·pos_i) +
  (1 − ramp_i)/pos_i``; cos and sin are multiplied by
  ``attention_factor``.
- Readout: ``RMSNorm(x)·g_f · Hᵀ`` with ``H`` its own table.

Departures from the published model: no q/k normalisation, no router
bias or correction term, no shared expert, no multi-token prediction head
(its config has no key for any of them).
"""

from __future__ import annotations

import numpy as np


def sizes_of(spec, vocab: int) -> dict:
    """``sz`` for a routed rotary ``ops.transformer.BlockSpec``, in the
    published config's terms."""
    def rope(rot):
        if rot.factor is None:
            return {"rope_type": "default", "rope_theta": rot.theta}
        return {"rope_type": "yarn", "rope_theta": rot.theta,
                "factor": rot.factor,
                "original_max_position_embeddings":
                    rot.original_max_position,
                "beta_fast": rot.beta_fast, "beta_slow": rot.beta_slow,
                "attention_factor": rot.attention_factor}

    kinds, ropes, window = [], {}, None
    for ls in spec.period * spec.n_periods:
        kind = ("full_attention" if ls.window is None
                else "sliding_attention")
        kinds.append(kind)
        ropes[kind] = rope(ls.rotary)
        window = ls.window if ls.window is not None else window
    return {"d": spec.d_model, "heads": spec.n_heads,
            "kv": spec.n_kv_heads, "dh": spec.head_dim,
            "eps": spec.norm_eps, "experts": spec.n_experts,
            "top": spec.experts_per_token, "width": spec.ffn_width,
            "kinds": kinds, "window": window, "rope": ropes, "vocab": vocab}


def layers_of(spec, weights) -> list:
    """One dict a layer, in depth order, of an
    ``ops.transformer.BlockWeights`` stacked over periods."""
    out = []
    for p in range(spec.n_periods):
        for lw in weights.layers:
            out.append({
                "ln1": lw.ln1_scale[p], "ln2": lw.ln2_scale[p],
                "wq": lw.wq[p], "wk": lw.wk[p], "wv": lw.wv[p],
                "wo": lw.wo[p], "router": lw.ffn.router[p],
                "w_gate": lw.ffn.w_gate[p], "w_up": lw.ffn.w_up[p],
                "w_down": lw.ffn.w_down[p]})
    return out


def inv_freq(rope: dict, dim: int) -> np.ndarray:
    theta = float(rope["rope_theta"])
    pos = theta ** (2.0 * np.arange(dim // 2) / dim)
    if rope["rope_type"] == "default":
        return 1.0 / pos
    if rope["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")

    def d(n):
        return (dim * np.log(rope["original_max_position_embeddings"]
                             / (2 * np.pi * n)) / (2 * np.log(theta)))

    low = max(np.floor(d(rope["beta_fast"])), 0)
    high = min(np.ceil(d(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return ramp / (rope["factor"] * pos) + (1 - ramp) / pos


def _rotate(x, rope: dict):
    """x [T, H, dim] with rotary positions 0 .. T − 1 (rotate-half)."""
    import jax.numpy as jnp

    dim = x.shape[-1]
    angles = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
              * jnp.asarray(inv_freq(rope, dim), jnp.float32)[None, :])
    angles = jnp.concatenate([angles, angles], axis=-1)
    factor = float(rope.get("attention_factor", 1.0))
    cos = (jnp.cos(angles) * factor)[:, None, :]
    sin = (jnp.sin(angles) * factor)[:, None, :]
    turned = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + turned * sin


def _rms_norm(x, g, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def forward_hidden(sz: dict, embed, final_norm, layers, tokens):
    """Final-normed hidden states [T, D] float32 of one window ``tokens``
    [T]; ``layers`` is a list of one dict a layer (the loader's names)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    tokens = jnp.asarray(tokens, jnp.int32)
    n_t, group = tokens.shape[0], sz["heads"] // sz["kv"]
    t_idx = jnp.arange(n_t)[:, None]
    s_idx = jnp.arange(n_t)[None, :]
    causal = (s_idx <= t_idx) & (tokens != 0)[None, :]
    with jax.default_matmul_precision("highest"):
        x = embed[tokens].astype(f32)
        for kind, raw in zip(sz["kinds"], layers):
            w = {name: t.astype(f32) for name, t in raw.items()}
            rope = sz["rope"][kind]
            h = _rms_norm(x, w["ln1"], sz["eps"])
            q = _rotate((h @ w["wq"]).reshape(n_t, sz["heads"], sz["dh"]),
                        rope)
            k = _rotate((h @ w["wk"]).reshape(n_t, sz["kv"], sz["dh"]),
                        rope)
            v = (h @ w["wv"]).reshape(n_t, sz["kv"], sz["dh"])
            q, k, v = q, k, v
            mask = causal
            if kind == "sliding_attention":
                mask = mask & (t_idx - s_idx < sz["window"])
            elif kind != "full_attention":
                raise ValueError(f"unknown layer type {kind!r}")
            # query head j reads key-value head j // group
            k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
            scores = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(sz["dh"])
            scores = jnp.where(mask[None], scores, -jnp.inf)
            top = jnp.max(scores, -1, keepdims=True)
            p = jnp.exp(scores - jnp.where(jnp.isfinite(top), top, 0.0))
            total = p.sum(-1, keepdims=True)
            # a query with no key to see (a PAD row) attends nothing
            p = p / jnp.where(total == 0, 1.0, total)
            heads = jnp.einsum("hts,shd->thd", p, v)
            x = x + heads.reshape(n_t, -1) @ w["wo"]
            h = _rms_norm(x, w["ln2"], sz["eps"])
            probs = jax.nn.softmax(h @ w["router"], axis=-1)
            top_p, top_e = jax.lax.top_k(probs, sz["top"])
            top_w = top_p / top_p.sum(-1, keepdims=True)
            y = jnp.zeros_like(x)
            routed = (top_e[:, :, None] == jnp.arange(sz["experts"])).any(1)
            counts = np.asarray(routed.sum(0))
            # an expert meets its own tokens, gathered into a block of
            # twice an even share (every token where more come); a token
            # off the expert weighs nothing, so what fills a block out
            # adds nothing
            block = min(n_t, 2 * n_t * sz["top"] // sz["experts"])
            for e in range(sz["experts"]):
                if counts[e] == 0:
                    continue
                mine = routed[:, e]
                gathered = counts[e] <= block
                # the expert's tokens first, in their order
                h_e = h[jnp.argsort(~mine, stable=True)[:block]] \
                    if gathered else h
                act = jax.nn.silu(h_e @ w["w_gate"][e]) * (
                    h_e @ w["w_up"][e])
                out = act @ w["w_down"][e]
                if gathered:
                    # a token finds its row of the block by its rank among
                    # the expert's tokens; the others read a row of zeros
                    row = jnp.where(mine, jnp.cumsum(mine) - 1, block)
                    out = jnp.concatenate(
                        [out, jnp.zeros((1, out.shape[1]), f32)])[row]
                w_e = jnp.where(top_e == e, top_w, 0.0).sum(-1)
                y = y + w_e[:, None] * out
            x = x + y
        return _rms_norm(x, final_norm.astype(f32), sz["eps"])


def head_logits(hidden, head):
    """[..., V] float32 logits of final-normed hidden states."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return hidden @ head.astype(jnp.float32).T
