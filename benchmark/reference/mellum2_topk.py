"""The plain reference of next-item serving through a Mellum2 block, and
its control.

What `pio deploy` has to answer for ``{"user": u, "num": k}``: u's window
of item tokens through the block, the last position against the head,
PAD and the window's own items struck out, the k largest logits in
descending order with those logits as scores. Here the forward is written
straight down in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: a Python loop over layers
and over experts, a dense [T, T] mask, no kernels, no cache,
no batching, one user at a time. The forward touches nothing of the
program; ``benchmark.seqmodel`` is imported for the seeded tensors and
windows alone, which are made again from the seed (nothing the program
held is taken). Weights are the loader's bfloat16 values raised to
float32 a layer at a time.

The block, as published (Mellum2-12B-A2.5B-Instruct ``config.json``;
sizes under their own keys in the configuration's file):

- ``x = E[tokens]`` (no positional embedding, no scaling).
- Each layer: ``h = RMSNorm(x)·g1``; ``q, k, v = h·Wq, h·Wk, h·Wv`` (no
  bias); rotary positions on q and k (rotate-half, all ``head_dim``
  dims); query head j reads key-value head ⌊j / (heads / kv heads)⌋;
  scores ``q·k/√head_dim``; mask: causal, in a sliding layer also
  ``t − s < sliding_window`` (the token itself and the window − 1 before
  it), PAD keys masked; softmax; ``x = x + concat(heads)·Wo``. Then
  ``h = RMSNorm(x)·g2``; ``p = softmax(h·Wr)`` over the experts; ``S`` =
  the ``num_experts_per_tok`` largest; ``w_e = p_e / Σ_S p``
  (``norm_topk_prob``); ``x = x + Σ_{e∈S} w_e · Wdown_e(silu(Wgate_e·h)
  ⊙ Wup_e·h)``. Every layer is sparse (``intermediate_size`` is used by
  no layer).
- Rotary, sliding layers: ``inv_freq_i = theta^(−2i/dim)``, angles
  ``pos·inv_freq``.
- Rotary, full layers (YaRN): ``pos_i = theta^(2i/dim)``; ``d(n) =
  dim·ln(original_max/(2π·n)) / (2·ln theta)``; ``low = ⌊d(beta_fast)⌋``,
  ``high = ⌈d(beta_slow)⌉``, clipped to [0, dim − 1]; ``ramp_i =
  clip((i − low)/(high − low), 0, 1)``; ``inv_freq_i = ramp_i/(factor·
  pos_i) + (1 − ramp_i)/pos_i``; cos and sin are multiplied by
  ``attention_factor``.
- Readout: ``RMSNorm(x_last)·g_f · Hᵀ`` with ``H`` its own table.

Departures (the configuration's ``assumed``): no q/k normalisation, no
router bias or correction term, no shared expert, no multi-token
prediction head — the config has no key for any of them.

Numbers compared, per run, over a seeded sample of the answers the timed
window got. For each sampled user, relative to the SPREAD of that user's
logits (their standard deviation over the items that may be served):

- the user's ``score_err``: the widest gap between a served score and
  the reference's logit of the SAME item;
- the user's ``rank_gap``: the widest gap by which the reference's logit
  of the item served at rank j lies below the reference's own j-th best
  after the same exclusions (0 when the order is the reference's).

A run's ``score_err`` and ``rank_gap`` are the MEDIAN of these over the
sampled users, not the widest: bfloat16 rounding flips a routed expert
at a user's last position in about one user of ten, which moves that
user's logits by 0.05–0.4 of the spread where the usual user reads
0.006–0.02 (PERF.md §6, PR 33: 61 of 578 users in 24 runs on the chip at
the timed sizes, none over 0.37) — a tail that is part of the sound
reading and says nothing of the precision of the arithmetic, while
anything systematic (a lower precision, a layer left out, a wrong mask)
moves every user and so the median: the control's LEAST user reads 0.13.
The median cannot see a fault that moves fewer than half the users, so
two more numbers are held to limits of their own:

- ``users_off``: the share of the sampled users whose ``score_err`` is
  over ``OFF`` (0.05, the median's limit). Sound runs read 0–29% (0 to 7
  users of 19–31); the control reads 100%.
- ``score_err_max``: the widest user's ``score_err``. A flipped expert
  reads up to 0.37 and the control's widest 0.50; another user's window,
  a wrong row of a fused batch or a mask that lets a future token in
  reads over 1 (the served scores are then unrelated to the reference's
  logits of the same items).

Every user's gap is printed, sorted.

- ``malformed``: sampled answers that are not k distinct servable items
  (known, not PAD, not in the user's window) in descending order.

The control is the same forward with every weight and every matmul's
activations rounded to ``float8_e4m3fn`` — the step below the bfloat16
the configuration states — put in the program's place.
"""

from __future__ import annotations

import numpy as np

from benchmark import seqmodel
# one wire shape for every engine's answers
from benchmark.reference.als_topk import judge as _judge_medians
from benchmark.reference.als_topk import parse_answer  # noqa: F401

#: a user's ``score_err`` over this counts the user as off (``users_off``)
OFF = 0.05


def judge(numbers: dict, limits: dict) -> bool:
    """``correct``: the medians within their limits as every engine's
    answers are judged, and neither too many users off nor any one user
    further off than a flipped expert puts one."""
    return (_judge_medians(numbers, limits)
            and numbers.get("users_off", 0.0)
            <= limits.get("users_off", 1.0)
            and numbers.get("score_err_max", 0.0)
            <= limits.get("score_err_max", float("inf")))

CONTROL_DTYPE = "float8_e4m3fn"


def sizes(config: dict) -> dict:
    n = int(config["num_hidden_layers"])
    return {
        "d": config["hidden_size"], "heads": config["num_attention_heads"],
        "kv": config["num_key_value_heads"], "dh": config["head_dim"],
        "eps": config["rms_norm_eps"], "experts": config["num_experts"],
        "top": config["num_experts_per_tok"],
        "width": config["moe_intermediate_size"],
        "kinds": list(config["layer_types"][:n]),
        "window": config["sliding_window"],
        "rope": config["rope_parameters"], "vocab": config["vocab_size"],
        "length": config["window_events"],
    }


def _rnd(x, low):
    """The control's rounding; nothing for the reference proper."""
    import jax.numpy as jnp

    return x if low is None else x.astype(low).astype(jnp.float32)


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


def forward_hidden(sz: dict, embed, final_norm, layers, tokens, low=None):
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
        x = _rnd(embed[tokens].astype(f32), low)
        for kind, raw in zip(sz["kinds"], layers):
            w = {name: _rnd(t.astype(f32), low) for name, t in raw.items()}
            rope = sz["rope"][kind]
            h = _rnd(_rms_norm(x, w["ln1"], sz["eps"]), low)
            q = _rotate((h @ w["wq"]).reshape(n_t, sz["heads"], sz["dh"]),
                        rope)
            k = _rotate((h @ w["wk"]).reshape(n_t, sz["kv"], sz["dh"]),
                        rope)
            v = (h @ w["wv"]).reshape(n_t, sz["kv"], sz["dh"])
            q, k, v = _rnd(q, low), _rnd(k, low), _rnd(v, low)
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
            heads = jnp.einsum("hts,shd->thd", _rnd(p, low), v)
            x = x + _rnd(heads.reshape(n_t, -1), low) @ w["wo"]
            h = _rnd(_rms_norm(x, w["ln2"], sz["eps"]), low)
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
                out = _rnd(act, low) @ w["w_down"][e]
                if gathered:
                    # a token finds its row of the block by its rank among
                    # the expert's tokens; the others read a row of zeros
                    row = jnp.where(mine, jnp.cumsum(mine) - 1, block)
                    out = jnp.concatenate(
                        [out, jnp.zeros((1, out.shape[1]), f32)])[row]
                w_e = jnp.where(top_e == e, top_w, 0.0).sum(-1)
                y = y + w_e[:, None] * out
            x = x + y
        return _rms_norm(x, _rnd(final_norm.astype(f32), low), sz["eps"])


def head_logits(hidden, head, low=None):
    """[..., V] float32 logits of final-normed hidden states."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return _rnd(hidden, low) @ _rnd(head.astype(jnp.float32), low).T


class Model:
    """The seeded tensors of one configuration, made once a comparison."""

    def __init__(self, config: dict, seed: int):
        self.sz = sz = sizes(config)
        self.seed = seed
        dtype = config["dtype"]
        self.tables = seqmodel.table_tensors(seed, sz["vocab"], sz["d"],
                                             dtype)
        self.layers = [
            seqmodel.layer_tensors(seed, n, sz["d"], sz["heads"] * sz["dh"],
                                   sz["kv"] * sz["dh"], sz["experts"],
                                   sz["width"], dtype)
            for n in range(len(sz["kinds"]))]

    def windows(self, rows) -> np.ndarray:
        return np.asarray(seqmodel.make_windows(
            self.seed, np.asarray(rows), self.sz["length"],
            self.sz["vocab"]))

    def last_logits(self, window, low=None) -> np.ndarray:
        """[V] float64 logits of the window's last position, PAD and the
        window's own items at −inf."""
        hidden = forward_hidden(self.sz, self.tables["embed"],
                                self.tables["final_norm"], self.layers,
                                window, low)
        out = np.asarray(head_logits(hidden[-1], self.tables["head"], low),
                         np.float64)
        out[0] = -np.inf
        out[np.asarray(window)] = -np.inf
        return out


def top_k(config: dict, seed: int, user_rows, k: int, low=None):
    """(scores [S, k], item tokens [S, k]) the forward at ``low`` (None:
    the reference's own float32) would serve for the users."""
    model = Model(config, seed)
    out_s, out_i = [], []
    for window in model.windows(user_rows):
        logits = model.last_logits(window, low)
        order = np.argsort(-logits, kind="stable")[:k]
        out_s.append(logits[order])
        out_i.append(order)
    return np.array(out_s), np.array(out_i)


def compare(config: dict, seed: int, user_rows, answers, k: int) -> dict:
    """The numbers compared, for answers = [(item tokens, scores) | None]
    aligned with ``user_rows``; each distinct user's window goes through
    the forward once. ``score_err`` and ``rank_gap`` are the MEDIAN over
    the users of each user's widest gap (see the module's docstring); the
    widest of all and the share of users off are given beside them."""
    user_rows = np.asarray(user_rows)
    numbers = {"compared": 0, "malformed": 0, "score_err": 0.0,
               "rank_gap": 0.0, "users": 0}
    model = Model(config, seed)
    vocab = model.sz["vocab"]
    uniq = np.unique(user_rows)
    errs, gaps = [], []
    for row, window in zip(uniq, model.windows(uniq)):
        seen = set(window.tolist()) | {0}
        mine = [a for a, r in zip(answers, user_rows) if r == row]
        formed = [a for a in mine
                  if a is not None and len(a[0]) == k and len(set(a[0])) == k
                  and all(0 < i < vocab and i not in seen for i in a[0])
                  and all(x >= y for x, y in zip(a[1], a[1][1:]))]
        numbers["malformed"] += len(mine) - len(formed)
        if not formed:
            continue
        logits = model.last_logits(window)
        servable = logits[np.isfinite(logits)]
        spread = float(servable.std())
        best = np.sort(servable)[::-1][:k]
        of_served = np.array([logits[np.asarray(items)]
                              for items, _scores in formed])
        served = np.array([scores for _items, scores in formed])
        errs.append(float(np.max(np.abs(served - of_served)) / spread))
        gaps.append(float(np.max(best[None, :] - of_served) / spread))
        numbers["compared"] += len(formed)
    if errs:
        numbers.update(
            users=len(errs), score_err=float(np.median(errs)),
            rank_gap=float(np.median(gaps)), score_err_max=max(errs),
            rank_gap_max=max(gaps),
            users_off=sum(e > OFF for e in errs) / len(errs))
        print("reference: a user's widest score_err, sorted: "
              + " ".join(f"{e:.4f}" for e in sorted(errs)), flush=True)
        print("reference: a user's widest rank_gap, sorted: "
              + " ".join(f"{g:.4f}" for g in sorted(gaps)), flush=True)
    return numbers


def control(config: dict, seed: int, user_rows, k: int,
            precision: str = CONTROL_DTYPE) -> dict:
    """The numbers the control reads: the forward at ``precision``
    ("float32": the reference itself) in the program's place, compared
    as a run's answers are."""
    top_s, top_i = top_k(config, seed, user_rows, k,
                         None if precision == "float32" else precision)
    answers = [(i.tolist(), s.tolist()) for s, i in zip(top_s, top_i)]
    return compare(config, seed, user_rows, answers, k)
