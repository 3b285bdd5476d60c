"""The work a sequence-block dispatch needs, from the configuration's
shapes (the published config's own keys).

The same whatever implements it. One query is one window of ``L`` tokens
through ``n`` layers and the head, the last position alone against the
head's ``V`` rows:

- matmul FLOPs a token a layer: ``2·d·(2·H·dh + 2·Hkv·dh)`` (q, o, k, v),
  ``2·d·E`` (router), ``top·3·2·d·F`` (the routed experts);
- attention FLOPs a layer: ``4·H·dh`` a (query, key) pair, causal and
  windowed pairs only: ``L(L+1)/2`` in a full layer, ``Σ_t min(t+1, w)``
  in a sliding one;
- head: ``2·d·V`` a query;
- bytes a dispatch: every weight once (all ``E`` experts: a dispatch of
  2,048 tokens or more reaches each), the head's table once; a query its
  window's embedding rows, its window and its packed answer. Activations
  are not counted: a fused implementation keeps them on the chip. No
  padding row or PAD position counts as work.

The grouped expert matmuls alone (``moe_*``): their FLOPs, their three
tables once a dispatch, and a query's rows in (``L·top·d``) and out.
"""

from __future__ import annotations

BYTES_PER_ELEM = {"float32": 4, "bfloat16": 2}


def _shape(config: dict) -> dict:
    n = int(config["num_hidden_layers"])
    kinds = config["layer_types"][:n]
    length, window = config["window_events"], config["sliding_window"]
    pairs = {
        "full_attention": length * (length + 1) // 2,
        "sliding_attention": sum(min(t + 1, window) for t in range(length)),
    }
    return {
        "d": config["hidden_size"],
        "hq": config["num_attention_heads"] * config["head_dim"],
        "hkv": config["num_key_value_heads"] * config["head_dim"],
        "experts": config["num_experts"],
        "top": config["num_experts_per_tok"],
        "width": config["moe_intermediate_size"],
        "vocab": config["vocab_size"], "length": length, "layers": n,
        "pairs": sum(pairs[k] for k in kinds),
        "elem": BYTES_PER_ELEM[config["dtype"]],
    }


def moe_query_flops(config: dict) -> float:
    s = _shape(config)
    return float(s["layers"] * s["length"] * s["top"] * 3 * 2
                 * s["d"] * s["width"])


def query_flops(config: dict) -> float:
    """Model FLOPs of one answered query."""
    s = _shape(config)
    dense = 2 * s["d"] * (2 * s["hq"] + 2 * s["hkv"]) \
        + 2 * s["d"] * s["experts"]
    return (s["layers"] * s["length"] * dense + moe_query_flops(config)
            + 4 * s["hq"] * s["pairs"] + 2 * s["d"] * s["vocab"])


def _expert_params(s: dict) -> int:
    return s["layers"] * s["experts"] * 3 * s["d"] * s["width"]


def dispatch_bytes(config: dict, dispatches: float, queries: float,
                   k: int) -> float:
    s = _shape(config)
    layer = s["d"] * (2 * s["hq"] + 2 * s["hkv"]) + s["d"] * s["experts"] \
        + 2 * s["d"]
    weights = (s["layers"] * layer + _expert_params(s)
               + s["vocab"] * s["d"] + s["d"]) * s["elem"]
    per_query = s["length"] * (s["d"] * s["elem"] + 4) + k * 8
    return dispatches * weights + queries * per_query


def moe_dispatch_bytes(config: dict, dispatches: float,
                       queries: float) -> float:
    s = _shape(config)
    rows = s["layers"] * s["length"] * s["top"] * s["d"] * s["elem"]
    return dispatches * _expert_params(s) * s["elem"] + queries * 2 * rows


def _least(flops: float, nbytes: float, peaks: dict):
    by_flops = flops / peaks["flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops > by_bytes \
        else (by_bytes, "memory")


def least_seconds(config: dict, peaks: dict, dispatches: float,
                  queries: float, k: int):
    """(least time, which bound) for ``dispatches`` executions of the
    forward that answered ``queries`` queries between them."""
    return _least(queries * query_flops(config),
                  dispatch_bytes(config, dispatches, queries, k), peaks)


def moe_least_seconds(config: dict, peaks: dict, dispatches: float,
                      queries: float):
    return _least(queries * moe_query_flops(config),
                  moe_dispatch_bytes(config, dispatches, queries), peaks)
