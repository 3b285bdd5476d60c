"""The work a sequence-block dispatch needs, from the configuration's
shapes (the published config's own keys).

The work the answer needs, whatever implements it. One query is one
window of ``L`` tokens through ``n`` layers and the head, and its answer
is read at the last position alone: the head counts that position
against its ``V`` rows, and so does the last layer, since no later layer
reads its other positions. Layers 1…n−1 run at all ``L`` positions; the
last layer needs its norm, K and V at every position (the last row
attends to them) and its Q, attention row, O, router and experts at the
last position only.

- matmul FLOPs a position a layer: ``2·d·2·Hkv·dh`` (k, v) at all ``n·L``
  positions; ``2·d·2·H·dh`` (q, o), ``2·d·E`` (router) and
  ``top·3·2·d·F`` (the routed experts) at the ``(n−1)·L + 1`` positions
  whose output is read;
- attention FLOPs: ``4·H·dh`` a (query, key) pair, causal and windowed
  pairs only: ``L(L+1)/2`` in a full layer, ``Σ_t min(t+1, w)`` in a
  sliding one; the last layer's one row sees ``L`` keys in a full layer,
  ``min(L, w)`` in a sliding one;
- head: ``2·d·V`` a query;
- bytes a dispatch: every attention, norm and router weight once, the
  head's table once; the expert tables of layers 1…n−1 all ``E`` once (a
  dispatch of 2,048 tokens or more reaches each), of the last layer at
  most ``top`` a query and ``E`` a dispatch, so ``min(E·dispatches,
  top·queries)`` over a window (exact where no dispatch fuses more than
  ``E / top`` queries); a query its window's embedding rows, its window
  and its packed answer. Activations are not counted: a fused
  implementation keeps them on the chip. No padding row or PAD position
  counts as work.

The grouped expert matmuls alone (``moe_*``): their FLOPs, their tables
as above, and a query's routed rows in (``((n−1)·L + 1)·top·d``) and out.
"""

from __future__ import annotations

BYTES_PER_ELEM = {"float32": 4, "bfloat16": 2}


def _pairs(kind: str, length: int, window: int) -> int:
    if kind == "full_attention":
        return length * (length + 1) // 2
    return sum(min(t + 1, window) for t in range(length))


def _shape(config: dict) -> dict:
    n = int(config["num_hidden_layers"])
    kinds = config["layer_types"][:n]
    length, window = config["window_events"], config["sliding_window"]
    last_row = length if kinds[-1] == "full_attention" \
        else min(length, window)
    return {
        "d": config["hidden_size"],
        "hq": config["num_attention_heads"] * config["head_dim"],
        "hkv": config["num_key_value_heads"] * config["head_dim"],
        "experts": config["num_experts"],
        "top": config["num_experts_per_tok"],
        "width": config["moe_intermediate_size"],
        "vocab": config["vocab_size"], "layers": n,
        # positions through K and V; through Q, O, the router and experts
        "keyed": n * length, "read": (n - 1) * length + 1,
        "pairs": sum(_pairs(k, length, window) for k in kinds[:-1])
        + last_row,
        "length": length,
        "elem": BYTES_PER_ELEM[config["dtype"]],
    }


def moe_query_flops(config: dict) -> float:
    s = _shape(config)
    return float(s["read"] * s["top"] * 3 * 2 * s["d"] * s["width"])


def query_flops(config: dict) -> float:
    """Model FLOPs of one answered query."""
    s = _shape(config)
    q_o_router = 2 * s["d"] * 2 * s["hq"] + 2 * s["d"] * s["experts"]
    return (s["read"] * q_o_router + s["keyed"] * 2 * s["d"] * 2 * s["hkv"]
            + moe_query_flops(config) + 4 * s["hq"] * s["pairs"]
            + 2 * s["d"] * s["vocab"])


def _table_bytes(s: dict, dispatches: float, queries: float) -> float:
    tables = (s["layers"] - 1) * s["experts"] * dispatches \
        + min(s["experts"] * dispatches, s["top"] * queries)
    return tables * 3 * s["d"] * s["width"] * s["elem"]


def dispatch_bytes(config: dict, dispatches: float, queries: float,
                   k: int) -> float:
    s = _shape(config)
    layer = s["d"] * (2 * s["hq"] + 2 * s["hkv"]) + s["d"] * s["experts"] \
        + 2 * s["d"]
    weights = (s["layers"] * layer + s["vocab"] * s["d"] + s["d"]) \
        * s["elem"]
    per_query = s["length"] * (s["d"] * s["elem"] + 4) + k * 8
    return (dispatches * weights + _table_bytes(s, dispatches, queries)
            + queries * per_query)


def moe_dispatch_bytes(config: dict, dispatches: float,
                       queries: float) -> float:
    s = _shape(config)
    rows = s["read"] * s["top"] * s["d"] * s["elem"]
    return _table_bytes(s, dispatches, queries) + queries * 2 * rows


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
