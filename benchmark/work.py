"""The work a scoring dispatch needs, from the configuration's shapes.

The same whatever implements it: per dispatch of ``B`` queries against
an ``n_items × rank`` table held in ``bytes_per_elem``-byte elements,
``2·B·n_items·rank`` FLOPs and ``n_items·rank·e + B·rank·e + B·k·8``
bytes (the table read once, the query rows, the packed top-k out)."""

from __future__ import annotations

BYTES_PER_ELEM = {"float32": 4, "bfloat16": 2, "int8": 1}


def query_flops(config: dict) -> float:
    """FLOPs one answered query needs: its row against every item."""
    return 2.0 * config["n_items"] * config["rank"]


def dispatch_flops(config: dict, queries: float) -> float:
    return queries * query_flops(config)


def dispatch_bytes(config: dict, dispatches: float, queries: float,
                   k: int) -> float:
    e = BYTES_PER_ELEM[config["dtype"]]
    table = config["n_items"] * config["rank"] * e
    return dispatches * table + queries * (config["rank"] * e + k * 8)


def least_seconds(config: dict, peaks: dict, dispatches: float,
                  queries: float, k: int):
    """(least time, which bound) for ``dispatches`` dispatches that
    answered ``queries`` queries between them."""
    by_flops = dispatch_flops(config, queries) / peaks["flops_per_s"]
    by_bytes = dispatch_bytes(config, dispatches, queries, k) \
        / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops > by_bytes \
        else (by_bytes, "memory")


def next_pow2(n: int) -> int:
    """The width ``k`` and the batch pad to on their way to the device."""
    return 1 << max(int(n) - 1, 0).bit_length()


def answered_in_trace(ctx: dict) -> int:
    """Good answers that completed inside the traced part of the window
    (client clock)."""
    log, (a, b) = ctx["log"], ctx["trace_window"]
    done = log["done"]
    return int((log["good"] & (done >= a) & (done <= b)).sum())
