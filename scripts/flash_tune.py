"""Flash-kernel block-shape sweep vs the XLA blockwise scan.

Run on the real chip (the CPU interpret path measures nothing useful):

    python scripts/flash_tune.py            # default sweep
    PIO_TUNE_SEQS=8192,32768 python scripts/flash_tune.py

Prints one JSON line per (S, q_block, kv_block) config plus the XLA
blockwise number per S, dispatch-amortized (20-rep loops, synced by a
dependent fetch). Use the
result to update the flash_attention block defaults
(ops/pallas_kernels.py) and transformer.FLASH_MIN_SEQ.

Round-4 state this sweeps against: 1024x1024 blocks lose to the scan at
S=8k (18.13 vs 12.33 ms) and win 5.76x at 32k — the hypothesis space is
(a) smaller q blocks raise grid parallelism for short S, (b) larger kv
blocks amortize the online-softmax epilogue, (c) the crossover simply
moves.
"""

import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    from incubator_predictionio_tpu.utils.lease import install_sigterm_exit

    import jax

    # SIGTERM → normal interpreter shutdown while this process holds
    # the chip (utils/lease.py)
    jax.devices()
    install_sigterm_exit()
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops.attention import blockwise_attention
    from incubator_predictionio_tpu.ops.pallas_kernels import (
        flash_attention,
        flash_available,
    )

    if not flash_available():
        print(json.dumps({"error": "flash kernel unavailable on this "
                                   "backend"}))
        return 1

    seqs = [int(v) for v in os.environ.get(
        "PIO_TUNE_SEQS", "8192,16384,32768").split(",") if v]
    blocks = [int(v) for v in os.environ.get(
        "PIO_TUNE_BLOCKS", "256,512,1024,2048").split(",") if v]
    reps = int(os.environ.get("PIO_TUNE_REPS", "20"))
    h, d = 8, 64

    import functools

    def timed(fn, *args):
        # jit BOTH sides so the comparison measures compiled dispatch —
        # production calls attention inside jit, where eager per-call
        # re-trace/custom-vjp overhead does not exist; timing flash
        # eagerly against a jitted scan would bias the crossover high
        jfn = jax.jit(fn)
        r = jfn(*args)
        np.asarray(r[0:1, 0:1, 0:1, 0:1])
        t0 = time.perf_counter()
        for _ in range(reps):
            r = jfn(*args)
        np.asarray(r[0:1, 0:1, 0:1, 0:1])
        return (time.perf_counter() - t0) / reps * 1e3

    for s in seqs:
        key = jax.random.key(0)
        q, k, v = (jax.random.normal(kk, (1, s, h, d), jnp.bfloat16)
                   for kk in jax.random.split(key, 3))
        xla_ms = timed(
            functools.partial(blockwise_attention, causal=True), q, k, v)
        print(json.dumps({"s": s, "impl": "xla_blockwise",
                          "ms": round(xla_ms, 2)}), flush=True)
        best = None
        for qb, kb in itertools.product(blocks, blocks):
            if qb > s or kb > s:
                continue
            try:
                ms = timed(
                    functools.partial(flash_attention, causal=True,
                                      q_block=qb, kv_block=kb), q, k, v)
            except Exception as e:
                print(json.dumps({"s": s, "q_block": qb, "kv_block": kb,
                                  "error": str(e)[:120]}), flush=True)
                continue
            rec = {"s": s, "impl": "flash", "q_block": qb, "kv_block": kb,
                   "ms": round(ms, 2), "vs_xla": round(xla_ms / ms, 2)}
            print(json.dumps(rec), flush=True)
            if best is None or ms < best["ms"]:
                best = rec
        if best:
            print(json.dumps({"s": s, "best": best}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
