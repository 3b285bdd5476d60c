"""The CPU rehearsal of one run, for the tests: everything `benchmark.run`
does after its look for a chip, at a tiny size, on whatever backend jax
finds (``JAX_PLATFORMS=cpu``). Not a chip run: its times mean nothing.

    python3 benchmark/tests/rehearse.py --workload W --seed N --trace 0|1
                                        [--fault NAME]

The tiny size, the rate and the settings are the workload's
configuration's own, under its ``rehearsal`` key.

``--fault`` breaks the timed path underneath the run, where the answer
is produced, so a test can see ``correct`` come out false:
``alter_item`` swaps the two best items of every answer, ``alter_score``
scales every score by 1.001 (the ALS engine's scoring program).
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def plant(fault: str) -> None:
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops import topk

    inner = topk._batch_score_top_k_xla

    def broken(*args, **kw):
        out = inner(*args, **kw)            # [2, B, k]: scores, items
        if fault == "alter_item":
            return out.at[1, :, 0].set(out[1, :, 1]).at[1, :, 1].set(
                out[1, :, 0])
        if fault == "alter_score":
            return out.at[0].multiply(jnp.float32(1.001))
        raise SystemExit(f"unknown fault {fault!r}")

    broken._cache_size = inner._cache_size
    topk._batch_score_top_k_xla = broken


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="als-lastfm360k-d2048.serve-steady-wide")
    ap.add_argument("--seed", type=int, default=2**31 + 17)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    from benchmark import run

    if args.fault:
        plant(args.fault)
    result, rc = run.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace),
                              rehearsal=run.find_cell(args.workload)[
                                  "config"]["rehearsal"])
    print(json.dumps(result), flush=True)
    if rc == run.LEFTOVER:
        sys.stderr.flush()
        os._exit(0)
    return rc


if __name__ == "__main__":
    sys.exit(main())
