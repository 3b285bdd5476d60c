"""ALS fused-kernel (Pallas) vs XLA bucket path at the bench shape.

Run on the real chip:

    python scripts/als_kernel_bench.py                  # full ML-20M shape
    PIO_TUNE_NNZ=2000000 python scripts/als_kernel_bench.py   # smoke

Prints one JSON line per configuration: warm train wall, derived MFU
(both peak conventions), and fit RMSE — kernel off vs on, plus the
planted heldout so numerics regressions show up next to the speed. Use
the result to confirm `PIO_ALS_KERNEL=auto` helps before the driver
bench, and to quantify the Gram-stream removal (expected: bf16-peak MFU
0.079 → 0.15+ per the round-4 verdict target).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> int:
    from incubator_predictionio_tpu.utils.lease import install_sigterm_exit

    import jax

    # SIGTERM → normal interpreter shutdown while this process holds
    # the chip (utils/lease.py)
    jax.devices()
    install_sigterm_exit()

    n_users = int(os.environ.get("PIO_TUNE_USERS", 138_493))
    n_items = int(os.environ.get("PIO_TUNE_ITEMS", 26_744))
    nnz = int(os.environ.get("PIO_TUNE_NNZ", 20_000_000))
    rank = int(os.environ.get("PIO_TUNE_RANK", 128))
    sweeps = int(os.environ.get("PIO_TUNE_SWEEPS", 10))
    l2 = float(os.environ.get("PIO_BENCH_L2", "0.03"))
    peak_f32 = float(os.environ.get("PIO_BENCH_PEAK_FLOPS", 98.5e12))
    peak_bf16 = float(os.environ.get("PIO_BENCH_PEAK_FLOPS_BF16", 197e12))

    rng = np.random.default_rng(7)
    iw = (np.arange(n_items) + 1.0) ** -0.55
    uw = (np.arange(n_users) + 1.0) ** -0.3

    def pairs(n):
        return (rng.choice(n_users, n, p=uw / uw.sum()).astype(np.int32),
                rng.choice(n_items, n, p=iw / iw.sum()).astype(np.int32))

    plant, noise = 16, 0.35
    u_true = rng.normal(0, 1 / np.sqrt(plant),
                        (n_users, plant)).astype(np.float32)
    v_true = rng.normal(0, 1.0, (n_items, plant)).astype(np.float32)

    def rate(u, i):
        return (3.5 + np.einsum("nk,nk->n", u_true[u], v_true[i])
                + rng.normal(0, noise, len(u))).astype(np.float32)

    users, items = pairs(nnz)
    ratings = rate(users, items)
    hu, hi = pairs(200_000)
    hr = rate(hu, hi)

    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops import als
    from incubator_predictionio_tpu.ops.sparse import build_both_sides

    (ul, uh), (il, ih) = build_both_sides(users, items, ratings,
                                          n_users, n_items)
    u_tree, i_tree = als._buckets_tree(ul), als._buckets_tree(il)
    u_hv, i_hv = als._heavy_tree(uh), als._heavy_tree(ih)

    # analytic FLOPs (the convention of ops/als.train_flops, bf16 CG budget)
    k = float(rank)
    iters_cg = min(als._CG_ITERS_BF16, als._CG_ITERS)
    per_sweep = (2 * (2.0 * nnz * k * k * 2.0) + 2 * (2.0 * nnz * k)
                 + (n_users + n_items) * iters_cg * 2.0 * k * k)
    flops = per_sweep * sweeps

    # measure what PIO_ALS_KERNEL=auto would actually select: gate the
    # kernel leg on the real Mosaic probe (forcing past a failed probe
    # would either crash mid-run or silently time interpret mode). The
    # legs run _mixed_run under the production warm-start default, so
    # probe that exact variant (warm adds the x0 operand — a different
    # kernel)
    kernel_ok = als._kernel_enabled(False, warm=als._CG_WARMSTART)
    # the fused gather+Gram+CG generation probes its own variant, and
    # only the VMEM-fitting side routes through it (als._fused_sides:
    # at ML-20M shape the user half-sweep, whose gather table is the
    # small item side)
    fused_sides = (als._fused_sides(n_users, n_items, False,
                                    als._CG_WARMSTART, jnp.bfloat16,
                                    rank)
                   if kernel_ok else (False, False))
    # each leg: (use_kernel, min-D routing cut, rows per program,
    # use_fused). PIO_TUNE_MIN_DS × PIO_TUNE_ROWS sweep both knobs so
    # one chip window yields the whole layout picture; the fused-gather
    # leg rides along when its probe passes and a side fits the budget
    legs = [(False, 0, 1, (False, False))]
    if kernel_ok:
        min_ds = [int(v) for v in os.environ.get(
            "PIO_TUNE_MIN_DS", "0,64").split(",") if v.strip()]
        rows_l = [int(v) for v in os.environ.get(
            "PIO_TUNE_ROWS", "1,8").split(",") if v.strip()]
        if not min_ds or not rows_l:
            print(json.dumps({"kernel": True,
                              "skipped": "PIO_TUNE_MIN_DS or "
                                         "PIO_TUNE_ROWS is empty"}),
                  flush=True)
        legs += [(True, d, r, (False, False))
                 for r in rows_l for d in min_ds]
        if any(fused_sides):
            legs += [(True, d, 1, fused_sides) for d in min_ds]
        else:
            print(json.dumps({"fused": True,
                              "skipped": "fused-gather probe failed or "
                                         "no side fits "
                                         "PIO_ALS_FUSED_VMEM_MB"}),
                  flush=True)
    else:
        print(json.dumps({"kernel": True,
                          "skipped": "als_kernel_available() is False on "
                                     "this backend (or PIO_ALS_KERNEL=off)"
                          }), flush=True)
    for use_kernel, min_d, rows, fused in legs:
        def train():
            out = als._mixed_run(
                als.als_init(jax.random.key(0), n_users, n_items, rank),
                u_tree, i_tree, l2, sweeps, sweeps, True,
                jnp.float32, jax.lax.Precision.HIGHEST,
                user_heavy=u_hv, item_heavy=i_hv,
                use_kernel=use_kernel, kernel_min_d=min_d,
                kernel_rows=rows, use_fused=fused)
            np.asarray(out.user_factors[0:1, 0:1])
            np.asarray(out.item_factors[0:1, 0:1])
            return out

        t0 = time.perf_counter()
        state = train()
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        state = train()
        warm = time.perf_counter() - t0
        rec = {
            "kernel": use_kernel,
            "kernel_min_d": min_d,
            "kernel_rows": rows,
            "fused_user_sweep": fused[0],
            "fused_item_sweep": fused[1],
            "warm_s": round(warm, 3),
            "compile_s": round(max(first - warm, 0.0), 1),
            "mfu_f32_peak": round(flops / warm / peak_f32, 4),
            "mfu_bf16_peak": round(flops / warm / peak_bf16, 4),
            "fit_rmse": round(float(als.rmse(state, users, items,
                                             ratings)), 4),
            "heldout_rmse": round(float(als.rmse(state, hu, hi, hr)), 4),
        }
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
