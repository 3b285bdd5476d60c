"""Per-configuration ALS schedule timing + quality at ML-20M shape.

Times the fused training run under candidate precision schedules AND
scores each against planted rank-16 ground truth (the bench's data
model), so the mixed-schedule defaults in ops/als.py are measured on
both axes — speed and RMSE parity with the all-f32 run.
Run on the real TPU. Usage: python scripts/als_profile.py [nnz]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

NNZ = int(sys.argv[1]) if len(sys.argv) > 1 else 20_000_000
N_USERS, N_ITEMS, RANK, SWEEPS = 138_493, 26_744, 128, 10
PLANT_RANK, NOISE = 16, 0.35


def main():
    from incubator_predictionio_tpu.utils.lease import install_sigterm_exit

    import jax

    # SIGTERM → normal interpreter shutdown while this process holds
    # the chip (utils/lease.py)
    jax.devices()
    install_sigterm_exit()
    import jax.numpy as jnp

    from incubator_predictionio_tpu.ops import als
    from incubator_predictionio_tpu.ops.sparse import (
        build_padded_rows,
        split_heavy,
    )

    rng = np.random.default_rng(7)
    iw = (np.arange(N_ITEMS) + 1.0) ** -0.55
    items = rng.choice(N_ITEMS, NNZ, p=iw / iw.sum()).astype(np.int32)
    uw = (np.arange(N_USERS) + 1.0) ** -0.3
    users = rng.choice(N_USERS, NNZ, p=uw / uw.sum()).astype(np.int32)
    u_true = rng.normal(0, 1.0 / np.sqrt(PLANT_RANK),
                        (N_USERS, PLANT_RANK)).astype(np.float32)
    v_true = rng.normal(0, 1.0, (N_ITEMS, PLANT_RANK)).astype(np.float32)

    def rate(uu, ii):
        sig = np.einsum("nk,nk->n", u_true[uu], v_true[ii])
        return (3.5 + sig + rng.normal(0, NOISE, len(uu))).astype(np.float32)

    vals = rate(users, items)
    ho_u, ho_i = (rng.integers(0, N_USERS, 200_000).astype(np.int32),
                  rng.integers(0, N_ITEMS, 200_000).astype(np.int32))
    ho_r = rate(ho_u, ho_i)
    print(f"data: {NNZ} nnz, planted rank {PLANT_RANK} noise {NOISE}",
          flush=True)

    t0 = time.perf_counter()
    u_light, u_heavy = split_heavy(
        build_padded_rows(users, items, vals, N_USERS))
    i_light, i_heavy = split_heavy(
        build_padded_rows(items, users, vals, N_ITEMS))
    print(f"prep: {time.perf_counter() - t0:.1f}s", flush=True)

    u_tree, i_tree = als._buckets_tree(u_light), als._buckets_tree(i_light)
    u_hv, i_hv = als._heavy_tree(u_heavy), als._heavy_tree(i_heavy)

    def timed(name, bf16_sweeps, precision, polish_cg=None):
        def run():
            st = als.als_init(jax.random.key(0), N_USERS, N_ITEMS, RANK)
            lo = bf16_sweeps
            if lo:
                st = als._als_run_fused(
                    st, u_tree, i_tree, 0.1, 0.0, lo, True,
                    jnp.bfloat16, jax.lax.Precision.DEFAULT, implicit=False,
                    user_heavy=u_hv, item_heavy=i_hv,
                    cg_iters=min(als._CG_ITERS_BF16, als._CG_ITERS),
                    warmstart=als._CG_WARMSTART)
            if SWEEPS - lo:
                st = als._als_run_fused(
                    st, u_tree, i_tree, 0.1, 0.0, SWEEPS - lo, True,
                    jnp.float32, precision, implicit=False,
                    user_heavy=u_hv, item_heavy=i_hv,
                    cg_iters=polish_cg or als._CG_ITERS,
                    warmstart=als._CG_WARMSTART)
            np.asarray(st.user_factors[0:1, 0:1])
            np.asarray(st.item_factors[0:1, 0:1])
            return st

        run()
        t0 = time.perf_counter()
        st = run()
        warm = time.perf_counter() - t0
        fit = als.rmse(st, users, items, vals)
        ho = als.rmse(st, ho_u, ho_i, ho_r)
        print(f"{name:26s} warm={warm:5.2f}s fit={fit:.4f} "
              f"heldout={ho:.4f}", flush=True)

    P = jax.lax.Precision
    timed("f32 HIGHEST x10", 0, P.HIGHEST)
    timed("bf16 x10", 10, P.HIGHEST)
    timed("mixed 9+1 cg16", 9, P.HIGHEST)
    timed("mixed 9+1 cg8", 9, P.HIGHEST, polish_cg=8)
    timed("mixed 8+2 cg16", 8, P.HIGHEST)


if __name__ == "__main__":
    main()
