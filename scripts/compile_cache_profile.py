"""Split the warm-process compile cost into trace/lower vs cache-hit
compile (dev tool for the persistent-cache numbers in BENCH/BASELINE).

Phase 1: lower + compile, writing the cache entry (cold only when the
resolved cache directory — JAX_COMPILATION_CACHE_DIR, else
<checkout>/.xla_cache — holds none for this program yet).
Phase 2 (jax.clear_caches): lower again (pure Python/trace cost), then
compile — which should be a persistent-cache HIT (deserialize only).
Run on the chip: python scripts/compile_cache_profile.py [nnz]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

NNZ = int(sys.argv[1]) if len(sys.argv) > 1 else 20_000_000
N_USERS, N_ITEMS, RANK, SWEEPS = 138_493, 26_744, 128, 10


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
    from incubator_predictionio_tpu.utils import compile_cache

    compile_cache.enable()
    cache_dir = compile_cache.cache_dir()

    rng = np.random.default_rng(7)
    iw = (np.arange(N_ITEMS) + 1.0) ** -0.55
    items = rng.choice(N_ITEMS, NNZ, p=iw / iw.sum()).astype(np.int32)
    uw = (np.arange(N_USERS) + 1.0) ** -0.3
    users = rng.choice(N_USERS, NNZ, p=uw / uw.sum()).astype(np.int32)
    vals = rng.normal(3.5, 1.0, NNZ).astype(np.float32)
    u_light, u_heavy = split_heavy(
        build_padded_rows(users, items, vals, N_USERS))
    i_light, i_heavy = split_heavy(
        build_padded_rows(items, users, vals, N_ITEMS))
    u_tree, i_tree = als._buckets_tree(u_light), als._buckets_tree(i_light)
    u_hv, i_hv = als._heavy_tree(u_heavy), als._heavy_tree(i_heavy)
    state = als.als_init(jax.random.key(0), N_USERS, N_ITEMS, RANK)

    kwargs = dict(l2=0.1, alpha=0.0, iterations=SWEEPS, reg_nnz=True,
                  compute_dtype=jnp.bfloat16,
                  precision=jax.lax.Precision.DEFAULT, implicit=False,
                  user_heavy=u_hv, item_heavy=i_hv, cg_iters=6)

    def lower():
        return als._als_run_fused.lower(state, u_tree, i_tree, **kwargs)

    for phase in ("first", "warm-cache"):
        if phase == "warm-cache":
            jax.clear_caches()
        t0 = time.perf_counter()
        lowered = lower()
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        lowered.compile()
        t_compile = time.perf_counter() - t0
        print(f"{phase:11s} trace+lower={t_lower:5.1f}s "
              f"compile={t_compile:5.1f}s", flush=True)
    sizes = sum(
        os.path.getsize(os.path.join(cache_dir, f))
        for f in os.listdir(cache_dir))
    print(f"cache dir: {len(os.listdir(cache_dir))} entries, "
          f"{sizes / 1e6:.1f} MB", flush=True)


if __name__ == "__main__":
    main()
