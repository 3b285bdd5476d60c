"""Worker program for the two-process multi-host test.

Run by tests/test_distributed.py as ONE OF TWO coordinated processes:
each process owns 4 virtual CPU devices, `jax.distributed.initialize`
(via parallel.distributed.ensure_initialized) joins them into one 8-device
global runtime, and both run the same program — the multi-controller SPMD
model that replaces the reference's spark-submit executor fan-out
(tools/.../Runner.scala:101-213).

Exercises, in order:
1. coordinator bring-up from the PIO_* env trio,
2. a DCN-aware pod mesh over both processes' devices,
3. host-local batch feeding → one global array (the PEvents partition
   assignment role),
4. a global-sum collective across processes,
5. ONE ALS sweep on globally-sharded buckets, numerics-checked against the
   process-local single-device reference.

Prints "WORKER_OK <checksum>" on success; the parent asserts both
processes print the same checksum.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as np  # noqa: E402

import jax  # noqa: E402

from incubator_predictionio_tpu.parallel import distributed  # noqa: E402

# jax.distributed.initialize must run before ANYTHING touches the XLA
# backend — and importing the ops package evaluates module-level jnp
# constants, so the join happens here, before those imports
_MULTI = distributed.ensure_initialized()

import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from incubator_predictionio_tpu.ops import als_init, als_sweep  # noqa: E402
from incubator_predictionio_tpu.ops.sparse import build_padded_rows  # noqa: E402


def main() -> None:
    assert _MULTI, "expected a multi-process runtime"
    assert jax.process_count() == 2, jax.process_count()
    assert distributed.process_count() == 2
    assert distributed.is_multihost()
    assert len(jax.devices()) == 8, "global device view spans both processes"
    assert len(jax.local_devices()) == 4

    # -- pod mesh over every process's devices ----------------------------
    mesh = distributed.make_pod_mesh(("dp", "mp"), (2, -1))
    assert dict(mesh.shape) == {"dp": 2, "mp": 4}

    # -- host-local feeding into one global array + a DCN collective ------
    global_batch = 16
    sl = distributed.host_local_batch_slice(global_batch)
    full = np.arange(global_batch, dtype=np.float32) + 1.0
    sharding = NamedSharding(mesh, P(("dp", "mp")))
    garr = distributed.global_array_from_local(full[sl], sharding)
    total = jax.jit(
        jnp.sum, out_shardings=NamedSharding(mesh, P()))(garr)
    np.testing.assert_allclose(np.asarray(total), full.sum())

    # -- one ALS sweep over the global mesh vs the local reference --------
    rng = np.random.default_rng(42)
    n_users, n_items, nnz, rank = 48, 32, 400, 8
    users = rng.integers(0, n_users, nnz)
    items = rng.integers(0, n_items, nnz)
    vals = rng.uniform(1, 5, nnz).astype(np.float32)

    ref_state = als_sweep(
        als_init(jax.random.key(0), n_users, n_items, rank),
        build_padded_rows(users, items, vals, n_users),
        build_padded_rows(items, users, vals, n_items),
        l2=0.1,
    )

    rows = NamedSharding(mesh, P(("dp", "mp")))
    repl = NamedSharding(mesh, P())

    def put_bucket(b):
        return type(b)(
            row_ids=jax.device_put(b.row_ids, rows),
            cols=jax.device_put(b.cols, rows),
            vals=jax.device_put(b.vals, rows),
            mask=jax.device_put(b.mask, rows),
        )

    ub = [put_bucket(b) for b in build_padded_rows(
        users, items, vals, n_users, row_multiple=8)]
    ib = [put_bucket(b) for b in build_padded_rows(
        items, users, vals, n_items, row_multiple=8)]
    state0 = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, repl),
        als_init(jax.random.key(0), n_users, n_items, rank))
    # validate=False: split-row validation fetches row_ids, which is not
    # possible for globally-sharded (cross-process) arrays — callers
    # validate BEFORE sharding (als_train does the same)
    out = als_sweep(state0, ub, ib, l2=0.1, validate=False)

    # re-replicate so every process holds the full factors for comparison
    gather = jax.jit(lambda t: t, out_shardings=repl)
    got = gather(out)
    np.testing.assert_allclose(
        np.asarray(ref_state.user_factors), np.asarray(got.user_factors),
        rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(ref_state.item_factors), np.asarray(got.item_factors),
        rtol=2e-4, atol=2e-5)

    checksum = float(np.abs(np.asarray(got.user_factors)).sum())
    print(f"WORKER_OK {checksum:.6f}", flush=True)


if __name__ == "__main__":
    main()
    sys.exit(0)
