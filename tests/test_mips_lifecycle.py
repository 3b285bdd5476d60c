"""MIPS catalogue lifecycle: PQ residual codes, the background
rebuild-and-swap, and host-tiered cold buckets (ops/mips.py +
ops/mips_daemon.py).

The pins, in the order the ISSUE promises them:

- PQ-vs-exhaustive recall parity at every ``PIO_SERVE_MIPS_PQ_M`` on a
  small planted catalogue (full probe, so the parity statement is about
  the residual codes, not the probe budget), plus the divisor snap;
- the ``adopt_index`` age-baseline reset regression (a hot-swapped
  index must never report as stale) on a fake clock, and the same
  reset through a rebuild swap;
- rebuild-under-serve correctness: every overlay-published key is
  findable at recall 1.0 before AND after the atomic swap, a known-row
  override survives, the old index object still serves (in-flight
  queries finish on the old arrays), and a publish that races the swap
  re-routes to the successor;
- cold-bucket tiering: rebuild demotes unprobed buckets to a host
  mini-index, cold rows stay findable through the merged host stage,
  probe pressure books ``cold.hits``, and a promote-triggered rebuild
  brings the pressured rows back to device;
- the daemon: trigger readers and ``check_trigger`` ordering,
  ``sweep_now`` folding a planted tail through a real rebuild under
  its own trace, refcounted acquire/release lifecycle;
- the exhaustive-fallback merge: published rows are visible on every
  fallback route (mode off, big exclude, batch path) — EXCEPT masked
  queries, where a virtual id cannot honor an item mask.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_predictionio_tpu.obs import metrics as obs_metrics
from incubator_predictionio_tpu.ops import mips, mips_daemon, topk
from incubator_predictionio_tpu.utils.planted import (
    exhaustive_top_k,
    planted_item_factors,
    planted_queries,
    recall_against_oracle,
)

N_ITEMS, RANK, K = 4096, 32, 10


@pytest.fixture(scope="module")
def planted():
    vf = planted_item_factors(N_ITEMS, RANK, seed=13)
    queries = planted_queries(vf, 8, seed=17)
    return vf, queries


@pytest.fixture
def mips_on(monkeypatch):
    monkeypatch.setenv("PIO_SERVE_MIPS", "on")


def _dominating(rng, n):
    """Fresh publish vectors whose self-score beats every base row —
    recall 1.0 on them is then a statement about the plumbing, not
    about probe luck."""
    v = rng.normal(size=(n, RANK)).astype(np.float32)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    return v * 8.0


def _top_ids(table, q, **kw):
    packed = np.asarray(topk.score_and_top_k(jnp.asarray(q), table,
                                             k=K, **kw))
    return packed[1].astype(np.int64).tolist()


# ---------------------------------------------------------------------------
# PQ residual codes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [4, 8, 16, 32])
def test_pq_recall_parity_at_every_m(planted, mips_on, monkeypatch, m):
    """Asymmetric PQ-over-residuals must hold the exhaustive recall
    gate at every registered subquantizer count. Full probe isolates
    the codes: any miss is the coarse PQ ranking dropping a true
    top-k row past the exact-rerank width."""
    monkeypatch.setenv("PIO_SERVE_MIPS_QUANT", "pq")
    monkeypatch.setenv("PIO_SERVE_MIPS_PQ_M", str(m))
    monkeypatch.setenv("PIO_SERVE_MIPS_NPROBE", str(N_ITEMS))
    vf, queries = planted
    table = jax.device_put(vf)
    index = mips.build_index(table, N_ITEMS, seed=13)
    assert index.quant == "pq"
    assert index.pq_m == m
    assert np.asarray(index.pq_books).shape == (m, 256, RANK // m)

    oracle = exhaustive_top_k(vf, queries, K)
    got = np.stack([
        np.asarray(mips.mips_score_and_top_k(q, table, index, K))[1]
        .astype(np.int64) for q in queries])
    recall, worst = recall_against_oracle(got, oracle, K)
    assert recall >= 0.95, (m, recall, worst)


def test_pq_m_snaps_down_to_a_rank_divisor(monkeypatch):
    """A knob step that lands on a non-divisor must degrade to the next
    divisor below, never crash a rebuild."""
    monkeypatch.setenv("PIO_SERVE_MIPS_PQ_M", "24")
    assert mips._pq_m(32) == 16
    monkeypatch.setenv("PIO_SERVE_MIPS_PQ_M", "7")
    assert mips._pq_m(32) == 4
    monkeypatch.delenv("PIO_SERVE_MIPS_PQ_M")
    assert mips._pq_m(32) == 16                    # default
    assert mips._pq_m(8) == 8                      # clamped to rank


# ---------------------------------------------------------------------------
# the age baseline (adopt + rebuild both reset it)
# ---------------------------------------------------------------------------

def test_adopt_and_rebuild_reset_the_age_baseline(planted, mips_on,
                                                  monkeypatch):
    """pio_mips_index_age_seconds must never report a hot-swapped index
    as stale: adopt_index (deploy-time table adoption) and the daemon's
    rebuild swap both reset ``built_at`` through the _now() seam."""
    t = {"now": 1000.0}
    monkeypatch.setattr(mips, "_now", lambda: t["now"])
    vf, _queries = planted
    table = jax.device_put(vf)
    index = mips.build_index(table, N_ITEMS, seed=13)
    assert index.built_at == 1000.0

    t["now"] = 1600.0
    table2 = jax.device_put(vf.copy())
    assert mips.adopt_index(table, table2) is index
    assert mips.index_for(table2) is index
    # the regression this pins: before the fix, adoption kept the OLD
    # build stamp and a freshly deployed model reported 600s of age
    assert index.built_at == 1600.0
    mips._collect_index_age()
    age = obs_metrics.REGISTRY.get("pio_mips_index_age_seconds")
    assert age.value == pytest.approx(0.0)

    t["now"] = 2500.0
    new = mips.rebuild_index(table2, trigger="manual")
    assert new is not None and new is not index
    assert mips.index_for(table2) is new
    assert new.built_at == 2500.0


# ---------------------------------------------------------------------------
# rebuild-under-serve: the swap choreography
# ---------------------------------------------------------------------------

def test_rebuild_swap_preserves_every_published_key(planted, mips_on):
    vf, _queries = planted
    table = jax.device_put(vf.copy())
    old = mips.build_index(table, N_ITEMS, seed=13)
    rng = np.random.default_rng(23)
    fresh = _dominating(rng, 24)
    vids = mips.publish_rows(table, fresh)
    assert vids is not None and (vids >= old.capacity).all()
    # known-row override: the published solve replaces the base row
    row = 99
    override = _dominating(rng, 1)[0]
    mips.publish_rows(table, override[None, :], rows=[row])

    # before: recall 1.0 on every published key (exact tail)
    for i, vid in enumerate(vids):
        assert _top_ids(table, fresh[i])[0] == int(vid)
    assert _top_ids(table, override)[0] == row

    new = mips.rebuild_index(table, trigger="tail", probe_recall=True)
    assert new is not None and new is not old
    assert mips.index_for(table) is new
    assert old._superseded is new
    # the tail folded into the dense ext block at the SAME ids — the
    # overlay's key→id map survives the swap untouched
    assert new.tail_virtual_size() == 0
    assert new.n_ext >= len(vids)

    # after: recall 1.0 on every key, now served from device ext rows
    for i, vid in enumerate(vids):
        ids = _top_ids(table, fresh[i])
        assert ids[0] == int(vid), (i, ids)
    assert _top_ids(table, override)[0] == row
    # in-flight queries holding the OLD index object finish on the old
    # arrays (the swap never mutates them)
    got_old = np.asarray(
        mips.mips_score_and_top_k(fresh[0], table, old, K))
    assert int(got_old[1][0]) == int(vids[0])

    # a publish racing the swap (publisher resolved the OLD index
    # before the registry flipped) re-routes to the successor
    late = _dominating(rng, 1)
    orig_index_for = mips.index_for
    mips.index_for = lambda _t: old
    try:
        (late_vid,) = mips.publish_rows(table, late)
    finally:
        mips.index_for = orig_index_for
    assert new.tail_virtual_size() == 1          # landed on NEW
    assert _top_ids(table, late[0])[0] == int(late_vid)

    # the rebuild counter booked its trigger
    reb = obs_metrics.REGISTRY.get("pio_mips_rebuilds_total")
    assert reb.labels(trigger="tail").value >= 1


def test_back_to_back_rebuilds_reuse_compiled_shapes(planted, mips_on):
    """The ext block's pow2 rung: consecutive rebuilds with a same-rung
    tail produce identical device shapes, so the steady churn cycle
    (publish → rebuild → publish → rebuild) compiles NOTHING after the
    first swap's warmup."""
    vf, queries = planted
    table = jax.device_put(vf.copy())
    mips.build_index(table, N_ITEMS, seed=13)
    rng = np.random.default_rng(29)
    mips.publish_rows(table, _dominating(rng, 12))
    mips.rebuild_index(table, trigger="tail")
    _top_ids(table, queries[0])                  # warm the serve path
    warm = mips.mips_compile_cache_size()
    # stay inside the ext block's pow2 rung (12 → 14 → 16 pads to 16):
    # the shapes the swap publishes are bit-identical, so the churn
    # cycle compiles nothing
    for _ in range(2):
        mips.publish_rows(table, _dominating(rng, 2))
        mips.rebuild_index(table, trigger="tail")
        _top_ids(table, queries[0])
    assert mips.mips_compile_cache_size() == warm


# ---------------------------------------------------------------------------
# host-tiered cold buckets
# ---------------------------------------------------------------------------

def test_cold_tier_demote_serve_and_promote(planted, mips_on,
                                            monkeypatch):
    monkeypatch.setenv("PIO_MIPS_TIER", "on")
    vf, _queries = planted
    table = jax.device_put(vf.copy())
    index = mips.build_index(table, N_ITEMS, seed=13)
    # plant the probe-hit profile the sampler would have produced: a
    # quarter of the buckets never probed over the sample window
    index.probe_hits[:] = 1
    index.probe_hits[: index.c_total // 4] = 0
    index._probe_samples = 10_000

    new = mips.rebuild_index(table, trigger="manual")
    assert new is not None and new.cold is not None
    assert new.cold.rows > 0
    dev, host = new.tier_rows()
    assert host == new.cold.rows
    assert dev + host == N_ITEMS
    mips._collect_index_age()
    tier = obs_metrics.REGISTRY.get("pio_mips_tier_rows")
    assert tier.labels(tier="host").value >= new.cold.rows

    # a cold row that is its own best match must still be findable —
    # served by the host mini-index merged into the device result
    cold_ids = np.concatenate(
        [ids for ids in new.cold.member_ids if len(ids)])
    cold_id = next(int(c) for c in cold_ids[:256]
                   if int(np.argmax(vf @ vf[int(c)])) == int(c))
    ids = _top_ids(table, vf[cold_id])
    assert ids[0] == cold_id, ids
    # probe pressure on the cold tier was booked
    assert int(new.cold.hits.sum()) > 0

    # promote: pressure past the trigger fires the daemon's promote
    # reason, and the rebuild brings the pressured rows back to device
    new.cold.hits[:] = 100
    assert mips_daemon.check_trigger(new) == "promote"
    promoted = mips.rebuild_index(table, trigger="promote")
    assert promoted is not None
    promoted_cold = (
        np.concatenate([ids for ids in promoted.cold.member_ids
                        if len(ids)])
        if promoted.cold is not None else np.empty(0, np.int64))
    assert cold_id not in promoted_cold.tolist()
    ids2 = _top_ids(table, vf[cold_id])
    assert ids2[0] == cold_id


def test_auto_tiering_waits_for_probe_samples(planted, mips_on,
                                              monkeypatch):
    """auto mode must NOT demote off an empty sample window — a
    freshly built index has all-zero counters and tiering on that
    evidence would demote the whole catalogue."""
    monkeypatch.setenv("PIO_MIPS_TIER", "auto")
    vf, _queries = planted
    table = jax.device_put(vf.copy())
    mips.build_index(table, N_ITEMS, seed=13)
    new = mips.rebuild_index(table, trigger="manual")
    assert new is not None
    assert new.cold is None


# ---------------------------------------------------------------------------
# the rebuild daemon
# ---------------------------------------------------------------------------

def test_daemon_triggers_and_sweep(planted, mips_on, monkeypatch):
    monkeypatch.setenv("PIO_MIPS_REBUILD_TAIL", "8")
    # a prior acquire/release leaves the daemon's stop flag set;
    # the synchronous sweep below must not be silenced by it
    mips_daemon.acquire()
    mips_daemon.release()
    vf, _queries = planted
    table = jax.device_put(vf.copy())
    index = mips.build_index(table, N_ITEMS, seed=13)
    assert mips_daemon.check_trigger(index) is None

    rng = np.random.default_rng(31)
    fresh = _dominating(rng, 8)
    vids = mips.publish_rows(table, fresh)
    assert mips_daemon.check_trigger(index) == "tail"

    assert mips_daemon.sweep_now() >= 1
    new = mips.index_for(table)
    assert new is not index
    assert new.tail_virtual_size() == 0
    for i, vid in enumerate(vids):
        assert _top_ids(table, fresh[i])[0] == int(vid)
    st = mips_daemon.stats()
    assert st["rebuilds"] >= 1
    assert st["tailTrigger"] == 8
    rec = st["last"][-1]
    assert rec["trigger"] == "tail"
    assert rec["traceId"]                         # booked under a trace
    assert rec["ext"] >= len(vids)

    # churn outranks age; age only fires with something to fold
    monkeypatch.setenv("PIO_MIPS_REBUILD_CHURN", "4")
    new.churn_rows = 5
    assert mips_daemon.check_trigger(new) == "churn"
    new.churn_rows = 0
    monkeypatch.setattr(mips, "_now",
                        lambda: new.built_at + 100_000.0)
    assert mips_daemon.check_trigger(new) is None  # quiet: no rebuild
    new.churn_rows = 1
    assert mips_daemon.check_trigger(new) == "age"


def test_daemon_lifecycle_is_refcounted():
    # a daemon of the test's own: the process's one may still be held by
    # a server that an earlier test file on this worker left deployed
    daemon = mips_daemon._RebuildDaemon()
    assert not daemon.running()
    daemon.acquire()
    daemon.acquire()
    try:
        assert daemon.running()
        daemon.release()
        assert daemon.running()                   # one holder left
    finally:
        daemon.release()
    assert not daemon.running()
    assert daemon.stats()["running"] is False


# ---------------------------------------------------------------------------
# exhaustive-fallback visibility of published rows
# ---------------------------------------------------------------------------

def test_fallback_routes_see_published_rows(planted, monkeypatch):
    monkeypatch.setenv("PIO_SERVE_MIPS", "on")
    vf, _queries = planted
    table = jax.device_put(vf.copy())
    mips.build_index(table, N_ITEMS, seed=13)
    rng = np.random.default_rng(37)
    fresh = _dominating(rng, 1)[0]
    (vid,) = mips.publish_rows(table, fresh[None, :])

    # a big exclusion list falls back to exhaustive — the published
    # key must still surface (and an excluded published key must not)
    big_ex = jnp.asarray(np.arange(1024, dtype=np.int32))
    assert mips.route(table, k=K, exclude=big_ex) is None
    ids = _top_ids(table, fresh, exclude=big_ex)
    assert ids[0] == int(vid)
    ex_vid = jnp.asarray(np.concatenate(
        [np.arange(1024), [int(vid)]]).astype(np.int32))
    assert int(vid) not in _top_ids(table, fresh, exclude=ex_vid)

    # serving mode off: the single-vector, user-row and batch wrappers
    # all merge the tail into their exhaustive results
    monkeypatch.setenv("PIO_SERVE_MIPS", "off")
    assert _top_ids(table, fresh)[0] == int(vid)
    uf = jax.device_put(np.stack([fresh, fresh]))
    packed = np.asarray(topk.score_user_and_top_k(uf, table, 1, k=K))
    assert int(packed[1][0]) == int(vid)
    batch = np.asarray(topk.batch_score_top_k(uf, table,
                                              np.asarray([0, 1]), k=K))
    assert int(batch[1][0][0]) == int(vid)
    assert int(batch[1][1][0]) == int(vid)

    # masked queries are the documented exception: a virtual id cannot
    # honor an item mask, so the mask wins and the tail stays out
    mask = jnp.asarray(np.ones(N_ITEMS, bool))
    assert int(vid) not in _top_ids(table, fresh, allowed_mask=mask)
