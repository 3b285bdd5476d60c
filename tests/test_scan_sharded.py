"""Sharded parallel event-log scan (data/storage/cpplog.py).

The contract under test: the sharded scan is byte-identical to the
sequential scan — same rows in the same order, same values, same
first-seen id tables down to the blob bytes — for every shard count,
across deletes/dead entries, out-of-order event times, time windows, and
the traincache tail-fold path; and the scan no longer holds the client
lock, so event writes proceed while a training scan is in flight (the
lock-narrowing invariant pio-lint's ``lock-native-scan`` rule encodes).
"""

import threading
import time

import numpy as np
import pytest

from incubator_predictionio_tpu.data.datamap import DataMap
from incubator_predictionio_tpu.data.event import Event
from incubator_predictionio_tpu.data.storage import (
    StorageClientConfig,
    cpplog,
    traincache,
)
from incubator_predictionio_tpu.data.storage.base import Interactions
from incubator_predictionio_tpu.utils.times import from_millis

pytestmark = pytest.mark.skipif(
    __import__("incubator_predictionio_tpu.native", fromlist=["load"]).load()
    is None,
    reason="native library unavailable",
)

SHARD_COUNTS = (1, 2, 7)


@pytest.fixture
def events(tmp_path, monkeypatch):
    monkeypatch.setattr(traincache, "MIN_NNZ", 4)
    client = cpplog.StorageClient(
        StorageClientConfig(properties={"PATH": str(tmp_path)}))
    ev = cpplog.CppLogEvents(client, None, prefix="t_")
    yield ev
    client.close()


def _cache_path(events, app_id=1):
    return traincache.path_for(
        events.client._file(events.ns, app_id, None))


def _scan(events, shards, monkeypatch, **kw):
    monkeypatch.setenv("PIO_SCAN_SHARDS", str(shards))
    kw.setdefault("entity_type", "user")
    kw.setdefault("target_entity_type", "item")
    kw.setdefault("event_names", ("rate",))
    kw.setdefault("value_prop", "rating")
    return events.scan_interactions(app_id=1, **kw)


def _assert_byte_identical(a, b):
    assert np.array_equal(a.user_idx, b.user_idx)
    assert np.array_equal(a.item_idx, b.item_idx)
    assert np.array_equal(a.values, b.values)
    for ta, tb in ((a.user_ids, b.user_ids), (a.item_ids, b.item_ids)):
        assert bytes(ta.blob) == bytes(tb.blob)
        assert np.array_equal(ta.offsets, tb.offsets)


def _build_random_log(events, rng, n=400, unordered=True):
    """Bulk import (+unordered times) + per-event inserts with explicit-id
    upserts + deletes — every path that shapes entry numbering."""
    users = rng.integers(0, 23, n).astype(np.int32)
    items = rng.integers(0, 11, n).astype(np.int32)
    inter = Interactions(
        user_idx=users, item_idx=items,
        values=rng.random(n).astype(np.float32),
        user_ids=[f"u{k}" for k in range(23)],
        item_ids=[f"i{k}" for k in range(11)],
    )
    times = (rng.integers(0, 50_000, n) if unordered
             else 1000 + np.arange(n)).astype(np.int64)
    assert events.import_interactions(inter, 1, times=times) == n
    ids = []
    for k in range(30):
        ids.append(events.insert(Event(
            event="rate", entity_type="user", entity_id=f"x{k % 5}",
            target_entity_type="item", target_entity_id=f"i{k % 4}",
            properties=DataMap({"rating": float(k)}),
            event_time=from_millis(int(rng.integers(0, 50_000))),
            event_id=f"{k % 9:032d}",  # small pool → upsert tombstones
        ), 1))
    for eid in ids[::4]:
        events.delete(eid, 1)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sharded_scan_byte_identical(events, monkeypatch, seed):
    rng = np.random.default_rng(seed)
    _build_random_log(events, rng, unordered=bool(seed % 2 == 0))
    ref = _scan(events, 1, monkeypatch, use_cache=False, seed_cache=False)
    assert len(ref)
    for shards in SHARD_COUNTS[1:]:
        stats = {}
        got = _scan(events, shards, monkeypatch, use_cache=False,
                    seed_cache=False, stats=stats)
        assert stats["scan_shards"] == shards
        assert len(stats["scan_shard_walls_s"]) == shards
        _assert_byte_identical(ref, got)


def test_sharded_scan_time_window_identical(events, monkeypatch):
    rng = np.random.default_rng(3)
    _build_random_log(events, rng)
    kw = dict(start_time=from_millis(10_000), until_time=from_millis(40_000),
              use_cache=False, seed_cache=False)
    ref = _scan(events, 1, monkeypatch, **kw)
    assert 0 < len(ref)
    for shards in SHARD_COUNTS[1:]:
        _assert_byte_identical(ref, _scan(events, shards, monkeypatch, **kw))


@pytest.mark.parametrize("shards", SHARD_COUNTS)
def test_warm_traincache_tail_fold_identical(events, monkeypatch, shards):
    """Cache written at import, tail appended via the REST path: the
    cache-served scan (tail folded through the sharded scanner) must be
    byte-identical to a cold full scan at every shard count."""
    n = 12
    inter = Interactions(
        user_idx=(np.arange(n, dtype=np.int32) % 5),
        item_idx=(np.arange(n, dtype=np.int32) % 3),
        values=np.arange(1, n + 1, dtype=np.float32),
        user_ids=[f"u{k}" for k in range(5)],
        item_ids=[f"i{k}" for k in range(3)],
    )
    assert events.import_interactions(
        inter, 1, times=1000 + np.arange(n, dtype=np.int64)) == n
    assert _cache_path(events).exists()
    for k in range(3):
        events.insert(Event(
            event="rate", entity_type="user", entity_id=f"tail{k}",
            target_entity_type="item", target_entity_id="i0",
            properties=DataMap({"rating": 9.0 + k}),
            event_time=from_millis(5000 + k)), 1)
    warm = _scan(events, shards, monkeypatch)  # cache + tail fold
    assert len(warm) == n + 3
    _cache_path(events).unlink()
    cold = _scan(events, shards, monkeypatch)  # full scan, reseeds
    _assert_byte_identical(warm, cold)


def test_insert_proceeds_during_inflight_scan(events, monkeypatch):
    """The lock-narrowing invariant: while a scan is mid-flight (the
    native call deliberately stalled), insert_batch must complete —
    before the narrowing it would block on client.lock for the whole
    scan. The mid-scan insert lands AFTER the scan's snapshot bound, so
    the scan result must not contain it."""
    _build_random_log(events, np.random.default_rng(5), n=50,
                      unordered=False)
    n_before = len(_scan(events, 1, monkeypatch, use_cache=False,
                         seed_cache=False))
    orig = cpplog.CppLogEvents._scan_native
    started, release = threading.Event(), threading.Event()

    def slow_scan(self, *a, **kw):
        started.set()
        assert release.wait(timeout=30)
        return orig(self, *a, **kw)

    monkeypatch.setattr(cpplog.CppLogEvents, "_scan_native", slow_scan)
    out = {}
    t = threading.Thread(target=lambda: out.setdefault("inter", _scan(
        events, 1, monkeypatch, use_cache=False, seed_cache=False)))
    t.start()
    try:
        assert started.wait(10)
        t0 = time.perf_counter()
        ids = events.insert_batch([Event(
            event="rate", entity_type="user", entity_id="concurrent",
            target_entity_type="item", target_entity_id="i0",
            properties=DataMap({"rating": 1.0}),
            event_time=from_millis(99_999))], 1)
        insert_wall = time.perf_counter() - t0
    finally:
        release.set()
    t.join(30)
    assert not t.is_alive()
    assert len(ids) == 1
    # the scan is stalled for up to 30 s; a blocked writer would sit on
    # client.lock that whole time — seconds of margin, not a tight race
    assert insert_wall < 5.0, insert_wall
    # snapshot semantics: the concurrent insert is past the end bound
    assert len(out["inter"]) == n_before


def test_delete_during_scan_skips_stale_cache_seed(events, monkeypatch):
    """Revalidation: a delete landing during the lock-free scan must
    prevent the scan result from seeding the projection cache (it still
    carries the now-dead row)."""
    _build_random_log(events, np.random.default_rng(6), n=40,
                      unordered=False)
    cpath = _cache_path(events)
    cpath.unlink(missing_ok=True)
    victim = next(iter(events.find(app_id=1))).event_id
    orig = cpplog.CppLogEvents._scan_native
    started, release = threading.Event(), threading.Event()

    def slow_scan(self, *a, **kw):
        started.set()
        assert release.wait(timeout=30)
        return orig(self, *a, **kw)

    monkeypatch.setattr(cpplog.CppLogEvents, "_scan_native", slow_scan)
    t = threading.Thread(
        target=lambda: _scan(events, 1, monkeypatch, use_cache=False))
    t.start()
    try:
        assert started.wait(10)
        assert events.delete(victim, 1)
    finally:
        release.set()
    t.join(30)
    assert not t.is_alive()
    assert not cpath.exists(), \
        "stale scan result must not seed the projection cache"
    # and the next scan (fresh snapshot) reflects the delete and reseeds
    monkeypatch.setattr(cpplog.CppLogEvents, "_scan_native", orig)
    after = _scan(events, 2, monkeypatch)
    assert len(after) == 40 + 30 - 8 - 1 - (30 - 9)  # see _build_random_log


def test_streaming_prep_matches_serial_prep(events, monkeypatch):
    """The pipelined scan→prep path (shard_sink → StreamingPrep with
    degree histograms accumulated during the scan) must produce buckets
    byte-identical to the serial build_both_sides."""
    from incubator_predictionio_tpu.ops.sparse import (
        StreamingPrep,
        build_both_sides,
    )

    rng = np.random.default_rng(7)
    _build_random_log(events, rng, n=600, unordered=False)
    prep = StreamingPrep()
    stats = {}
    inter = _scan(events, 3, monkeypatch, use_cache=False, seed_cache=False,
                  stats=stats, shard_sink=prep.add_shard)
    assert prep.shards == 3
    piped = prep.finish(inter, max_width=8,
                        reordered=bool(stats["scan_reordered"]))
    serial = build_both_sides(
        inter.user_idx, inter.item_idx, inter.values,
        len(inter.user_ids), len(inter.item_ids), max_width=8)

    def flatten(sides):
        out = []
        for light, heavy in sides:
            for b in light:
                out.append((b.row_ids, b.cols, b.vals, b.mask))
            if heavy is not None:
                out.append((heavy.seg_ids, heavy.row_ids, heavy.cols,
                            heavy.vals, heavy.mask))
        return out

    a, b = flatten(piped), flatten(serial)
    assert len(a) == len(b)
    for xs, ys in zip(a, b):
        for x, y in zip(xs, ys):
            assert np.array_equal(x, y)


def test_degree_plan_mismatch_falls_back_to_exact(events):
    """A wrong degree histogram must never corrupt buckets: the native
    fill rejects it (bound check / segment total) and the builder redoes
    the exact plan."""
    from incubator_predictionio_tpu.native.csr import build_buckets_native

    rows = np.array([0, 0, 0, 0, 1], np.int32)
    cols = np.arange(5, dtype=np.int32)
    vals = np.ones(5, np.float32)
    exact = build_buckets_native(rows, cols, vals, 2, 2, 8)
    for bad in (np.array([1, 4], np.int64),    # wrong multiset, right sum
                np.array([2, 2, 1], np.int64),  # wrong length
                np.array([5, 0], np.int64)):    # over-allocates bucket
        got = build_buckets_native(rows, cols, vals, 2, 2, 8, degrees=bad)
        assert len(got) == len(exact)
        for (w1, *a1), (w2, *a2) in zip(got, exact):
            assert w1 == w2
            for x, y in zip(a1, a2):
                assert np.array_equal(x, y)


def test_concurrent_cache_stages_use_distinct_tmp_files(tmp_path):
    """Cache serialization runs OUTSIDE the storage lock, so two
    concurrent seeds of the same cache must stage to distinct temp
    files — a shared name would truncate/interleave the bytes one of
    them later renames into the live cache."""
    spec = traincache.Spec("user", "item", "rate", "rating")

    def make(val):
        return traincache.TrainCache(
            spec=spec,
            uidx=np.zeros(4, np.int32), iidx=np.zeros(4, np.int32),
            vals=np.full(4, val, np.float32),
            times=np.arange(4, dtype=np.int64),
            user_tab=traincache._build_table([b"u0"]),
            item_tab=traincache._build_table([b"i0"]),
            raw_count=4, dead_count=0)

    cpath = tmp_path / "log.traincache"
    a = traincache.stage(cpath, make(1.0))
    b = traincache.stage(cpath, make(2.0))  # before a commits
    assert a._tmp != b._tmp
    a.commit()
    b.commit()  # last writer wins, never FileNotFoundError
    loaded = traincache.load(cpath)
    assert loaded is not None and loaded.vals[0] == 2.0
    assert not list(tmp_path.glob("*.tmp*"))  # no stray temp files


def test_scan_stats_report_lock_narrowing(events, monkeypatch):
    """The scan's stats channel: shard walls and the native
    lock-held wall must be present and the lock-held share must be far
    below the scan wall at any real size (here just sanity > 0 keys)."""
    _build_random_log(events, np.random.default_rng(8), n=200,
                      unordered=False)
    stats = {}
    _scan(events, 2, monkeypatch, use_cache=False, seed_cache=False,
          stats=stats)
    assert stats["scan_shards"] == 2
    assert len(stats["scan_shard_walls_s"]) == 2
    assert stats["scan_lock_held_s"] >= 0.0
    assert stats["scan_rows"] == len(_scan(events, 1, monkeypatch,
                                           use_cache=False,
                                           seed_cache=False))
