"""Native runtime components: event-log engine + CSR builder.

The storage behavior spec runs against cpplog via test_storage_conformance;
this file covers what only the native layer has: durability across reopen
(the reference proves the same with live-service storage tests,
data/src/test/.../storage/LEventsSpec.scala), tombstone persistence, and
bit-equality of the C++ CSR builder with the numpy reference.
"""

from datetime import timedelta

import numpy as np
import pytest

from incubator_predictionio_tpu import native
from incubator_predictionio_tpu.data.datamap import DataMap
from incubator_predictionio_tpu.data.event import Event
from incubator_predictionio_tpu.data.storage import StorageClientConfig
from incubator_predictionio_tpu.ops.sparse import build_padded_rows
from incubator_predictionio_tpu.utils.times import parse_iso8601

pytestmark = pytest.mark.skipif(
    native.load() is None, reason="native library unavailable")

T0 = parse_iso8601("2021-06-01T00:00:00Z")


def _client(path):
    from incubator_predictionio_tpu.data.storage import cpplog
    return cpplog.StorageClient(
        StorageClientConfig(properties={"PATH": str(path)}))


def _events(client):
    from incubator_predictionio_tpu.data.storage import cpplog
    return cpplog.CppLogEvents(client, client.config, prefix="t_")


def ev(name="rate", eid="u1", minutes=0, target=None, props=None):
    return Event(
        event=name, entity_type="user", entity_id=eid,
        target_entity_type="item" if target else None,
        target_entity_id=target,
        properties=DataMap(props or {}),
        event_time=T0 + timedelta(minutes=minutes),
    )


class TestEventLogDurability:
    def test_events_survive_reopen(self, tmp_path):
        c1 = _client(tmp_path)
        d1 = _events(c1)
        d1.init(1)
        ids = [d1.insert(ev(minutes=i, eid=f"u{i}"), 1) for i in range(5)]
        d1.delete(ids[2], 1)
        c1.close()

        c2 = _client(tmp_path)  # fresh handle: index rebuilt from disk
        d2 = _events(c2)
        found = list(d2.find(app_id=1))
        assert [e.event_id for e in found] == [
            ids[0], ids[1], ids[3], ids[4]]  # tombstone persisted
        assert d2.get(ids[2], 1) is None
        assert d2.get(ids[3], 1).entity_id == "u3"
        c2.close()

    def test_upsert_replaces_across_reopen(self, tmp_path):
        c1 = _client(tmp_path)
        d1 = _events(c1)
        d1.init(1)
        eid = d1.insert(ev(props={"rating": 1}), 1)
        d1.insert(ev(props={"rating": 9}).with_id(eid), 1)
        assert d1.get(eid, 1).properties.get("rating") == 9
        assert len(list(d1.find(app_id=1))) == 1
        c1.close()

        c2 = _client(tmp_path)
        d2 = _events(c2)
        assert d2.get(eid, 1).properties.get("rating") == 9
        assert len(list(d2.find(app_id=1))) == 1
        c2.close()

    def test_torn_tail_truncated_on_reopen(self, tmp_path):
        """A crash mid-append leaves a record claiming payload past EOF; the
        reopen scan must drop + truncate it so later appends never start
        inside its claimed range (eventlog.cc open-scan extent check)."""
        c1 = _client(tmp_path)
        d1 = _events(c1)
        d1.init(1)
        good = [d1.insert(ev(minutes=i, eid=f"u{i}"), 1) for i in range(3)]
        c1.close()

        log_file = next(tmp_path.glob("*.log"))
        intact = log_file.stat().st_size
        # forge a torn record: full 48-byte header claiming a 500-byte
        # payload, but only 10 payload bytes made it to disk
        import struct
        with open(log_file, "ab") as f:
            f.write(struct.pack("<qQQQQIi", 12345, 2, 3, 4, 5, 500, 0))
            f.write(b"x" * 10)

        c2 = _client(tmp_path)
        d2 = _events(c2)
        found = list(d2.find(app_id=1))
        assert [e.event_id for e in found] == good
        # the torn tail was physically truncated away
        assert log_file.stat().st_size == intact
        # appends after recovery frame correctly across another reopen
        extra = d2.insert(ev(minutes=9, eid="u9"), 1)
        c2.close()
        c3 = _client(tmp_path)
        d3 = _events(c3)
        assert [e.event_id for e in d3.find(app_id=1)] == good + [extra]
        c3.close()

    def test_torn_header_truncated_on_reopen(self, tmp_path):
        c1 = _client(tmp_path)
        d1 = _events(c1)
        d1.init(1)
        good = d1.insert(ev(minutes=0, eid="u0"), 1)
        c1.close()

        log_file = next(tmp_path.glob("*.log"))
        intact = log_file.stat().st_size
        with open(log_file, "ab") as f:
            f.write(b"\x01" * 20)  # partial header

        c2 = _client(tmp_path)
        d2 = _events(c2)
        assert [e.event_id for e in d2.find(app_id=1)] == [good]
        assert log_file.stat().st_size == intact
        c2.close()

    def test_out_of_order_times_sorted_and_limited(self, tmp_path):
        c = _client(tmp_path)
        d = _events(c)
        d.init(1)
        for m in (5, 1, 9, 3, 7):
            d.insert(ev(minutes=m, eid=f"u{m}"), 1)
        asc = [e.entity_id for e in d.find(app_id=1)]
        assert asc == ["u1", "u3", "u5", "u7", "u9"]
        top2 = [e.entity_id for e in d.find(app_id=1, reversed=True, limit=2)]
        assert top2 == ["u9", "u7"]
        window = [e.entity_id for e in d.find(
            app_id=1, start_time=T0 + timedelta(minutes=3),
            until_time=T0 + timedelta(minutes=9))]
        assert window == ["u3", "u5", "u7"]
        c.close()


class TestNativeCsrBuilder:
    @pytest.mark.parametrize("seed,n_rows,n_cols,nnz,max_width", [
        (0, 50, 40, 600, 64),
        (1, 7, 5, 30, 8),      # tiny, single bucket
        (2, 100, 30, 2000, 16),  # heavy rows split at max_width
    ])
    def test_matches_numpy_reference(self, seed, n_rows, n_cols, nnz,
                                     max_width):
        rng = np.random.default_rng(seed)
        rows = rng.integers(0, n_rows, nnz).astype(np.int64)
        cols = rng.integers(0, n_cols, nnz).astype(np.int32)
        vals = rng.random(nnz).astype(np.float32)
        ref = build_padded_rows(rows, cols, vals, n_rows,
                                max_width=max_width, impl="numpy")
        got = build_padded_rows(rows, cols, vals, n_rows,
                                max_width=max_width, impl="native")
        assert len(ref) == len(got)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(r.row_ids, g.row_ids)
            np.testing.assert_array_equal(r.cols, g.cols)
            np.testing.assert_array_equal(r.vals, g.vals)
            np.testing.assert_array_equal(r.mask, g.mask)

    def test_ids_beyond_int32_fall_back_to_numpy_path(self):
        """Indices ≥ 2^31 would silently wrap in the int32 cast for C++;
        the guard must return None (→ caller uses the int64 numpy path)."""
        from incubator_predictionio_tpu.native.csr import build_buckets_native
        rows = np.array([0, 2**31 + 5], np.int64)
        cols = np.array([0, 1], np.int64)
        vals = np.array([1.0, 2.0], np.float32)
        assert build_buckets_native(
            rows, cols, vals, n_rows=2**31 + 6, min_width=8, max_width=64,
        ) is None

    def test_empty_rows_and_empty_input(self):
        # rows 3..9 have no entries; row 0 dense
        rows = np.array([0] * 10 + [2], np.int64)
        cols = np.arange(11, dtype=np.int32)
        vals = np.ones(11, np.float32)
        ref = build_padded_rows(rows, cols, vals, 10, impl="numpy")
        got = build_padded_rows(rows, cols, vals, 10, impl="native")
        assert len(ref) == len(got)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(r.cols, g.cols)
        assert build_padded_rows(
            np.empty(0, np.int64), np.empty(0, np.int32),
            np.empty(0, np.float32), 4, impl="native") == []

    def test_auto_dispatch_threshold(self, monkeypatch):
        import incubator_predictionio_tpu.ops.sparse as sparse
        monkeypatch.setattr(sparse, "NATIVE_MIN_NNZ", 10)
        rng = np.random.default_rng(3)
        rows = rng.integers(0, 20, 500).astype(np.int64)
        cols = rng.integers(0, 20, 500).astype(np.int32)
        vals = rng.random(500).astype(np.float32)
        auto = sparse.build_padded_rows(rows, cols, vals, 20)
        ref = sparse.build_padded_rows(rows, cols, vals, 20, impl="numpy")
        for a, r in zip(auto, ref):
            np.testing.assert_array_equal(a.cols, r.cols)


class TestCompactRecords:
    """Compact interaction records (kCompact): sidecar-only storage with
    JSON rendered on read — readers must not be able to tell."""

    def test_rendered_json_matches_canonical_shape(self, tmp_path):
        import json

        from incubator_predictionio_tpu.data.storage.base import (
            IdTable,
            Interactions,
        )

        client = _client(tmp_path)
        dao = _events(client)
        inter = Interactions(
            user_idx=np.array([0, 1], np.int32),
            item_idx=np.array([1, 0], np.int32),
            values=np.array([4.5, 2.0], np.float32),
            user_ids=IdTable.from_list(['u"quote', "uplain"]),
            item_ids=IdTable.from_list(["i\\back", "iplain"]),
        )
        n = dao.import_interactions(
            inter, 1, event_name="rate", value_prop="rating",
            base_time=None)
        assert n == 2
        got = sorted(dao.find(app_id=1), key=lambda e: e.entity_id)
        # rendered JSON must re-serialize losslessly through the DAO's
        # canonical json.dumps(to_jsonable) — same keys, escapes, values
        for e in got:
            doc = e.to_jsonable()
            round2 = Event.from_jsonable(
                json.loads(json.dumps(doc))).to_jsonable()
            assert round2 == doc
        assert got[0].entity_id == 'u"quote'
        assert got[0].target_entity_id == "iplain"
        assert got[1].target_entity_id == "i\\back"
        assert got[0].properties.get("rating") == 4.5
        assert got[0].event_id and len(got[0].event_id) == 32
        # compact storage really is compact: well under the JSON form
        size = sum(f.stat().st_size for f in tmp_path.iterdir())
        assert size < 2 * 250, size

    def test_compact_records_survive_reopen_and_tombstone(self, tmp_path):
        from incubator_predictionio_tpu.data.storage.base import (
            IdTable,
            Interactions,
        )

        client = _client(tmp_path)
        dao = _events(client)
        inter = Interactions(
            user_idx=np.arange(5, dtype=np.int32),
            item_idx=np.zeros(5, np.int32),
            values=np.ones(5, np.float32),
            user_ids=IdTable.from_list([f"u{k}" for k in range(5)]),
            item_ids=IdTable.from_list(["i0"]),
        )
        dao.import_interactions(inter, 1, event_name="rate",
                                value_prop="rating", base_time=None)
        first = next(iter(dao.find(app_id=1, limit=1)))
        assert dao.delete(first.event_id, 1)
        client.close()

        client2 = _client(tmp_path)
        dao2 = _events(client2)
        live = list(dao2.find(app_id=1))
        assert len(live) == 4
        assert first.event_id not in {e.event_id for e in live}
        # columnar scan over reopened compact records
        back = dao2.scan_interactions(
            app_id=1, entity_type="user", target_entity_type="item",
            event_names=("rate",), value_prop="rating")
        assert len(back) == 4
        client2.close()


class TestParallelBulkAppend:
    """The multi-super-batch threaded render path of
    pio_evlog_append_interactions (eventlog.cc): >2M events span two
    super-batches, and PIO_NATIVE_THREADS forces the thread pool on."""

    N = 2_100_000  # crosses the 2M super-batch boundary

    def _import(self, tmp_path, monkeypatch, threads):
        from incubator_predictionio_tpu.data.storage.base import (
            IdTable,
            Interactions,
        )

        monkeypatch.setenv("PIO_NATIVE_THREADS", str(threads))
        # keep the projection cache out of the way: this test targets the
        # native append + scan, not the cache fold (setattr, not reload —
        # a reload would leak the changed MIN_NNZ to later test modules)
        from incubator_predictionio_tpu.data.storage import traincache
        monkeypatch.setattr(traincache, "MIN_NNZ", self.N * 10)
        rng = np.random.default_rng(3)
        nu, ni = 5_000, 1_200
        users = rng.integers(0, nu, self.N).astype(np.int32)
        items = rng.integers(0, ni, self.N).astype(np.int32)
        vals = rng.random(self.N).astype(np.float32)
        inter = Interactions(
            user_idx=users, item_idx=items, values=vals,
            user_ids=IdTable.from_list([f"u{k}" for k in range(nu)]),
            item_ids=IdTable.from_list([f"i{k}" for k in range(ni)]),
        )
        client = _client(tmp_path)
        events = _events(client)
        n = events.import_interactions(
            inter, 1, event_name="rate", value_prop="rating",
            base_time=T0)
        assert n == self.N
        out = events.scan_interactions(
            app_id=1, entity_type="user", target_entity_type="item",
            event_names=("rate",), value_prop="rating")
        client.close()
        return users, items, vals, out

    def test_two_superbatches_threaded_roundtrip(self, tmp_path,
                                                 monkeypatch):
        users, items, vals, out = self._import(tmp_path, monkeypatch, 4)
        assert len(out) == self.N
        # scan returns events in append (= time) order with first-seen
        # interned ids; translate back and compare exactly
        u_names = np.array([f"u{k}" for k in range(5_000)])
        got_users = np.asarray(out.user_ids.tolist())[out.user_idx]
        assert (got_users == u_names[users]).all()
        i_names = np.array([f"i{k}" for k in range(1_200)])
        got_items = np.asarray(out.item_ids.tolist())[out.item_idx]
        assert (got_items == i_names[items]).all()
        np.testing.assert_allclose(out.values, vals, rtol=1e-6)

    def test_threaded_matches_single_thread_bytes(self, tmp_path,
                                                  monkeypatch):
        # determinism: the rendered log must be byte-identical no matter
        # how many threads rendered it (same seed → same event ids)
        import hashlib

        d1, d4 = tmp_path / "t1", tmp_path / "t4"
        d1.mkdir(), d4.mkdir()
        from incubator_predictionio_tpu.data.storage.base import (
            IdTable,
            Interactions,
        )

        rng = np.random.default_rng(5)
        n = 200_000
        from incubator_predictionio_tpu.data.storage import traincache
        monkeypatch.setattr(traincache, "MIN_NNZ", n * 10)
        inter = Interactions(
            user_idx=rng.integers(0, 50, n).astype(np.int32),
            item_idx=rng.integers(0, 20, n).astype(np.int32),
            values=rng.random(n).astype(np.float32),
            user_ids=IdTable.from_list([f"u{k}" for k in range(50)]),
            item_ids=IdTable.from_list([f"i{k}" for k in range(20)]),
        )

        def run(path, threads):
            monkeypatch.setenv("PIO_NATIVE_THREADS", str(threads))
            client = _client(path)
            events = _events(client)
            # fixed base time AND fixed id seed → byte-identical logs
            events.import_interactions(
                inter, 1, event_name="rate", value_prop="rating",
                base_time=T0, id_seed=12345)
            client.close()
            return [
                (p.name, hashlib.sha256(p.read_bytes()).hexdigest())
                for p in sorted(path.iterdir())
            ]

        assert run(d1, 1) == run(d4, 4)


class TestRandomTruncationRecovery:
    """Crash-at-any-byte durability: truncating the log at EVERY possible
    cut point (or a random sample at scale) must reopen to a clean prefix
    of whole events, never a crash, never a partial record, and appends
    after recovery must frame correctly."""

    def test_every_cut_point_recovers_prefix(self, tmp_path):
        import shutil

        base = tmp_path / "orig"
        base.mkdir()
        c1 = _client(base)
        d1 = _events(c1)
        d1.init(1)
        ids = [d1.insert(ev(minutes=i, eid=f"u{i}"), 1) for i in range(3)]
        c1.close()
        log_file = next(base.glob("*.log"))
        blob = log_file.read_bytes()

        # EVERY byte offset is a cut point (3 records keep the blob small
        # enough to be exhaustive — a sampled test left header regions
        # permanently unexercised under a fixed seed)
        cuts = range(len(blob) + 1)
        prev_count = -1
        for cut in cuts:
            work = tmp_path / f"cut{cut}"
            shutil.copytree(base, work)
            wf = next(work.glob("*.log"))
            wf.write_bytes(blob[:cut])
            c = _client(work)
            d = _events(c)
            found = [e.event_id for e in d.find(app_id=1)]
            # always a strict prefix of the original insert order, and
            # monotone in the cut position (cuts iterate ascending)
            assert found == ids[:len(found)]
            assert len(found) >= prev_count
            # recovery is physical: the file holds only whole records now,
            # and a post-recovery append survives another reopen
            extra = d.insert(ev(minutes=99, eid="u99"), 1)
            c.close()
            c2 = _client(work)
            found2 = [e.event_id for e in _events(c2).find(app_id=1)]
            assert found2 == ids[:len(found)] + [extra]
            c2.close()
            prev_count = len(found)


class TestUniformBatchFastPath:
    """insert_batch routes uniform id-less interaction batches through the
    columnar import; the returned ids must be the ones the log stored
    (derived in Python from the same id_seed formula as eventlog.cc)."""

    def _batch(self, n, name="rate"):
        return [ev(name=name, eid=f"u{k % 5}", minutes=k,
                   target=f"i{k % 3}", props={"rating": float(k % 4)})
                for k in range(n)]

    def test_fast_path_ids_resolve_and_scan_matches(self, tmp_path):
        c = _client(tmp_path)
        d = _events(c)
        d.init(1)
        ids = d.insert_batch(self._batch(20), 1)
        assert len(ids) == 20 and len(set(ids)) == 20
        for k, eid in enumerate(ids):
            got = d.get(eid, 1)
            assert got is not None and got.event_id == eid
            assert got.entity_id == f"u{k % 5}"
            assert got.properties.get("rating") == float(k % 4)
        inter = d.scan_interactions(
            app_id=1, entity_type="user", target_entity_type="item",
            event_names=("rate",), value_prop="rating")
        assert len(inter) == 20
        # delete through a derived id works like any other id
        assert d.delete(ids[3], 1)
        assert d.get(ids[3], 1) is None
        c.close()

    def test_non_utc_batches_take_the_generic_path(self, tmp_path):
        """Compact columnar records store only epoch millis and re-render
        eventTime as UTC, so a uniform batch carrying a non-UTC offset
        (e.g. +09:00) must fall back to the generic path — same screen as
        the CLI import gate — or the timezone silently vanishes on
        read-back (other backends preserve tzinfo)."""
        import dataclasses
        from datetime import timezone as _tz

        c = _client(tmp_path)
        d = _events(c)
        d.init(1)
        jst = _tz(timedelta(hours=9))
        batch = [
            dataclasses.replace(e, event_time=e.event_time.astimezone(jst))
            for e in self._batch(12)
        ]
        ids = d.insert_batch(batch, 1)
        assert len(ids) == 12
        for src, eid in zip(batch, ids):
            got = d.get(eid, 1)
            assert got is not None
            assert got.event_time == src.event_time
            # the offset itself survives, not just the instant
            assert got.event_time.utcoffset() == timedelta(hours=9)
        c.close()

    def test_non_uniform_batches_take_the_generic_path(self, tmp_path):
        c = _client(tmp_path)
        d = _events(c)
        d.init(1)
        mixed = self._batch(10)
        mixed[4] = ev(name="view", eid="u1", minutes=4, target="i1",
                      props={"rating": 1.0})  # breaks uniformity
        ids = d.insert_batch(mixed, 1)
        assert len(ids) == 10
        assert all(d.get(e, 1) is not None for e in ids)
        # explicit ids also force the generic (upsert-capable) path
        explicit = [e.with_id(f"{k:032d}") for k, e in
                    enumerate(self._batch(10))]
        ids2 = d.insert_batch(explicit, 1)
        assert ids2 == [f"{k:032d}" for k in range(10)]
        c.close()


def test_build_is_keyed_by_source_content_not_mtime(tmp_path, monkeypatch):
    """A copied tree's mtimes say nothing: the library's name carries a
    digest of the sources, so changed CONTENT under an unchanged (even
    older) mtime builds and loads a new library, and the stale one is
    removed."""
    import os
    import shutil

    src = tmp_path / "src"
    shutil.copytree(native._SRC_DIR, src)
    monkeypatch.setattr(native, "_SRC_DIR", src)
    monkeypatch.setattr(native, "_BUILD_DIR", tmp_path / "_build")
    first = native.build()
    assert first.exists() and native.build() == first  # idempotent
    target = src / "csr_builder.cc"
    before = target.stat()
    target.write_text(target.read_text() + "\n// content changed\n")
    # the .so is NEWER than every source: the old mtime rule would keep it
    os.utime(target, ns=(before.st_atime_ns, before.st_mtime_ns))
    assert target.stat().st_mtime_ns < first.stat().st_mtime_ns
    second = native.build()
    assert second != first and second.exists() and not first.exists()
    import ctypes

    assert ctypes.CDLL(str(second)).pio_csr_plan is not None
