"""The trace → metrics reduction against a small recorded trace (cut
from a real one of cell 1 on a TPU v5 lite; see its ``note``)."""

import os

import pytest

from benchmark import trace_reduce, work

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "trace_lastfm_steady.json.gz")
LASTFM = {"n_items": 294015, "rank": 2048, "dtype": "float32"}
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.load_recorded(TRACE)


def test_planes_and_lines_are_where_the_reduction_looks(trace):
    planes = trace_reduce.device_planes(trace)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    lines = {ln["name"]: len(ln["events"]) for ln in planes[0]["lines"]}
    assert lines[trace_reduce.MODULES_LINE] == 60
    assert lines[trace_reduce.OPS_LINE] == 417


def test_busy_is_the_union_of_the_op_intervals(trace):
    busy = trace_reduce.busy_seconds(trace)
    count, seconds = trace_reduce.module_executions(
        trace, "_batch_score_top_k_xla")
    assert count == 60
    # the ops tile their programs: the union of ops is the programs' time
    assert busy == pytest.approx(seconds, rel=1e-4)
    assert busy == pytest.approx(0.20228, rel=1e-3)
    lo, hi = trace_reduce.event_span_ns(trace)
    gaps = dict(trace_reduce.idle_gaps(trace))
    assert set(gaps) == {"between_dispatches"}       # no gap over 50 ms
    assert busy + gaps["between_dispatches"] == pytest.approx(
        (hi - lo) / 1e9, rel=1e-6)
    assert trace_reduce.module_executions(trace, "no_such_program") is None


def test_top_ops_are_named_short_and_ordered(trace):
    ops = trace_reduce.top_ops(trace, 10)
    assert ops[0][0] == "fusion.1 f32[16,294015]"
    assert [s for _n, s in ops] == sorted((s for _n, s in ops),
                                          reverse=True)
    assert len(ops) <= 10 and all(len(n) <= 80 for n, _s in ops)


def test_roofline_share_by_hand(trace):
    count, seconds = trace_reduce.module_executions(
        trace, "_batch_score_top_k_xla")
    # 60 executions, each streaming the 2.41 GB table once: 2.941 ms each
    # at 819 GB/s; the queries' own bytes are thousands of times fewer
    least, bound = work.least_seconds(LASTFM, V5E, count, 600, 16)
    assert bound == "memory"
    assert least == pytest.approx(60 * 294015 * 2048 * 4 / 819e9, rel=1e-3)
    share = 100 * least / seconds
    assert 80 < share < 100
    # compute-bound only past ~480 queries a dispatch at float32
    assert work.least_seconds(LASTFM, V5E, 1, 512, 16)[1] == "compute"


def test_a_trace_without_device_ops_reads_as_nothing():
    empty = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": []}]}]}
    assert trace_reduce.busy_seconds(empty) is None
    assert trace_reduce.top_ops(empty) == []
    assert trace_reduce.idle_gaps(empty) == []
