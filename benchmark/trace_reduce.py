"""From a profiler trace to numbers: the reduction every PR shares.

``load`` turns the ``.xplane.pb`` the jax profiler wrote into a plain
structure — ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}`` — and everything else works on that
structure, so the reduction is checked against a small recorded trace
kept beside it (``tests/data``) without a chip.

What a TPU trace holds (looked at by hand, PERF.md §3): one plane per
chip named ``/device:TPU:<n>``; on it the line ``XLA Ops`` carries one
event per executed HLO op (fusions, custom calls, copies) and the line
``XLA Modules`` one event per executed program, named
``<jit name>(<fingerprint>)``. Host threads are lines of ``/host:CPU``.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: a gap between device ops longer than this is a stall, not the pause
#: between two dispatches
STALL_NS = 50e6


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as the plain
    structure (device planes whole; of host planes only their names)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                lines.append({"name": line.name, "events": [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events]})
        else:
            lines = [{"name": line.name, "events": []}
                     for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_recorded(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def describe(trace: dict) -> list:
    """[(plane, line, number of events, first event names)] — for the
    look by hand."""
    out = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            names = []
            for e in line["events"]:
                if e[0] not in names:
                    names.append(e[0])
                if len(names) >= 8:
                    break
            out.append((plane["name"], line["name"], len(line["events"]),
                        names))
    return out


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _union(events: list) -> list:
    """Sorted, merged [start, end] intervals of the events."""
    spans = sorted((s, s + d) for _n, s, d in events if d > 0)
    out = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def busy_seconds(trace: dict):
    """Seconds in which an op ran on the device, averaged over the
    chips; None when no device plane holds an op (nothing to read)."""
    per_chip = []
    for plane in device_planes(trace):
        spans = _union(_line(plane, OPS_LINE))
        if spans:
            per_chip.append(sum(e - s for s, e in spans) / 1e9)
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip)


def short_name(op: str) -> str:
    """``%fusion.1 = f32[16,294015]{1,0:T(8,128)} fusion(...)`` →
    ``fusion.1 f32[16,294015]``: the trace names an op by its whole HLO
    line; the op's name and result shape tell the rungs apart."""
    head, sep, rest = op.partition(" = ")
    if not sep:
        return op[:80]
    shape = re.match(r"\(?[a-z0-9]+\[[0-9,]*\]", rest)
    return (head.lstrip("%") + (" " + shape.group(0).lstrip("(")
                                if shape else ""))[:80]


def top_ops(trace: dict, n: int = 10) -> list:
    """[[op name, seconds]] of the device ops that took most time,
    summed over chips."""
    total: dict = {}
    for plane in device_planes(trace):
        for name, _s, d in _line(plane, OPS_LINE):
            name = short_name(name)
            total[name] = total.get(name, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, window_ns=None) -> list:
    """[[what the host was doing, seconds]] of the device's idle time on
    the first chip: gaps between ops, told apart by length alone (the
    program writes no host spans into the trace yet): ``stall`` for a
    gap over 50 ms, ``between_dispatches`` for the rest. ``window_ns``
    = (start, end) adds the edges of the traced window."""
    planes = device_planes(trace)
    if not planes:
        return []
    spans = _union(_line(planes[0], OPS_LINE))
    if not spans:
        return []
    gaps = [b[0] - a[1] for a, b in zip(spans, spans[1:])]
    if window_ns is not None:
        gaps += [max(spans[0][0] - window_ns[0], 0.0),
                 max(window_ns[1] - spans[-1][1], 0.0)]
    out = {"between_dispatches": 0.0, "stall": 0.0}
    for g in gaps:
        out["stall" if g > STALL_NS else "between_dispatches"] += g / 1e9
    return [[k, v] for k, v in out.items() if v > 0]


def module_executions(trace: dict, name_part: str):
    """(count, seconds) of the executions of the programs whose XLA
    module name holds ``name_part``, on the first chip; None if the
    trace shows none."""
    planes = device_planes(trace)
    if not planes:
        return None
    hits = [d for n, _s, d in _line(planes[0], MODULES_LINE)
            if name_part in n]
    if not hits:
        return None
    return len(hits), sum(hits) / 1e9


def event_span_ns(trace: dict):
    """(first start, last end) over all device events, or None."""
    lo, hi = None, None
    for plane in device_planes(trace):
        for line in plane["lines"]:
            for _n, s, d in line["events"]:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
    return None if lo is None else (lo, hi)
