"""Capacity/regression model over a bench trajectory.

The acceptance contract: ``scripts/capacity_report.py`` run over a
directory of ``BENCH_*.json``/``MULTICHIP_*.json`` records emits a
``capacity.json`` with a rows-per-chip estimate and a NON-NULL verdict
for every record — including structured reasons for the r04/r05-style
failed runs (``accelerator init still blocked`` rc=3, driver-kill
rc=124), which used to be unexplainable ``parsed: null`` rows. Every
test writes its own records under ``tmp_path`` (the driver's old
``BENCH_r01``–``r05`` files are gone; ``PERF_LEDGER.jsonl`` is the
record now), so the contract does not depend on what is checked in.
"""

import json
import os
import subprocess
import sys

import pytest

from incubator_predictionio_tpu.obs import capacity

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "capacity_report.py")


# -- the tier-1 gate: the real script over an r04/r05-shaped trajectory ------

def _write_failed_run_trajectory(root):
    """The record shapes the driver really produced, written fresh: one
    fully parsed chip-backed builder record (the pinned baseline's
    source), an rc=3 run whose tail says the accelerator never
    initialized, an rc=124 run the driver killed at its deadline, and a
    dry-run multichip record — plus a copy of the pinned baseline."""
    import shutil

    shutil.copy(os.path.join(REPO, "CAPACITY_BASELINE.json"),
                root / "CAPACITY_BASELINE.json")
    base = capacity.load_baseline(REPO)
    (root / "BENCH_r04_builder.json").write_text(json.dumps(base["keys"]))
    blocked = "".join(
        f"accelerator init still blocked (attempt {i}) — retrying\n"
        for i in range(1, 11))
    (root / "BENCH_r04.json").write_text(json.dumps({
        "n": 4, "cmd": "python bench.py", "rc": 3, "parsed": None,
        "tail": blocked + "accelerator unavailable after 1200s; "
                          "aborting\n"}))
    (root / "BENCH_r05.json").write_text(json.dumps({
        "n": 5, "cmd": "python bench.py", "rc": 124, "parsed": None,
        "tail": "dataset: 138493x26744, nnz=20000000, rank=128\n"
                "seed: 20000000 events in 32.9s (0.61M ev/s)\n"
                "ingest scan: 13.1s (1.52M ev/s)\n"
                "prep (bucketed padded rows): 7.1s\n"
                "tpu child attempt 1 did not claim within 180s — "
                "recycling\n"
                "tpu child attempt 3 did not claim within 720s — "
                "recycling\n"}))
    (root / "MULTICHIP_r05.json").write_text(json.dumps({
        "n_devices": 8, "rc": 0, "ok": True, "skipped": False,
        "tail": ""}))


def test_capacity_report_check_over_failed_run_records(tmp_path):
    _write_failed_run_trajectory(tmp_path)
    out = tmp_path / "capacity.json"
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--repo-dir", str(tmp_path),
         "--out", str(out), "--check"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CHECK OK" in proc.stderr
    report = json.loads(out.read_text())

    # a rows-per-chip estimate exists and is a real rate
    cap = report["capacity"]
    assert cap["rows_per_chip_per_s"] and cap["rows_per_chip_per_s"] > 0
    assert cap["rows_per_chip_at_staleness"] > 0
    assert cap["train_source_record"]
    assert cap["qps_per_worker"] and cap["qps_per_worker"] > 0
    assert cap["projections"]["workers_for_qps"]

    # EVERY record carries a non-null verdict
    by_name = {}
    for rec in report["records"]:
        assert rec["verdict"] is not None, rec["name"]
        assert rec["verdict"].get("status"), rec["name"]
        by_name[rec["name"]] = rec
    assert set(by_name) == {"BENCH_r04_builder", "BENCH_r04", "BENCH_r05",
                            "MULTICHIP_r05"}

    # the r04/r05 failure modes are STRUCTURED, never bare nulls
    r04 = by_name["BENCH_r04"]
    assert r04["verdict"]["status"] == "skipped"
    assert r04["skipped_reason"]["class"] == "accelerator_unavailable"
    assert r04["rc"] == 3
    assert "accelerator init still blocked" in " ".join(
        r04["skipped_reason"]["matched"])
    r05 = by_name["BENCH_r05"]
    assert r05["verdict"]["status"] == "skipped"
    assert r05["skipped_reason"]["class"] == "driver_deadline"
    assert r05["rc"] == 124

    # regression section names the pinned baseline and a real status
    reg = report["regression"]
    assert reg["baseline"] is not None
    assert reg["status"] in ("ok", "regressed", "baseline",
                             "incomparable_shape")


def test_pinned_baseline_file_is_valid():
    base = capacity.load_baseline(REPO)
    assert base is not None, "CAPACITY_BASELINE.json missing/invalid"
    assert base["record"]
    assert isinstance(base["keys"], dict) and base["keys"]
    # the pinned record actually exists in the trajectory
    names = {r.name for r in capacity.load_trajectory(REPO)}
    assert base["record"] in names


# -- failure classifier ------------------------------------------------------

def test_classify_accelerator_wedge_rc3():
    tail = ("accelerator init still blocked (attempt 9) - likely a "
            "stale chip lease; retrying\n"
            "accelerator unavailable after 1200s; aborting\n")
    reason = capacity.classify_failure(tail, 3)
    assert reason["class"] == "accelerator_unavailable"
    assert reason["rc"] == 3
    assert reason["matched"]


def test_classify_driver_kill_rc124_wins_over_tail():
    tail = "tpu child attempt 3 did not claim within 720s\n"
    reason = capacity.classify_failure(tail, 124)
    assert reason["class"] == "driver_deadline"
    assert "accelerator" in reason["detail"]  # the secondary cause rides


def test_classify_unknown_nonzero_and_clean_exit():
    r = capacity.classify_failure("boom\nlast words", 7)
    assert r["class"] == "error_exit" and "last words" in r["detail"]
    r = capacity.classify_failure("", 0)
    assert r["class"] == "no_record"


# -- tolerance compare -------------------------------------------------------

BASE = {"value": 2.0, "serve_qps": 1000.0, "nnz": 100, "rank": 8,
        "sweeps": 4, "heldout_rmse": 0.6}


def test_compare_flags_regressions_both_directions():
    worse = dict(BASE, value=3.0, serve_qps=500.0)
    v = capacity.compare_record(worse, BASE, tolerance=0.25)
    assert v["status"] == "regressed"
    keys = {r["key"] for r in v["regressed"]}
    assert keys == {"value", "serve_qps"}   # wall UP, qps DOWN


def test_compare_skips_null_keys_and_tolerates_noise():
    rec = dict(BASE, value=2.2, serve_qps=None, heldout_rmse=0.65)
    v = capacity.compare_record(rec, BASE, tolerance=0.25)
    assert v["status"] == "ok"
    assert "serve_qps" in v["skipped"]       # null = skipped, not failed


def test_compare_shape_mismatch_is_incomparable_not_green():
    rec = dict(BASE, nnz=999)
    v = capacity.compare_record(rec, BASE, tolerance=0.25)
    assert v["status"] == "incomparable_shape"


def test_improvements_are_reported_not_flagged():
    rec = dict(BASE, value=1.0, serve_qps=2000.0)
    v = capacity.compare_record(rec, BASE, tolerance=0.25)
    assert v["status"] == "ok"
    assert set(v["improved"]) == {"value", "serve_qps"}


def test_key_direction_classes():
    assert capacity.key_direction("value") == "lower"
    assert capacity.key_direction("serve_p99_ms") == "lower"
    assert capacity.key_direction("heldout_rmse") == "lower"
    assert capacity.key_direction("serve_qps_concurrent") == "higher"
    assert capacity.key_direction("mfu") == "higher"
    assert capacity.key_direction("ingest_http_eps") == "higher"
    assert capacity.key_direction("nnz") is None        # shape key
    assert capacity.key_direction("als_kernel") is None  # informational


# -- record normalization ----------------------------------------------------

def _write(tmp_path, name, obj):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return str(p)


def test_normalize_wrapped_flat_and_multichip(tmp_path):
    wrapped = _write(tmp_path, "BENCH_r07.json", {
        "n": 7, "cmd": "python bench.py", "rc": 0, "tail": "",
        "parsed": {"value": 1.5, "nnz": 100, "degraded": False,
                   "bench_env": {"backend": "tpu"}}})
    flat = _write(tmp_path, "BENCH_r08.json", {
        "metric": "als_ml20m_train_wall_s", "value": 1.4, "nnz": 100})
    multi = _write(tmp_path, "MULTICHIP_r07.json", {
        "n_devices": 8, "rc": 0, "ok": True, "skipped": False,
        "tail": ""})
    w = capacity.normalize_record(wrapped)
    assert w.kind == "bench" and w.round == 7
    assert w.parsed["value"] == 1.5
    assert w.bench_env == {"backend": "tpu"}
    assert w.skipped_reason is None
    f = capacity.normalize_record(flat)
    assert f.parsed["value"] == 1.4 and f.round == 8
    m = capacity.normalize_record(multi)
    assert m.kind == "multichip" and m.ok is True


def test_normalize_surfaces_bench_emitted_skip_reason(tmp_path):
    # post-PR-9 degraded rounds: the bench ITSELF ships the structured
    # reason inside parsed — the normalizer surfaces it as-is
    p = _write(tmp_path, "BENCH_r09.json", {
        "n": 9, "rc": 0, "tail": "", "parsed": {
            "value": 300.0, "nnz": 100, "degraded": True,
            "skipped_reason": {"class": "accelerator_unavailable",
                               "stage": "tpu_child", "rc": 3}}})
    r = capacity.normalize_record(p)
    assert r.degraded is True
    assert r.skipped_reason["class"] == "accelerator_unavailable"


def test_trajectory_verdicts_every_record_non_null(tmp_path):
    _write(tmp_path, "BENCH_r01.json", {
        "n": 1, "rc": 0, "tail": "", "parsed": {
            "value": 2.0, "nnz": 100, "rank": 8, "sweeps": 4,
            "serve_qps_concurrent": 900.0}})
    _write(tmp_path, "BENCH_r02.json", {
        "n": 2, "rc": 3,
        "tail": "accelerator init still blocked (attempt 1)",
        "parsed": None})
    report = capacity.capacity_report(str(tmp_path))
    assert len(report["records"]) == 2
    for rec in report["records"]:
        assert rec["verdict"]["status"]
    # no pinned baseline file in tmp: the oldest parsed record becomes
    # the honest fallback baseline
    assert report["regression"]["baseline"] == "BENCH_r01"
    assert report["regression"]["baseline_provenance"] \
        == "fallback:oldest_parsed"
    cap = report["capacity"]
    assert cap["rows_per_chip_per_s"] == pytest.approx(50.0)
    assert cap["qps_per_worker"] == 900.0


def test_degraded_records_never_feed_the_chip_rate(tmp_path):
    _write(tmp_path, "BENCH_r01.json", {
        "n": 1, "rc": 0, "tail": "", "parsed": {
            "value": 10.0, "nnz": 1000, "degraded": False}})
    _write(tmp_path, "BENCH_r02.json", {
        "n": 2, "rc": 0, "tail": "", "parsed": {
            "value": 300.0, "nnz": 1000, "degraded": True}})
    cap = capacity.fit_capacity(capacity.load_trajectory(str(tmp_path)))
    # the newer record is degraded (CPU fallback): the chip rate comes
    # from r01, the newest NON-degraded training wall
    assert cap["train_source_record"] == "BENCH_r01"
    assert cap["rows_per_chip_per_s"] == pytest.approx(100.0)
