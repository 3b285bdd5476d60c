"""The one command end to end at a tiny size on the CPU, both --trace
values, and the faults that have to turn `correct` false."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(*args: str):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", [w["name"]
                                      for w in bench()["workloads"]])
def test_untraced_run_reports_the_end_to_end_metrics(workload):
    result, err = rehearse("--workload", workload, "--trace", "0")
    assert KEYS <= set(result)
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 600
    assert set(result["metrics"]) == {m["name"]
                                      for m in bench()["end_to_end"]}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"   # a rehearsal says so
    # the numbers compared, each beside its limit, end standard error
    tail = err.strip().splitlines()[-4:]
    assert all(line.startswith("compared: ") for line in tail)
    assert result["compared"]["score_err"]["value"] \
        <= result["compared"]["score_err"]["limit"]


def test_traced_run_reports_per_layer_metrics_the_cpu_can_read():
    result, _err = rehearse("--trace", "1", "--seed", "5")
    assert KEYS <= set(result) and "breakdown" in result
    names = {m["name"] for m in bench()["per_layer"]}
    assert set(result["metrics"]) <= names
    # the client's log and the scrapes exist on any backend; the device
    # trace's readers find no TPU plane here and return nothing, not 0
    assert {"gen_late_p95_ms", "stall_max_ms", "queue_wait_p95_ms",
            "batch_size_mean", "compiles_in_window"} <= set(result["metrics"])
    assert "score_roofline" not in result["metrics"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("fault", ["alter_item", "alter_score"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(fault):
    result, _err = rehearse("--fault", fault, "--seed", "9")
    assert result["correct"] is False
    assert result["failed"] == 0          # well-formed, in time — and wrong
    c = result["compared"]
    assert (c["score_err"]["value"] > c["score_err"]["limit"]
            or c["rank_gap"]["value"] > c["rank_gap"]["limit"])


def test_the_command_refuses_to_run_without_an_accelerator():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         bench()["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
