"""Pod launcher (parallel/launcher.py) — the Runner.runOnSpark role.

The heavyweight proof: PodLauncher actually brings up a 2-process pod on
localhost whose workers join one jax.distributed runtime and run a
numerics-checked ALS sweep (tests/distributed_worker.py — the same worker
the raw 2-process test uses, now spawned and supervised by the launcher).
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from incubator_predictionio_tpu.parallel.launcher import PodLauncher


def _base_env():
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    repo_root = str(Path(__file__).parent.parent)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (repo_root, env.get("PYTHONPATH")) if p)
    return env


def test_launcher_runs_two_process_pod():
    worker = str(Path(__file__).parent / "distributed_worker.py")
    lines = []
    launcher = PodLauncher(
        ["local", "localhost"], [sys.executable, worker],
        env_extra=_base_env(),
    )
    # env_extra must reach the workers; the trio is set per process
    assert launcher._worker_env(1)["PIO_PROCESS_ID"] == "1"
    assert launcher._worker_env(1)["PIO_NUM_PROCESSES"] == "2"
    rc = launcher.launch(sink=lines.append, timeout=240)
    joined = "\n".join(lines)
    assert rc == 0, joined
    # both workers streamed through the supervisor with host tags
    assert any(line.startswith("[0:local]") for line in lines), joined
    assert any(line.startswith("[1:localhost]") for line in lines), joined
    assert joined.count("WORKER_OK") == 2, joined


def test_launcher_tears_down_pod_on_first_failure():
    ok = [sys.executable, "-c",
          "import time, os\n"
          "time.sleep(0 if os.environ['PIO_PROCESS_ID']=='0' else 120)\n"
          "raise SystemExit(3 if os.environ['PIO_PROCESS_ID']=='0' else 0)"]
    launcher = PodLauncher(["local", "local"], ok, env_extra=_base_env())
    rc = launcher.launch(sink=lambda _l: None, timeout=60)
    assert rc != 0
    # the healthy-but-sleeping worker was terminated, not waited out
    assert all(p.poll() is not None for p in launcher.procs)


def test_ssh_command_construction():
    launcher = PodLauncher(
        ["tpu-host-a", "tpu-host-b"], ["pio", "train"],
        coordinator_port=5555,
    )
    assert launcher.coordinator == "tpu-host-a:5555"
    cmd_env = launcher._worker_env(1)
    assert cmd_env["PIO_COORDINATOR_ADDRESS"] == "tpu-host-a:5555"
    assert cmd_env["PIO_NUM_PROCESSES"] == "2"
    # remote spawn goes through ssh with env on the command line
    captured = {}

    def fake_popen(cmd, **kw):
        captured["cmd"] = cmd
        raise RuntimeError("stop here")

    import incubator_predictionio_tpu.parallel.launcher as mod
    orig = mod.subprocess.Popen
    mod.subprocess.Popen = fake_popen
    try:
        with pytest.raises(RuntimeError):
            launcher._spawn("user@tpu-host-b", 1)
    finally:
        mod.subprocess.Popen = orig
    cmd = captured["cmd"]
    assert cmd[:3] == ["ssh", "-o", "BatchMode=yes"]
    assert "user@tpu-host-b" in cmd
    assert any(a.startswith("PIO_PROCESS_ID=") for a in cmd)
    assert cmd[-2:] == ["pio", "train"]


def test_relaunch_strips_hosts_flag(monkeypatch):
    import incubator_predictionio_tpu.parallel.launcher as mod

    seen = {}

    class FakeLauncher:
        def __init__(self, hosts, argv, **kw):
            seen["hosts"] = hosts
            seen["argv"] = argv

        def launch(self):
            return 0

    monkeypatch.setattr(mod, "PodLauncher", FakeLauncher)
    monkeypatch.setattr(
        mod.sys, "argv",
        ["pio", "train", "--hosts", "a,b", "--variant", "engine.json"])
    assert mod.relaunch_over_hosts(["a", "b"]) == 0
    assert seen["hosts"] == ["a", "b"]
    assert "--hosts" not in seen["argv"] and "a,b" not in seen["argv"]
    assert seen["argv"][-2:] == ["--variant", "engine.json"]


def test_cli_worker_joins_runtime_when_coordinator_set():
    """`pio train` inside a launched worker must call
    jax.distributed.initialize before engine code runs — proven by a
    1-process pod whose worker reports process_count from inside the CLI
    path (eval of a trivial command avoids needing an engine dir)."""
    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import jax\n"
        "from incubator_predictionio_tpu.parallel.distributed import "
        "ensure_initialized\n"
        "ensure_initialized()\n"
        "print('COUNT', jax.process_count())\n"
    )
    # use the launcher itself for a 1-process pod: trio set, port picked
    launcher = PodLauncher(["local"], [sys.executable, "-c", code],
                           env_extra=_base_env())
    lines = []
    rc = launcher.launch(sink=lines.append, timeout=120)
    assert rc == 0, "\n".join(lines)
    assert any("COUNT 1" in line for line in lines)


def test_killed_worker_fails_cleanly_no_corrupt_instance(tmp_path):
    """The supervision half of Runner.scala:101-213: a pod worker dying
    mid-train (SIGKILL — a crash, not a polite exit) must produce a clean
    nonzero supervisor failure with the surviving worker torn down and NO
    corrupt EngineInstance — the store may hold an ABORTED record or
    nothing, but never COMPLETED and never a model blob."""
    import json
    import signal
    import sqlite3

    engine_dir = tmp_path / "engine"
    engine_dir.mkdir()
    (engine_dir / "crashengine.py").write_text(
        "import os, signal\n"
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "from incubator_predictionio_tpu.core import (\n"
        "    Algorithm, DataSource, Engine, EngineFactory, FirstServing,\n"
        "    IdentityPreparator)\n"
        "\n"
        "class DS(DataSource):\n"
        "    def read_training(self, ctx):\n"
        "        return np.arange(32, dtype=np.float32)\n"
        "\n"
        "class Algo(Algorithm):\n"
        "    def train(self, ctx, td):\n"
        "        if os.environ.get('PIO_PROCESS_ID') == '1':\n"
        "            os.kill(os.getpid(), signal.SIGKILL)  # worker crash\n"
        "        return float(jnp.mean(jnp.asarray(td)))\n"
        "    def predict(self, model, query):\n"
        "        return model\n"
        "\n"
        "class CrashEngine(EngineFactory):\n"
        "    def apply(self):\n"
        "        return Engine(DS, IdentityPreparator, {'a': Algo},\n"
        "                      FirstServing)\n"
    )
    (engine_dir / "engine.json").write_text(json.dumps({
        "id": "crash-test",
        "engineFactory": "crashengine:CrashEngine",
    }))
    env = _base_env()
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_HOME": str(tmp_path / "home"),
        "PIO_STORAGE_SOURCES_SQL_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQL_PATH": str(tmp_path / "pio.db"),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "m",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "e",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQL",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "d",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQL",
    })
    proc = subprocess.run(
        [sys.executable, "-m", "incubator_predictionio_tpu.cli.main",
         "train", "--hosts", "local,localhost"],
        cwd=engine_dir, env=env, capture_output=True, text=True,
        timeout=420,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode != 0, out

    db = tmp_path / "pio.db"
    if db.exists():
        conn = sqlite3.connect(str(db))
        try:
            statuses = [r[0] for r in conn.execute(
                "SELECT status FROM engine_instances").fetchall()]
        except sqlite3.OperationalError:
            statuses = []  # table never created — also clean
        assert "COMPLETED" not in statuses, statuses
        try:
            (n_models,) = conn.execute(
                "SELECT COUNT(*) FROM models").fetchone()
        except sqlite3.OperationalError:
            n_models = 0
        assert n_models == 0, n_models
        conn.close()
