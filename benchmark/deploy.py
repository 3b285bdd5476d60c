"""Hosting the system under test: a real `pio deploy`, in this process.

The process that holds the chip has to be the one that reports the
device's memory and takes the trace, and `pio deploy` offers neither, so
the harness calls the CLI's own entry (``cli.main.main(["deploy", ...])``
— what the ``pio`` command runs) on a thread: real socket, real
scheduler, real warm-up. The engine instance is written where `pio
deploy` looks for it (metadata + model store under a ``PIO_HOME`` in the
run's temporary directory); its model is a PersistentModel manifest that
makes the factors from the seed (``benchmark.factors``).

The server's span log (one JSON line a request on standard error, the
CLI's default) goes to a file in the temporary directory for as long as
the deploy lives, as a production deploy logs to a file: the harness's
own standard error is a pipe someone else drains, and a full pipe would
block the server's event loop on a write.
"""

from __future__ import annotations

import datetime
import json
import os
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

WARMUP_THREAD = "pio-serving-warmup"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def set_environment(tmp: str, config: dict, mix: dict, extra=None) -> dict:
    """The deployment's environment: storage under the run's temporary
    directory, the compile cache at its fixed in-checkout path, the
    configuration's settings, then the mix's overrides. Returns what was
    set beyond storage (for the record)."""
    for key in list(os.environ):
        if key.startswith("PIO_STORAGE_") or key.startswith("PIO_SERVE_"):
            del os.environ[key]
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.environ["PIO_HOME"] = os.path.join(tmp, "pio_home")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # one fixed directory inside the checkout, unless the machine names
    # one: the path is part of the cache's key
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(checkout, ".xla_cache"))
    # every program in the cache after the first run, the quick ones too
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    settings = dict(config.get("deployment_env", {}))
    settings.update(mix.get("env", {}))
    settings.update(extra or {})
    os.environ.update(settings)
    return settings


def write_instance(tmp: str, config: dict, seed: int) -> str:
    """`pio app new`, an engine directory, and a COMPLETED engine
    instance whose model blob is the seeded loader's manifest. Returns
    the variant path `pio deploy` is given."""
    from incubator_predictionio_tpu.cli import commands
    from incubator_predictionio_tpu.cli.main import main as pio
    from incubator_predictionio_tpu.core.persistent_model import (
        PersistentModelManifest,
    )
    from incubator_predictionio_tpu.data.storage import (
        EngineInstance,
        Model,
        Storage,
    )
    from incubator_predictionio_tpu.utils import json_codec
    from incubator_predictionio_tpu.workflow import checkpoint

    from benchmark import factors

    app = "BenchApp"
    if pio(["app", "new", app]) != 0:
        raise SystemExit("benchmark: `pio app new` failed")
    engine_dir = os.path.join(tmp, "engine")
    os.makedirs(engine_dir, exist_ok=True)
    variant_path = os.path.join(engine_dir, "engine.json")
    variant = {
        "id": "default",
        "description": f"benchmark: {config['name']}",
        "engineFactory": config["engine_factory"],
        "datasource": {"params": {"appName": app}},
        "algorithms": [config["algorithm"]],
    }
    with open(variant_path, "w") as f:
        json.dump(variant, f, indent=2)
    _engine, params = commands.engine_from_variant(variant)
    now = datetime.datetime.now(datetime.timezone.utc)
    instances = Storage.get_meta_data_engine_instances()
    iid = instances.insert(EngineInstance(
        id="", status="COMPLETED", start_time=now, end_time=now,
        engine_id=commands.engine_id_for_variant_path(variant_path, variant),
        engine_version="NOT_VERSIONED", engine_variant="default",
        engine_factory=config["engine_factory"],
        data_source_params=json_codec.dumps(params.data_source_params),
        preparator_params=json_codec.dumps(params.preparator_params),
        algorithms_params=json_codec.dumps(params.algorithm_params_list),
        serving_params=json_codec.dumps(params.serving_params)))
    p = config["planted"]
    factors.write_spec(iid, {
        "seed": int(seed), "n_users": config["n_users"],
        "n_items": config["n_items"], "rank": config["rank"],
        "plant_rank": p["rank"], "noise": p["noise"]})
    blob = checkpoint.dumps([PersistentModelManifest(
        class_path=config["loader"], instance_id=iid)])
    Storage.get_model_data_models().insert(Model(iid, blob))
    return variant_path


class Deployment:
    """One `pio deploy` on a thread of this process."""

    def __init__(self, variant_path: str, log_path: str) -> None:
        self.variant_path = variant_path
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.rc: list = []
        self.log_path = log_path
        self._log = None
        self._stderr = None
        self.thread = threading.Thread(target=self._run, name="pio-deploy",
                                       daemon=True)
        self.leftover = False

    def _run(self) -> None:
        from incubator_predictionio_tpu.cli.main import main as pio

        try:
            self.rc.append(pio([
                "deploy", "--variant", self.variant_path,
                "--ip", "127.0.0.1", "--port", str(self.port)]))
        except BaseException as e:  # shown by whoever waits on us
            self.rc.append(e)

    def start(self) -> None:
        self._log = open(self.log_path, "w", buffering=1)
        self._stderr = sys.stderr
        sys.stderr = self._log
        self.t_start = time.perf_counter()
        self.thread.start()

    def _check_alive(self) -> None:
        if not self.thread.is_alive():
            self.restore_stderr()
            raise SystemExit(
                f"benchmark: `pio deploy` exited early (rc={self.rc}); "
                f"its log ends:\n{self.log_tail()}")

    def wait_bound(self, limit_s: float = 900.0) -> None:
        while True:
            self._check_alive()
            if time.perf_counter() - self.t_start > limit_s:
                raise SystemExit("benchmark: `pio deploy` never bound")
            try:
                urllib.request.urlopen(self.base + "/", timeout=5).read()
                break
            except (OSError, urllib.error.URLError):
                time.sleep(0.1)

    def wait_warm(self, limit_s: float = 1100.0) -> None:
        """`pio deploy` binds, then warms its serving programs on a
        thread of its own and gives no readiness signal: wait for that
        thread to end."""
        while any(t.name == WARMUP_THREAD and t.is_alive()
                  for t in threading.enumerate()):
            self._check_alive()
            if time.perf_counter() - self.t_start > limit_s:
                raise SystemExit("benchmark: serving warm-up never ended")
            time.sleep(0.1)

    def stop(self) -> None:
        """`pio undeploy`, and wait for `pio deploy` to return. A deploy
        that answers /stop and then does not end (asyncio's server waits
        for every connection to drop) is left to die with the process:
        ``leftover`` says so, and the run, whose window is over, goes on.
        """
        from incubator_predictionio_tpu.cli.main import main as pio

        if self.thread.is_alive():
            pio(["undeploy", "--ip", "127.0.0.1", "--port", str(self.port)])
            self.thread.join(30)
        self.restore_stderr()
        if self.thread.is_alive():
            self.leftover = True
            print("benchmark: `pio deploy` answered /stop and has not "
                  f"ended after 30 s; it stands at:\n{self.where()}",
                  flush=True)
        elif self.rc != [0]:
            raise SystemExit(
                f"benchmark: `pio deploy` did not exit cleanly "
                f"(rc={self.rc}); its log ends:\n{self.log_tail(1500)}")

    def where(self) -> str:
        """The deploy thread's stack, if it still runs."""
        import traceback

        frame = sys._current_frames().get(self.thread.ident)
        out = "".join(traceback.format_stack(frame)) if frame else "(ended)"
        # what its event loop still waits on: the handlers of connections
        # that never closed keep `pio deploy` from ending
        try:
            import asyncio
            import gc

            from incubator_predictionio_tpu.utils.http import HttpServer

            for srv in [o for o in gc.get_objects()
                        if isinstance(o, HttpServer)]:
                loop = getattr(srv, "_loop", None)
                if loop is None or loop.is_closed():
                    continue
                tasks = asyncio.all_tasks(loop)
                out += f"{len(tasks)} task(s) on the server's loop\n"
                for task in list(tasks)[:5]:
                    out += "".join(
                        traceback.format_list(traceback.extract_stack(
                            task.get_stack(limit=4)[-1]))[-2:]) \
                        if task.get_stack(limit=1) else repr(task) + "\n"
        except Exception as e:  # diagnosis only
            out += f"(no task list: {e!r})\n"
        return out

    def restore_stderr(self) -> None:
        if self._stderr is not None:
            sys.stderr = self._stderr
            self._stderr = None
        if self._log is not None:
            self._log.close()
            self._log = None

    def log_tail(self, n: int = 3000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, 2)
                f.seek(max(f.tell() - n, 0))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return ""

    def log_lines(self, skip_prefix: str = "{") -> list:
        """The deploy log's lines that are not span lines (warnings,
        tracebacks): what a run should show of it."""
        try:
            with open(self.log_path, errors="replace") as f:
                return [ln.rstrip("\n") for ln in f
                        if not ln.startswith(skip_prefix)]
        except OSError:
            return []
