"""One cell, once.

    python3 -m benchmark.run --workload <config>.<traffic> --seed N
                             --seconds S --trace 0|1

Everything a cell needs is found by the names in ``BENCHMARK.json``:
``benchmark/configs/<config>.json`` (sizes, deployment settings, the
limits ``correct`` is held to, the reference's name),
``benchmark/traffic/<traffic>.json`` (the arrival process and query
shape), ``benchmark/cells/<config>.<traffic>.json`` (the fixed rate and
the sweep it came from) and, for ``--trace 1``,
``benchmark/metrics/<metric>.py`` for every per-layer metric that lists
the cell. A workload's name splits at its first ``.``.

The run: make the factors from the seed, write the instance where `pio
deploy` looks, deploy on a real socket in this process, wait for the
whole ladder, start the open-loop generator (a child that stays off
jax), let it send ``warm_s`` untimed seconds and then the window, read
the chip's peak memory, undeploy, and hold a seeded sample of what the
window answered against the plain reference. The last line on standard
output is the result; every other number is on earlier lines.

``setup_s`` runs from the instant the accelerator's runtime is up
(``jax.devices()`` has returned) to the first timed request: the
program's imports, the instance, the model made and restored, `pio
deploy` bound, the ladder warm, the generator ready and the mix's
untimed seconds. What comes before (the interpreter, ``import jax`` and
libtpu pinning its host transfer buffer, 8 to 21 s by the state of the
machine's memory and the same for any program) is printed as
``runtime_start_s`` on the ``setup:`` line and is in no metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


#: run_cell's code for "done, but a thread of the server is left": main
#: prints the result and leaves through os._exit(0)
LEFTOVER = -1


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def find_cell(workload: str) -> dict:
    """The cell's entry of BENCHMARK.json and its three files."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         "BENCHMARK.json")
    config_name, _, traffic_name = workload.partition(".")
    if (config_name, traffic_name) != (entry["config"], entry["traffic"]):
        raise SystemExit(f"benchmark: {workload!r} does not split into its "
                         "config and traffic at the first '.'")
    config = load_json("configs", f"{config_name}.json")
    config["name"] = config_name
    from benchmark import traffic

    return {
        "bench": bench, "entry": entry, "config": config,
        "mix": traffic.load_mix(traffic_name),
        "cell": load_json("cells", f"{workload}.json"),
    }


def device_or_exit(chips: int, peaks: dict, rehearsal: bool) -> dict:
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if rehearsal:
        return dev
    if dev["platform"] != "tpu":
        raise SystemExit(f"benchmark: no accelerator: jax reports "
                         f"{dev['platform']!r}")
    if dev["count"] < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chip(s), "
                         f"jax reports {dev['count']}")
    if dev["kind"] not in peaks:
        raise SystemExit(f"benchmark: device kind {dev['kind']!r} is not "
                         "in benchmark/peaks.json")
    return dev


class Watch:
    """What would betray a stall's cause, watched from inside the
    serving process: garbage collections and jax compilations, each with
    the epoch second it ended at."""

    def __init__(self) -> None:
        self.gcs: list = []        # (ended at, generation, seconds)
        self.compiles: list = []   # (ended at, seconds)
        self._gc_t0 = 0.0

    def install(self) -> None:
        import jax.monitoring

        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gcs.append((time.time(), info.get("generation", -1),
                             time.perf_counter() - self._gc_t0))

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.time(), duration))

    def within(self, t0: float, t1: float, origin: float = 0.0) -> dict:
        gcs = [g for g in self.gcs if t0 <= g[0] <= t1]
        comp = [c for c in self.compiles if t0 <= c[0] <= t1]
        return {
            # collections over 5 ms: (seconds into the schedule at which
            # it ended, generation, ms) — to set beside the longest gaps
            "gc_long": [(round(g[0] - origin, 3), g[1], round(g[2] * 1e3, 2))
                        for g in gcs if g[2] > 5e-3],
            "gc_gen2": sum(1 for g in gcs if g[1] == 2),
            "gc_all": len(gcs),
            "gc_pause_max_ms": max([g[2] for g in gcs], default=0.0) * 1e3,
            "gc_pause_sum_ms": sum(g[2] for g in gcs) * 1e3,
            "jax_compiles": len(comp),
            "jax_compile_s": sum(c[1] for c in comp),
        }


class GilWatchdog:
    """For the stall hunt alone (``benchmark.sweep --watchdog``): a
    thread that sleeps 5 ms at a time and, when it wakes over 30 ms
    late, notes where every other thread stands. Whatever kept the
    interpreter from it — a collection, a call that holds the lock — is
    as a rule still on a stack. It costs the server some of its lock, so
    no measured run starts it."""

    def __init__(self, late_s: float = 0.030) -> None:
        self.late_s = late_s
        self.hits: list = []      # (epoch, ms late, {thread: frames})
        self._stop = False

    def start(self) -> None:
        import threading

        def loop() -> None:
            import traceback

            while not self._stop:
                t0 = time.perf_counter()
                time.sleep(0.005)
                late = time.perf_counter() - t0 - 0.005
                if late > self.late_s:
                    names = {t.ident: t.name for t in threading.enumerate()}
                    me = threading.get_ident()
                    stacks = {
                        names.get(i, str(i)): [
                            f"{f.filename.rsplit('/', 2)[-1]}:{f.lineno} "
                            f"{f.name}" for f in
                            traceback.extract_stack(fr)[-4:]]
                        for i, fr in sys._current_frames().items()
                        if i != me}
                    self.hits.append((time.time(), late * 1e3, stacks))

        threading.Thread(target=loop, name="bench-gil-watchdog",
                         daemon=True).start()

    def stop(self) -> None:
        self._stop = True


def percentile_ms(lat_s: np.ndarray, q: float) -> float:
    return float(np.quantile(lat_s, q) * 1e3)


def read_metric(name: str, ctx: dict):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


class Session:
    """One deployment of one configuration, and windows driven on it.
    ``run_cell`` drives one window; ``benchmark.sweep`` several."""

    def __init__(self, found: dict, seed: int, rehearsal: dict | None):
        self.found = found
        self.seed = seed
        self.rehearsal = rehearsal
        self.config = found["config"]
        self.mix = found["mix"]
        self.chips = int(found["entry"]["chips"])
        self.tmp = tempfile.mkdtemp(prefix="pio_bench_")
        self.dep = None
        self.proc = None
        self.watch = Watch()
        self.stages = 0
        self.leftover = False

    def start(self) -> None:
        from benchmark import deploy

        extra_env = (self.rehearsal or {}).get("env")
        self.settings = deploy.set_environment(self.tmp, self.config,
                                               self.mix, extra_env)
        peaks_all = load_json("peaks.json")
        self.dev = device_or_exit(self.chips, peaks_all,
                                  self.rehearsal is not None)
        self.peaks = peaks_all.get(self.dev["kind"])
        # the set-up clock starts here, with the accelerator's runtime up:
        # see "setup_s" in the module's docstring
        self.t_runtime_up = t_a = time.time()
        say(f"device: {self.dev}; deployment settings: {self.settings}")
        self.watch.install()
        variant = deploy.write_instance(self.tmp, self.config, self.seed)
        t_b = time.time()
        self.dep = deploy.Deployment(
            variant, os.path.join(self.tmp, "deploy.log"))
        self.dep.start()
        self.dep.wait_bound()
        self.t_bound = time.time()
        self.walls = {"runtime_start_s": t_a - T_PROCESS_START,
                      "write_instance_s": t_b - t_a}

    def window(self, rate: float, seed: int, seconds: float,
               trace: bool) -> dict:
        """Warm (first call), start a generator, let it send the mix's
        untimed seconds and then ``seconds`` timed ones; returns what
        the window left: the client's log, the two scrapes, the trace."""
        from benchmark import factors, prom

        mix, dep = self.mix, self.dep
        self.stages += 1
        out_path = os.path.join(self.tmp, f"client_log_{self.stages}.npz")
        spec = {
            "port": dep.port, "traffic": self.found["entry"]["traffic"],
            "rate": rate, "warm_s": float(mix["warm_s"]),
            "seconds": seconds, "n_users": self.config["n_users"],
            "seed": seed, "sample": int(self.config.get("sample", 256)),
            "out": out_path,
        }
        spec_path = os.path.join(self.tmp, f"loadgen_{self.stages}.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        # the generator gets ready while the ladder still warms
        self.proc = proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.loadgen", spec_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env, cwd=ROOT)
        dep.wait_warm()
        t_warm = time.time()
        ready = proc.stdout.readline().strip()
        if ready != "READY":
            raise SystemExit(f"benchmark: the generator said {ready!r}")
        warm_s = float(mix["warm_s"])
        start_at = time.time() + 1.0
        proc.stdin.write(f"{start_at!r}\n")
        proc.stdin.flush()
        w0, w1 = start_at + warm_s, start_at + warm_s + seconds
        setup_s = w0 - self.t_runtime_up
        if self.stages == 1:
            lw = factors.LOAD_WALLS
            say("setup: " + json.dumps({
                **self.walls,
                "generate_s": lw.get("generate_s"),
                "bimaps_s": lw.get("bimaps_s"),
                "prepare_and_bind_s": (self.t_bound - lw["loaded_at"])
                if "loaded_at" in lw else None,
                "ladder_warm_s": t_warm - self.t_bound,
                "generator_and_untimed_s": w0 - t_warm,
                "setup_s": setup_s}))
        time.sleep(max(w0 - time.time(), 0))
        scrape0 = prom.scrape(dep.base)
        trace_dir, trace_window = None, None
        if trace:
            import jax

            trace_len = min(seconds / 2.0, float(mix.get("trace_s", 4.0)))
            time.sleep(max(w0 + (seconds - trace_len) / 2 - time.time(), 0))
            trace_dir = os.path.join(self.tmp, f"trace_{self.stages}")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t_tr0 = time.time()
            time.sleep(trace_len)
            t_tr1 = time.time()
            jax.profiler.stop_trace()
            trace_window = (t_tr0 - start_at, t_tr1 - start_at)
        time.sleep(max(w1 - time.time(), 0))
        scrape1 = prom.scrape(dep.base)
        try:
            proc.wait(timeout=float(mix["time_limit_s"]) + 120)
        except subprocess.TimeoutExpired:
            raise SystemExit("benchmark: the generator did not finish")
        if proc.returncode != 0:
            raise SystemExit(f"benchmark: the generator exited "
                             f"{proc.returncode}")
        self.proc = None
        log = dict(np.load(out_path))
        os.remove(out_path)
        return {"log": log, "scrape0": scrape0, "scrape1": scrape1,
                "trace_dir": trace_dir, "trace_window": trace_window,
                "epoch_window": (w0, w1), "setup_s": setup_s,
                "warm_s": warm_s, "seconds": seconds}

    def context(self, win: dict) -> dict:
        """What the per-layer readers are given."""
        return {
            "config": self.config, "cell": self.found["cell"],
            "mix": self.mix, "peaks": self.peaks, "chips": self.chips,
            "log": win["log"],
            "window": (win["warm_s"], win["warm_s"] + win["seconds"]),
            "scrape0": win["scrape0"], "scrape1": win["scrape1"],
            "trace": None, "trace_window": win["trace_window"],
        }

    def summary(self, win: dict, ctx: dict) -> dict:
        """The window's numbers on the client's clock, with what would
        betray a stall's cause; printed by every run."""
        from benchmark import clientlog, prom

        log, mix = win["log"], self.mix
        timed, good = log["timed"], log["good"]
        lat = np.where(good, log["done"] - log["due"],
                       float(mix["time_limit_s"]))[timed]
        due = log["due"][timed]
        span = due.max() - due.min() if len(due) else 0.0
        first = lat[due <= due.min() + span / 4]
        last = lat[due >= due.max() - span / 4]
        status = {int(s): int(c) for s, c in zip(*np.unique(
            log["status"][timed], return_counts=True))}
        return {
            "attempted": int(timed.sum()),
            "failed": int((timed & ~good).sum()), "status": status,
            "query_p50_ms": percentile_ms(lat, 0.5),
            "query_p95_ms": percentile_ms(lat, 0.95),
            "query_p99_ms": percentile_ms(lat, 0.99),
            "query_max_ms": float(lat.max() * 1e3),
            "first_quarter_p50_ms": percentile_ms(first, 0.5),
            "last_quarter_p50_ms": percentile_ms(last, 0.5),
            "stall_max_ms": read_metric("stall_max_ms", ctx),
            "gen_late_p95_ms": read_metric("gen_late_p95_ms", ctx),
            "gen_late_max": clientlog.latest_sends(log),
            "compiles_in_window": read_metric("compiles_in_window", ctx),
            "queue_wait_p95_ms": read_metric("queue_wait_p95_ms", ctx),
            "batch_size_mean": read_metric("batch_size_mean", ctx),
            "sheds": prom.delta(win["scrape0"], win["scrape1"],
                                "pio_serve_shed_total"),
            "connections_opened": int(log["opened"]),
            "longest_gaps": clientlog.longest_gaps(log),
            **self.watch.within(*win["epoch_window"],
                                origin=float(log["t0_epoch"])),
        }

    def memory(self) -> tuple:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        peak = max(
            ((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
            for d in jax.devices()[:self.chips]) or None
        say(f"memory: peak_bytes_in_use {peak} bytes_in_use "
            f"{stats.get('bytes_in_use')} of {stats.get('bytes_limit')}")
        return peak

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        dep, self.dep = self.dep, None
        try:
            if dep is not None:
                dep.stop()
                self.leftover = dep.leftover
                for line in dep.log_lines()[-40:]:
                    say("deploy log: " + line)
        finally:
            shutil.rmtree(self.tmp, ignore_errors=True)
        # the server's objects hold each other in cycles: collect them,
        # so that the device lets go of the program's tables
        gc.collect()


def check_answers(config: dict, mix: dict, seed: int, log: dict) -> tuple:
    """(correct, the numbers compared with their limits): the seeded
    sample of the window's own answers against the plain reference."""
    reference = importlib.import_module(
        "benchmark.reference." + config["reference"])
    k = int(mix["query"]["num"])
    idx, ends = log["sample_idx"], log["sample_ends"]
    blob = log["sample_bytes"].tobytes()
    bodies = [blob[a:b] for a, b in zip(np.r_[0, ends[:-1]], ends)]
    answers = [reference.parse_answer(b) for b in bodies]
    t_ref = time.time()
    numbers = reference.compare(config, seed, log["users"][idx], answers, k)
    limits = config["limits"]
    say(f"reference: {len(idx)} sampled answers compared in "
        f"{time.time() - t_ref:.1f} s")
    # every limit the configuration states; a number the reference did
    # not reach (nothing compared) reads as the judge reads it, 0
    compared = {name: {"value": numbers.get(name, 0.0), "limit": limit}
                for name, limit in limits.items()}
    compared.update(
        malformed={"value": numbers["malformed"], "limit": 0},
        compared={"value": numbers["compared"], "limit": 1})
    return bool(reference.judge(numbers, limits)), compared


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal: dict | None = None) -> tuple:
    """Runs the cell; returns (result object, exit code). ``rehearsal``
    is for the tests alone: a tiny configuration on whatever backend jax
    finds, ``{"config": {...}, "rate": r, "env": {...}}``."""
    found = find_cell(workload)
    rate = float(found["cell"]["rate_qps"])
    if rehearsal is not None:
        found["config"] = {**found["config"], **rehearsal["config"]}
        rate = float(rehearsal["rate"])
    from benchmark import trace_reduce

    session = Session(found, seed, rehearsal)
    try:
        session.start()
        say(f"rate {rate:g} queries/s for {seconds:g} s after "
            f"{found['mix']['warm_s']:g} s untimed")
        win = session.window(rate, seed, seconds, trace)
        # what the device held, read before anything else runs on it
        peak_bytes = session.memory()
        tr = trace_reduce.load(win["trace_dir"]) if trace else None
    finally:
        session.stop()
    ctx = session.context(win)
    summary = session.summary(win, ctx)
    say("window: " + json.dumps(summary))
    device = {**session.dev, "memory_peak_bytes": peak_bytes}
    breakdown = None
    if not trace:
        metrics = {"query_p50_ms": summary["query_p50_ms"],
                   "query_p95_ms": summary["query_p95_ms"],
                   "setup_s": win["setup_s"]}
    else:
        ctx["trace"] = tr
        for row in trace_reduce.describe(tr):
            say(f"trace: {row}")
        busy = trace_reduce.busy_seconds(tr)
        if busy:
            device["busy_s"] = busy
            device["window_s"] = \
                win["trace_window"][1] - win["trace_window"][0]
        elif rehearsal is None:
            raise SystemExit("benchmark: the trace shows no operation on "
                             "the device")
        metrics = {}
        for m in found["bench"]["per_layer"]:
            if workload in m.get("workloads", [workload]):
                value = read_metric(m["name"], ctx)
                if value is not None:
                    metrics[m["name"]] = value
        breakdown = {"device_ops": trace_reduce.top_ops(tr, 10),
                     "idle_gaps": trace_reduce.idle_gaps(tr)[:10]}
    units = {m["name"]: m["unit"] for m in found["bench"]["end_to_end"]
             + found["bench"]["per_layer"]}
    result = {
        "correct": False, "attempted": summary["attempted"],
        "failed": summary["failed"],
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    # correct: the window's own answers against the plain reference, once
    # the peak is read and the program's state is let go
    gc.collect()
    result["correct"], result["compared"] = check_answers(
        found["config"], found["mix"], seed, win["log"])
    for name, c in result["compared"].items():
        cmp = ">=" if name == "compared" else "<="
        print(f"compared: {name} = {c['value']!r} (limit {cmp} "
              f"{c['limit']!r})", file=sys.stderr, flush=True)
    # a deploy thread that would not end dies with the process, at once:
    # the interpreter's own shutdown could wait on it
    return result, (LEFTOVER if session.leftover else 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result, rc = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    print(json.dumps(result), flush=True)
    if rc == LEFTOVER:
        sys.stderr.flush()
        os._exit(0)
    return rc


if __name__ == "__main__":
    sys.exit(main())
