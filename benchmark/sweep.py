"""Finding a cell's rate: one deployment, stages at rising rates.

    python3 -m benchmark.sweep --workload <config>.<traffic> --seed N
        --rates 800,1200,1600 --seconds 15 [--repeat 3] [--trace-rate R]
        [--control-seeds 3] [--out chiprun_out/sweep.json]

Used once, when a cell is defined (and again by a later `benchmark` PR
when an optimisation has moved the knee); the benchmark's own runs never
call it. Every stage is a window of the cell's mix at one rate, driven
exactly as ``benchmark.run`` drives it, on a deployment set up once.
A stage is STEADY when no request failed, no serving program compiled
and the backlog did not grow: the last quarter's median latency is at
most ``GROWTH`` times the first quarter's. The knee is the highest rate
whose stages (``--repeat`` of them) are all steady; the cell's rate is
the mix's ``share_of_knee`` of it, written by hand into the cell's file
with the sweep it came from.

Each stage's answers are held against the plain reference (the numbers
compared are printed: they are what the limits are set from), and
``--control-seeds`` reads the lower-precision control on as many fresh
model seeds, at the cell's own size, without the server.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from benchmark import run

#: the last quarter's median may exceed the first quarter's by this
#: factor before the backlog counts as growing (medians of ~10 ms
#: differ by a few percent from one quarter to the next)
GROWTH = 1.25


def steady(summary: dict) -> bool:
    return (summary["failed"] == 0 and not summary["compiles_in_window"]
            and summary["jax_compiles"] == 0
            and summary["last_quarter_p50_ms"]
            <= GROWTH * summary["first_quarter_p50_ms"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="",
                    help="rising rates; none = the control alone")
    ap.add_argument("--stop-on-unsteady", action="store_true",
                    help="end the sweep at the first stage that is not "
                         "steady (an overloaded server takes minutes to "
                         "drain)")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--trace-rate", type=float, default=None)
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--env", action="append", default=[],
                    help="NAME=VALUE deployment setting for this sweep")
    ap.add_argument("--out", default=None)
    ap.add_argument("--gc-freeze", action="store_true",
                    help="the stall hunt: after the first stage, move "
                         "every live object out of the collector's reach "
                         "(gc.freeze), as a later PR of the program might")
    ap.add_argument("--watchdog", action="store_true",
                    help="the stall hunt: note every thread's stack "
                         "whenever the interpreter is held for 30 ms")
    ap.add_argument("--rehearsal", default=None,
                    help="tests only: a tiny configuration as JSON")
    ap.add_argument("--dump-trace", default=None,
                    help="write the traced stage's plain structure here")
    args = ap.parse_args(argv)
    found = run.find_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",") if r]
    extra = dict(e.split("=", 1) for e in args.env)
    rehearsal = json.loads(args.rehearsal) if args.rehearsal else None
    if rehearsal:
        found["config"] = {**found["config"], **rehearsal["config"]}
    session = run.Session(found, args.seed, rehearsal)
    if extra:
        found["config"] = session.config = {
            **found["config"],
            "deployment_env": {**found["config"]["deployment_env"], **extra}}
    stages = []
    try:
        if rates:
            session.start()
        dog = run.GilWatchdog() if args.watchdog else None
        if dog:
            dog.start()
        n = 0
        for rate in rates:
            if args.stop_on_unsteady and stages and not stages[-1]["steady"]:
                break
            for rep in range(args.repeat):
                if args.stop_on_unsteady and stages \
                        and not stages[-1]["steady"]:
                    break
                n += 1
                traced = args.trace_rate == rate and rep == 0
                win = session.window(rate, args.seed * 1000 + n,
                                     args.seconds, traced)
                ctx = session.context(win)
                summary = session.summary(win, ctx)
                summary.update(rate=rate, rep=rep, steady=steady(summary))
                if traced:
                    from benchmark import trace_reduce

                    tr = trace_reduce.load(win["trace_dir"])
                    ctx["trace"] = tr
                    for row in trace_reduce.describe(tr):
                        run.say(f"trace: {row}")
                    for m in found["bench"]["per_layer"]:
                        summary[m["name"]] = run.read_metric(m["name"], ctx)
                    summary["busy_s"] = trace_reduce.busy_seconds(tr)
                    summary["trace_s"] = \
                        win["trace_window"][1] - win["trace_window"][0]
                    summary["device_ops"] = trace_reduce.top_ops(tr, 10)
                    summary["idle_gaps"] = trace_reduce.idle_gaps(tr)
                    summary["modules"] = sorted({
                        e[0] for p in trace_reduce.device_planes(tr)
                        for ln in p["lines"] if ln["name"] ==
                        trace_reduce.MODULES_LINE for e in ln["events"]})
                    if args.dump_trace:
                        os.makedirs(os.path.dirname(args.dump_trace) or ".",
                                    exist_ok=True)
                        with open(args.dump_trace, "w") as f:
                            json.dump(tr, f)
                correct, compared = run.check_answers(
                    found["config"], found["mix"], args.seed, win["log"])
                summary["correct"] = correct
                summary["compared"] = {k: v["value"]
                                       for k, v in compared.items()}
                if dog:
                    t0 = float(win["log"]["t0_epoch"])
                    w0, w1 = win["epoch_window"]
                    for at, late, stacks in dog.hits:
                        if w0 <= at <= w1:
                            run.say(f"held: at {at - t0:.3f} s for "
                                    f"{late:.1f} ms: {json.dumps(stacks)}")
                run.say("stage: " + json.dumps(summary))
                stages.append(summary)
                if args.gc_freeze and n == 1:
                    import gc

                    gc.collect()
                    gc.freeze()
                    run.say(f"gc.freeze(): {gc.get_freeze_count()} objects")
        if rates:
            session.memory()
    finally:
        session.stop()
    if stages:
        run.say("after undeploy:")
        session.memory()
    controls = []
    if args.control_seeds:
        import importlib

        import numpy as np

        reference = importlib.import_module(
            "benchmark.reference." + found["config"]["reference"])
        k = int(found["mix"]["query"]["num"])
        for s in range(args.control_seeds):
            seed = args.seed + 7919 * (s + 1)
            rows = np.random.default_rng(seed).choice(
                found["config"]["n_users"], 256, replace=False)
            for precision in ("highest", "high", "high_emulated"):
                numbers = reference.control(found["config"], seed, rows, k,
                                            precision)
                numbers.update(seed=seed, precision=precision)
                run.say("control: " + json.dumps(numbers))
                controls.append(numbers)
            # the fault of an answer altered where it is produced: the
            # two best items of every answer swapped
            top_s, top_i = reference.top_k(found["config"], seed, rows, k)
            top_i[:, [0, 1]] = top_i[:, [1, 0]]
            numbers = reference.compare(
                found["config"], seed, rows,
                [(i.tolist(), s.astype(float).tolist())
                 for s, i in zip(top_s, top_i)], k)
            numbers.update(seed=seed, fault="two best items swapped")
            run.say("fault: " + json.dumps(numbers))
            controls.append(numbers)
    by_rate: dict = {}
    for s in stages:
        by_rate.setdefault(s["rate"], []).append(s["steady"])
    steady_rates = [r for r, oks in by_rate.items() if all(oks)]
    knee = max(steady_rates) if steady_rates else None
    run.say(f"knee: {knee} (steady rates {sorted(steady_rates)} of "
            f"{sorted(by_rate)})")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "stages": stages,
                       "controls": controls, "knee": knee}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
