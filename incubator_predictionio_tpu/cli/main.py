"""``pio`` CLI — the full verb set.

Parity: tools/.../console/Console.scala:153-600 subcommand matrix:
version / status / app {new,list,show,delete,data-delete,channel-new,
channel-delete} / accesskey {new,list,delete} / train / eval / deploy /
undeploy / eventserver / adminserver / dashboard / export / import / build /
run / template {get,list}.

Design delta from the reference: no spark-submit process hop
(Runner.runOnSpark, tools/.../Runner.scala:101-213) — train/eval/deploy run
in-process on the TPU host, so ``pio build`` has no sbt step (it validates
engine.json and importability instead).
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import sys
from typing import Any, List, Optional

from incubator_predictionio_tpu import __version__
from incubator_predictionio_tpu.cli import commands
from incubator_predictionio_tpu.cli.commands import CommandError


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="pio",
        description="TPU-native PredictionIO-compatible machine learning server",
    )
    parser.add_argument("--version", action="version",
                        version=f"pio-tpu {__version__}")
    sub = parser.add_subparsers(dest="command")

    sub.add_parser("version", help="show version")
    sub.add_parser("status", help="validate storage + compute configuration")

    # -- app ---------------------------------------------------------------
    app = sub.add_parser("app", help="manage apps").add_subparsers(
        dest="app_command"
    )
    p = app.add_parser("new")
    p.add_argument("name")
    p.add_argument("--id", type=int, default=0)
    p.add_argument("--description")
    p.add_argument("--access-key", default="")
    app.add_parser("list")
    p = app.add_parser("show")
    p.add_argument("name")
    p = app.add_parser("delete")
    p.add_argument("name")
    p.add_argument("-f", "--force", action="store_true")
    p = app.add_parser("data-delete")
    p.add_argument("name")
    p.add_argument("--channel")
    p.add_argument("-f", "--force", action="store_true")
    p = app.add_parser("channel-new")
    p.add_argument("name")
    p.add_argument("channel")
    p = app.add_parser("channel-delete")
    p.add_argument("name")
    p.add_argument("channel")
    p.add_argument("-f", "--force", action="store_true")

    # -- accesskey ---------------------------------------------------------
    ak = sub.add_parser("accesskey", help="manage access keys").add_subparsers(
        dest="accesskey_command"
    )
    p = ak.add_parser("new")
    p.add_argument("app_name")
    p.add_argument("--key", default="")
    p.add_argument("--events", nargs="*", default=[])
    p = ak.add_parser("list")
    p.add_argument("app_name", nargs="?")
    p = ak.add_parser("delete")
    p.add_argument("key")

    # -- engine lifecycle --------------------------------------------------
    for name, help_text in (
        ("build", "validate the engine in the current directory"),
        ("train", "train the engine in the current directory"),
        ("deploy", "deploy the latest trained engine instance"),
    ):
        p = sub.add_parser(name, help=help_text)
        p.add_argument("--variant", default="engine.json")
        if name in ("train", "deploy"):
            p.add_argument(
                "--hosts", default="",
                help="comma-separated pod hosts: launch this command on "
                     "every host with the coordinator env trio set "
                     "(parallel/launcher.py; Runner.scala:101-213 parity)")
        if name == "train":
            p.add_argument("--batch", default="")
            p.add_argument("--skip-sanity-check", action="store_true")
            p.add_argument("--stop-after-read", action="store_true")
            p.add_argument("--stop-after-prepare", action="store_true")
            p.add_argument("--seed", type=int, default=0)
            p.add_argument("--model-parallelism", type=int, default=1)
        if name == "deploy":
            p.add_argument("--ip", default="0.0.0.0")
            p.add_argument("--port", type=int, default=8000)
            p.add_argument("--engine-instance-id")
            p.add_argument("--event-server-ip", default="0.0.0.0")
            p.add_argument("--event-server-port", type=int, default=7070)
            p.add_argument("--accesskey", default=None)
            p.add_argument("--feedback", action="store_true")
            p.add_argument("--server-key", default=None)
            p.add_argument("--log-url", default=None,
                           help="POST query errors to this collector URL")
            p.add_argument("--log-prefix", default="",
                           help="prefix prepended to each shipped log line")

    sub.add_parser("unregister",
                   help="unregister the engine in the current directory")

    p = sub.add_parser("eval", help="run evaluation / hyperparameter tuning")
    p.add_argument("evaluation_class",
                   help="module:attr of the Evaluation object")
    p.add_argument("engine_params_generator_class", nargs="?",
                   help="module:attr of the EngineParamsGenerator")
    p.add_argument("--batch", default="")
    p.add_argument("--output-best", default="best.json")
    p.add_argument("--hosts", default="",
                   help="comma-separated pod hosts (see `pio train --hosts`)")

    p = sub.add_parser("undeploy", help="stop a deployed engine server")
    p.add_argument("--ip", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--server-key", default=None)

    # -- servers -----------------------------------------------------------
    p = sub.add_parser("eventserver", help="start the event server")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=7070)
    p.add_argument("--stats", action="store_true")
    def _positive_int(v: str) -> int:
        n = int(v)
        if n <= 0:
            raise argparse.ArgumentTypeError(
                f"must be a positive integer (got {v})")
        return n

    p.add_argument(
        "--batch-cap", type=_positive_int, default=None, metavar="N",
        help="max events per POST /batch/events.json (default 50 — the "
             "reference's wire contract; raise for columnar bulk loaders)")
    p = sub.add_parser("adminserver", help="start the admin API server")
    p.add_argument("--ip", default="127.0.0.1")
    p.add_argument("--port", type=int, default=7071)
    p = sub.add_parser("dashboard", help="start the evaluation dashboard")
    p.add_argument("--ip", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9000)
    p = sub.add_parser(
        "storageserver",
        help="export this box's storage source to other boxes "
             "(point their PIO_STORAGE_SOURCES_<N>_TYPE=remote at it)")
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=7077)
    p.add_argument("--source", default=None,
                   help="export ONE PIO_STORAGE_SOURCES_<NAME>; default "
                        "routes by repository (metadata/eventdata/"
                        "modeldata each to its configured source)")
    p.add_argument("--auth-key", default=None,
                   help="shared key clients must send (X-Pio-Storage-Key)")

    # -- data --------------------------------------------------------------
    p = sub.add_parser("export",
                       help="export app events to JSON lines or parquet")
    p.add_argument("--appid-or-name", dest="app_name", required=True)
    p.add_argument("--output", required=True)
    p.add_argument("--channel")
    p.add_argument("--format", choices=("json", "parquet"), default="json")
    p = sub.add_parser("import", help="import exported events into an app")
    p.add_argument("--appid-or-name", dest="app_name", required=True)
    p.add_argument("--input", required=True)
    p.add_argument("--channel")
    p.add_argument("--format", choices=("json", "parquet"), default="json")

    # -- misc --------------------------------------------------------------
    p = sub.add_parser("run", help="run an arbitrary main in the engine env")
    p.add_argument("main_class")
    p.add_argument("args", nargs="*")
    tpl = sub.add_parser("template", help="(deprecated)").add_subparsers(
        dest="template_command"
    )
    tpl.add_parser("get")
    tpl.add_parser("list")
    # `pio upgrade` (Console.scala upgrade subcommand → the HBase upgrade
    # tool's role): rewrite event stores in the current on-disk format —
    # drops tombstoned records, adds sidecars to pre-sidecar records
    # (cpplog), VACUUMs the JDBC store (sqlite)
    p = sub.add_parser(
        "upgrade", help="rewrite event stores in the current format")
    p.add_argument("app", nargs="?", default=None,
                   help="app name or id (default: every app)")

    return parser


def _confirm(prompt: str, force: bool) -> bool:
    if force:
        return True
    answer = input(f"{prompt} (YES to confirm): ")
    return answer == "YES"


#: verbs that never need the accelerator. A chip belongs to ONE process
#: at a time: an ingest or metadata process that lazily initializes the
#: device backend takes libtpu's lock, and `pio train` on the same box
#: then fails to load it. Pin these verbs to the CPU platform before any
#: backend can initialize (a config update: the user's JAX_PLATFORMS,
#: if any, names the platform for the device verbs).
_STORAGE_ONLY_VERBS = frozenset({
    "eventserver", "adminserver", "dashboard", "storageserver",
    "app", "accesskey", "export", "import", "upgrade", "unregister",
    "template", "undeploy", "build",
})


def _ensure_accelerator(timeout_s: float) -> None:
    """Fail fast — with an actionable message — when the accelerator
    cannot initialize.

    A chip belongs to one process at a time. On a local chip a second
    process normally fails at once — libtpu names its lock file and the
    process holding it — and that error is surfaced below as is. The
    probe also runs device init on a daemon thread and gives up after
    ``timeout_s`` (PIO_ACCEL_INIT_TIMEOUT_S, default 180), so an init
    that hangs instead of failing reads as a diagnosis, not as a silent
    `pio train`. The CommandError propagates to a normal interpreter
    exit (the blocked daemon thread cannot be cancelled)."""
    import threading

    done = threading.Event()
    err: list = []

    def probe() -> None:
        try:
            import jax

            jax.devices()
        except Exception as e:  # surfaced as the real failure below
            err.append(e)
        finally:
            done.set()

    t = threading.Thread(target=probe, daemon=True, name="pio-accel-probe")
    t.start()
    if not done.wait(timeout_s):
        raise CommandError(
            f"accelerator did not initialize within {timeout_s:.0f}s — a "
            "chip belongs to one process at a time, so this usually means "
            "another process holds it (a deployed engine server, a stuck "
            "run). Stop it (`pio undeploy`, kill the process) and retry, "
            "or raise PIO_ACCEL_INIT_TIMEOUT_S if initialization is "
            "genuinely slow here.")
    if err:
        raise CommandError(f"accelerator initialization failed: {err[0]}")


def _backends_initialized() -> bool:
    """Whether any JAX backend has already been constructed (private-API
    probe, single copy — main() and dispatch() both need it)."""
    try:
        from jax._src import xla_bridge as _xb

        return bool(getattr(_xb, "_backends", None))
    except Exception:
        return False


def _accel_timeout_s() -> float:
    raw = os.environ.get("PIO_ACCEL_INIT_TIMEOUT_S", "180")
    try:
        return float(raw)
    except ValueError:
        print(f"warning: PIO_ACCEL_INIT_TIMEOUT_S={raw!r} is not a "
              "number; using 180", file=sys.stderr)
        return 180.0


def dispatch(args: argparse.Namespace) -> int:  # noqa: C901
    cmd = args.command
    if cmd is None:
        build_parser().print_help()
        return 1
    if cmd == "status":
        # train/eval/deploy run their watchdog AFTER the pod relaunch
        # branch (the launcher must never hold the chip its own workers
        # need) and after jax.distributed joins — see below
        _ensure_accelerator(_accel_timeout_s())
    if cmd in _STORAGE_ONLY_VERBS:
        # PIO_STORAGE_VERB_PLATFORM overrides the cpu pin for users who
        # genuinely want a storage verb on the device (JAX_PLATFORMS
        # cannot express that: it names the device verbs' platform)
        platform = os.environ.get("PIO_STORAGE_VERB_PLATFORM", "cpu")
        try:
            import jax

            if not _backends_initialized():
                jax.config.update("jax_platforms", platform)
        except Exception:
            print("warning: could not pin the storage-only verb to the "
                  f"{platform} platform; this process may claim the "
                  "accelerator", file=sys.stderr)
    if cmd in ("deploy", "eventserver", "adminserver", "dashboard",
               "storageserver"):
        # long-running server verbs emit the per-request JSON span log
        # out of the box (one line per request on stderr, trace-ID
        # correlated; PIO_TRACE_LOG=off disables — docs/observability.md)
        from incubator_predictionio_tpu.obs.trace import enable_span_logging

        enable_span_logging()
    if cmd == "version":
        print(f"pio-tpu {__version__}")
        return 0

    if cmd == "status":
        return 0 if commands.status() else 1

    if cmd == "app":
        ac = args.app_command
        if ac == "new":
            commands.app_new(args.name, args.id, args.description,
                             args.access_key)
        elif ac == "list":
            commands.app_list()
        elif ac == "show":
            commands.app_show(args.name)
        elif ac == "delete":
            if not _confirm(f"Delete app {args.name} and ALL its data?",
                            args.force):
                print("Aborted.")
                return 1
            commands.app_delete(args.name)
        elif ac == "data-delete":
            if not _confirm(f"Delete ALL data of app {args.name}?", args.force):
                print("Aborted.")
                return 1
            commands.app_data_delete(args.name, args.channel)
        elif ac == "channel-new":
            commands.channel_new(args.name, args.channel)
        elif ac == "channel-delete":
            if not _confirm(
                f"Delete channel {args.channel} of app {args.name}?",
                args.force,
            ):
                print("Aborted.")
                return 1
            commands.channel_delete(args.name, args.channel)
        else:
            print("Usage: pio app {new,list,show,delete,data-delete,"
                  "channel-new,channel-delete}")
            return 1
        return 0

    if cmd == "accesskey":
        kc = args.accesskey_command
        if kc == "new":
            commands.accesskey_new(args.app_name, args.key,
                                   tuple(args.events))
        elif kc == "list":
            commands.accesskey_list(args.app_name)
        elif kc == "delete":
            commands.accesskey_delete(args.key)
        else:
            print("Usage: pio accesskey {new,list,delete}")
            return 1
        return 0

    if cmd == "build":
        commands.build(engine_json=args.variant)
        print("No compilation step is needed; your engine is ready to train.")
        return 0

    # pod launch (Runner.runOnSpark parity, Runner.scala:101-213): when
    # --hosts is given and we are NOT already a launched worker, re-run
    # this exact command once per host with the coordinator trio set —
    # each worker then joins the multi-controller runtime via
    # parallel.distributed.ensure_initialized.
    if cmd in ("train", "eval", "deploy") and getattr(args, "hosts", "") \
            and "PIO_PROCESS_ID" not in os.environ:
        from incubator_predictionio_tpu.parallel.launcher import (
            relaunch_over_hosts,
        )

        hosts = [h.strip() for h in args.hosts.split(",") if h.strip()]
        return relaunch_over_hosts(
            hosts, argv=getattr(args, "_invocation_argv", None))

    # a launched worker (or an externally-provisioned pod process) joins
    # the multi-controller runtime before any engine code builds a mesh
    if cmd in ("train", "eval", "deploy"):
        if os.environ.get("PIO_COORDINATOR_ADDRESS"):
            from incubator_predictionio_tpu.parallel.distributed import (
                ensure_initialized,
            )

            ensure_initialized()
        # watchdog AFTER the relaunch branch (the launcher returned above
        # without ever touching the device) and AFTER distributed init
        # (backend construction must follow jax.distributed.initialize)
        _ensure_accelerator(_accel_timeout_s())

    if cmd == "unregister":
        commands.unregister()
        return 0

    if cmd == "train":
        from incubator_predictionio_tpu.core.params import WorkflowParams
        from incubator_predictionio_tpu.workflow import CoreWorkflow

        variant = commands.load_variant(args.variant)
        engine, engine_params = commands.engine_from_variant(variant)
        params = WorkflowParams(
            batch=args.batch,
            skip_sanity_check=args.skip_sanity_check,
            stop_after_read=args.stop_after_read,
            stop_after_prepare=args.stop_after_prepare,
            runtime_conf={
                "seed": str(args.seed),
                "model_parallelism": str(args.model_parallelism),
            },
        )
        instance_id = CoreWorkflow.run_train(
            engine,
            engine_params,
            engine_id=commands.engine_id_for_variant_path(args.variant, variant),
            engine_version=variant.get("version", "NOT_VERSIONED"),
            engine_variant=variant.get("id", "default"),
            engine_factory=variant.get("engineFactory", ""),
            params=params,
        )
        if instance_id:
            print(f"Training completed. Engine instance ID: {instance_id}")
        else:
            print("Training shard completed (pod worker; process 0 "
                  "persists the engine instance).")
        return 0

    if cmd == "eval":
        from incubator_predictionio_tpu.workflow import CoreWorkflow

        evaluation = commands.resolve_engine_factory(args.evaluation_class)
        if args.engine_params_generator_class:
            generator = commands.resolve_engine_factory(
                args.engine_params_generator_class
            )
            params_list = generator.engine_params_list
        else:
            params_list = getattr(evaluation, "engine_params_list", None)
            if not params_list:
                raise CommandError(
                    "Provide an EngineParamsGenerator class or set "
                    "engine_params_list on the Evaluation."
                )
        evaluator = evaluation.evaluator
        if args.output_best and hasattr(evaluator, "output_path"):
            evaluator.output_path = args.output_best
        from incubator_predictionio_tpu.core.params import WorkflowParams

        instance_id, result = CoreWorkflow.run_evaluation(
            evaluation, params_list,
            evaluation_class=args.evaluation_class,
            engine_params_generator_class=(
                args.engine_params_generator_class or ""
            ),
            params=WorkflowParams(batch=args.batch),
        )
        if instance_id:
            print(result.to_one_liner())
            print(f"Evaluation completed. Instance ID: {instance_id}")
        else:
            print("Evaluation shard completed (pod worker; process 0 "
                  "persists the result).")
        return 0

    if cmd == "deploy":
        from incubator_predictionio_tpu.servers.prediction_server import (
            PredictionServer,
            ServerConfig,
        )

        variant = commands.load_variant(args.variant)
        engine, _params = commands.engine_from_variant(variant)
        server = PredictionServer(engine, ServerConfig(
            ip=args.ip,
            port=args.port,
            engine_instance_id=args.engine_instance_id,
            engine_id=commands.engine_id_for_variant_path(args.variant, variant),
            engine_version=variant.get("version", "NOT_VERSIONED"),
            engine_variant=variant.get("id", "default"),
            event_server_ip=args.event_server_ip,
            event_server_port=args.event_server_port,
            access_key=args.accesskey,
            feedback=args.feedback,
            server_key=args.server_key,
            log_url=args.log_url,
            log_prefix=args.log_prefix,
        ))
        print(f"Deploying on http://{args.ip}:{args.port} ...")
        try:
            asyncio.run(server.serve_forever())
        except asyncio.CancelledError:
            pass  # POST /stop (`pio undeploy`) closed the listener
        return 0

    if cmd == "undeploy":
        from incubator_predictionio_tpu.servers.prediction_server import undeploy

        if undeploy(args.ip, args.port, args.server_key):
            print("Undeployed.")
            return 0
        print("Nothing at the given address responded to /stop.")
        return 1

    if cmd == "eventserver":
        from incubator_predictionio_tpu.servers.event_server import (
            EventServer,
            EventServerConfig,
        )

        conf_kw = {}
        if getattr(args, "batch_cap", None) is not None:
            conf_kw["max_batch"] = args.batch_cap
        server = EventServer(EventServerConfig(
            ip=args.ip, port=args.port, stats=args.stats, **conf_kw,
        ))
        print(f"Event Server running on http://{args.ip}:{args.port}")
        asyncio.run(server.serve_forever())
        return 0

    if cmd == "adminserver":
        from incubator_predictionio_tpu.servers.admin import AdminServer

        server = AdminServer(args.ip, args.port)
        print(f"Admin API running on http://{args.ip}:{args.port}")
        asyncio.run(server.serve_forever())
        return 0

    if cmd == "dashboard":
        from incubator_predictionio_tpu.servers.dashboard import DashboardServer

        server = DashboardServer(args.ip, args.port)
        print(f"Dashboard running on http://{args.ip}:{args.port}")
        asyncio.run(server.serve_forever())
        return 0

    if cmd == "storageserver":
        from incubator_predictionio_tpu.data.storage.server import (
            StorageServer,
        )

        server = StorageServer.from_env(
            source=args.source, host=args.ip, port=args.port,
            auth_key=args.auth_key)

        def announce(port: int) -> None:
            # announced AFTER the bind with the KERNEL-assigned port:
            # `--port 0` (ephemeral bind) makes parents stop racing the
            # box for a pre-picked "free" port — they parse this line
            print(f"Storage Server running on http://{args.ip}:{port}",
                  flush=True)

        asyncio.run(server.serve_forever(on_started=announce))
        return 0

    if cmd == "export":
        commands.export_events(args.app_name, args.output, args.channel,
                               format=args.format)
        return 0

    if cmd == "import":
        commands.import_events(args.app_name, args.input, args.channel,
                               format=args.format)
        return 0

    if cmd == "run":
        target = commands.resolve_engine_factory(args.main_class)
        result = target(*args.args) if callable(target) else None
        if result is not None:
            print(result)
        return 0

    if cmd == "template":
        print("The template command is deprecated; browse the template "
              "gallery instead (reference: commands/Template.scala:38-83).")
        return 0

    if cmd == "upgrade":
        results = commands.upgrade(args.app)
        if not results:
            print("Nothing to upgrade: the configured event backend has "
                  "no store-level migration/compaction (memory backend), "
                  "or no apps exist.")
            return 0
        for r in results:
            saved = r["bytes_before"] - r["bytes_after"]
            print(f"  app {r['app']} channel {r['channel']}: "
                  f"{r['events']} live events rewritten, "
                  f"{r['bytes_before']} -> {r['bytes_after']} bytes "
                  f"({saved:+d} reclaimed)")
        print("Upgrade complete: stores rewritten in the current format.")
        return 0

    print(f"Unknown command {cmd!r}")
    return 1


def main(argv: Optional[List[str]] = None) -> int:
    from incubator_predictionio_tpu.utils.lease import install_sigterm_exit

    # device verbs may hold the chip: SIGTERM exits via normal
    # interpreter shutdown, releasing it (utils/lease.py)
    install_sigterm_exit()
    args = build_parser().parse_args(argv)
    # the true invocation argv, for pod relaunch (programmatic main(argv)
    # must not fall back to the host process's sys.argv — e.g. pytest's)
    args._invocation_argv = list(argv) if argv is not None else sys.argv[1:]
    # persistent XLA cache: every pio process after the first skips the
    # multi-second compile (the TPU analogue of the reference's JVM/Spark
    # startup cost per spark-submit)
    from incubator_predictionio_tpu.utils.compile_cache import enable
    enable()
    try:
        return dispatch(args)
    except CommandError as e:
        print(f"Error: {e}", file=sys.stderr)
        return 1
    finally:
        # a server verb's last span lines (its /stop among them) are
        # written before the verb returns: whoever called it may close
        # standard error next
        from incubator_predictionio_tpu.obs.trace import flush_span_log

        flush_span_log()


if __name__ == "__main__":
    sys.exit(main())
