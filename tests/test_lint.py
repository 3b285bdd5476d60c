"""pio-lint: per-rule positive/negative fixtures + the repo-wide gate.

Each rule gets a seeded violation (must be detected) and a hazard-free
twin (must stay silent), so a rule that goes blind or trigger-happy
fails here before it rots. The repo-wide test shells out exactly the
way CI and scripts/lint.sh do and is the tier-1 guarantee that the
tree stays clean modulo the checked-in baseline.
"""

import subprocess
import sys
import time
from pathlib import Path

import pytest

from incubator_predictionio_tpu.analysis import (
    ALL_RULES,
    RULES_BY_NAME,
    apply_baseline,
    lint_paths,
    repo_root,
    write_baseline,
)
from incubator_predictionio_tpu.analysis.engine import load_baseline

# (bad source that MUST trigger the rule, good twin that MUST NOT)
FIXTURES = {
    "host-sync": (
        """
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def step(x):
    host = np.asarray(x)
    jax.device_get(x)
    x.block_until_ready()
    return host

@jax.jit
def train_sweeps(state):
    # the per-sweep convergence-check anti-pattern: float() on a traced
    # value forces a device round trip (or TracerError) EVERY sweep
    for _ in range(10):
        state = state * 0.5
        if float(jnp.linalg.norm(state)) < 1e-3:
            break
    return state
""",
        """
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def step(x):
    return x + 1

def fetch(x):
    return np.asarray(jax.device_get(x))

@jax.jit
def sweep_chunk(state):
    # the early-stop probe pattern (ops/retrain.py): the delta is
    # computed IN-trace and returned; the host fetches it outside
    state = state * 0.5
    return state, jnp.linalg.norm(state)

def train(state, tol, budget=10):
    done = 0
    while done < budget:
        state, delta = sweep_chunk(state)
        done += 2
        if float(delta) < tol:  # host sync at the probe boundary only
            break
    return state
""",
    ),
    "neg-gather": (
        """
import jax.numpy as jnp

def warm(prev, row_ids):
    return prev[row_ids]
""",
        """
import jax.numpy as jnp

def warm(prev, row_ids):
    safe_ids = jnp.maximum(row_ids, 0)
    x0 = prev[safe_ids]
    return jnp.where(row_ids[:, None] >= 0, x0, 0.0)
""",
    ),
    "probe-arity": (
        """
import jax

def solve(a: jax.Array, x0: "Optional[jax.Array]" = None,
          yty: "Optional[jax.Array]" = None) -> jax.Array:
    out = a if x0 is None else a + x0
    return out if yty is None else out + yty

def solve_kernel_available():
    return bool(solve(jax.numpy.zeros((2,)), x0=jax.numpy.zeros((2,))))
""",
        """
import jax

def solve(a: jax.Array, x0: "Optional[jax.Array]" = None,
          yty: "Optional[jax.Array]" = None) -> jax.Array:
    out = a if x0 is None else a + x0
    return out if yty is None else out + yty

def solve_kernel_available():
    return bool(solve(jax.numpy.zeros((2,)), x0=jax.numpy.zeros((2,)),
                      yty=jax.numpy.zeros((2,))))
""",
    ),
    "tracer-branch": (
        """
import jax
import jax.numpy as jnp

@jax.jit
def clip(x):
    if jnp.any(x < 0):
        return jnp.zeros_like(x)
    return x
""",
        """
import functools
import jax
import jax.numpy as jnp

@functools.partial(jax.jit, static_argnames=("training",))
def clip(x, training):
    if training:
        return jnp.where(x < 0, 0.0, x)
    if x is None:
        return x
    return x
""",
    ),
    "env-import": (
        """
import os

CHUNK = int(os.environ.get("PIO_CHUNK", "64"))
""",
        """
import os

def chunk_default():
    return int(os.environ.get("PIO_CHUNK", "64"))
""",
    ),
    "f64": (
        """
import jax.numpy as jnp

def histogram(x):
    return jnp.zeros((4,), jnp.float64)
""",
        """
import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

def histogram(x):
    return jnp.zeros((4,), jnp.float64)
""",
    ),
    "wallclock": (
        """
import time
import jax

@jax.jit
def step(x):
    return x * time.time()
""",
        """
import time
import jax

@jax.jit
def step(x):
    return x + 1

def timed_step(x):
    t0 = time.time()
    return step(x), time.time() - t0
""",
    ),
    "lock-native-scan": (
        """
class Events:
    def scan(self, h):
        with self.client.lock:
            raw = self.count(h)
            inter, times = self._scan_native(h, raw)
        return inter
""",
        """
class Events:
    def scan(self, h):
        with self.client.lock:
            raw = self.count(h)
            pin = self.client.pin(h)
        try:
            inter, times = self._scan_native(h, raw)
        finally:
            self.client.unpin(pin)
        return inter

    def helper(self, h):
        with self.client.lock:
            def deferred():
                return self._scan_native(h, 0)
            return deferred
""",
    ),
    "metric-in-trace": (
        """
import jax
from incubator_predictionio_tpu.obs import metrics

QUERIES = metrics.REGISTRY.counter("q_total", "queries")
LAT = metrics.REGISTRY.histogram("q_seconds", "latency")

@jax.jit
def step(x):
    QUERIES.inc()
    LAT.observe(0.1)
    return x + 1
""",
        """
import jax
from incubator_predictionio_tpu.obs import metrics

QUERIES = metrics.REGISTRY.counter("q_total", "queries")

@jax.jit
def step(x, ids):
    return x.at[ids].set(0.0)

def serve(x, ids):
    out = step(x, ids)
    QUERIES.inc()
    return out
""",
    ),
    "host-gather-in-mesh": (
        """
import numpy as np
import jax

def train_loop(mesh, step, xs):
    with mesh:
        out = step(xs)
        host = np.asarray(out)
        ids = out.tolist()
        return jax.device_get(host), ids
""",
        """
import numpy as np
import jax

def train_loop(mesh, step, xs):
    with mesh:
        out = step(xs)

    def fetch(v):
        # a function DEFINED under a mesh elsewhere is not a gather;
        # shard_map-traced bodies are host-sync's jurisdiction
        return np.asarray(v)

    # the sanctioned pattern: one fetch after the mesh context closes
    return fetch(out)
""",
    ),
    "blocking-profiler": (
        """
import jax

class Algo:
    def _score(self, model, query):
        out = model.score(query)
        jax.block_until_ready(out)
        return out

    def predict(self, model, query):
        return self._score(model, query)
""",
        """
import jax
from incubator_predictionio_tpu.obs import profile

class Algo:
    def train(self, ctx, pd):
        # training may block: it is not the serving hot path
        out = pd.run()
        jax.block_until_ready(out)
        return out

    def predict(self, model, query):
        # the sanctioned pattern: env-gated attribution via obs/profile
        t0 = profile.t0()
        out = model.score(query)
        profile.record(t0, "serve", "score", 0.0, out)
        return out
""",
    ),
    "serve-blocking-io": (
        """
from incubator_predictionio_tpu.data.store import EventStore

class Algo:
    def _recent(self, user):
        return list(EventStore.find_by_entity(
            app_name="app", entity_type="user", entity_id=user))

    def predict(self, model, query):
        return self._recent(query.user)
""",
        """
from incubator_predictionio_tpu.data.store import EventStore

class Algo:
    def train(self, ctx, pd):
        # train-time reads are not the serving hot path
        return list(EventStore.find(app_name="app"))

    def predict(self, model, query):
        # serving reads go through the TTL micro-cache's public API;
        # the cache-miss loader lives outside predict's reach
        return self._cache.get_or_load(query.user, _load_recent)
""",
    ),
    "server-state": (
        """
class Handler:
    async def handle(self, request):
        self.count += 1
        self.seen.append(request)
        return self.count
""",
        """
class Handler:
    async def handle(self, request):
        with self._lock:
            self.count += 1
            self.seen.append(request)
        local = 1
        local += 1
        return self.count
""",
    ),
    "unbatched-dispatch": (
        """
from incubator_predictionio_tpu.ops.topk import score_and_top_k

class Server:
    async def handle_query(self, request):
        # direct device dispatch from a request handler: no queue
        # coalescing, no shed policy
        packed = score_and_top_k(self.user_vec, self.item_factors, 10)
        preds = self.algo.predict(self.model, request)
        return packed, preds
""",
        """
import asyncio

class Server:
    async def handle_query(self, request):
        # the sanctioned seam: enqueue, let the scheduler coalesce the
        # in-flight queries into one fused dispatch
        return await self.batcher.submit(
            request.body, loop=asyncio.get_running_loop())
""",
    ),
    "exhaustive-scan": (
        """
import jax
from incubator_predictionio_tpu.ops.topk import (
    sharded_top_k,
    top_k_with_exclusions,
)

class Server:
    async def handle_query(self, request):
        # full-table scoring below the MIPS auto-router: even with a
        # registered two-stage index the query pays the linear scan
        scores = self.item_factors @ self.user_vec
        top = jax.lax.top_k(scores, 10)
        packed = sharded_top_k(self.user_vec, self.item_factors, 10)
        return top_k_with_exclusions(scores, 10), packed, top
""",
        """
from incubator_predictionio_tpu.ops.topk import score_and_top_k

class Server:
    async def handle_query(self, request):
        # the sanctioned entry: the auto-router serves two-stage when
        # an index is registered and falls back to exhaustive itself
        return score_and_top_k(self.user_vec, self.item_factors, 10)
""",
    ),
    "unbounded-retry": (
        """
import time

def post_event(conn, body):
    while True:
        try:
            return conn.post(body)
        except ConnectionError:
            # fixed delay, no deadline: every client re-offers the
            # same load in lockstep, forever
            time.sleep(1.0)
""",
        """
import time
from incubator_predictionio_tpu.utils.http import (
    RetryableError,
    RetryPolicy,
)

_POLICY = RetryPolicy(attempts=3, deadline_s=10.0)

def post_event(conn, body):
    def attempt():
        try:
            return conn.post(body)
        except ConnectionError as e:
            raise RetryableError(e) from e
    return _POLICY.call(attempt)

def poll_until_ready(probe, budget_s=10.0):
    # a sleep with a COMPUTED delay in a loop that swallows nothing is
    # a poll, not a retry loop; and backoff expressions stay silent
    delay = 0.05
    for _ in range(int(budget_s / delay)):
        if probe():
            return True
        time.sleep(delay)
    return False
""",
    ),
    "unaudited-actuation": (
        """
class FreshnessController:
    def evaluate_once(self):
        # actuation OUTSIDE the decision-record emitter: the fleet
        # mutates with no audit-ring entry and no trace context
        if self.breached():
            self._retrain_fn()
            self._reload_fn()

    def panic_reload(self, fd):
        fd.rolling_reload(timeout=30)
""",
        """
class FreshnessController:
    def evaluate_once(self):
        if self.breached():
            self._actuate(self.new_decision())

    def _actuate(self, decision):
        # THE emitter: trace context + outcome into the audit ring
        self._retrain_fn()
        self._reload_fn()
        decision["outcome"] = {"actuated": True}


def workflow_retrain_fn(engine, engine_params):
    # actuator FACTORY (*_fn): builds the callable _actuate invokes
    def retrain():
        from incubator_predictionio_tpu.workflow.workflow import (
            CoreWorkflow,
        )

        return CoreWorkflow.run_train(engine, engine_params)

    return retrain
""",
    ),
    "unaudited-knob-write": (
        """
import os


def emergency_widen(scheduler):
    # knob writes OUTSIDE the audited seam: serving behavior mutates
    # with no knob.decision record and nothing to roll back to
    os.environ["PIO_SERVE_MIPS_NPROBE"] = "4096"
    os.environ.setdefault("PIO_SERVE_MAX_WAIT_MS", "1000")
    os.putenv("PIO_SERVE_SHED", "0")
    scheduler.cap = 4096
    scheduler.max_batch = 4096
""",
        """
import os


class KnobController:
    def _apply(self, decision, vector):
        # THE audited seam: trace context + ring entry wrap the write
        for env, v in sorted(vector.items()):
            os.environ[env] = str(v)


def post_knobs(request, batcher):
    # the /knobs route handlers share the sanction by name
    os.environ["PIO_SERVE_MIPS_NPROBE"] = "128"
    batcher.apply_knobs()


def local_knobs_fn():
    # actuator FACTORY (*_fn): builds the callable _apply invokes
    def apply(vector):
        os.environ["PIO_SERVE_MAX_BATCH"] = "512"
        return {"local": True}

    return apply


class Batcher:
    def apply_knobs(self):
        # the scheduler re-reading its OWN fields on self is the
        # refresh seam, not a bypass
        self.cap = 512
        self.max_batch = self.cap
""",
    ),
    "recorder-in-serve-path": (
        """
from incubator_predictionio_tpu.obs import recorder as obs_recorder

class Server:
    def _freeze(self):
        # registry walk + bundle write inline with the dispatch: the
        # incident stalls the very queries it is diagnosing
        obs_recorder.get_recorder().sample_now()
        cap = obs_recorder.get_capture()
        cap.capture_now("serve_p99")

    def _handle_batch(self, bodies):
        out = [self.score(b) for b in bodies]
        self._freeze()
        return out
""",
        """
from incubator_predictionio_tpu.obs import recorder as obs_recorder

class Server:
    def __init__(self):
        # registering a state provider is not a snapshot — the
        # recorder's OWN thread calls it later
        obs_recorder.register_state_provider(
            "server", lambda: {"ok": True})

    def _handle_batch(self, bodies):
        out = [self.score(b) for b in bodies]
        if self.overloaded():
            # the sanctioned serve-path hook: non-blocking enqueue
            self._capture.trigger("serve_p99")
        return out

    def admin_dump(self, request):
        # admin/debug handlers are not the serving hot path
        return obs_recorder.get_recorder().dump()
""",
    ),
    "metric-label-cardinality": (
        """
from incubator_predictionio_tpu.obs import metrics

REQS = metrics.REGISTRY.counter("t_total", "x", labels=("who", "why"))

def handle(request, user_id):
    # every distinct user/path/exception mints a new time series
    REQS.labels(who=user_id, why=request.path).inc()
    REQS.labels(who=f"user-{user_id}", why="x").inc()
    try:
        run(request)
    except Exception as e:
        REQS.labels(who="x", why=str(e)).inc()
""",
        """
from incubator_predictionio_tpu.obs import metrics

REQS = metrics.REGISTRY.counter("t_total", "x", labels=("route", "status"))

def handle(request, route_label, response):
    # bounded sets: the route PATTERN, the status code, enum names
    REQS.labels(route=route_label, status=str(response.status)).inc()
    REQS.labels(route="/events.json", status="201").inc()
    for phase, secs in timings.items():
        PHASES.labels(phase=phase).set(secs)
""",
    ),
    "unscoped-tenant-metric": (
        """
from incubator_predictionio_tpu.obs import metrics as obs_metrics
from incubator_predictionio_tpu.serving import tenancy

LAT = obs_metrics.REGISTRY.histogram(
    "pio_query_latency_seconds", "per-query wall", labels=("tenant",))
SHED = obs_metrics.REGISTRY.counter(
    "pio_serve_shed_total", "sheds", labels=("tenant", "reason"))


def book(dt, tenant):
    LAT.labels().observe(dt)                       # no tenant label
    SHED.labels(tenant=tenant, reason="quota").inc()   # raw wire value
""",
        """
from incubator_predictionio_tpu.obs import metrics as obs_metrics
from incubator_predictionio_tpu.serving import tenancy

LAT = obs_metrics.REGISTRY.histogram(
    "pio_query_latency_seconds", "per-query wall", labels=("tenant",))
SHED = obs_metrics.REGISTRY.counter(
    "pio_serve_shed_total", "sheds", labels=("tenant", "reason"))


def book(dt, tenant):
    reg = tenancy.get_registry()
    LAT.labels(tenant=reg.label(tenant)).observe(dt)
    SHED.labels(tenant=reg.label(tenant), reason="quota").inc()
""",
    ),
    "unguarded-shared-state": (
        """
import threading


class Poller:
    def __init__(self):
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.count = 0
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.is_set():
            self.count += 1      # poller write, no lock

    def stats(self):
        with self._lock:         # scrape read, under the lock
            return {"count": self.count}
""",
        """
import threading


class Poller:
    def __init__(self):
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.count = 0
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.is_set():
            with self._lock:
                self.count += 1

    def stats(self):
        with self._lock:
            return {"count": self.count}
""",
    ),
    "thread-lifecycle": (
        """
import threading


def kick(fn):
    threading.Thread(target=fn).start()
""",
        """
import threading


def kick(fn):
    t = threading.Thread(target=fn, daemon=True)
    t.start()
    return t
""",
    ),
}


def _lint_source(tmp_path: Path, source: str, rule: str, name="fixture.py"):
    # server-state / unbatched-dispatch / exhaustive-scan only apply
    # under servers/ (exhaustive-scan also covers serving/);
    # unaudited-actuation only applies to obs/controller.py itself
    if rule == "unaudited-actuation":
        target_dir = tmp_path / "obs"
        name = "controller.py"
    elif rule in ("server-state", "unbatched-dispatch",
                  "exhaustive-scan"):
        target_dir = tmp_path / "servers"
    elif rule == "unscoped-tenant-metric":
        target_dir = tmp_path / "serving"
    else:
        target_dir = tmp_path
    target_dir.mkdir(exist_ok=True)
    target = target_dir / name
    target.write_text(source, encoding="utf-8")
    return lint_paths([target], [RULES_BY_NAME[rule]])


def test_registry_has_at_least_eight_rules():
    assert len(ALL_RULES) >= 10
    assert set(FIXTURES) == set(RULES_BY_NAME), (
        "every rule needs a positive/negative fixture pair")


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_seeded_violation_is_detected(tmp_path, rule):
    findings = _lint_source(tmp_path, FIXTURES[rule][0], rule)
    assert findings, f"rule {rule} missed its seeded violation"
    assert all(f.rule == rule for f in findings)


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_hazard_free_twin_is_silent(tmp_path, rule):
    findings = _lint_source(tmp_path, FIXTURES[rule][1], rule)
    assert not findings, (
        f"rule {rule} false-positived: {[f.format() for f in findings]}")


def test_inline_suppression(tmp_path):
    src = FIXTURES["env-import"][0].replace(
        'CHUNK = int(os.environ.get("PIO_CHUNK", "64"))',
        'CHUNK = int(os.environ.get("PIO_CHUNK", "64"))'
        '  # pio-lint: disable=env-import')
    assert not _lint_source(tmp_path, src, "env-import")


def test_comment_line_above_suppression(tmp_path):
    src = FIXTURES["env-import"][0].replace(
        'CHUNK = int(os.environ.get("PIO_CHUNK", "64"))',
        '# pio-lint: disable=env-import\n'
        'CHUNK = int(os.environ.get("PIO_CHUNK", "64"))')
    assert not _lint_source(tmp_path, src, "env-import")


def test_file_level_suppression(tmp_path):
    src = "# pio-lint: disable-file=env-import\n" + FIXTURES["env-import"][0]
    assert not _lint_source(tmp_path, src, "env-import")


def test_docstring_directive_does_not_suppress(tmp_path):
    """Documenting the suppression syntax in a docstring must not
    disable anything — only real COMMENT tokens count."""
    src = '''
"""Module doc: use `# pio-lint: disable-file=env-import` to suppress.

# pio-lint: disable=env-import
"""
import os

CHUNK = int(os.environ.get("PIO_CHUNK", "64"))
'''
    assert _lint_source(tmp_path, src, "env-import")


def test_clamp_in_other_function_does_not_exempt(tmp_path):
    """A clamp assignment in one function must not blind neg-gather to
    a same-named raw gather in another function."""
    src = """
import jax.numpy as jnp

def safe(prev, ids):
    safe_ids = jnp.maximum(ids, 0)
    return prev[safe_ids]

def unsafe(prev, safe_ids):
    return prev[safe_ids]
"""
    findings = _lint_source(tmp_path, src, "neg-gather")
    assert len(findings) == 1 and "'safe_ids'" in findings[0].message


def test_partial_bound_kernel_body_is_traced(tmp_path):
    """A kernel bound through an intermediate (`body = partial(k, ...)`
    then `pallas_call(body)`) is still traced, with partial keywords
    treated as statics — the repo's main ALS kernels use this shape."""
    src = """
import functools
import time
from jax.experimental import pallas as pl

def _kernel(x_ref, o_ref, *, precise):
    if precise:
        o_ref[...] = x_ref[...] * time.time()

def launch(x, precise):
    body = functools.partial(_kernel, precise=precise)
    kfn = body
    return pl.pallas_call(kfn, out_shape=None)(x)
"""
    findings = _lint_source(tmp_path, src, "wallclock")
    assert len(findings) == 1 and "time.time" in findings[0].message
    # and `precise` (partial-bound) must be static for tracer-branch
    assert not _lint_source(tmp_path, src, "tracer-branch")


def test_metric_set_flagged_but_chained_at_set_exempt(tmp_path):
    """`g.set(...)`-style metric writes in a trace are flagged while the
    JAX functional-update idiom — including chained `.at[].set()` — is
    not."""
    src = """
import jax

@jax.jit
def step(x, ids, g):
    y = x.at[ids].set(0.0).at[0].set(1.0)
    g.set(1.0)
    return y
"""
    findings = _lint_source(tmp_path, src, "metric-in-trace")
    assert len(findings) == 1
    assert ".set() metric mutation" in findings[0].message


def test_write_baseline_preserves_justifications(tmp_path):
    findings = _lint_source(tmp_path, FIXTURES["env-import"][0],
                            "env-import")
    baseline = tmp_path / "baseline.json"
    write_baseline(baseline, findings)
    entries = load_baseline(baseline)
    entries[0]["justification"] = "hand-written reason"
    baseline.write_text(
        __import__("json").dumps({"entries": entries}), encoding="utf-8")
    write_baseline(baseline, findings)  # regenerate over the curated file
    assert load_baseline(baseline)[0]["justification"] == \
        "hand-written reason"


def test_nested_async_def_reported_once(tmp_path):
    src = """
class Handler:
    async def handle(self, request):
        async def inner():
            self.count += 1
        await inner()
"""
    findings = _lint_source(tmp_path, src, "server-state")
    assert len(findings) == 1, [f.format() for f in findings]
    assert "'inner'" in findings[0].message


def test_write_baseline_select_keeps_out_of_scope_entries(tmp_path):
    """--write-baseline under --select must not wipe entries whose rule
    the filtered run could not even see."""
    import json
    target = tmp_path / "code.py"
    target.write_text(FIXTURES["env-import"][0] + FIXTURES["wallclock"][0],
                      encoding="utf-8")
    bl = tmp_path / "bl.json"
    run = [sys.executable, "-m", "incubator_predictionio_tpu.analysis",
           str(target), "--write-baseline", str(bl)]
    subprocess.run(run, cwd=repo_root(), check=True, capture_output=True,
                   timeout=120)
    rules_before = sorted(e["rule"]
                          for e in json.loads(bl.read_text())["entries"])
    assert rules_before == ["env-import", "wallclock"]
    subprocess.run(run + ["--select", "env-import"], cwd=repo_root(),
                   check=True, capture_output=True, timeout=120)
    rules_after = sorted(e["rule"]
                         for e in json.loads(bl.read_text())["entries"])
    assert rules_after == rules_before


def test_baseline_roundtrip(tmp_path):
    findings = _lint_source(tmp_path, FIXTURES["env-import"][0],
                            "env-import")
    baseline = tmp_path / "baseline.json"
    write_baseline(baseline, findings)
    entries = load_baseline(baseline)
    unmatched, stale = apply_baseline(findings, entries)
    assert not unmatched and not stale
    # fixing the violation leaves the entry stale, never hidden
    unmatched, stale = apply_baseline([], entries)
    assert not unmatched and len(stale) == len(entries)


def test_baseline_entries_all_have_real_justifications():
    entries = load_baseline(
        repo_root() / "incubator_predictionio_tpu/analysis/baseline.json")
    assert entries, "checked-in baseline should record the deliberate "\
        "exceptions (read-once env knobs)"
    for e in entries:
        assert e.get("justification", "").strip(), e
        assert "TODO" not in e["justification"], e


def test_repo_is_clean_modulo_baseline():
    """THE CI gate: the tree must lint clean the way scripts/lint.sh and
    the acceptance criteria run it."""
    proc = subprocess.run(
        [sys.executable, "-m", "incubator_predictionio_tpu.analysis",
         "--baseline"],
        cwd=repo_root(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (
        f"pio-lint found new violations:\n{proc.stdout}\n{proc.stderr}")
    assert "stale baseline entry" not in proc.stderr, proc.stderr


def test_cli_list_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "incubator_predictionio_tpu.analysis",
         "--list-rules"],
        cwd=repo_root(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    for rule in RULES_BY_NAME:
        assert rule in proc.stdout


def test_cli_exit_one_on_findings(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(FIXTURES["env-import"][0], encoding="utf-8")
    proc = subprocess.run(
        [sys.executable, "-m", "incubator_predictionio_tpu.analysis",
         str(bad)],
        cwd=repo_root(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "[env-import]" in proc.stdout

# ---------------------------------------------------------------------------
# whole-program concurrency pass (unguarded-shared-state / thread-lifecycle)
# ---------------------------------------------------------------------------

def test_timer_spawn_without_daemon_flagged(tmp_path):
    src = """
import threading


class Refresher:
    def kick(self):
        t = threading.Timer(5.0, self._tick)
        t.start()

    def _tick(self):
        pass
"""
    findings = _lint_source(tmp_path, src, "thread-lifecycle")
    assert len(findings) == 1 and "timer" in findings[0].message.lower()


def test_timer_daemonized_on_local_is_silent(tmp_path):
    src = """
import threading


class Refresher:
    def kick(self):
        t = threading.Timer(5.0, self._tick)
        t.daemon = True
        t.start()

    def _tick(self):
        pass
"""
    assert not _lint_source(tmp_path, src, "thread-lifecycle")


def test_executor_without_shutdown_flagged_with_block_silent(tmp_path):
    bad = """
from concurrent.futures import ThreadPoolExecutor


def fan_out(items, fn):
    ex = ThreadPoolExecutor(max_workers=4)
    return [ex.submit(fn, it) for it in items]
"""
    good = """
from concurrent.futures import ThreadPoolExecutor


def fan_out(items, fn):
    with ThreadPoolExecutor(max_workers=4) as ex:
        return [f.result() for f in [ex.submit(fn, it) for it in items]]
"""
    findings = _lint_source(tmp_path, bad, "thread-lifecycle")
    assert len(findings) == 1 and "executor" in findings[0].message.lower()
    assert not _lint_source(tmp_path, good, "thread-lifecycle")


def test_nested_and_aliased_lock_regions_count_as_guarded(tmp_path):
    src = """
import threading


class Ledger:
    def __init__(self):
        self._lock = threading.Lock()
        self._io_lock = threading.Lock()
        self.total = 0
        t = threading.Thread(target=self._run, daemon=True)
        t.start()

    def _run(self):
        lk = self._lock
        with lk:
            with self._io_lock:
                self.total += 1

    def read(self):
        with self._lock:
            return self.total
"""
    assert not _lint_source(tmp_path, src, "unguarded-shared-state")


def test_publish_only_annotation_honored_for_single_writer(tmp_path):
    src = """
import threading


class Sampler:
    def __init__(self):
        self.snapshot = ()  # pio-lint: publish-only
        t = threading.Thread(target=self._run, daemon=True)
        t.start()

    def _run(self):
        while True:
            self.snapshot = (1, 2, 3)

    def read(self):
        return self.snapshot
"""
    assert not _lint_source(tmp_path, src, "unguarded-shared-state")


def test_publish_only_annotation_verified_multi_writer_flagged(tmp_path):
    src = """
import threading


class Sampler:
    def __init__(self):
        self.snapshot = ()  # pio-lint: publish-only
        t = threading.Thread(target=self._run, daemon=True)
        t.start()

    def _run(self):
        while True:
            self.snapshot = (1, 2, 3)

    def reset(self):
        self.snapshot = ()

    def read(self):
        return self.snapshot
"""
    findings = _lint_source(tmp_path, src, "unguarded-shared-state")
    assert len(findings) == 1
    assert "publish-only" in findings[0].message


def test_guarded_by_annotation_honored(tmp_path):
    src = """
import threading


class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        # pio-lint: guarded-by(_lock)
        self.count = 0
        t = threading.Thread(target=self._run, daemon=True)
        t.start()

    def _run(self):
        with self._lock:
            self.count += 1

    def read(self):
        return self.count
"""
    assert not _lint_source(tmp_path, src, "unguarded-shared-state")


def test_guarded_by_annotation_verified_bare_write_flagged(tmp_path):
    src = """
import threading


class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        # pio-lint: guarded-by(_lock)
        self.count = 0
        t = threading.Thread(target=self._run, daemon=True)
        t.start()

    def _run(self):
        self.count += 1

    def read(self):
        with self._lock:
            return self.count
"""
    findings = _lint_source(tmp_path, src, "unguarded-shared-state")
    assert len(findings) == 1
    assert "guarded-by" in findings[0].message


def test_queue_handoff_is_sanctioned(tmp_path):
    src = """
import queue
import threading


class Pipeline:
    def __init__(self):
        self._q = queue.Queue()
        t = threading.Thread(target=self._run, daemon=True)
        t.start()

    def _run(self):
        while True:
            self._q.put(1)

    def drain(self):
        return self._q.get()
"""
    assert not _lint_source(tmp_path, src, "unguarded-shared-state")


def test_cross_method_reachability_through_call_graph(tmp_path):
    """A write two hops away from the thread entry (entry -> helper) is
    still on a thread-side path and must be flagged."""
    src = """
import threading


class Meter:
    def __init__(self):
        self._lock = threading.Lock()
        self.total = 0
        t = threading.Thread(target=self._loop, daemon=True)
        t.start()

    def _loop(self):
        while True:
            self._step()

    def _step(self):
        self.total += 1

    def report(self):
        with self._lock:
            return self.total
"""
    findings = _lint_source(tmp_path, src, "unguarded-shared-state")
    assert len(findings) == 1
    assert findings[0].line and "total" in findings[0].message


def test_caller_held_lock_propagates_into_private_helper(tmp_path):
    """A `_locked`-style helper whose every call site holds the lock is
    effectively guarded — no finding."""
    src = """
import threading


class Meter:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0
        t = threading.Thread(target=self._run, daemon=True)
        t.start()

    def _run(self):
        while True:
            with self._lock:
                self._bump()

    def _bump(self):
        self.n += 1

    def get(self):
        with self._lock:
            return self.n
"""
    assert not _lint_source(tmp_path, src, "unguarded-shared-state")


def test_fully_unguarded_conflict_reported_once_per_attr(tmp_path):
    """Tier B: no lock anywhere, but a genuine cross-domain conflict —
    one finding anchored at the thread-side write, not one per access."""
    src = """
import threading


class Tally:
    def __init__(self):
        self.hits = 0
        t = threading.Thread(target=self._run, daemon=True)
        t.start()

    def _run(self):
        while True:
            self.hits += 1

    def read(self):
        return self.hits

    def read_again(self):
        return self.hits
"""
    findings = _lint_source(tmp_path, src, "unguarded-shared-state")
    assert len(findings) == 1 and "hits" in findings[0].message


def test_single_domain_state_never_flagged(tmp_path):
    """No spawn, or all accesses on one side: no conflict, no finding."""
    src = """
class Plain:
    def __init__(self):
        self.count = 0

    def bump(self):
        self.count += 1

    def read(self):
        return self.count
"""
    assert not _lint_source(tmp_path, src, "unguarded-shared-state")


def test_cli_format_json(tmp_path):
    import json
    bad = tmp_path / "bad.py"
    bad.write_text(FIXTURES["env-import"][0], encoding="utf-8")
    proc = subprocess.run(
        [sys.executable, "-m", "incubator_predictionio_tpu.analysis",
         str(bad), "--format", "json"],
        cwd=repo_root(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["version"] == 1
    assert (doc["summary"]["errors"] + doc["summary"]["warnings"] >= 1
            and not doc["summary"]["clean"])
    assert any(f["rule"] == "env-import" and not f["suppressed"]
               for f in doc["findings"])
    assert "ruleTimingsMs" in doc


def test_cli_json_out_artifact(tmp_path):
    import json
    bad = tmp_path / "bad.py"
    bad.write_text(FIXTURES["env-import"][0], encoding="utf-8")
    out = tmp_path / "artifacts" / "lint-report.json"
    proc = subprocess.run(
        [sys.executable, "-m", "incubator_predictionio_tpu.analysis",
         str(bad), "--json-out", str(out)],
        cwd=repo_root(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "[env-import]" in proc.stdout  # stdout stays text format
    doc = json.loads(out.read_text(encoding="utf-8"))
    assert doc["version"] == 1
    assert doc["summary"]["errors"] + doc["summary"]["warnings"] >= 1


def test_cli_prune_baseline_drops_stale_keeps_live(tmp_path):
    import json
    target = tmp_path / "code.py"
    target.write_text(FIXTURES["env-import"][0] + FIXTURES["wallclock"][0],
                      encoding="utf-8")
    bl = tmp_path / "bl.json"
    base = [sys.executable, "-m", "incubator_predictionio_tpu.analysis",
            str(target)]
    subprocess.run(base + ["--write-baseline", str(bl)], cwd=repo_root(),
                   check=True, capture_output=True, timeout=120)
    for e in json.loads(bl.read_text())["entries"]:
        assert e["rule"] in ("env-import", "wallclock")
    # fix only the env-import half; its entry goes stale
    target.write_text(FIXTURES["env-import"][1] + FIXTURES["wallclock"][0],
                      encoding="utf-8")
    proc = subprocess.run(
        base + ["--baseline-path", str(bl), "--prune-baseline"],
        cwd=repo_root(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "pruned 1 stale entry" in proc.stderr
    left = json.loads(bl.read_text())["entries"]
    assert [e["rule"] for e in left] == ["wallclock"]


def test_timings_within_tier1_budget():
    """--timings reports every rule, and the whole-program pass keeps the
    full-package lint inside a tier-1-friendly wall-clock budget."""
    start = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "incubator_predictionio_tpu.analysis",
         "--baseline", "--timings"],
        cwd=repo_root(), capture_output=True, text=True, timeout=180)
    elapsed = time.monotonic() - start
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "rule timings" in proc.stderr
    for rule in ("unguarded-shared-state", "thread-lifecycle"):
        assert rule in proc.stderr
    assert elapsed < 90.0, f"full-package lint took {elapsed:.1f}s"
