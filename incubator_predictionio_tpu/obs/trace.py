"""Request trace IDs + structured JSON span logs.

The propagation contract (docs/observability.md): every request to any
of the servers gets a trace ID — accepted from an incoming
``X-PIO-Trace-Id`` header when it is well-formed (1-128 chars of
``[A-Za-z0-9._:-]``), freshly generated otherwise — which is

- echoed back on the response in the same header,
- installed in a contextvar for the duration of the handler (the HTTP
  layer copies the context into the executor for sync handlers), and
- emitted in one structured JSON span line per request, gated on the
  ``pio.trace`` logger (level INFO; silence it with
  ``logging.getLogger("pio.trace").setLevel(logging.WARNING)``). Under
  the CLI's server verbs the line is one formatted string appended to
  the span sink (:func:`enable_span_logging`), which a thread of its
  own writes to standard error a batch at a time; any handler that can
  be reached from ``pio.trace`` receives it through ``logging`` too.

A client that stamps its POST /events.json and POST /queries.json with
the same trace ID can therefore join the ingest span, the serving span
and any operator-side logs on one key — the distributed-tracing
contract at log-line cost, with no collector dependency.

Below the request level, :func:`stage` marks the boundaries of the
serving dispatcher's cycle (``serve.wait``, ``serve.dispatch`` and its
children; docs/observability.md "The dispatcher's cycle"): one call
feeds the profiler's host timeline, while a ``jax.profiler`` trace is
being taken, and ``pio_serve_phase_seconds_total{phase}`` always.
:class:`GcPauseHook` does the same for the collector's pauses.
"""

from __future__ import annotations

import atexit
import collections
import contextvars
import gc
import json
import logging
import math
import os
import queue
import random
import re
import secrets
import sys
import threading
import time
import weakref
from typing import Any, Deque, Dict, List, Optional, Tuple

from incubator_predictionio_tpu.obs import metrics as obs_metrics

#: the propagation header, request and response side
TRACE_HEADER = "X-PIO-Trace-Id"
#: the cross-process PARENT link: an in-repo HTTP client stamps its own
#: span ID here so the downstream server's span line carries
#: ``parentSpanId`` and the two processes' spans join into one tree
#: (scripts/trace_stitch.py reconstructs the timeline)
PARENT_SPAN_HEADER = "X-PIO-Parent-Span"
#: response-side: the span ID the server assigned to THIS request, so
#: an external client can reference the server-side span in its own logs
SPAN_HEADER = "X-PIO-Span-Id"

_TRACE_ID_RE = re.compile(r"^[A-Za-z0-9._:-]{1,128}$")
#: span IDs share the trace-ID charset (locally generated ones are 8
#: hex chars, but a foreign tracer's IDs must survive the hop too)
_SPAN_ID_RE = _TRACE_ID_RE

_current: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "pio_trace_id", default=None
)
_current_span: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("pio_span_id", default=None)

#: one JSON object per line; operators point this at their log shipper
span_logger = logging.getLogger("pio.trace")
logger = logging.getLogger(__name__)


def new_trace_id() -> str:
    """16 hex chars — collision-safe for log correlation windows."""
    return secrets.token_hex(8)


def accept_trace_id(incoming: Optional[str]) -> str:
    """The incoming header value when well-formed, else a fresh ID.
    Malformed values are REPLACED, not rejected: a trace header must
    never be able to fail a request (or smuggle log-breaking bytes)."""
    if incoming and _TRACE_ID_RE.match(incoming):
        return incoming
    return new_trace_id()


def current_trace_id() -> Optional[str]:
    """The ambient request's trace ID (None outside a request)."""
    return _current.get()


def set_current(trace_id: Optional[str]) -> contextvars.Token:
    return _current.set(trace_id)


def reset_current(token: contextvars.Token) -> None:
    _current.reset(token)


def new_span_id() -> str:
    """8 hex chars — unique within one trace's fan-out."""
    return secrets.token_hex(4)


def accept_parent_span(incoming: Optional[str]) -> Optional[str]:
    """The incoming parent-span header when well-formed, else None.
    Unlike trace IDs a malformed parent is DROPPED, not replaced: a
    fabricated parent would invent linkage that never happened."""
    if incoming and _SPAN_ID_RE.match(incoming):
        return incoming
    return None


def current_span_id() -> Optional[str]:
    """The ambient request's server-side span ID (None outside one)."""
    return _current_span.get()


def set_current_span(span_id: Optional[str]) -> contextvars.Token:
    return _current_span.set(span_id)


def reset_current_span(token: contextvars.Token) -> None:
    _current_span.reset(token)


def client_headers() -> dict:
    """Headers an in-repo HTTP client attaches to a downstream hop
    (prediction/event server → storage server, admin → workers,
    bench → servers): the ambient trace ID plus this request's span ID
    as the downstream parent. Empty outside a request — a client with
    no ambient trace forwards nothing and the server starts a fresh
    trace, exactly as before."""
    tid = _current.get()
    if tid is None:
        return {}
    out = {TRACE_HEADER: tid}
    sid = _current_span.get()
    if sid is not None:
        out[PARENT_SPAN_HEADER] = sid
    return out


#: how often the span sink writes what has gathered: the longest a line
#: waits for its write, and the most a hard kill can lose
SPAN_WRITE_PERIOD_S = 0.05
#: lines the sink holds between two writes; past it a line is dropped
#: and counted (a stream that blocks must not grow the process)
SPAN_BUFFER_LINES = 65536

_SPAN_LINES = obs_metrics.REGISTRY.counter(
    "pio_trace_span_lines_total",
    "span lines the span sink wrote to its stream")
_SPAN_WRITES = obs_metrics.REGISTRY.counter(
    "pio_trace_span_writes_total",
    "writes the span sink made, each carrying every line that gathered "
    "in one period (lines over writes: how far the batching engages)")
_SPAN_DROPPED = obs_metrics.REGISTRY.counter(
    "pio_trace_span_lines_dropped_total",
    "span lines that never reached the stream: the buffer was full, or "
    "the stream was closed or refused the write")


class _SpanSink:
    """Where the CLI's span lines go. ``put`` is an append and nothing
    else, from whatever thread ends a request; a daemon thread of the
    sink's own joins what has gathered and hands it to the stream in one
    ``write`` a period, so a stream that blocks (a full pipe) blocks that
    thread and never an event loop or a dispatcher. The counters are
    booked by the writer, a batch at a time."""

    def __init__(self, stream: Any) -> None:
        self._stream = stream
        # put to by any thread, taken from only under `_write_lock`
        self._lines: "queue.SimpleQueue[str]" = queue.SimpleQueue()
        self._overflow = 0
        self._overflow_lock = threading.Lock()
        self._write_lock = threading.Lock()
        self._wake = threading.Event()
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="pio-span-writer", daemon=True)
        self._thread.start()

    def put(self, line: str) -> None:
        if self._lines.qsize() < SPAN_BUFFER_LINES:
            self._lines.put(line)
        else:
            with self._overflow_lock:
                self._overflow += 1

    def _run(self) -> None:
        while not self._closed:
            self._wake.wait(SPAN_WRITE_PERIOD_S)
            self._wake.clear()
            self.flush()

    def flush(self) -> None:
        """Write what has gathered, on the calling thread. A stream
        closed under the sink (the process that owns it closed its log)
        is swallowed: nothing may print where span lines were due."""
        with self._write_lock:
            lines = self._lines
            batch = [lines.get_nowait() for _ in range(lines.qsize())]
            with self._overflow_lock:
                dropped, self._overflow = self._overflow, 0
            if batch:
                try:
                    self._stream.write("\n".join(batch) + "\n")
                    self._stream.flush()
                except (OSError, ValueError):
                    dropped += len(batch)
                else:
                    _SPAN_LINES.inc(len(batch))
                    _SPAN_WRITES.inc()
            if dropped:
                _SPAN_DROPPED.inc(dropped)

    def close(self) -> None:
        """End the writer and write what is left (``atexit``: no daemon
        thread may be inside the stream when the interpreter goes)."""
        self._closed = True
        self._wake.set()
        self._thread.join()
        self.flush()


#: the process's span sink, once a CLI server verb has asked for one
_sink: Optional[_SpanSink] = None


def enable_span_logging() -> None:
    """Give the span lines a real sink: one bare-JSON line per request
    on stderr (as it stands at this call), written by the sink's own
    thread every ``SPAN_WRITE_PERIOD_S`` and at an orderly stop. The CLI
    server verbs call this so `pio eventserver` / `pio deploy` emit
    spans out of the box; library embedders configure logging themselves
    and never pay for it (an unconfigured logger fails the
    ``isEnabledFor`` gate). ``PIO_TRACE_LOG=off`` disables. Idempotent;
    the sink is no ``logging`` handler, so pytest caplog and operator
    handlers on ``pio.trace`` or above keep seeing the records."""
    global _sink
    if os.environ.get("PIO_TRACE_LOG", "").lower() in (
            "off", "0", "false", "disable"):
        return
    if _sink is not None:
        return
    _sink = _SpanSink(sys.stderr)
    atexit.register(_sink.close)
    span_logger.setLevel(logging.INFO)


def flush_span_log(wait: bool = True) -> None:
    """The lines the span sink still holds go to its stream: on the
    calling thread (a verb's exit: the caller may close the stream
    next), or with ``wait=False`` on the sink's writer, woken now
    (``HttpServer.stop()``, which may run on an event loop). Nothing to
    do in a process without the sink."""
    sink = _sink
    if sink is None:
        return
    if wait:
        sink.flush()
    else:
        sink._wake.set()


#: last parsed PIO_TRACE_SAMPLE value, keyed by the raw env string so a
#: runtime change re-parses but the steady state pays one dict-free
#: string compare per request (no float() on the hot path)
_sample_cache: Tuple[Optional[str], float] = (None, 1.0)


def sample_rate() -> float:
    """The span sampling rate from ``PIO_TRACE_SAMPLE`` (default 1.0 —
    every request emits its span line). Clamped to [0, 1]; read per call
    so operators can retune a live server, with the parse cached on the
    raw string value."""
    global _sample_cache
    raw = os.environ.get("PIO_TRACE_SAMPLE")
    cached_raw, cached = _sample_cache
    if raw == cached_raw:
        return cached
    try:
        rate = min(max(float(raw), 0.0), 1.0) if raw else 1.0
    except ValueError:
        rate = 1.0
    _sample_cache = (raw, rate)
    return rate


def span_sampled() -> bool:
    """Coin flip for THIS request's span line. Sampled-out requests
    still carry (and echo) their trace IDs — sampling drops only the
    JSON log line (a format and an append per request, a share of one
    write on the span sink's thread); the propagation contract is
    unconditional."""
    rate = sample_rate()
    if rate >= 1.0:
        return True
    return rate > 0.0 and random.random() < rate


def _json(value: Any) -> str:
    """``value`` as ``json.dumps`` writes it: a string that holds no
    byte JSON escapes is quoted in place, an int or a finite float is
    its ``repr``, anything else goes through ``json.dumps``."""
    kind = type(value)
    if kind is str:
        if (value.isascii() and value.isprintable() and '"' not in value
                and "\\" not in value):
            return f'"{value}"'
    elif kind is int or (kind is float and math.isfinite(value)):
        return repr(value)
    return json.dumps(value, separators=(",", ":"))


#: the keys the two kinds of line set themselves
_FIXED_FIELDS = frozenset((
    "span", "server", "method", "route", "status", "ts", "durationMs",
    "traceId", "spanId", "parentSpanId"))


def _emit(head: str, extra: Dict[str, Any]) -> None:
    """Close the span line ``head`` with ``extra`` and send it: to the
    span sink if the CLI made one, and through ``logging`` if no sink
    is there or any handler can be reached from ``pio.trace`` (caplog,
    an embedder's, an operator's): the sink took the place of the CLI's
    ``StreamHandler`` and of nothing else."""
    if extra:
        if not _FIXED_FIELDS.isdisjoint(extra):
            # a keyword that names a field already on the line replaces
            # it where it stands, as ``dict.update`` did: rare enough to
            # parse back (exact for any keyword, so one set serves both
            # kinds of line)
            record = json.loads(head + "}")
            record.update(extra)
            head = json.dumps(record, separators=(",", ":"))[:-1]
        else:
            head += "".join([f",{_json(k)}:{_json(v)}"
                             for k, v in extra.items()])
    line = head + "}"
    sink = _sink
    if sink is not None:
        sink.put(line)
        if not span_logger.hasHandlers():
            return
    span_logger.info("%s", line)


def log_span(server: str, method: str, route: str, status: int,
             duration_s: float, trace_id: str,
             span_id: Optional[str] = None,
             parent_span_id: Optional[str] = None,
             **extra: Any) -> None:
    """Emit the per-request JSON span line. Pre-gated on the logger
    level so a silenced logger costs one attribute read per request.
    ``span_id``/``parent_span_id`` carry the cross-process parenting
    contract: the downstream hop's line names the upstream span, so
    span lines from multiple processes link into one request tree.
    The line is one string format, byte for byte what ``json.dumps`` of
    the same fields (separators ``,`` and ``:``) gives."""
    if not span_logger.isEnabledFor(logging.INFO):
        return
    # ts: wall stamp (epoch s, ms precision): cross-PROCESS span lines
    # have no shared log stream, so the stitcher orders them by wall
    # clock — NTP-grade skew is fine at request granularity
    head = (f'{{"span":"http.request","server":{_json(server)}'
            f',"method":{_json(method)},"route":{_json(route)}'
            f',"status":{_json(status)},"ts":{_json(round(time.time(), 3))}'
            f',"durationMs":{_json(round(duration_s * 1e3, 3))}'
            f',"traceId":{_json(trace_id)}')
    if span_id is not None:
        head += f',"spanId":{_json(span_id)}'
    if parent_span_id is not None:
        head += f',"parentSpanId":{_json(parent_span_id)}'
    _emit(head, extra)


def log_stage_span(span: str, trace_id: str, duration_s: float,
                   **extra: Any) -> None:
    """Emit a non-HTTP pipeline-stage span (the speed layer's freshness
    chain: ``speed.poll`` → ``speed.foldin`` → ``speed.serve``) with the
    same shape and through the same sink as the request spans, so one
    trace ID joins an event's whole journey across log lines, in the
    order they were made. Pre-gated like :func:`log_span`."""
    if not span_logger.isEnabledFor(logging.INFO):
        return
    _emit(f'{{"span":{_json(span)},"ts":{_json(round(time.time(), 3))}'
          f',"durationMs":{_json(round(duration_s * 1e3, 3))}'
          f',"traceId":{_json(trace_id)}', extra)


# ---------------------------------------------------------------------------
# stages of the serving dispatcher's cycle: one primitive, two sinks
# ---------------------------------------------------------------------------

#: seconds the dispatcher threads spent in each phase of their cycle.
#: ``wait`` and the phases inside a dispatch tile a dispatcher thread's
#: life: summed over ``phase`` the family grows by one second per second
#: per dispatcher thread (tests/test_serve_phases.py holds it to that).
_PHASE_SECONDS = obs_metrics.REGISTRY.counter(
    "pio_serve_phase_seconds_total",
    "seconds of the serving dispatcher's cycle, by phase (wait = nothing "
    "it may pick; other = a dispatch's own time outside its child "
    "phases; fetch = device execution + device-to-host copy)",
    labels=("phase",))
_phase_children: Dict[str, Any] = {}
_annotation_cls: Any = None

#: a stall's floor: a phase (``serve.wait`` aside), a beat of the event
#: loop or a wake of the stall watch this far past its due is booked. A
#: dispatch of the ALS cells is 2-5 ms, a full collection 38-48 ms
STALL_S = 0.025
#: 25 ms to 20 s in steps of at most 1.5, as ``pio_gc_pause_seconds``
_STALL_BUCKETS = obs_metrics.geometric_buckets(STALL_S, 20.0)
_PHASE_LONG = obs_metrics.REGISTRY.histogram(
    "pio_serve_phase_long_seconds",
    "one phase of the dispatcher's cycle whose own time passed 25 ms "
    "(wait aside): the phases of pio_serve_phase_seconds_total, each run "
    "that long observed alone", labels=("phase",), buckets=_STALL_BUCKETS)
#: (phase, seconds) of the phases that ran long, booked at scrape time
_long_phases: Deque[Tuple[str, float]] = collections.deque(maxlen=16384)


class _ThreadStages:
    """One thread's innermost open stage, where the stall watch reads it
    from its own thread (one attribute read, nothing the owner pays)."""

    __slots__ = ("top", "__weakref__")

    def __init__(self) -> None:
        self.top: Any = None


#: {thread ident: that thread's stages}, for every live thread that ever
#: opened a stage; an entry whose thread has ended reads None
_stage_threads: Dict[int, "weakref.ref[_ThreadStages]"] = {}


class _StageLocal(threading.local):
    def __init__(self) -> None:
        # runs once in each thread, on the thread's first stage
        self.me = _ThreadStages()
        _stage_threads[threading.get_ident()] = weakref.ref(self.me)


_stage_local = _StageLocal()


def _annotation() -> Any:
    """``jax.profiler.TraceAnnotation``, or None in a process that never
    imported jax (the event and storage servers share this module and
    must not pay for, or initialize, jax). It neither synchronizes the
    device nor starts a capture; whether one is running is one atomic
    read (``is_enabled``), and only then is an annotation made."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        _annotation_cls = jax.profiler.TraceAnnotation
    return _annotation_cls


def _phase_child(name: str, phase: Optional[str]) -> Any:
    """The counter child a stage books under, cached by the stage's
    name: ``phase`` if given, else the name's last component."""
    phase = phase or name.rpartition(".")[2]
    child = _phase_children[name] = _PHASE_SECONDS.labels(phase=phase)
    if name != "serve.wait":
        # the phase's series is there before its first long run, so a
        # reader tells "none ran long" from "not exported"
        _PHASE_LONG.labels(phase=phase)
    return child


class stage:
    """``with stage("serve.fetch"):`` — one boundary of the dispatcher's
    cycle. While a ``jax.profiler`` trace is being taken it holds an
    annotation of that name (with ``attrs``) open on the calling
    thread's line of the host plane, on the device trace's own clock;
    always, on exit, it adds its SELF time — its duration less the
    stages nested inside it on the same thread — to
    ``pio_serve_phase_seconds_total{phase}``, so no second is counted
    twice. ``phase`` defaults to the name's last component. The clock
    is read first on entry and last on exit: a stage's own bookkeeping
    is inside its time, so consecutive stages tile a thread's time to
    within a microsecond. A self time over ``STALL_S`` (``serve.wait``
    aside) is queued for ``pio_serve_phase_long_seconds{phase}`` too: one
    compare on the way out. Called per dispatch, never per query."""

    __slots__ = ("_name", "_phase", "_attrs", "_ann", "_parent", "_t0",
                 "_child_s")

    def __init__(self, name: str, phase: Optional[str] = None,
                 **attrs: Any) -> None:
        self._name = name
        self._phase = phase
        self._attrs = attrs

    def __enter__(self) -> "stage":
        self._t0 = time.perf_counter()
        self._child_s = 0.0
        local = _stage_local.me
        parent = self._parent = local.top
        local.top = self
        self._ann = None
        if parent is not None:
            # a trace that starts in mid-dispatch shows from the next one
            traced = parent._ann is not None
        else:
            cls = _annotation_cls or _annotation()
            traced = cls is not None and cls.is_enabled()
        if traced:
            self._ann = _annotation_cls(self._name, **self._attrs)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc: Any) -> None:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        parent = _stage_local.me.top = self._parent
        child = _phase_children.get(self._name) or _phase_child(
            self._name, self._phase)
        dt = time.perf_counter() - self._t0
        if parent is not None:
            parent._child_s += dt
        dt -= self._child_s
        child.inc(dt if dt > 0.0 else 0.0)
        if dt > STALL_S and self._name != "serve.wait":
            # a wait is idleness, however long; anything else this long
            # is a stall (booked at scrape time: no lock here)
            _long_phases.append(
                (self._phase or self._name.rpartition(".")[2], dt))


#: one collection of the serving process, start to stop, by generation:
#: the stall the harness's own callback found (PERF.md, PR 25) is a full
#: collection of 38-48 ms; an operator reads it here
_GC_PAUSE = obs_metrics.REGISTRY.histogram(
    "pio_gc_pause_seconds",
    "one garbage collection of the serving process, start to stop",
    labels=("generation",),
    buckets=obs_metrics.geometric_buckets(0.25e-3, 1.0))


class GcPauseHook:
    """A ``gc.callbacks`` hook: holds a ``gc.pause`` annotation open
    from a collection's start to its stop and books the pause in
    ``pio_gc_pause_seconds{generation}``. It books nothing under
    ``pio_serve_phase_seconds_total``: a collection runs inside whatever
    phase set it off, and the phases go on tiling the thread's time.

    The hook itself takes no lock: a collection can start between two
    bytecodes of ANY thread, the scrape thread inside the histogram's
    own lock included, and a locked add from there would wait on
    itself. It appends to a bounded deque (two clock reads and one
    append a collection) and a scrape-time collector moves the pauses
    into the histogram."""

    #: pauses kept between two scrapes; beyond it the oldest are dropped
    PENDING_MAX = 16384

    def __init__(self) -> None:
        self._pending: Deque[Tuple[int, float]] = collections.deque(
            maxlen=self.PENDING_MAX)
        self._ann: Any = None
        self._t0 = 0.0

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            cls = _annotation()
            if cls is not None and cls.is_enabled():
                self._ann = cls("gc.pause", gen=info.get("generation", -1))
                self._ann.__enter__()
            self._t0 = time.perf_counter()
        elif self._t0:  # a stop whose start came before the hook: skip
            dt = time.perf_counter() - self._t0
            self._t0 = 0.0
            ann, self._ann = self._ann, None
            if ann is not None:
                ann.__exit__(None, None, None)
            self._pending.append((info.get("generation", -1), dt))

    def flush(self) -> None:
        """Scrape time: the pauses since the last flush → the histogram.
        The ambient trace here is the scrape's own request, which no
        pause belongs to: cleared, so that none becomes an exemplar."""
        token = set_current(None)
        try:
            while True:
                try:
                    gen, dt = self._pending.popleft()
                except IndexError:
                    return
                _GC_PAUSE.labels(generation=str(gen)).observe(dt)
        finally:
            reset_current(token)


#: the collector is the process's, so the hook is too: servers that
#: start serving hold it (the ops/mips_daemon acquire/release idiom) and
#: the last one to stop takes it out of ``gc.callbacks``
_gc_hook = GcPauseHook()
_gc_lock = threading.Lock()
_gc_holders = 0


def acquire_gc_hook() -> None:
    global _gc_holders
    with _gc_lock:
        _gc_holders += 1
        if _gc_holders == 1:
            gc.callbacks.append(_gc_hook)
            obs_metrics.REGISTRY.register_collector("gc_pause",
                                                    _gc_hook.flush)


def release_gc_hook() -> None:
    global _gc_holders
    with _gc_lock:
        if _gc_holders == 0:
            return
        _gc_holders -= 1
        if _gc_holders == 0:
            gc.callbacks.remove(_gc_hook)
            obs_metrics.REGISTRY.unregister_collector("gc_pause")
            _gc_hook.flush()


# ---------------------------------------------------------------------------
# stalls of the serving process, booked where they were seen
# ---------------------------------------------------------------------------

#: the stall watch's sleep and the heartbeat's period
WATCH_PERIOD_S = 0.02
#: the process held (a late wake of the watch) or the loop late this
#: long writes a stall line
STALL_LOG_S = 0.1
#: a dispatcher phase open this long writes one: above the sequence
#: cell's longest dispatch (eight windows, 112 ms)
PHASE_LOG_S = 0.25
#: frames of a thread a stall line carries, innermost last
STALL_FRAMES = 6
#: the heartbeat's clock, bound here: a test that stands a clock of its
#: own in for this module's ``time`` moves the stages' clock alone
_perf_counter = time.perf_counter

_LOOP_LAG = obs_metrics.REGISTRY.histogram(
    "pio_serve_loop_lag_seconds",
    "a beat of the prediction server's event loop that ran over 25 ms "
    "past its due: how long the loop's thread was kept from it",
    buckets=_STALL_BUCKETS)
_HOST_STALL = obs_metrics.REGISTRY.histogram(
    "pio_host_stall_seconds",
    "a wake of the stall watch over 25 ms past its due: no thread of the "
    "serving process ran Python that long (one held the interpreter, or "
    "the process or its host did not run)", buckets=_STALL_BUCKETS)
#: seconds late of the loops' beats that ran long, booked at scrape time
_loop_lags: Deque[float] = collections.deque(maxlen=16384)

#: the stall line: one ``logging`` record a stall, never one a request,
#: one JSON object (``{"stall": "host" | "loop" | "dispatcher", ...}``)
#: like the span lines
stall_logger = logging.getLogger("pio.stall")

#: innermost frames of a thread that waits for work, not one that works
_IDLE_FRAMES = frozenset((
    ("threading.py", "wait"), ("selectors.py", "select"),
    ("queue.py", "get"), ("thread.py", "_worker")))


class LoopHeartbeat:
    """A callback that re-arms itself on an event loop every
    ``WATCH_PERIOD_S``: a beat that runs over ``STALL_S`` late books the
    lateness in ``pio_serve_loop_lag_seconds`` (queued: booked at scrape
    time), and every beat stamps ``last``, which the stall watch reads to
    see a loop that is late right now. Fifty callbacks a second on the
    loop, nothing a request."""

    def __init__(self, loop: Any) -> None:
        self.loop = loop
        #: the loop's thread, known from the first beat
        self.ident: Optional[int] = None
        #: perf_counter of the last beat
        self.last = _perf_counter()
        #: set by the loop's server once its warm-up has ended: stall
        #: lines before that are the warm-up's own
        self.ready = False
        self._due = self.last
        self._handle: Any = None
        self._stopped = False

    def start(self) -> "LoopHeartbeat":
        self.loop.call_soon_threadsafe(self._first)
        return self

    def _first(self) -> None:
        self.ident = threading.get_ident()
        self._arm(_perf_counter())

    def _arm(self, now: float) -> None:
        self.last = now
        if not self._stopped:
            self._due = now + WATCH_PERIOD_S
            self._handle = self.loop.call_later(WATCH_PERIOD_S, self._beat)

    def _beat(self) -> None:
        now = _perf_counter()
        late = now - self._due
        if late > STALL_S:
            _loop_lags.append(late)
        self._arm(now)

    def cancel(self) -> None:
        """Stop re-arming, and cancel the armed beat on the loop's own
        thread (from any thread; a closed loop has nothing armed)."""
        self._stopped = True
        try:
            _heartbeats.remove(self)
        except ValueError:
            pass
        try:
            self.loop.call_soon_threadsafe(self._cancel_armed)
        except RuntimeError:
            pass

    def _cancel_armed(self) -> None:
        if self._handle is not None:
            self._handle.cancel()


def _frames(frame: Any) -> List[str]:
    """The innermost ``STALL_FRAMES`` frames of a stack, innermost last,
    as ``file:line function`` (no source read: a stall is no time to
    touch the disk)."""
    out = []
    while frame is not None and len(out) < STALL_FRAMES:
        code = frame.f_code
        out.append(f"{code.co_filename.rsplit('/', 1)[-1]}:"
                   f"{frame.f_lineno} {code.co_name}")
        frame = frame.f_back
    return out[::-1]


class StallWatch:
    """The thread ``pio-stall-watch``: it sleeps ``WATCH_PERIOD_S`` at a
    time and, at each wake (:meth:`tick`),

    - books a wake over ``STALL_S`` late in ``pio_host_stall_seconds``:
      for that long no thread ran Python, because one held the
      interpreter or because the process did not run. Which of the two
      the watch does not say: a host that charges its own pauses to the
      process as CPU time (as the benchmark's TPU hosts do) leaves the
      process's CPU clock no witness. The line's frames name a holder
      where there was one;
    - reads each dispatcher thread's open stage and each loop's last
      beat, which costs those threads nothing;
    - writes one stall line (``pio.stall``) for a stall that passes its
      log threshold: a late wake over ``STALL_LOG_S`` (or over
      ``STALL_S`` with a loop over ``STALL_LOG_S`` late: the loop stood
      through the same stall), a loop whose beat is that late now, a
      phase open over ``PHASE_LOG_S``. A stall that began before the
      last logged one ended is that one. Until a watched server is
      ready the line is the warm-up's: INFO, with ``"warmup": true``.

    The clocks are arguments, so a test can drive a wake by hand."""

    def __init__(self, clock: Any = time.perf_counter,
                 wall: Any = time.time) -> None:
        self._clock, self._wall = clock, wall
        self._t = 0.0
        #: perf_counter at which the last logged stall ended
        self._covered = 0.0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="pio-stall-watch", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(5.0)

    def reset(self) -> None:
        self._t = self._clock()

    def _run(self) -> None:
        self.reset()
        while not self._stop.wait(WATCH_PERIOD_S):
            try:
                self.tick()
            except Exception:  # the watch must outlive what it watches
                logger.exception("stall watch: a wake failed")

    def tick(self) -> Optional[Dict[str, Any]]:
        """One wake: book, read, log. Returns the stall line's record,
        where this wake wrote one."""
        now = self._clock()
        late = now - self._t - WATCH_PERIOD_S
        self._t = now
        if late > STALL_S:
            _HOST_STALL.observe(late)
        beats = self._beats(now)
        opened = self._open_stages()
        record = None
        if late > STALL_LOG_S or late > STALL_S and any(
                lag > STALL_LOG_S for _i, _l, lag in beats):
            record = {"stall": "host", "start": now - late}
        else:
            record = self._ongoing(now, beats, opened)
        if record is None:
            return None
        self._covered = now
        return self._log(record, now, beats, opened)

    @staticmethod
    def _beats(now: float) -> List[Tuple[Optional[int], float, float]]:
        """(loop thread, perf_counter of its last beat, seconds late now)
        of every watched loop."""
        return [(hb.ident, hb.last, now - hb.last - WATCH_PERIOD_S)
                for hb in list(_heartbeats) if not hb.loop.is_closed()]

    @staticmethod
    def _open_stages() -> List[Tuple[int, Any]]:
        """(thread, innermost open stage) of every thread inside a stage
        other than ``serve.wait``; ended threads are dropped."""
        out = []
        for ident, ref in list(_stage_threads.items()):
            stages = ref()
            if stages is None:
                if _stage_threads.get(ident) is ref:
                    del _stage_threads[ident]
                continue
            top = stages.top
            if top is not None and top._name != "serve.wait":
                out.append((ident, top))
        return out

    def _ongoing(self, now: float, beats: list,
                 opened: list) -> Optional[Dict[str, Any]]:
        """A loop late or a phase open past its threshold now, that began
        after the last logged stall ended."""
        for _ident, last, lag in beats:
            if lag > STALL_LOG_S and last >= self._covered:
                return {"stall": "loop", "start": last + WATCH_PERIOD_S}
        for _ident, top in opened:
            if (now - top._t0 > PHASE_LOG_S and top._t0 >= self._covered
                    and _dispatch_of(top) is not None):
                return {"stall": "dispatcher", "start": top._t0}
        return None

    def _log(self, record: Dict[str, Any], now: float, beats: list,
             opened: list) -> Dict[str, Any]:
        wall = self._wall()
        start = record.pop("start")
        names = {t.ident: t.name for t in threading.enumerate()}
        record.update(
            ms=round((now - start) * 1e3, 3),
            start=round(wall - (now - start), 3), end=round(wall, 3),
            open=[], loopLagMs=round(
                max([lag for _i, _l, lag in beats] + [0.0]) * 1e3, 3),
            frames={})
        for ident, top in opened:
            record["open"].append({
                "thread": f"{names.get(ident, '?')}/{ident}",
                "stage": top._name, "id": _dispatch_of(top),
                "openMs": round((now - top._t0) * 1e3, 3)})
        # the dispatcher threads and the loops' own, always; for a host
        # stall, whichever other thread stands in code of its own and not
        # in a wait for work: a holder of the interpreter, if one held it
        watched = set(_stage_threads) | {i for i, _l, _g in beats}
        me = threading.get_ident()
        for ident, frame in sys._current_frames().items():
            stack = [] if ident == me else _frames(frame)
            if not stack:
                continue
            if ident not in watched:
                file_line, _sp, func = stack[-1].partition(" ")
                if record["stall"] != "host" or (
                        file_line.partition(":")[0], func) in _IDLE_FRAMES:
                    continue
            record["frames"][f"{names.get(ident, '?')}/{ident}"] = stack
        loops = [hb for hb in list(_heartbeats) if not hb.loop.is_closed()]
        warmup = bool(loops) and not any(hb.ready for hb in loops)
        if warmup:
            record["warmup"] = True
        stall_logger.log(logging.INFO if warmup else logging.WARNING, "%s",
                         json.dumps(record, separators=(",", ":"),
                                    default=str))
        return record


def _dispatch_of(top: Any) -> Any:
    """The ``id`` of the ``serve.dispatch`` an open stage lies in; None
    outside a dispatch (a warm-up's stages)."""
    while top is not None and "id" not in top._attrs:
        top = top._parent
    return None if top is None else top._attrs["id"]


def _flush_stalls() -> None:
    """Scrape time: the phases that ran long and the loops' late beats
    since the last flush → their histograms, with no request's trace as
    an exemplar (the ambient one is the scrape's own)."""
    token = set_current(None)
    try:
        while True:
            try:
                phase, dt = _long_phases.popleft()
            except IndexError:
                break
            _PHASE_LONG.labels(phase=phase).observe(dt)
        while True:
            try:
                late = _loop_lags.popleft()
            except IndexError:
                break
            _LOOP_LAG.observe(late)
    finally:
        reset_current(token)


#: one watch a process, held while any prediction server serves (the
#: GC hook's idiom); the loops it reads are each server's own, in a list
#: that is only appended to, removed from and copied (no lock)
_watch = StallWatch()
_watch_lock = threading.Lock()
_watch_holders = 0
_heartbeats: List[LoopHeartbeat] = []


def acquire_stall_watch() -> None:
    global _watch_holders
    with _watch_lock:
        _watch_holders += 1
        if _watch_holders == 1:
            _watch.start()
            obs_metrics.REGISTRY.register_collector("stall_watch",
                                                    _flush_stalls)


def release_stall_watch() -> None:
    global _watch_holders
    with _watch_lock:
        if _watch_holders == 0:
            return
        _watch_holders -= 1
        if _watch_holders == 0:
            _watch.stop()
            obs_metrics.REGISTRY.unregister_collector("stall_watch")
            _flush_stalls()


def watch_loop(loop: Any) -> LoopHeartbeat:
    """A heartbeat on ``loop``, read by the stall watch until its
    ``cancel()``."""
    hb = LoopHeartbeat(loop)
    _heartbeats.append(hb)
    return hb.start()
