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
from typing import Any, Deque, Dict, Optional, Tuple

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


class _StageLocal(threading.local):
    #: the innermost open stage on this thread (class default: a thread
    #: that never opened one reads None without a miss)
    top: Any = None


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
    child = _phase_children[name] = _PHASE_SECONDS.labels(
        phase=phase or name.rpartition(".")[2])
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
    within a microsecond. Called per dispatch, never per query."""

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
        local = _stage_local
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
        parent = _stage_local.top = self._parent
        child = _phase_children.get(self._name) or _phase_child(
            self._name, self._phase)
        dt = time.perf_counter() - self._t0
        if parent is not None:
            parent._child_s += dt
        dt -= self._child_s
        child.inc(dt if dt > 0.0 else 0.0)


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
