"""obs — unified telemetry: metrics registry, exposition, trace IDs.

The reference leaned on the implicit Spark UI plus ad-hoc bookkeeping
(per-query latency in CreateServer.scala:426-428, per-app hourly ingest
counters in Stats.scala:51-80); the rebuild had reproduced those
fragments piecemeal (``utils/tracing.py`` phase walls, ``servers/
stats.py`` counters, native group-commit/scan counters nothing
exported). This package is the one coherent layer over all of them:

- :mod:`.metrics` — a process-wide registry of Counter / Gauge /
  Histogram metrics, thread-safe and cheap enough for the serving hot
  path (one uncontended lock + int add per observation, no host syncs,
  never called from inside traced code — the ``metric-in-trace`` lint
  rule enforces that last invariant repo-wide);
- :mod:`.exposition` (via :func:`metrics.Registry.expose`) —
  Prometheus text format, served at ``GET /metrics`` on every server
  (:func:`.http.add_metrics_route`);
- :mod:`.trace` — per-request trace IDs and span parenting: accepted
  from an incoming ``X-PIO-Trace-Id`` header, generated otherwise,
  propagated into the structured JSON span log and echoed on the
  response; in-repo client hops forward ``X-PIO-Parent-Span`` so span
  lines from multiple processes link into one tree
  (``scripts/trace_stitch.py``);
- :mod:`.expofmt` — the exposition grammar parser (promoted from the
  test oracle) that :mod:`.federate` uses to scrape and merge worker
  ``/metrics`` under an ``instance`` label (admin ``GET /federate``,
  fleet-mode SLOs);
- :mod:`.capacity` — the offline capacity/regression model over the
  checked-in bench trajectory (``scripts/capacity_report.py``);
- :mod:`.controller` — the self-driving freshness controller: consumes
  the fleet SLO burn rates, projects error-budget exhaustion, and
  autonomously triggers continuation retrain + rolling hot swap with a
  trace-linked decision audit trail (admin ``GET/POST /controller``);
- :mod:`.recorder` — the flight recorder: a bounded delta-encoded
  metric-history ring on every server (``GET /recorder``), histogram
  trace exemplars, and SLO-breach-triggered incident bundles that
  freeze the fleet-merged pre-breach window + exemplar trace IDs +
  scheduler state + controller decisions under ``PIO_INCIDENT_DIR``
  (admin ``GET /incidents`` / ``POST /incident``).

See ``docs/observability.md`` for the metric catalog and the scrape /
trace-propagation / fleet contracts.
"""

from incubator_predictionio_tpu.obs.metrics import (  # noqa: F401
    CONTENT_TYPE,
    DEFAULT_LATENCY_BUCKETS,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    Registry,
)
from incubator_predictionio_tpu.obs.trace import (  # noqa: F401
    TRACE_HEADER,
    accept_trace_id,
    current_trace_id,
    new_trace_id,
)
