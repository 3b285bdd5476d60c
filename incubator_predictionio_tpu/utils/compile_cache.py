"""Persistent XLA compilation cache for the CLI/server processes.

The reference pays a JVM+Spark startup cost on every ``pio train``/``pio
deploy`` (spark-submit process hop, tools/.../Runner.scala:101-213); the
TPU-native analogue of that fixed cost is XLA compilation (tens of
seconds for the fused ALS program at ML-20M shape). JAX ships a
persistent compilation cache keyed on the HLO and on its own settings —
the directory among them, so a cache that moves never hits.

Where it lives is decided from OUTSIDE the program, by one rule:

- ``JAX_COMPILATION_CACHE_DIR`` set → that directory, and no code path
  sets another;
- unset → ``<checkout>/.xla_cache`` (git-ignored): one fixed directory
  for every process of this checkout, whatever its ``PIO_HOME``, pid or
  start time. It is exported so child processes resolve the same one.

Enabled automatically by the CLI and servers; ``PIO_COMPILE_CACHE=off``
opts out.
"""

from __future__ import annotations

import logging
import os
import pathlib

logger = logging.getLogger(__name__)

_enabled = False
_listener_installed = False


def _install_metrics_listener() -> None:
    """Bridge JAX's compilation-cache monitoring events into the obs
    registry: ``pio_compile_cache_hits_total`` / ``_requests_total``
    counters (misses = requests − hits, derived as a gauge at scrape
    time). Counters exist from the moment the cache is enabled, so a
    scrape always sees the series even before the first compile. The
    jax.monitoring event names are version-dependent — the whole bridge
    is best-effort and a missing API degrades to zero counters, never
    an error."""
    global _listener_installed
    if _listener_installed:
        return
    from incubator_predictionio_tpu.obs import metrics as obs_metrics

    hits = obs_metrics.REGISTRY.counter(
        "pio_compile_cache_hits_total",
        "XLA persistent-cache hits (compile skipped)")
    requests = obs_metrics.REGISTRY.counter(
        "pio_compile_cache_requests_total",
        "compile requests eligible for the persistent cache")
    misses = obs_metrics.REGISTRY.gauge(
        "pio_compile_cache_misses",
        "cache-eligible compiles that missed (requests - hits)")
    obs_metrics.REGISTRY.register_collector(
        "compile_cache_misses",
        lambda: misses.set(max(requests.value - hits.value, 0)))
    try:
        from jax._src import monitoring

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                hits.inc()
            elif event == "/jax/compilation_cache/compile_requests_use_cache":
                requests.inc()

        monitoring.register_event_listener(on_event)
        _listener_installed = True
    except Exception:  # pragma: no cover - monitoring API drift
        logger.debug("jax monitoring unavailable; compile-cache "
                     "counters stay at zero", exc_info=True)
        _listener_installed = True  # don't retry (and re-register) forever


#: the one in-checkout default: <repo>/.xla_cache next to the package
_DEFAULT_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".xla_cache")


def cache_dir() -> str:
    """The directory the rule above resolves to (whether or not the
    cache is enabled yet)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_DIR


def enable() -> None:
    """Idempotently enable the persistent compilation cache at
    :func:`cache_dir`. jax reads its ``JAX_*`` environment at import, so
    when jax is already imported the settings go through
    ``jax.config.update``; otherwise the environment is enough (and
    `pio app new`-style commands never pay the jax import)."""
    global _enabled
    if _enabled:
        return
    if os.environ.get("PIO_COMPILE_CACHE", "").lower() in (
            "off", "0", "false", "disable"):
        return
    try:
        directory = cache_dir()
        os.makedirs(directory, exist_ok=True)
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", directory)
        # cache every program that takes noticeable time to compile
        # (setdefault: a user-tuned threshold wins)
        os.environ.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
        min_compile_s = float(
            os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"])
        import sys
        if "jax" in sys.modules:
            import jax

            jax.config.update("jax_compilation_cache_dir", directory)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", min_compile_s)
        _enabled = True
        _install_metrics_listener()
    except Exception as exc:  # pragma: no cover - cache is best-effort
        logger.warning("compilation cache unavailable: %s", exc)
