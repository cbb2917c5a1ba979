"""Observability plane: metrics registry, structured trace, exposition.

Telemetry on the stdlib, the rest of ``repro`` and ``jax.profiler``,
threaded through every hot path of the serving stack:

* ``obs.metrics`` — process-wide named counters/gauges/histograms with
  ``snapshot()``/``reset()`` (WAL append/fsync latency, flush size, peel
  wall time, pipeline queue depth and sheds, replica lag, router
  decisions);
* ``obs.trace`` — ring-buffered span events with injectable clocks, JSONL
  (``TraceWriter``) and Chrome ``trace_event`` export, so the pipelined
  flush→dispatch→land overlap is visually inspectable; every span is also
  a ``jax.profiler.TraceAnnotation``, so it lands in any profiler trace;
* ``obs.expo`` — Prometheus text rendering, a round-trip parser, and the
  stdlib HTTP ``MetricsServer`` behind ``serve_truss --metrics-port``
  (``/metrics`` + the SLO-backed ``/healthz``);
* ``obs.profiling`` — gated ``jax.profiler`` start/stop hooks around flush
  and decompose (``--profile-dir``), and the compile counter
  (``truss_xla_compiles_total``, ``xla.compile`` spans);
* ``obs.slo`` — declarative objectives evaluated with multi-window
  burn-rate over the live registry (``truss_slo_*``, ``stats()["slo"]``,
  ``/healthz``);
* ``obs.flightrec`` — the always-on flight recorder that dumps postmortem
  bundles to ``--postmortem-dir`` when the degradation ladder fires;
* ``obs.merge`` — cross-process JSONL trace merging into one wall-aligned
  Chrome trace (clock-sync headers written by ``trace.TraceWriter``).

The whole plane gates on one process-wide flag: ``with obs.disabled():``
turns every record into a single attribute check, which is how
``benchmarks/obs_overhead.py`` A/Bs the instrumented stack against its
uninstrumented self (committed gate: < 3% throughput cost).

See ``docs/OBSERVABILITY.md`` for the metric catalog and span taxonomy.
"""
from __future__ import annotations

from contextlib import contextmanager

from . import (expo, flightrec, merge, metrics,  # noqa: F401 — re-exports
               profiling, slo, trace)
from .state import STATE


def is_enabled() -> bool:
    """Whether telemetry recording is currently on."""
    return STATE.enabled


def enable(on: bool = True):
    """Turn telemetry recording on/off process-wide."""
    STATE.enabled = bool(on)


@contextmanager
def disabled():
    """Context manager: suspend all telemetry recording inside the block
    (metrics increments, span recording, instants all become no-ops)."""
    prev = STATE.enabled
    STATE.enabled = False
    try:
        yield
    finally:
        STATE.enabled = prev
