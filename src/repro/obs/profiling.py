"""Gated ``jax.profiler`` hooks around the stack's expensive regions.

Off by default: ``profile_region("flush")`` is a no-op until
``configure(profile_dir)`` arms it (the ``serve_truss --profile-dir`` flag
does).  Once armed, entering a region starts a JAX profiler trace into
``<profile_dir>/<region>-<n>`` and exiting stops it, so a pipelined run
leaves one XLA-level trace per flush/decompose to open in TensorBoard or
Perfetto alongside the host-side Chrome trace from ``obs.trace``.

Two guards keep this safe in a serving loop: ``jax.profiler`` traces don't
nest, so a region entered inside an active region records nothing extra
(reentrance guard); and ``max_traces`` caps how many traces a long run
writes (profiling every generation of a million-write ingest would fill
the disk before it filled a timeline).  A trace that fails to start does
not stop the region it wraps, but it is counted
(``truss_profiler_start_failures_total``) so a measurement run can refuse
a trace that silently never happened.

Compilations are counted here too: one ``jax.monitoring`` listener, set up
at import, advances ``truss_xla_compiles_total`` for every backend compile
and records it as an ``xla.compile`` span that ends when JAX reports it and
lasts as long as JAX timed it — so a stall caused by a recompile is named
in the span trace, under the span that triggered it.
"""
from __future__ import annotations

import os
from contextlib import contextmanager

import jax

from . import metrics, trace

_START_FAILED = metrics.counter(
    "truss_profiler_start_failures_total",
    "jax.profiler traces that failed to start (the region ran untraced)")
_COMPILES = metrics.counter(
    "truss_xla_compiles_total",
    "XLA backend compilations in this process (jax.monitoring)")

#: the ``jax.monitoring`` duration event of one backend compilation
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_compile_event(event: str, duration_secs: float, **_):
    if event == COMPILE_EVENT:
        _COMPILES.inc()
        trace.TRACER.complete("xla.compile", int(duration_secs * 1e9))


jax.monitoring.register_event_duration_secs_listener(_on_compile_event)

_DIR: str | None = None
_MAX = 8
_COUNT = 0
_ACTIVE = False


def configure(profile_dir: str | None, max_traces: int = 8):
    """Arm (or, with ``None``, disarm) profiling into ``profile_dir``;
    at most ``max_traces`` traces are recorded per process."""
    global _DIR, _MAX, _COUNT
    _DIR = profile_dir
    _MAX = int(max_traces)
    _COUNT = 0
    if profile_dir:
        os.makedirs(profile_dir, exist_ok=True)


def is_configured() -> bool:
    """Whether a profile directory is armed and under its trace cap."""
    return _DIR is not None and _COUNT < _MAX


@contextmanager
def profile_region(name: str):
    """Context manager: JAX profiler trace around the block when armed
    (no-op otherwise; reentrant regions record once)."""
    global _COUNT, _ACTIVE
    if not is_configured() or _ACTIVE:
        yield
        return
    path = os.path.join(_DIR, f"{name}-{_COUNT}")
    _COUNT += 1
    _ACTIVE = True
    try:
        jax.profiler.start_trace(path)
    except Exception as exc:
        _ACTIVE = False  # profiler unavailable on this backend/build
        _START_FAILED.inc()
        trace.instant("profiler.start_failed", region=name,
                      err=repr(exc)[:120])
        yield
        return
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
        finally:
            _ACTIVE = False
