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
"""
from __future__ import annotations

import os
from contextlib import contextmanager

from . import metrics, trace

_START_FAILED = metrics.counter(
    "truss_profiler_start_failures_total",
    "jax.profiler traces that failed to start (the region ran untraced)")

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
    import jax

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
