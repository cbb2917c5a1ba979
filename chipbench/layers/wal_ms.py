"""Admission and WAL (``service/engine.py``, ``service/store.py``): time in
the ``wal.append`` and ``wal.fsync`` spans per committed generation."""

SPANS = ("wal.append", "wal.fsync")


def read(ctx):
    ns = [s.dur_ns for s in ctx.spans if s.name in SPANS]
    if not ns or not ctx.generations:
        return None
    return sum(ns) * 1e-6 / ctx.generations
