"""Service host path (``service/engine.py``, ``core/dynamic.py``): time in
the ``service.admit``, ``graph.net``, ``graph.bitmap_apply`` and
``gen.commit`` spans per committed generation — the host work of a serial
generation other than the WAL (``wal_ms``) and the wait for the device."""

SPANS = ("service.admit", "graph.net", "graph.bitmap_apply", "gen.commit")


def read(ctx):
    ns = [s.dur_ns for s in ctx.spans if s.name in SPANS]
    if not ns or not ctx.generations:
        return None
    return sum(ns) * 1e-6 / ctx.generations
