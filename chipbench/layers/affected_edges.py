"""Fused batch maintain (``core/batch.py``): the affected set |A| per
committed generation, the edges the closure collects and the re-peel
re-peels, from the ``truss_affected_edges_total`` counter."""


def read(ctx):
    edges = ctx.counters.get("truss_affected_edges_total")
    if edges is None or not ctx.generations:
        return None
    return edges / ctx.generations
