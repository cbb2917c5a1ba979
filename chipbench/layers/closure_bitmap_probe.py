"""Affected-set closure (``core/batch.py``): the share of committed
generations whose closure tested partner edges against the resident
adjacency bitmap instead of binary-searching the sorted rows, from the
``truss_closure_bitmap_probe_total`` counter.  A program without the
counter gives nothing."""


def read(ctx):
    probed = ctx.counters.get("truss_closure_bitmap_probe_total")
    if probed is None or not ctx.generations:
        return None
    return 100.0 * probed / ctx.generations
