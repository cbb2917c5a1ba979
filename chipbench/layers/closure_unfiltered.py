"""Affected-set closure (``core/batch.py``): the share of committed
generations whose closure ran without the range filter (a mixed batch that
is not separable), from the ``truss_closure_unfiltered_total`` counter.  An
unfiltered closure collects every edge triangle-connected to the batch's
seeds, whatever its phi."""


def read(ctx):
    unfiltered = ctx.counters.get("truss_closure_unfiltered_total")
    if unfiltered is None or not ctx.generations:
        return None
    return 100.0 * unfiltered / ctx.generations
