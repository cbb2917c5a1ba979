"""Affected-set closure (``core/batch.py``): the closure's while-loop
iterations per committed generation, from the
``truss_closure_iterations_total`` counter.  Each iteration expands at most
one 256-edge chunk of the frontier, so this counts the closure's work."""


def read(ctx):
    iters = ctx.counters.get("truss_closure_iterations_total")
    if iters is None or not ctx.generations:
        return None
    return iters / ctx.generations
