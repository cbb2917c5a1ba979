"""Peel engine (``core/peel.py``): peel waves per committed generation, from
the ``truss_peel_waves_total`` counter."""


def read(ctx):
    waves = ctx.counters.get("truss_peel_waves_total")
    if waves is None or not ctx.generations:
        return None
    return waves / ctx.generations
