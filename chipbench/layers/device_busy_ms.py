"""Fused batch maintain (``core/batch.py``) on the device: the union of the
device's operation intervals in the traced window, per committed
generation."""


def read(ctx):
    if ctx.trace is None or not ctx.generations:
        return None
    return ctx.trace.busy_s * 1e3 / ctx.generations
