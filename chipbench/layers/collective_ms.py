"""Interconnect: device time of the collective operations per committed
generation, averaged over the cell's chips.

A collective is an operation whose HLO opcode is ``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute`` or
``all-to-all``, the ``-start`` and ``-done`` halves of their asynchronous
forms included; no collective contains another operation, so each one's
own interval is its time.  On one chip the trace holds none, and the
metric reads 0."""
import re

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
         "all-to-all")
#: the opcode: the first lower-case word followed by its operand list on
#: the right-hand side (shapes and layouts are upper-case or bracketed)
_OPCODE = re.compile(r"([a-z][a-z0-9-]*)\(")


def opcode(hlo: str) -> str:
    """The HLO opcode of an operation's text (``%x = s32[4] all-reduce(...)``
    gives ``all-reduce``)."""
    m = _OPCODE.search(hlo.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def is_collective(hlo: str) -> bool:
    op = opcode(hlo)
    for suffix in ("-start", "-done"):
        op = op.removesuffix(suffix)
    return op in KINDS


def read(ctx):
    if ctx.trace is None or not ctx.generations or not ctx.trace.chips:
        return None
    ns = sum(op.t1 - op.t0 for op in ctx.trace.ops if is_collective(op.hlo))
    return ns * 1e-6 / ctx.trace.chips / ctx.generations
