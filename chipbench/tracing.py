"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The benchmark records one JAX profiler trace of its measured window (only
with ``--trace 1``) and reduces it here:

* the traced window: the host annotation ``chipbench.window``;
* device busy time: the union of the intervals in which an operation ran
  on a device's ``XLA Ops`` line, clipped to the window and averaged over
  the chips the cell uses;
* kernel calls: every operation named after a Pallas kernel (the name of
  its HLO instruction, e.g. ``%peel_wave_kernel.8 = ... custom-call(...)``;
  an op's event name is its HLO text), with the bytes its operand and
  result shapes hold (``call_bytes``) — the least any implementation of
  that call moves;
* the breakdown: the operations that took the most device time (leaf
  operations only: a ``while`` op's event spans its body's), each keyed by
  its program, instruction kind and result shape, and the longest idle
  gaps, each named by the innermost host span open across it.

Host spans come from two clocks: the harness's own annotations are in the
trace, and the program's ``repro.obs`` spans (``time.perf_counter_ns``)
are moved onto the trace's clock by the offset between the window
annotation's start in the trace and on ``perf_counter_ns``.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from dataclasses import dataclass, field

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HARNESS_PREFIX = "client."

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_SHAPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f(?:16|32|64))"
                    r"\[([0-9,]*)\]")


@dataclass
class Op:
    """One operation run on a device: its HLO text, start and end (ns,
    trace clock), and the program (XLA module) it ran in."""
    hlo: str
    t0: int
    t1: int
    module: str = ""

    @property
    def instruction(self) -> str:
        """The instruction's name without its number (``peel_wave_kernel``,
        ``fusion``, ``while``)."""
        head = self.hlo.split(" = ", 1)[0].lstrip("%")
        return re.sub(r"[.]\d+$", "", head)


@dataclass
class Span:
    """One host span on the trace's clock."""
    name: str
    t0: int
    t1: int


@dataclass
class Summary:
    """What the per-layer readers and the breakdown take from a trace."""
    chips: int
    window_s: float
    busy_s: float                      # averaged over the chips
    ops: list = field(default_factory=list)        # Op, all chips, clipped
    gaps: list = field(default_factory=list)       # (name, seconds), longest first

    def kernel_calls(self, kernel: str) -> list:
        """``(seconds, bytes)`` of every call of the Pallas kernel named
        ``kernel``."""
        out = []
        for op in self.ops:
            if op.instruction != kernel:
                continue
            shapes = custom_call_shapes(op.hlo)
            if shapes is None:
                raise ValueError(f"{kernel} is not a custom call: "
                                 f"{op.hlo[:200]}")
            out.append(((op.t1 - op.t0) * 1e-9, call_bytes(*shapes)))
        return out

    def device_ops(self, top: int = 10) -> list:
        """``[key, seconds]`` of the leaf operations with the most device
        time, keyed ``program/instruction result-shape``."""
        tot: dict[str, float] = {}
        for op in leaves(self.ops):
            m = _SHAPE.search(op.hlo.split(" = ", 1)[-1])
            shape = f"{m.group(1)}[{m.group(2)}]" if m else ""
            key = f"{op.module}/{op.instruction} {shape}".strip()
            tot[key] = tot.get(key, 0.0) + (op.t1 - op.t0) * 1e-9
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def leaves(ops) -> list:
    """The operations that contain no other (a ``while`` op's event spans
    the events of its body)."""
    ops = sorted(ops, key=lambda o: (o.t0, -o.t1))
    return [o for i, o in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1].t0 >= o.t1]


def parse_shape(dtype: str, dims: str) -> tuple:
    return dtype, tuple(int(d) for d in dims.split(",") if d)


def shape_bytes(shape) -> int:
    dtype, dims = shape
    n = 1
    for d in dims:
        n *= d
    return n * _DTYPE_BYTES[dtype]


def custom_call_shapes(hlo: str):
    """``(results, operands)`` shapes of a ``custom-call`` instruction's
    HLO text; ``None`` for any other instruction."""
    i = hlo.find("custom-call(")
    if i < 0 or "=" not in hlo[:i]:
        return None
    results = [parse_shape(*m) for m in
               _SHAPE.findall(hlo[hlo.index("=", 0, i) + 1:i])]
    depth, j = 0, i + len("custom-call")
    for j in range(j, len(hlo)):
        depth += {"(": 1, ")": -1}.get(hlo[j], 0)
        if depth == 0:
            break
    operands = [parse_shape(*m) for m in
                _SHAPE.findall(hlo[i + len("custom-call("):j])]
    return results, operands


def call_bytes(results, operands) -> int:
    """Bytes a call must move at least: every operand read once, every
    result written once."""
    return sum(map(shape_bytes, results)) + sum(map(shape_bytes, operands))


def load(path: str):
    """``ProfileData`` of an ``.xplane.pb`` file (gzip-compressed or not),
    or of the one such file under a profiler log directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{len(found)} traces under {path}")
        path = found[0]
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def host_spans(pd) -> list:
    """Every host annotation of the trace (the harness's and the
    runtime's), as ``Span``s."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                t0 = int(ev.start_ns)
                out.append(Span(ev.name, t0, t0 + int(ev.duration_ns)))
    return out


def device_ops(pd, chips: int) -> dict:
    """``{device index: [Op]}`` from the first ``chips`` devices' op lines,
    each op tagged with the program it ran in."""
    out = {}
    for plane in pd.planes:
        m = re.match(r"/device:[A-Z]+:(\d+)$", plane.name)
        if not m or int(m.group(1)) >= chips:
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                       re.sub(r"[(]\d+[)]$", "", ev.name))
                      for ev in lines.get(MODULES_LINE, ()))
        starts = [t0 for t0, _, _ in mods]
        ops = []
        for ev in lines.get(OPS_LINE, ()):
            t0 = int(ev.start_ns)
            i = bisect.bisect_right(starts, t0) - 1
            mod = mods[i][2] if i >= 0 and t0 < mods[i][1] else ""
            ops.append(Op(ev.name, t0, t0 + int(ev.duration_ns), mod))
        out[int(m.group(1))] = ops
    return out


def union(intervals) -> list:
    """Disjoint, sorted union of ``(t0, t1)`` intervals."""
    out = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return [tuple(iv) for iv in out]


def _clip(t0, t1, w0, w1):
    return max(t0, w0), min(t1, w1)


def _name_gap(g0: int, g1: int, spans, own) -> str:
    """The innermost of the harness's and the program's spans that covers
    the gap's midpoint, else the innermost runtime span, else none."""
    mid = (g0 + g1) // 2
    for group in (own, spans):
        cover = [s for s in group if s.t0 <= mid <= s.t1 and s.name != WINDOW]
        if cover:
            return min(cover, key=lambda s: s.t1 - s.t0).name
    return "(no host span)"


def summarize(pd, chips: int, obs_spans=(), window_perf_ns: int | None = None,
              top: int = 10) -> Summary:
    """Reduce a trace to a ``Summary``.  ``obs_spans`` are the program's
    ``SpanEvent``s and ``window_perf_ns`` the ``perf_counter_ns`` reading
    taken as the window annotation opened."""
    hosts = host_spans(pd)
    wins = [s for s in hosts if s.name == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"{len(wins)} '{WINDOW}' annotations in the trace")
    w0, w1 = wins[0].t0, wins[0].t1
    spans = [s for s in hosts if s.t1 > w0 and s.t0 < w1]
    own = [s for s in spans if s.name.startswith(HARNESS_PREFIX)]
    if window_perf_ns is not None:
        off = w0 - window_perf_ns
        own += [Span(e.name, e.t0_ns + off, e.t0_ns + e.dur_ns + off)
                for e in obs_spans]
    per_dev = device_ops(pd, chips)
    if not per_dev:
        raise ValueError("the trace holds no device op line")
    ops, busy, gaps = [], 0, []
    for dev, dev_ops in sorted(per_dev.items()):
        clipped = []
        for op in dev_ops:
            t0, t1 = _clip(op.t0, op.t1, w0, w1)
            if t1 > t0:
                clipped.append((t0, t1))
                ops.append(Op(op.hlo, t0, t1, op.module))
        u = union(clipped)
        busy += sum(t1 - t0 for t0, t1 in u)
        if dev == min(per_dev):
            edges = [w0] + [t for iv in u for t in iv] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_name_gap(g0, g1, spans, own), (g1 - g0) * 1e-9]
             for g0, g1 in gaps[:top]]
    return Summary(chips=len(per_dev), window_s=(w1 - w0) * 1e-9,
                   busy_s=busy * 1e-9 / len(per_dev), ops=ops, gaps=named)
