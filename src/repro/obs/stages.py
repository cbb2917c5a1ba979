"""The stage of each device op in a JAX profiler trace, and device time per
stage.

    python -m repro.obs.stages <trace.xplane.pb[.gz] | profile dir>

prints one JSON line: ``device_scopes``, device seconds of leaf ops per
``module/stage`` summed over every device of the trace, longest first, and
``stage_share``, the share of the fused batch maintain's leaf time that
falls in its four stages.  Point it at a ``serve_truss --profile-dir``
region directory.

The program names its stages with ``jax.named_scope`` (``core/batch.py``:
``ranges``, ``structural``, ``closure``, ``repeel``).  A scope becomes part
of each op's ``tf_op`` stat, e.g. ``jit(batch_maintain)/closure/while/body/
gather:gather``; an op's stage is the first path component after the
outermost ``jit(...)``.  ``jax.profiler.ProfileData`` does not hand out
event-metadata stats, so they are read from the serialized ``XSpace``
itself, with a wire-format reader of only what is needed: each device
plane's ``stat_metadata`` (XPlane field 5: id -> name) and
``event_metadata`` (field 4: in each ``XEventMetadata``, its ``name``, 2,
and ``stats``, 5; in each ``XStat``, ``metadata_id``, 1, and ``str_value``,
5, or ``ref_value``, 7).  The planes' ``lines`` (field 3), which hold
nearly all the bytes, are skipped unread.  The metadata name is the op's
HLO text, which is also its event name on the plane's ``XLA Ops`` line.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import sys

#: the stages ``core/batch.py`` names in ``batch_maintain``
STAGES = ("ranges", "structural", "closure", "repeel")
#: the fused batch maintain's program, as the trace's module line names it
BATCH_MODULE = "jit_batch_maintain"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"

_LEN = 2  # wire type of a length-delimited field


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """``(field, wire type, value)`` of each field of the message in
    ``buf[i:end]``; a length-delimited value is its ``(start, end)``."""
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = None, i + 8
        elif wire == _LEN:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield field, wire, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span) -> tuple:
    key = value = None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane_scopes(buf, span) -> dict:
    """``{op HLO text: tf_op}`` of one ``XPlane``; empty unless it is a
    device's."""
    name, stat_names, metas = "", {}, []
    for f, wire, v in _fields(buf, *span):
        if wire != _LEN:
            continue
        if f == 2:
            name = _text(buf, v)
        elif f == 4:
            metas.append(v)
        elif f == 5:
            key, value = _map_entry(buf, v)
            if value is not None:
                for sf, _, sv in _fields(buf, *value):
                    if sf == 2:
                        stat_names[key] = _text(buf, sv)
    if not name.startswith("/device:"):
        return {}
    tf_ids = {k for k, n in stat_names.items() if n == "tf_op"}
    out = {}
    for span in metas:
        _, value = _map_entry(buf, span)
        if value is None:
            continue
        op, tf_op = None, None
        for mf, _, mv in _fields(buf, *value):
            if mf == 2:
                op = _text(buf, mv)
            elif mf == 5:
                mid, val = None, None
                for xf, _, xv in _fields(buf, *mv):
                    if xf == 1:
                        mid = xv
                    elif xf == 5:
                        val = _text(buf, xv)
                    elif xf == 7:
                        val = stat_names.get(xv)
                if mid in tf_ids:
                    tf_op = val
        if op is not None and tf_op:
            out[op] = tf_op
    return out


def stage(tf_op: str) -> str:
    """The first path component after the outermost ``jit(...)`` of a
    ``tf_op`` (``jit(f)/closure/while:`` -> ``closure``; the part after
    the last ``:`` is the op's type); ``""`` for an op at the program's
    top level or outside any ``jit``."""
    path = tf_op.rsplit(":", 1)[0]
    if not path.startswith("jit("):
        return ""
    depth, i = 1, len("jit(")
    while i < len(path) and depth:
        depth += {"(": 1, ")": -1}.get(path[i], 0)
        i += 1
    parts = path[i:].split("/")        # "/closure/while" -> ["", ...]
    return parts[1] if len(parts) > 2 else ""


def op_scopes(raw: bytes) -> dict:
    """``{op HLO text: stage}`` over every device plane of a serialized
    ``XSpace``; an HLO text the planes give two stages maps to ``""``."""
    buf = memoryview(raw)
    out: dict = {}
    for f, wire, v in _fields(buf, 0, len(buf)):
        if f != 1 or wire != _LEN:
            continue
        for op, tf_op in _plane_scopes(buf, v).items():
            s = stage(tf_op)
            out[op] = s if out.get(op, s) == s else ""
    return out


def read_raw(path: str) -> bytes:
    """The serialized ``XSpace`` of an ``.xplane.pb`` file (gzip-compressed
    or not), or of the one such file under a profiler log directory."""
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{len(found)} traces under {path}")
        path = found[0]
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def device_ops(pd) -> dict:
    """``{device plane name: [(module, op HLO text, t0_ns, t1_ns)]}`` of
    every device plane of a ``jax.profiler.ProfileData``."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
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
            ops.append((mod, ev.name, t0, t0 + int(ev.duration_ns)))
        out[plane.name] = ops
    return out


def device_scopes(devices, scopes: dict) -> list:
    """``[module/stage, seconds]`` of the leaf ops of every device, longest
    first; ``devices`` holds one list of ``device_ops`` per device.  A
    leaf contains no other op of its device (a ``while`` op's event spans
    the events of its body).  An op of no known stage counts under
    ``module/``."""
    tot: dict = {}
    for ops in devices:
        ops = sorted(ops, key=lambda o: (o[2], -o[3]))
        for i, (mod, hlo, t0, t1) in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1][2] < t1:
                continue
            key = f"{mod}/{scopes.get(hlo, '')}"
            tot[key] = tot.get(key, 0.0) + (t1 - t0) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])]


def stage_share(rows, module: str = BATCH_MODULE,
                stages=STAGES) -> float | None:
    """Share of ``module``'s leaf time that falls in ``stages`` (``rows``:
    all of ``device_scopes``); ``None`` when the module ran no op."""
    mine = {k.split("/", 1)[1]: v for k, v in rows
            if k.split("/", 1)[0] == module}
    total = sum(mine.values())
    if total <= 0:
        return None
    return sum(mine.get(s, 0.0) for s in stages) / total


def summarize(path: str) -> dict:
    """``device_scopes`` and ``stage_share`` of the trace at ``path``."""
    from jax.profiler import ProfileData

    raw = read_raw(path)
    ops = device_ops(ProfileData.from_serialized_xspace(raw))
    rows = device_scopes(ops.values(), op_scopes(raw))
    return {"device_scopes": rows, "stage_share": stage_share(rows)}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    print(json.dumps(summarize(argv[0])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
