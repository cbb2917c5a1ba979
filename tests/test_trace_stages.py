"""Tier-1 tests for the served write path's instrumentation.

* device stages — ``batch_maintain``'s four ``jax.named_scope`` stages
  (``ranges``, ``structural``, ``closure``, ``repeel``) name every op of
  its compiled program;
* work counters — ``truss_closure_iterations_total``,
  ``truss_affected_edges_total`` and ``truss_closure_unfiltered_total``
  advance by what the generation's ``batch_maintain`` returned
  (``PeelStats.closure_iters``, ``closure_unfiltered``, ``frontier``);
* host spans — a serial flush records one span per host stage, nested as
  ``docs/OBSERVABILITY.md`` documents;
* profiler clock — every ``repro.obs`` span is a ``TraceAnnotation`` in a
  running ``jax.profiler`` trace (and none under ``obs.disabled()``);
* compiles — ``truss_xla_compiles_total`` and ``xla.compile`` spans;
* reading stages back — ``repro.obs.stages`` on hand-made ``XSpace``
  bytes (the stage of a ``tf_op``, device planes only, leaf time per
  ``module/stage`` and device).

The registry and default tracer are process-global, so tests assert
deltas.
"""
import glob
import os
import re
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import GraphSpec, decompose
from repro.core.batch import batch_maintain
from repro.core.graph import from_edge_list
from repro.core.maintenance import OP_DELETE, OP_INSERT
from repro.obs import metrics, stages, trace
from repro.obs.trace import Tracer
from repro.service import TrussService, TrussStore

N = 13
D_MAX = 16
E_CAP = 160
SPEC = GraphSpec(n_nodes=N, d_max=D_MAX, e_cap=E_CAP)
STAGES = {"ranges", "structural", "closure", "repeel"}
COUNTERS = ("truss_closure_iterations_total", "truss_affected_edges_total",
            "truss_closure_unfiltered_total")


def _graph(seed=3, p=0.4):
    rng = np.random.default_rng(seed)
    return [(i, j) for i in range(N) for j in range(i + 1, N)
            if rng.random() < p]


def _absent(edges):
    present = set(edges)
    return [(i, j) for i in range(N) for j in range(i + 1, N)
            if (i, j) not in present]


def _pad(pairs, bsz=4):
    a, b, m = (np.zeros(bsz, np.int32), np.zeros(bsz, np.int32),
               np.zeros(bsz, bool))
    for i, (x, y) in enumerate(pairs):
        a[i], b[i], m[i] = x, y, True
    return jnp.asarray(a), jnp.asarray(b), jnp.asarray(m)


def _counters():
    return {c: metrics.REGISTRY.value(c) for c in COUNTERS}


# -- device stages -------------------------------------------------------------
@pytest.mark.parametrize("method", ["sorted", "bitmap"])
def test_batch_maintain_stages_name_every_op(method):
    """Every op of the compiled program carries one of the four stages as
    the first component of its ``op_name`` after ``jit(batch_maintain)``,
    and every stage is there."""
    edges = np.asarray(_graph())
    st = from_edge_list(SPEC, edges)
    z = jnp.zeros(4, jnp.int32)
    m = jnp.zeros(4, bool)
    hlo = batch_maintain.lower(SPEC, st, z, z, m, z, z, m,
                               method=method).compile().as_text()
    names = re.findall(r'op_name="jit\(batch_maintain\)/([^"]*)"', hlo)
    assert names
    stages = {n.split("/")[0].split(":")[0] for n in names}
    assert stages == STAGES, stages


# -- work counters ---------------------------------------------------------------
def test_closure_iterations_cover_the_affected_set():
    """Each edge of A enters the closure's frontier once and an iteration
    expands at most one chunk, so iterations × chunk ≥ |A|; the returned
    |A| is the re-peel's ``PeelStats.frontier``."""
    edges = _graph()
    dels = edges[:3]
    inss = _absent(edges)[:3]
    st = from_edge_list(SPEC, np.asarray(edges))
    st = st._replace(phi=decompose(SPEC, st))
    chunk = 2
    st1, _lo, _hi, stats = batch_maintain(
        SPEC, st, *_pad(dels), *_pad(inss), batch=chunk)
    affected, iters = int(stats.frontier), int(stats.closure_iters)
    assert affected >= len(inss)          # inserted edges are always in A
    assert iters >= 1
    assert iters * chunk >= affected
    assert affected <= int(np.asarray(st1.active).sum())


def test_work_counters_advance_by_the_generation_returned(tmp_path):
    edges = _graph()
    svc = TrussService(N, edges, d_max=D_MAX, e_cap=E_CAP, flush_every=6,
                       strategy="fused",
                       store=TrussStore(str(tmp_path / "store")))
    before = _counters()
    ins = _absent(edges)
    batch = ([(OP_DELETE, a, b) for a, b in edges[:3]]
             + [(OP_INSERT, a, b) for a, b in ins[:3]])
    svc.submit_many(batch)
    assert svc.gen == 1
    after = _counters()
    peel = svc.stats()["peel"]
    assert peel["frontier"] > 0
    assert (after["truss_affected_edges_total"]
            - before["truss_affected_edges_total"]) == peel["frontier"]
    iters = (after["truss_closure_iterations_total"]
             - before["truss_closure_iterations_total"])
    assert iters == peel["closure_iters"] >= 1
    assert iters * 256 >= peel["frontier"]
    assert svc.stats()["counters"]["xla_compiles"] == \
        metrics.REGISTRY.value("truss_xla_compiles_total")
    # a query's flush commits nothing new: no counter moves again
    svc.flush()
    assert _counters() == after


@pytest.mark.parametrize("mixed", [True, False],
                         ids=["mixed_touching", "insert_only"])
def test_unfiltered_closure_counted_only_when_not_separable(tmp_path, mixed):
    """A mixed batch whose insert shares a node with a delete runs the
    closure unfiltered (``hi`` = +inf); an insert-only batch keeps the
    range filter."""
    edges = _graph()
    svc = TrussService(N, edges, d_max=D_MAX, e_cap=E_CAP, flush_every=4,
                       strategy="fused")
    ins = _absent(edges)
    if mixed:
        da, db = edges[0]
        touching = [e for e in ins if da in e or db in e][:2]
        batch = [(OP_DELETE, da, db)] + [(OP_INSERT, a, b)
                                         for a, b in touching]
    else:
        batch = [(OP_INSERT, a, b) for a, b in ins[:4]]
    before = metrics.REGISTRY.value("truss_closure_unfiltered_total")
    svc.submit_many(batch)
    svc.flush()
    assert svc.stats()["peel"]["closure_unfiltered"] == int(mixed)
    assert (metrics.REGISTRY.value("truss_closure_unfiltered_total")
            - before) == int(mixed)


# -- host spans ------------------------------------------------------------------
def test_serial_flush_records_every_host_stage(tmp_path):
    edges = _graph()
    svc = TrussService(N, edges, d_max=D_MAX, e_cap=E_CAP, flush_every=8,
                       strategy="fused", support_method="bitmap",
                       store=TrussStore(str(tmp_path / "store")))
    ins = _absent(edges)
    batch = ([(OP_DELETE, a, b) for a, b in edges[:4]]
             + [(OP_INSERT, a, b) for a, b in ins[:4]])
    trace.TRACER.clear()
    svc.submit_many(batch)
    assert svc.gen == 1
    ev = trace.TRACER.events()
    top = sorted((e for e in ev if e.depth == 0), key=lambda e: e.t0_ns)
    assert [e.name for e in top] == ["service.admit", "wal.append",
                                     "flush"], top
    flush = top[-1]
    kids = [e.name for e in sorted((e for e in ev if e.parent == flush.seq),
                                   key=lambda e: e.t0_ns)]
    assert kids == ["wal.fsync", "graph.net", "graph.bitmap_apply",
                    "graph.apply_batch", "gen.wait", "gen.commit"], kids
    for e in ev:
        if e.parent == flush.seq:
            assert flush.t0_ns <= e.t0_ns
            assert e.t0_ns + e.dur_ns <= flush.t0_ns + flush.dur_ns


# -- profiler clock --------------------------------------------------------------
@pytest.mark.parametrize("enabled", [True, False],
                         ids=["enabled", "disabled"])
def test_spans_reach_the_profiler_trace(tmp_path, enabled):
    from jax.profiler import ProfileData

    name = f"test.span.{'on' if enabled else 'off'}"
    with jax.profiler.trace(str(tmp_path)):
        if enabled:
            with trace.span(name):
                jnp.ones(4).block_until_ready()
        else:
            with obs.disabled(), trace.span(name):
                jnp.ones(4).block_until_ready()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1
    pd = ProfileData.from_file(found[0])
    host = {ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert (name in host) is enabled


# -- compiles --------------------------------------------------------------------
def test_compiles_are_counted_and_spanned():
    before = metrics.REGISTRY.value("truss_xla_compiles_total")
    trace.TRACER.clear()
    with trace.span("outer"):
        # a shape no other test compiles: a fresh backend compilation
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(37)).block_until_ready()
    assert metrics.REGISTRY.value("truss_xla_compiles_total") > before
    ev = trace.TRACER.events()
    outer = next(e for e in ev if e.name == "outer")
    comp = [e for e in ev if e.name == "xla.compile"]
    assert comp and all(e.parent == outer.seq and e.dur_ns > 0
                        for e in comp)
    assert all(outer.t0_ns <= e.t0_ns
               and e.t0_ns + e.dur_ns <= outer.t0_ns + outer.dur_ns
               for e in comp)


def test_complete_records_a_span_ending_now():
    now = [1000]
    tr = Tracer(clock=lambda: now[0])
    with tr.span("outer"):
        now[0] = 1500
        tr.complete("xla.compile", 300, k=1)
        now[0] = 1600
    inner, outer = tr.events()
    assert (inner.name, inner.t0_ns, inner.dur_ns) == ("xla.compile", 1200,
                                                       300)
    assert inner.parent == outer.seq and inner.depth == 1
    assert inner.attrs == {"k": 1}
    with obs.disabled():
        tr.complete("ignored", 5)
    assert len(tr.events()) == 2


# -- reading stages back ---------------------------------------------------------
def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _msg(field: int, payload) -> bytes:
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _entry(key: int, value: bytes) -> bytes:
    return _int(1, key) + _msg(2, value)


def _plane(name: str, ops: dict) -> bytes:
    """An ``XPlane`` whose event metadata carries ``{HLO text: tf_op}``:
    a ``str_value`` for odd ids, a ``ref_value`` (an interned string in
    ``stat_metadata``) for even ones; ``None`` gives no ``tf_op`` stat.
    Every event also has a double-valued stat, and the plane a ``lines``
    field that is not a message: neither may be parsed as a stage."""
    stat_meta = {7: "tf_op", 8: "flops"}
    events = b""
    for i, (op, tf_op) in enumerate(ops.items(), start=1):
        stats = _msg(5, _int(1, 8) + _varint(2 << 3 | 1)
                     + struct.pack("<d", 1.5))
        if tf_op is not None and i % 2:
            stats += _msg(5, _int(1, 7) + _msg(5, tf_op))
        elif tf_op is not None:
            ref = 100 + i
            stat_meta[ref] = tf_op
            stats += _msg(5, _int(1, 7) + _int(7, ref))
        meta = _int(1, i) + _msg(2, op) + stats
        events += _msg(4, _entry(i, meta))
    stat_bytes = b"".join(_msg(5, _entry(k, _int(1, k) + _msg(2, n)))
                          for k, n in stat_meta.items())
    lines = _msg(3, b"\xff\xfe\xfd not a message, never parsed")
    return _int(1, 3) + _msg(2, name) + lines + events + stat_bytes


def _space(*planes) -> bytes:
    return b"".join(_msg(1, p) for p in planes)


@pytest.mark.parametrize("tf_op, want", [
    ("jit(batch_maintain)/closure/while/body/gather:", "closure"),
    ("jit(batch_maintain)/repeel/jit(delta_peel)/while:while", "repeel"),
    ("jit(f(g))/ranges/jit(searchsorted)/select_n:", "ranges"),
    ("jit(batch_maintain)/concatenate:", ""),
    ("args[1]:", ""),
    ("", ""),
])
def test_stage_of_tf_op(tf_op, want):
    assert stages.stage(tf_op) == want


def test_op_scopes_reads_device_planes_only():
    ops = {"%fusion.1 = s32[8] fusion()": "jit(f)/closure/while/body/x:",
           "%copy.2 = s32[8] copy()": "jit(f)/repeel/y:",
           "%sort.3 = s32[8] sort()": None,
           "%and.4 = pred[8] and()": "jit(f)/and:"}
    raw = _space(_plane("/host:CPU", {"%host.9": "jit(f)/ranges/z:"}),
                 _plane("/device:TPU:0", ops))
    assert stages.op_scopes(raw) == {
        "%fusion.1 = s32[8] fusion()": "closure",
        "%copy.2 = s32[8] copy()": "repeel",
        "%and.4 = pred[8] and()": ""}


def test_op_scopes_drops_a_stage_two_planes_disagree_on():
    raw = _space(_plane("/device:TPU:0", {"%op.1": "jit(f)/closure/a:",
                                          "%op.2": "jit(f)/ranges/b:"}),
                 _plane("/device:TPU:1", {"%op.1": "jit(f)/repeel/a:",
                                          "%op.2": "jit(f)/ranges/b:"}))
    assert stages.op_scopes(raw) == {"%op.1": "", "%op.2": "ranges"}


def test_device_scopes_counts_leaves_by_module_stage_and_device():
    """A ``while`` op that spans its body is not a leaf; an op that
    overlaps another device's op in time still is."""
    m = stages.BATCH_MODULE
    dev0 = [(m, "%while.1", 0, 100),          # encloses the body
            (m, "%fusion.2", 0, 60),
            (m, "%fusion.3", 60, 90),
            (m, "%copy.4", 100, 110),
            ("jit_other", "%fusion.5", 200, 205)]
    dev1 = [(m, "%fusion.2", 10, 50)]          # inside device 0's while
    sc = {"%while.1": "closure", "%fusion.2": "closure",
          "%fusion.3": "repeel"}
    rows = stages.device_scopes([dev0, dev1], sc)
    assert rows == [[f"{m}/closure", pytest.approx(100e-9)],
                    [f"{m}/repeel", pytest.approx(30e-9)],
                    [f"{m}/", pytest.approx(10e-9)],
                    ["jit_other/", pytest.approx(5e-9)]]
    assert stages.stage_share(rows) == pytest.approx(130 / 140)
    assert stages.stage_share(rows, "jit_missing") is None
