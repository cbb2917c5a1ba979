"""Tier-1 tests for the operability plane (ISSUE-9).

Three load-bearing properties:

1. **SLO state machine** — multi-window burn rates computed from real
   registry snapshots under an injected clock walk ok -> burning ->
   violated at the declared horizons, and recovery is hysteretic (a clear
   must hold for ``clear_s`` before the objective returns to ok).
2. **Postmortem bundles** — a seeded ``FaultyIO`` schedule that drives the
   breaker open makes the flight recorder dump a self-contained JSON
   bundle whose trace excerpt, metrics snapshot, frontier, and SLO state
   all reference real recorded facts; a clean run dumps nothing.
3. **Cross-process trace join** — a router -> pipelined-primary -> replica
   round trip recorded by two processes merges into one Chrome trace where
   a single ``trace_id`` spans all three components, and every per-process
   track is well-nested.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.cluster import QueryRouter, Replica
from repro.faults import FaultyIO, seeded_schedule
from repro.obs import flightrec, merge, metrics
from repro.obs import trace as obs_trace
from repro.obs.slo import BURNING, OK, VIOLATED, Objective, SLOEngine
from repro.core import OP_INSERT
from repro.service import (MEMBERS, QueryRequest, TrussService, TrussStore)
from repro.service.api import Unavailable

N = 13
D_MAX = 16
E_CAP = 160


def _svc(edges, tmpdir=None, **kw):
    kw.setdefault("tracked_ks", (3, 4))
    kw.setdefault("flush_every", 5)
    store = TrussStore(str(tmpdir)) if tmpdir is not None else None
    return TrussService(N, edges, d_max=D_MAX, e_cap=E_CAP, store=store, **kw)


def _random_graph(rng, p, n=N):
    return [(i, j) for i in range(n) for j in range(i + 1, n)
            if rng.random() < p]


# -- SLO burn-rate state machine ---------------------------------------------

def _slo_fixture():
    """A private registry + latency objective + engine on a fake clock."""
    reg = metrics.Registry()
    hist = reg.histogram("truss_query_seconds", buckets=(0.01, 0.05, 0.1))
    obj = Objective("q-p99", "latency", "truss_query_seconds", target=0.99,
                    threshold=0.05, fast_s=10.0, slow_s=50.0,
                    burn_threshold=2.0, violate_after_s=30.0, clear_s=20.0)
    clock = {"t": 0.0}
    eng = SLOEngine([obj], registry=reg, clock=lambda: clock["t"],
                    min_interval_s=0.0)
    return reg, hist, obj, clock, eng


def test_slo_ok_under_budget():
    _, hist, _, clock, eng = _slo_fixture()
    for t in range(0, 60, 5):
        clock["t"] = float(t)
        for _ in range(100):
            hist.observe(0.001)          # all under the 50ms threshold
        state = eng.evaluate(force=True)
    assert state["overall"] == OK
    assert state["objectives"]["q-p99"]["burn_fast"] == 0.0


def test_slo_burning_violated_and_hysteretic_recovery():
    _, hist, _, clock, eng = _slo_fixture()
    # error storm: every observation blows the 50ms threshold -> burn
    # rate = (1.0 error rate)/(0.01 budget) = 100x in both windows
    for t in range(0, 30, 5):
        clock["t"] = float(t)
        hist.observe(1.0)
        eng.evaluate(force=True)
        want = BURNING if t < 30 else VIOLATED
        assert eng._state["q-p99"] == want, t
    # sustained past violate_after_s=30 -> violated
    clock["t"] = 31.0
    hist.observe(1.0)
    eng.evaluate(force=True)
    assert eng.overall() == VIOLATED
    assert eng.health()["status"] == VIOLATED
    # recovery: fast window (10s) goes clean but the slow window (50s)
    # still holds the storm -> not burning-now, hysteresis countdown starts
    for t in range(35, 52, 4):
        clock["t"] = float(t)
        for _ in range(500):
            hist.observe(0.001)
        eng.evaluate(force=True)
        assert eng.overall() == VIOLATED  # clear_s=20 not yet served
    clock["t"] = 56.0                     # clean since t=35 -> 21s >= 20s
    for _ in range(500):
        hist.observe(0.001)
    eng.evaluate(force=True)
    assert eng.overall() == OK
    # the transition counter saw the full walk
    snap = metrics.REGISTRY.snapshot()["truss_slo_transitions_total"]
    trans = {k: v for k, v in snap["values"].items() if k[0] == "q-p99"}
    assert trans[("q-p99", "burning")] >= 1
    assert trans[("q-p99", "violated")] >= 1
    assert trans[("q-p99", "ok")] >= 1


def test_slo_gauge_and_availability_objectives():
    reg = metrics.Registry()
    lag = reg.gauge("truss_replica_lag_gens", labels=("replica",))
    good = reg.counter("good_total")
    bad = reg.counter("bad_total")
    objs = [
        Objective("lag", "gauge", "truss_replica_lag_gens", target=0.9,
                  threshold=8.0, fast_s=10.0, slow_s=20.0),
        Objective("avail", "availability", "good_total", target=0.9,
                  bad_family="bad_total", fast_s=10.0, slow_s=20.0),
    ]
    clock = {"t": 0.0}
    eng = SLOEngine(objs, registry=reg, clock=lambda: clock["t"],
                    min_interval_s=0.0)
    lag.labels(replica="r0").set(2)
    good.inc(100)
    eng.evaluate(force=True)
    assert eng._state["lag"] == OK and eng._state["avail"] == OK
    # lag blows the threshold; every availability event is now bad
    lag.labels(replica="r0").set(50)
    bad.inc(100)
    clock["t"] = 5.0
    eng.evaluate(force=True)
    assert eng._state["lag"] == BURNING
    assert eng._state["avail"] == BURNING
    d = eng.state_dict()["objectives"]
    assert d["lag"]["burn_fast"] > 1.0 and d["avail"]["burn_fast"] > 1.0


def test_slo_rate_limit_and_stats_surface(tmp_path):
    """stats()["slo"] appears when an engine is attached, and evaluate()
    honors min_interval_s unless forced."""
    rng = np.random.default_rng(0)
    svc = _svc(_random_graph(rng, 0.3), tmp_path)
    clock = {"t": 0.0}
    # private registry: under the full suite the process-global one carries
    # hours of compile-inclusive query latencies from earlier tests, and a
    # fresh engine's first window would see them all at once as burn
    eng = SLOEngine(registry=metrics.Registry(),
                    clock=lambda: clock["t"], min_interval_s=10.0)
    svc.attach_slo(eng)
    out = svc.stats()
    assert out["slo"]["overall"] == OK
    assert set(out["slo"]["objectives"]) == {
        "query-p99", "write-ack-p99", "replica-lag",
        "committed-read-availability"}
    n0 = len(eng._samples)
    clock["t"] = 1.0
    eng.evaluate()               # rate-limited: no new sample
    assert len(eng._samples) == n0
    eng.evaluate(force=True)
    assert len(eng._samples) == n0 + 1


# -- flight recorder / postmortems -------------------------------------------

@pytest.fixture
def flight(tmp_path):
    """A freshly reset process-global recorder dumping into tmp_path."""
    flightrec.FLIGHT.reset()
    flightrec.FLIGHT.configure(str(tmp_path / "pm"))
    yield flightrec.FLIGHT
    flightrec.FLIGHT.reset()


def test_clean_run_dumps_nothing(flight, tmp_path):
    rng = np.random.default_rng(1)
    svc = _svc(_random_graph(rng, 0.3), tmp_path / "store")
    for i in range(5, 10):
        a, b = i % N, (i + 3) % N
        key = (min(a, b), max(a, b))
        svc.submit(OP_INSERT if key not in svc._view else 0, a, b)
    svc.handle(QueryRequest(kind=MEMBERS, k=3))
    svc.scrub()
    assert flight.dumps == []
    assert os.listdir(tmp_path / "pm") == []


def _drive_until_degraded(svc, rng, max_steps=200):
    """Submit random writes until the breaker opens (or give up)."""
    for i in range(max_steps):
        a, b = int(rng.integers(0, N)), int(rng.integers(0, N))
        if a == b:
            continue
        key = (min(a, b), max(a, b))
        try:
            svc.submit(OP_INSERT if key not in svc._view else 0, a, b)
        except (Unavailable, OSError):
            pass
        if svc._degraded_reason is not None:
            return True
    return False


def test_seeded_chaos_dumps_validated_bundle(flight, tmp_path):
    """A sticky seeded fault schedule opens the breaker; the dumped bundle
    is valid JSON whose excerpt/metrics/frontier/SLO sections reference
    only facts the process actually recorded."""
    rng = np.random.default_rng(2)
    edges = _random_graph(rng, 0.3)
    faults = seeded_schedule(3, n_faults=4, sticky=True)
    store = TrussStore(str(tmp_path / "store"), io=FaultyIO(faults))
    svc = TrussService(N, edges, d_max=D_MAX, e_cap=E_CAP, store=store,
                       tracked_ks=(3,), flush_every=3)
    eng = SLOEngine()
    svc.attach_slo(eng)
    flight.configure(frontier=lambda: {"gen": svc.gen,
                                       "wal_applied": svc._applied_wal},
                     slo=eng.state_dict)
    assert _drive_until_degraded(svc, rng), "schedule never tripped"
    assert len(flight.dumps) >= 1
    bundle = json.load(open(flight.dumps[0]))
    assert bundle["format"] == "truss-postmortem-v1"
    assert bundle["trigger"] in ("breaker_open", "quarantine",
                                 "scrub_violation", "slo_violation")
    # the trace excerpt holds only spans the tracer actually recorded
    assert bundle["trace_excerpt"], "excerpt must not be empty"
    recorded = {e.name for e in obs_trace.TRACER.events()}
    recorded |= {"wal.append", "wal.fsync", "service.degraded",
                 "wal.append_failed", "gen.commit", "graph.apply_batch"}
    for ev in bundle["trace_excerpt"]:
        assert set(ev) >= {"seq", "name", "t0_ns", "dur_ns"}
    # every metric family in the snapshot exists in the live registry
    fams = metrics.REGISTRY.families()
    for name in bundle["metrics"]:
        assert name in fams, name
    assert bundle["metrics"]["truss_postmortem_trips_total"]["values"]
    # provider sections: frontier matches the engine, SLO state is shaped
    assert bundle["frontier"]["gen"] == svc.gen
    assert bundle["frontier"]["wal_applied"] == svc._applied_wal
    assert bundle["slo"]["overall"] in (OK, BURNING, VIOLATED)
    assert set(bundle["slo"]["objectives"]) == {
        o.name for o in eng.objectives}
    # the wal-op ring captured commits before the trip
    assert any(n["kind"] == "commit" for n in bundle["wal_ops"])


def test_trip_without_dir_only_counts(tmp_path):
    flightrec.FLIGHT.reset()
    try:
        before = metrics.REGISTRY.value("truss_postmortem_trips_total")
        assert flightrec.FLIGHT.trip("unit-test", detail=1) is None
        after = metrics.REGISTRY.value("truss_postmortem_trips_total")
        assert after == before + 1
    finally:
        flightrec.FLIGHT.reset()


def test_dump_cap(tmp_path):
    flightrec.FLIGHT.reset()
    try:
        flightrec.FLIGHT.configure(str(tmp_path), max_dumps=2)
        paths = [flightrec.FLIGHT.trip("t") for _ in range(5)]
        assert sum(p is not None for p in paths) == 2
        assert len(os.listdir(tmp_path)) == 2
    finally:
        flightrec.FLIGHT.reset()


# -- cross-process trace merge ------------------------------------------------

def _well_nested(events):
    """Spans on one track must nest: any two overlapping intervals are
    contained one in the other (zero-duration instants always nest)."""
    spans = sorted(((e["ts"], e["ts"] + e["dur"]) for e in events
                    if e.get("ph") == "X"), key=lambda s: (s[0], -s[1]))
    stack = []
    for s0, s1 in spans:
        while stack and stack[-1] <= s0:
            stack.pop()
        if stack and s1 > stack[-1] + 1e-9:
            return False  # overlaps the enclosing span's end: not nested
        stack.append(s1)
    return True


def test_merge_rebases_clocks_and_separates_pids(tmp_path):
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    a.write_text(json.dumps({"clock_sync": {"wall_ns": 1_000_000,
                                            "perf_ns": 0},
                             "pid": 7, "proc": "alpha"}) + "\n"
                 + json.dumps({"seq": 0, "parent": -1, "depth": 0,
                               "name": "x", "t0_ns": 5_000, "dur_ns": 2_000,
                               "attrs": {"trace_id": "t1"}}) + "\n")
    b.write_text(json.dumps({"clock_sync": {"wall_ns": 4_000_000,
                                            "perf_ns": 3_000_000},
                             "pid": 7, "proc": "beta"}) + "\n"
                 + json.dumps({"seq": 0, "parent": -1, "depth": 0,
                               "name": "y", "t0_ns": 5_000,
                               "dur_ns": 1_000,
                               "attrs": {"trace_id": "t1"}}) + "\n")
    doc = merge.merge_files([str(a), str(b)])
    xs = {e["name"]: e for e in doc["traceEvents"] if e.get("ph") == "X"}
    # both events rebase onto the same wall timeline: 1.005ms and 1.005ms
    assert xs["x"]["ts"] == pytest.approx(xs["y"]["ts"])
    assert xs["x"]["pid"] != xs["y"]["pid"]  # colliding pids separated
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M"}
    assert names == {"alpha", "beta"}
    ids = merge.trace_ids(doc)
    assert set(ids) == {"t1"}
    assert set(ids["t1"]) == {xs["x"]["pid"], xs["y"]["pid"]}


_REPLICA_SCRIPT = """
import sys
from repro.cluster import Replica
from repro.obs import trace

writer = trace.TraceWriter(sys.argv[2], proc="replica")
rep = Replica(sys.argv[1], "r-sub")
rep.poll()
writer.close()
print(f"applied={rep.gen}")
"""


def test_e2e_router_primary_replica_single_trace(tmp_path):
    """The acceptance trace: writes enter at the router edge of a pipelined
    primary, a *separate process* tails the WAL, and the merged Chrome
    trace shows one trace id spanning router, primary, and replica spans —
    each process track well-nested."""
    obs_trace.TRACER.clear()
    rng = np.random.default_rng(3)
    edges = _random_graph(rng, 0.35)
    svc = _svc(edges, tmp_path / "store", pipeline=True, flush_every=4)
    router = QueryRouter(svc, [], poll_on_miss=False)
    writer = obs_trace.TraceWriter(str(tmp_path / "edge.jsonl"),
                                   proc="router-primary")
    for i in range(8):
        a, b = int(rng.integers(0, N)), int(rng.integers(0, N))
        if a == b:
            continue
        key = (min(a, b), max(a, b))
        router.submit(OP_INSERT if key not in svc._view else 0, a, b)
    router.route(QueryRequest(kind=MEMBERS, k=3))
    svc.flush()           # land the pipelined tail; commit.json published
    writer.close()        # (no final snapshot: the replica must TAIL the
                          # WAL through gen.replay, not bootstrap past it)

    proc = subprocess.run(
        [sys.executable, "-c", _REPLICA_SCRIPT, str(tmp_path / "store"),
         str(tmp_path / "replica.jsonl")],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.pathsep.join(
                 [os.path.join(os.path.dirname(__file__), "..", "src")]
                 + sys.path)})
    assert proc.returncode == 0, proc.stderr
    assert f"applied={svc.gen}" in proc.stdout

    doc = merge.merge_files([str(tmp_path / "edge.jsonl"),
                             str(tmp_path / "replica.jsonl")])
    by_pid = {}
    for ev in doc["traceEvents"]:
        if ev.get("ph") == "X":
            by_pid.setdefault(ev["pid"], []).append(ev)
    assert len(by_pid) == 2, "expected two process tracks"
    for pid, events in by_pid.items():
        assert _well_nested(events), f"track {pid} is not well-nested"
    # at least one router-minted trace id was joined by the replica's
    # gen.replay span in the other process
    spanning = {tid: pids for tid, pids in merge.trace_ids(doc).items()
                if len(pids) == 2}
    assert spanning, "no trace id spans both processes"
    names_by_tid = {}
    for ev in doc["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        tid = (ev.get("args") or {}).get("trace_id")
        if tid in spanning:
            names_by_tid.setdefault(tid, set()).add(ev["name"])
    joined = set().union(*names_by_tid.values())
    assert any(n.startswith("router.") for n in joined)   # router edge
    assert "wal.append" in joined or "gen.commit" in joined  # primary
    assert "gen.replay" in joined                         # replica apply


def test_wal_trace_annotations_round_trip(tmp_path):
    """The # trace record: appended next to its generation, read back by
    scans and tails, checksummed, and invisible to record counting."""
    store = TrussStore(str(tmp_path))
    store.append_annotation(1, "ab" * 16)
    store.append(1, [(OP_INSERT, 0, 1), (OP_INSERT, 1, 2)])
    store.append_annotation(2, "cd" * 16)
    store.append(2, [(OP_INSERT, 2, 3)])
    assert store.wal_len == 3            # annotations are not records
    assert store.read_trace_annotations() == {1: "ab" * 16, 2: "cd" * 16}
    fresh = TrussStore(str(tmp_path), readonly=True)
    assert fresh.read_trace_annotations() == {1: "ab" * 16, 2: "cd" * 16}
    assert len(fresh.read_wal()) == 3
    # a corrupted annotation is skipped by the scan, not fatal
    raw = open(store.wal_path, "rb").read()
    bad = raw.replace(b"# trace 2", b"# trace x", 1)
    open(store.wal_path, "wb").write(bad)
    again = TrussStore(str(tmp_path), readonly=True)
    assert again.read_trace_annotations().get(1) == "ab" * 16
