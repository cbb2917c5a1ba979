"""Dev smoke: core truss engine vs oracle on small random graphs, a ~30s
end-to-end service smoke (ingest, query, snapshot, restore, re-answer), a
cluster smoke (primary + 2 WAL-tailing replicas + consistency-aware router
over one store dir: write, read under every policy, promote), a sharded
smoke (4 emulated devices in a subprocess: decompose + fused batch bitwise
vs the single-device engine and the oracle), a scale smoke (4 emulated
devices: ~10^5-edge node-partitioned decompose bitwise vs the replicated
single-device engine), and an obs smoke (serve_truss
subprocess with --metrics-port/--trace-out: scrape /metrics mid-run, parse
it, assert the serving metric families; the exit trace must load as Chrome
JSON), and a chaos smoke (sticky fsync EIO mid-run: writes shed, committed
reads keep serving, then clean recovery bitwise vs the oracle).

    python scripts/smoke_core.py              # everything
    python scripts/smoke_core.py obs          # one section
    python scripts/smoke_core.py core service # several
"""
import os
import subprocess
import sys
import tempfile
import numpy as np

sys.path.insert(0, "src")

from repro.core import (GraphSpec, from_edge_list, decompose, DynamicGraph,
                        oracle)
from repro.launch.mesh import emulated_devices_env


def rand_graph(rng, n, p):
    edges = [(i, j) for i in range(n) for j in range(i + 1, n) if rng.random() < p]
    return edges


def run_one(seed):
    rng = np.random.default_rng(seed)
    n = 12
    edges = rand_graph(rng, n, 0.35)
    if not edges:
        return
    # oracle decomposition
    adj = {i: set() for i in range(n)}
    for a, b in edges:
        adj[a].add(b)
        adj[b].add(a)
    ref = oracle.truss_decomposition(adj)

    spec = GraphSpec(n_nodes=n, d_max=n, e_cap=len(edges) + 8)
    st = from_edge_list(spec, np.asarray(edges))
    for method in ("sorted", "bitmap"):
        phi = np.asarray(decompose(spec, st, method))
        got = {tuple(e): int(p) for e, p in
               zip(np.asarray(st.edges)[: len(edges)], phi[: len(edges)])}
        assert got == ref, (seed, method, {k: (got[k], ref[k]) for k in ref if got[k] != ref[k]})

    # dynamic maintenance vs from-scratch on a random update stream
    g = DynamicGraph(n, edges)
    orc = oracle.Oracle(n, edges)
    present = set(map(tuple, edges))
    absent = [(i, j) for i in range(n) for j in range(i + 1, n) if (i, j) not in present]
    rng.shuffle(absent)
    for step in range(12):
        if present and (not absent or rng.random() < 0.5):
            e = list(present)[rng.integers(len(present))]
            present.discard(e)
            absent.append(e)
            g.delete(*e)
            orc.delete(*e)
        else:
            e = absent.pop()
            present.add(e)
            g.insert(*e)
            orc.insert(*e)
        orc.check()  # oracle incremental == oracle from-scratch
        got = g.phi_dict()
        exp = orc.phi
        assert got == exp, (seed, step, e,
                            {k: (got.get(k), exp.get(k)) for k in set(got) | set(exp)
                             if got.get(k) != exp.get(k)})


def smoke_service(n_updates=60, n_queries=20, seed=0):
    """Service lifecycle: ingest N updates in fused batches, answer M
    queries, snapshot, crash, restore, re-answer — restored answers must be
    identical and phi must match the oracle replay."""
    from repro.data.streams import GraphUpdateStream
    from repro.service import (MEMBERS, REPRESENTATIVES, QueryRequest,
                               TrussService, TrussStore)

    rng = np.random.default_rng(seed)
    n = 24
    edges = rand_graph(rng, n, 0.25)
    stream = GraphUpdateStream(np.asarray(edges), n, chunk=6, seed=seed + 1)
    with tempfile.TemporaryDirectory() as root:
        svc = TrussService(n, edges, tracked_ks=(3, 4), flush_every=8,
                           store=TrussStore(root))
        acked = []
        for _ in range(n_updates // 6):
            ups = [tuple(map(int, r)) for r in stream.next()]
            svc.submit_many(ups)
            acked += ups
        reqs = [QueryRequest(MEMBERS, k=3 + i % 2) for i in range(n_queries // 2)]
        reqs += [QueryRequest(REPRESENTATIVES, k=3 + i % 2)
                 for i in range(n_queries - len(reqs))]
        before = [{tuple(map(int, e)) for e in svc.handle(r).edges} for r in reqs]
        svc.snapshot(stream_state=stream.state_dict())
        del svc

        restored = TrussService.restore(TrussStore(root))
        after = [{tuple(map(int, e)) for e in restored.handle(r).edges} for r in reqs]
        assert before == after, "restored service answers diverged"
        orc = oracle.Oracle(n, edges)
        orc.apply(acked)
        assert restored.graph.phi_dict() == orc.phi, "restored phi != oracle"
        s2 = GraphUpdateStream(np.asarray(edges), n, chunk=6, seed=seed + 1)
        s2.load_state_dict(restored.stream_state)
        restored.submit_many([tuple(map(int, r)) for r in s2.next()])
        restored.flush()
    print(f"service smoke ok ({len(acked)} updates, {len(reqs)} queries, "
          f"snapshot/restore exact)")


def smoke_cluster(n_updates=48, seed=0):
    """Cluster lifecycle over one store dir: primary ingests, two replicas
    tail, the router serves every consistency policy (RYW never below the
    session token), then the primary dies and a promoted replica — checked
    bitwise against the oracle replay — keeps serving."""
    from repro.cluster import QueryRouter, Replica
    from repro.data.streams import GraphUpdateStream
    from repro.service import (BOUNDED, MEMBERS, READ_YOUR_WRITES, STRONG,
                               QueryRequest, TrussService, TrussStore)

    rng = np.random.default_rng(seed)
    n = 24
    edges = rand_graph(rng, n, 0.25)
    stream = GraphUpdateStream(np.asarray(edges), n, chunk=6, seed=seed + 1)
    with tempfile.TemporaryDirectory() as root:
        primary = TrussService(n, edges, tracked_ks=(3,), flush_every=8,
                               store=TrussStore(root))
        replicas = [Replica(root, f"replica-{i}") for i in range(2)]
        router = QueryRouter(primary, replicas)
        sess = router.session()
        acked = []
        for _ in range(n_updates // 6):
            ups = [tuple(map(int, r)) for r in stream.next()]
            sess.submit_many(ups)
            acked += ups
            router.poll_replicas()
            for consistency in (STRONG, BOUNDED, READ_YOUR_WRITES):
                resp = sess.query(QueryRequest(MEMBERS, k=3,
                                               consistency=consistency,
                                               bound=2))
                assert resp.gen >= (sess.token if consistency != BOUNDED
                                    else primary.gen - 2), consistency
        # replicas converged bitwise at the committed boundary
        router.poll_replicas()
        for rep in replicas:
            assert rep.gen == primary.gen
            for name, a, b in zip(primary.graph.state._fields,
                                  primary.graph.state, rep.svc.graph.state):
                assert np.array_equal(np.asarray(a), np.asarray(b)), name
        served = dict(router.served)
        del primary  # primary crash
        promoted = router.promote()
        orc = oracle.Oracle(n, edges)
        orc.apply(acked)
        assert promoted.graph.phi_dict() == orc.phi, "promoted phi != oracle"
        ups = [tuple(map(int, r)) for r in stream.next()]
        promoted.submit_many(ups)
        orc.apply(ups)
        promoted.flush()
        assert promoted.graph.phi_dict() == orc.phi
    print(f"cluster smoke ok ({len(acked)} writes, reads served {served}, "
          f"promote exact)")


def smoke_sharded(devices=4, seed=0):
    """Sharded peel substrate: re-exec on ``devices`` emulated host devices
    and check decompose (every discipline) + a fused batch flush bitwise
    against the single-device engine and the oracle."""
    code = f"""
import sys
sys.path.insert(0, "src")
import numpy as np
from repro.core import DynamicGraph, GraphSpec, from_edge_list, oracle
from repro.core.graph import pad_state, with_mesh
from repro.core.peel import peel
from repro.launch.mesh import make_shard_mesh

rng = np.random.default_rng({seed})
n = 20
edges = [(i, j) for i in range(n) for j in range(i + 1, n)
         if rng.random() < 0.3]
adj = {{i: set() for i in range(n)}}
for a, b in edges:
    adj[a].add(b); adj[b].add(a)
ref = oracle.truss_decomposition(adj)
mesh = make_shard_mesh({devices})
spec0 = GraphSpec(n_nodes=n, d_max=n, e_cap=len(edges))
spec = with_mesh(spec0, mesh)
st = pad_state(spec0, from_edge_list(spec0, np.asarray(edges)), spec)
for method, engine in (("bitmap", "delta"), ("bitmap", "recompute"),
                       ("sorted", "recompute")):
    p1, s1 = peel(spec, st, st.active, method=method, engine=engine)
    p2, s2 = peel(spec, st, st.active, method=method, engine=engine,
                  mesh=mesh)
    assert np.array_equal(np.asarray(p1), np.asarray(p2)), (method, engine)
    got = {{tuple(e): int(p) for e, p in
           zip(edges, np.asarray(p2)[:len(edges)])}}
    assert got == ref, (method, engine)

g1 = DynamicGraph(n, edges, support_method="bitmap")
g2 = DynamicGraph(n, edges, support_method="bitmap", mesh=mesh)
orc = oracle.Oracle(n, edges)
present = set(map(tuple, edges))
ins = sorted((i, j) for i in range(n) for j in range(i + 1, n)
             if (i, j) not in present)[:10]
ups = [(1, a, b) for a, b in ins] + [(0, a, b) for a, b in sorted(present)[:4]]
g1.apply_batch(ups, strategy="fused")
g2.apply_batch(ups, strategy="fused")
orc.apply(ups)
assert g1.phi_dict() == g2.phi_dict() == orc.phi
print("ok")
"""
    env = emulated_devices_env(devices)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    print(f"sharded smoke ok ({devices} devices, decompose + fused batch "
          f"bitwise vs single-device and oracle)")


def smoke_scale(devices=4, seed=7):
    """Node-partitioned bitmap at ~10^5 edges: re-exec on ``devices``
    emulated host devices, decompose with ``partition="nodes"`` and check
    phi + peel stats bitwise against the replicated single-device engine,
    plus the per-device slab footprint (1/S of the full bitmap)."""
    code = f"""
import sys
sys.path.insert(0, "src")
import numpy as np
from repro.core import GraphSpec, from_edge_list
from repro.core.graph import (build_bitmap_partitioned, pad_state,
                              shard_state, with_mesh)
from repro.core.peel import peel
from repro.launch.mesh import make_shard_mesh
from repro.data.synthetic import powerlaw_graph

n, m, cap = 8192, 16, 512
edges = powerlaw_graph(n, m, seed={seed}, max_degree=cap)
assert len(edges) > 100_000, len(edges)
spec0 = GraphSpec(n_nodes=n, d_max=cap, e_cap=len(edges))
st0 = from_edge_list(spec0, np.asarray(edges))
phi1, ps1 = peel(spec0, st0, st0.active, method="bitmap", engine="delta")

mesh = make_shard_mesh({devices})
spec = with_mesh(spec0, mesh, partition="nodes")
st = shard_state(spec, pad_state(spec0, st0, spec), mesh)
phi2, ps2 = peel(spec, st, st.active, method="bitmap", engine="delta",
                 mesh=mesh)
assert np.array_equal(np.asarray(phi2)[:spec0.e_cap], np.asarray(phi1))
assert all(int(a) == int(b) for a, b in zip(ps1, ps2))

bm = build_bitmap_partitioned(spec, st, st.active, mesh)
for sh in bm.addressable_shards:
    assert sh.data.shape == (spec.n_nodes, spec.word_block)
    assert sh.data.nbytes == spec.bitmap_bytes_per_device
print("ok %d edges %d waves" % (len(edges), int(ps2.waves)))
"""
    env = emulated_devices_env(devices)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    print(f"scale smoke ok ({devices} devices, ~10^5-edge partitioned "
          f"decompose bitwise vs replicated single-device; "
          f"{out.stdout.strip().splitlines()[-1]})")


def smoke_obs(ticks=4, seed=0):
    """Telemetry plane, end to end against a real subprocess: launch
    ``serve_truss`` with ``--metrics-port 0 --trace-out --pipeline``, scrape
    ``/metrics`` while it serves, parse the page with ``repro.obs.expo`` and
    assert the serving metric families carry real values; after exit the
    Chrome trace must load and contain the generation-commit spans."""
    import json
    import re
    import urllib.request

    from repro.obs import expo

    with tempfile.TemporaryDirectory() as root:
        trace_out = os.path.join(root, "trace.json")
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.serve_truss",
             "--store", os.path.join(root, "store"), "--nodes", "60",
             "--ticks", str(ticks), "--chunk", "6", "--seed", str(seed),
             "--pipeline", "--metrics-port", "0", "--trace-out", trace_out],
            env=dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu"),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        try:
            # the launcher prints the picked port before serving starts
            line = proc.stdout.readline()
            m = re.search(r"http://127\.0\.0\.1:(\d+)/metrics", line)
            assert m, f"no metrics URL in first line: {line!r}"
            url = m.group(0)
            import time as _time
            page = None
            while proc.poll() is None:  # scrape until the run finishes
                try:
                    with urllib.request.urlopen(url, timeout=5) as r:
                        assert r.headers["Content-Type"] == expo.CONTENT_TYPE
                        page = r.read().decode()
                except OSError:
                    break  # server already shut down between poll and GET
                _time.sleep(0.2)
            out, _ = proc.communicate(timeout=120)
            assert proc.returncode == 0, out
        finally:
            if proc.poll() is None:
                proc.kill()
        assert page is not None, "never managed a successful scrape"
        snap = expo.parse(page)
        for fam in ("truss_flush_total", "truss_wal_append_records_total",
                    "truss_wal_fsync_total", "truss_peel_seconds",
                    "truss_committed_gen", "truss_edges",
                    "truss_query_seconds"):
            assert fam in snap, (fam, sorted(snap))
        assert snap["truss_wal_append_records_total"]["values"][()] > 0
        with open(trace_out) as f:
            doc = json.load(f)
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"flush", "wal.append", "query"} <= names, names
    print(f"obs smoke ok (scraped {len(snap)} metric families, "
          f"{len(doc['traceEvents'])} trace spans)")


def smoke_operability(ticks=8, seed=1):
    """Operability plane, end to end against a real subprocess: launch
    ``serve_truss`` under a seeded *sticky* fault schedule with a
    postmortem directory and a metrics server, poll ``/healthz`` while it
    serves, and assert (a) health flips to HTTP 503 / ``violated`` once
    the breaker opens, (b) the run survives to its documented
    ended-degraded exit code 3 (degradation is a serving state, not a
    crash), and (c) a validated postmortem bundle was dumped by the
    breaker-open trip."""
    import json
    import re
    import urllib.error
    import urllib.request

    with tempfile.TemporaryDirectory() as root:
        pm_dir = os.path.join(root, "pm")
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.serve_truss",
             "--store", os.path.join(root, "store"), "--nodes", "60",
             "--ticks", str(ticks), "--chunk", "8", "--seed", str(seed),
             "--chaos-seed", "1", "--chaos-faults", "4", "--chaos-sticky",
             "--postmortem-dir", pm_dir, "--metrics-port", "0",
             "--linger", "8"],
            env=dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu"),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        saw_degraded = False
        try:
            line = proc.stdout.readline()
            m = re.search(r"http://127\.0\.0\.1:(\d+)/", line)
            assert m, f"no metrics URL in first line: {line!r}"
            url = f"http://127.0.0.1:{m.group(1)}/healthz"
            import time as _time
            while proc.poll() is None:
                try:
                    with urllib.request.urlopen(url, timeout=5) as r:
                        json.loads(r.read().decode())
                except urllib.error.HTTPError as e:
                    # 503: some objective violated / service degraded
                    verdict = json.loads(e.read().decode())
                    if e.code == 503 and verdict["status"] == "violated":
                        saw_degraded = True
                        break  # seen what we came for; let the run finish
                except OSError:
                    break  # server already shut down between poll and GET
                _time.sleep(0.1)
            out, _ = proc.communicate(timeout=120)
            # graceful degradation: shed ticks, loud report, exit code 3
            # (the documented ended-degraded outcome — NOT a crash)
            assert proc.returncode == 3, (proc.returncode, out)
        finally:
            if proc.poll() is None:
                proc.kill()
        assert saw_degraded, "healthz never flipped to 503/violated"
        bundles = sorted(os.listdir(pm_dir))
        assert bundles, "no postmortem bundle despite sticky faults"
        with open(os.path.join(pm_dir, bundles[0])) as f:
            bundle = json.load(f)
        assert bundle["format"] == "truss-postmortem-v1", bundle["format"]
        assert bundle["trigger"] == "breaker_open", bundle["trigger"]
        assert bundle["trace_excerpt"], "postmortem carries no spans"
        assert "truss_breaker_state" in bundle["metrics"]
        assert "chaos_schedule" in bundle, sorted(bundle)
    print(f"operability smoke ok (healthz flipped to violated, "
          f"{len(bundles)} postmortem bundle(s), trigger="
          f"{bundle['trigger']})")


def smoke_chaos(n_updates=36, seed=0):
    """Chaos plane, end to end: ingest under a healthy store, inject a
    sticky fsync EIO mid-run (writes shed with a reason, committed reads
    keep answering at the pre-fault state), then clear the fault and
    verify clean recovery — breaker closed, pending writes committed,
    phi bitwise vs the oracle replay of the surviving WAL, scrub clean."""
    import time
    from repro.data.streams import GraphUpdateStream
    from repro.faults import CircuitBreaker, Fault, FaultyIO, RetryPolicy
    from repro.service import (MEMBERS, Overloaded, QueryRequest,
                               TrussService, TrussStore)

    rng = np.random.default_rng(seed)
    n = 24
    edges = rand_graph(rng, n, 0.25)
    stream = GraphUpdateStream(np.asarray(edges), n, chunk=6, seed=seed + 1)
    fio = FaultyIO()
    with tempfile.TemporaryDirectory() as root:
        svc = TrussService(n, edges, tracked_ks=(3,), flush_every=6,
                           store=TrussStore(root, io=fio),
                           breaker=CircuitBreaker(failure_threshold=2,
                                                  cooldown_s=0.05),
                           retry=RetryPolicy(max_attempts=2, base_ms=0.01,
                                             cap_ms=0.01, scope="fsync"))
        for _ in range(n_updates // 12):  # healthy warmup
            svc.submit_many([tuple(map(int, r)) for r in stream.next()])
        svc.flush()
        baseline = svc.handle_committed(QueryRequest(MEMBERS, k=3)).value

        fio.inject(Fault("fsync_eio", at=0, sticky=True))
        shed = 0
        for _ in range(n_updates // 12):
            for r in stream.next():
                try:
                    ack = svc.submit(*map(int, r))
                except (OSError, ValueError):
                    continue
                shed += isinstance(ack, Overloaded)
        try:
            svc.flush()
        except OSError:
            pass
        s = svc.stats()
        assert s["degraded"] == "io", s  # outage detected, reason surfaced
        # degraded reads: committed state keeps answering during the outage
        assert svc.handle_committed(
            QueryRequest(MEMBERS, k=3)).value == baseline

        fio.clear()
        for _ in range(20):  # cooldown -> half-open probe -> closed
            time.sleep(0.08)
            try:
                svc.flush()
            except OSError:
                continue
            s = svc.stats()
            if s["degraded"] is None and s["breaker"]["state"] == "closed":
                break
        assert s["degraded"] is None and s["breaker"]["state"] == "closed", s
        survivors = svc.store.read_wal(start=0)
        orc = oracle.Oracle(n, edges)
        orc.apply([(int(op), int(a), int(b)) for _g, op, a, b in survivors])
        assert svc.graph.phi_dict() == orc.phi, "recovered phi != oracle"
        assert svc.scrub(deep=True)["ok"], "post-recovery scrub not clean"
        svc.store.close()
    print(f"chaos smoke ok (outage shed {shed} writes, degraded reads "
          f"served, recovery exact over {len(survivors)} WAL records)")


def smoke_core():
    """The original per-seed engine-vs-oracle sweep."""
    for s in range(15):
        run_one(s)
        print(f"seed {s} ok")


SECTIONS = {"core": smoke_core, "service": smoke_service,
            "cluster": smoke_cluster, "sharded": smoke_sharded,
            "scale": smoke_scale, "obs": smoke_obs,
            "operability": smoke_operability, "chaos": smoke_chaos}

if __name__ == "__main__":
    picked = sys.argv[1:] or list(SECTIONS)
    unknown = [s for s in picked if s not in SECTIONS]
    assert not unknown, f"unknown sections {unknown}; know {sorted(SECTIONS)}"
    for s in picked:
        SECTIONS[s]()
    print("ALL OK")
