"""Sharded peel substrate scaling (ISSUE-5 acceptance).

Device-count sweep of the mesh-partitioned peel engine: each point re-execs
this module's worker in a subprocess under
``XLA_FLAGS=--xla_force_host_platform_device_count={1,2,4,8}`` (the main
process keeps its single device) and measures

  * **decompose** — full bitmap decomposition, sharded delta engine
    (incremental bit-clearing, one decision all-reduce + one cleared-bits
    psum per wave) and sharded recompute engine (full psum per wave), vs
    the single-device engine in the same process;
  * **repeel** — the fused batch re-peel through ``DynamicGraph.apply_batch``
    with a mesh (the service flush path), vs ``mesh=None``;

with **phi asserted bitwise-equal to the single-device engine (and the
oracle for decompose) at every point** — a failed assertion fails the
bench.  Per-wave time (total / waves) is the scaling curve: on emulated
host devices all shards share one CPU, so wall-clock *gain* is not
expected here — the curve records collective overhead at each device count
honestly and becomes a speedup curve on real multi-chip hardware.  Emits
``BENCH_sharded.json``; rows carry their own device count so
``results.csv`` never merges single- and multi-device numbers.

    PYTHONPATH=src python -m benchmarks.sharded_peel
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from repro.launch.mesh import emulated_devices_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEVICE_COUNTS = (1, 2, 4, 8)

_WORKER = """
import sys, time, json
sys.path.insert(0, {src!r})
import numpy as np
import jax
from repro.core import DynamicGraph, GraphSpec, from_edge_list, oracle
from repro.core.graph import pad_state, with_mesh
from repro.core.peel import peel
from repro.launch.mesh import make_shard_mesh
from repro.data.synthetic import powerlaw_graph

devices = {devices}
n, m_per, seed = {n}, {m_per}, 3
repeats = {repeats}
edges = powerlaw_graph(n, m_per, seed=seed)
mesh = make_shard_mesh(devices)
spec0 = GraphSpec(n_nodes=n, d_max=n, e_cap=len(edges))
spec = with_mesh(spec0, mesh)
st = pad_state(spec0, from_edge_list(spec0, np.asarray(edges)), spec)


def timed(fn):
    jax.block_until_ready(fn())  # warm the jit cache
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


adj = {{i: set() for i in range(n)}}
for a, b in edges:
    adj[a].add(b); adj[b].add(a)
ref = oracle.truss_decomposition(adj)

out = {{"devices": devices, "n_nodes": n, "n_edges": len(edges)}}
phi_single, stats_single = peel(spec, st, st.active, method="bitmap",
                                engine="delta")
got = {{tuple(e): int(p) for e, p in
       zip(edges, np.asarray(phi_single)[:len(edges)])}}
assert got == ref, "single-device decompose != oracle"
out["waves"] = int(stats_single.waves)
out["t_single_s"] = timed(lambda: peel(spec, st, st.active, method="bitmap",
                                       engine="delta")[0])
for engine in ("delta", "recompute"):
    phi_sh, stats_sh = peel(spec, st, st.active, method="bitmap",
                            engine=engine, mesh=mesh)
    ref_phi, _ = peel(spec, st, st.active, method="bitmap", engine=engine)
    assert np.array_equal(np.asarray(phi_sh), np.asarray(ref_phi)), engine
    t = timed(lambda: peel(spec, st, st.active, method="bitmap",
                           engine=engine, mesh=mesh)[0])
    out["t_sharded_%s_s" % engine] = t
    out["wave_us_%s" % engine] = t / int(stats_sh.waves) * 1e6

# fused batch re-peel (the service flush path) with and without the mesh
rng = np.random.default_rng(0)
present = set(map(tuple, edges))
absent = [(i, j) for i in range(n) for j in range(i + 1, n)
          if (i, j) not in present]
rng.shuffle(absent)
ins = [absent.pop() for _ in range(64)]
dels = sorted(present)[:64]
ups = [(1, a, b) for a, b in ins] + [(0, a, b) for a, b in dels]
orc = oracle.Oracle(n, edges)
orc.apply(ups)
g1 = DynamicGraph(n, edges, support_method="bitmap")
g1.apply_batch(ups, strategy="fused")
assert g1.phi_dict() == orc.phi, "single-device repeel != oracle"
g2 = DynamicGraph(n, edges, support_method="bitmap", mesh=mesh)
g2.apply_batch(ups, strategy="fused")
assert g2.phi_dict() == orc.phi, "sharded repeel != oracle"


def repeel_sharded():
    g = DynamicGraph(n, edges, support_method="bitmap", mesh=mesh)
    t0 = time.perf_counter()
    g.apply_batch(ups, strategy="fused")
    jax.block_until_ready(g.state.phi)
    return time.perf_counter() - t0


repeel_sharded()  # warm
out["t_repeel_sharded_s"] = min(repeel_sharded() for _ in range(repeats))
out["repeel_waves"] = int(g2.last_peel_stats.waves)
print("RESULT " + json.dumps(out))
"""


def run_point(devices: int, n: int, m_per: int, repeats: int) -> dict:
    code = _WORKER.format(src=os.path.join(ROOT, "src"), devices=devices,
                          n=n, m_per=m_per, repeats=repeats)
    env = emulated_devices_env(devices)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(out.stdout + "\n" + out.stderr)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line:\n{out.stdout}")


def main(rows: list, quick: bool = True):
    n, m_per = (300, 5) if quick else (800, 6)
    repeats = 3 if quick else 5
    results = {"graph": {"n_nodes": n, "m_per_node": m_per},
               "platform": "cpu-emulated", "points": {}}
    for devices in DEVICE_COUNTS:
        try:
            pt = run_point(devices, n, m_per, repeats)
        except Exception as e:  # pragma: no cover — env without headroom
            print(f"  ({devices} devices skipped: {str(e)[-400:]})")
            continue
        results["points"][str(devices)] = pt
        rows.append((f"sharded/decompose/delta/d{devices}",
                     pt["t_sharded_delta_s"] * 1e6,
                     f"wave_us={pt['wave_us_delta']:.0f};exact=True",
                     devices))
        rows.append((f"sharded/decompose/recompute/d{devices}",
                     pt["t_sharded_recompute_s"] * 1e6,
                     f"wave_us={pt['wave_us_recompute']:.0f};exact=True",
                     devices))
        rows.append((f"sharded/repeel/fused/d{devices}",
                     pt["t_repeel_sharded_s"] * 1e6,
                     f"waves={pt['repeel_waves']};exact=True", devices))
        print(f"  {devices} devices: decompose delta {pt['t_sharded_delta_s']:.3f}s "
              f"({pt['wave_us_delta']:.0f}us/wave), recompute "
              f"{pt['t_sharded_recompute_s']:.3f}s, repeel "
              f"{pt['t_repeel_sharded_s']:.3f}s, single-dev "
              f"{pt['t_single_s']:.3f}s — phi bitwise-exact")
    if results["points"]:
        base = results["points"].get("1")
        if base:
            results["wave_time_curve"] = {
                d: {"delta_us": p["wave_us_delta"],
                    "recompute_us": p["wave_us_recompute"],
                    "vs_1dev": round(p["wave_us_delta"]
                                     / base["wave_us_delta"], 3)}
                for d, p in results["points"].items()}
        results["exact_everywhere"] = True  # assertions inside each worker
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_sharded.json")
        with open(out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"  wrote {out}")
    return rows


if __name__ == "__main__":
    rows = []
    main(rows, quick="--full" not in sys.argv)
    for r in rows:
        print(",".join(map(str, r)))
