"""Million-edge scale tier (ISSUE-10 acceptance).

The graph-scale leap: full bitmap decomposition at 10^6+ edges, with the
adjacency bitmap either replicated (every device holds ``[N, W]``) or
node-partitioned (``partition="nodes"``: device ``s`` owns the word slab
``bm[:, s*W/S:(s+1)*W/S]``, support recovered per wave as a psum of
per-slab partial popcounts).  Each point re-execs this module's worker in
a subprocess under ``XLA_FLAGS=--xla_force_host_platform_device_count=S``
(same pattern as benchmarks/sharded_peel.py) and reports

  * **decompose** — full delta-engine decomposition wall-clock, replicated
    vs partitioned at one device (the partitioning-overhead criterion:
    partitioned must stay within 1.3x) and partitioned at S >= 2, with
    **phi asserted bitwise-equal to the pure-python slow-lane oracle** —
    a failed assertion fails the bench;
  * **memory curve** — bytes-per-device at S in {1, 2, 4} under
    ``partition="nodes"``: the ``GraphSpec`` footprint model *and* the
    actual per-device slab ``nbytes`` of an instantiated partitioned
    bitmap (they must agree), strictly below the replicated footprint at
    every S >= 2 (~1/S).

Emulated host devices share one CPU, so partitioned wall-clock at S >= 2
records collective + slab-addressing overhead honestly; the memory curve
is layout arithmetic and transfers to real multi-chip hardware as-is.
Emits ``BENCH_scale.json``; rows carry a ``mem_bytes_per_device``
telemetry column.

    PYTHONPATH=src python -m benchmarks.million_edge [--full]

Quick mode runs the same pipeline at ~10^5 edges (CI smoke); ``--full``
is the committed >= 10^6-edge tier.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from repro.launch.mesh import emulated_devices_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: graph operating points: (n_nodes, m_per_node, max_degree) — degree capped
#: so d_max (the CSR neighbor capacity) stays bounded at a million edges.
QUICK_GRAPH = (8192, 16, 512)     # ~1.2e5 edges
FULL_GRAPH = (32768, 32, 1024)    # ~1.05e6 edges
SEED = 7

_WORKER = """
import sys, time, json
sys.path.insert(0, {src!r})
import numpy as np
import jax
import jax.numpy as jnp
from repro.core import GraphSpec, from_edge_list
from repro.core.graph import (with_mesh, pad_state, shard_state,
                              build_bitmap_partitioned)
from repro.core.peel import peel
from repro.launch.mesh import make_shard_mesh
from repro.data.synthetic import powerlaw_graph

devices = {devices}
partition = {partition!r}
n, m_per, cap = {n}, {m_per}, {cap}
decompose = {decompose}
oracle_path = {oracle_path!r}

edges = powerlaw_graph(n, m_per, seed={seed}, max_degree=cap)
mesh = make_shard_mesh(devices)
spec0 = GraphSpec(n_nodes=n, d_max=cap, e_cap=len(edges))
spec = with_mesh(spec0, mesh, partition=partition)
st = shard_state(spec, pad_state(spec0, from_edge_list(
    spec0, np.asarray(edges)), spec), mesh)

out = {{"devices": devices, "partition": partition, "n_nodes": n,
       "n_edges": len(edges),
       "bitmap_bytes_per_device": spec.bitmap_bytes_per_device,
       "state_bytes_per_device": spec.state_bytes_per_device}}

# the footprint model vs the real array: per-device slab nbytes of an
# instantiated partitioned bitmap must match GraphSpec's arithmetic
if partition == "nodes":
    bm = build_bitmap_partitioned(spec, st, st.active, mesh)
    shard_bytes = {{int(sh.data.nbytes) for sh in bm.addressable_shards}}
    assert shard_bytes == {{spec.bitmap_bytes_per_device}}, (
        shard_bytes, spec.bitmap_bytes_per_device)
    out["measured_slab_bytes"] = max(shard_bytes)
    del bm

if decompose:
    t0 = time.perf_counter()
    phi, stats = peel(spec, st, st.active, method="bitmap", engine="delta",
                      mesh=mesh if partition == "nodes" else None)
    jax.block_until_ready(phi)
    out["t_decompose_s"] = time.perf_counter() - t0
    out["waves"] = int(stats.waves)
    if oracle_path:
        ref = np.load(oracle_path)
        got = np.asarray(phi)[:len(edges)]
        assert np.array_equal(got, ref), (
            "phi != slow-lane oracle: first mismatch at edge %d"
            % int(np.argmin(got == ref)))
        out["oracle_exact"] = True

print("RESULT " + json.dumps(out))
"""


def run_point(devices: int, partition: str, graph: tuple, *,
              decompose: bool, oracle_path: str = "",
              timeout: int = 7200) -> dict:
    n, m_per, cap = graph
    code = _WORKER.format(src=os.path.join(ROOT, "src"), devices=devices,
                          partition=partition, n=n, m_per=m_per, cap=cap,
                          seed=SEED, decompose=decompose,
                          oracle_path=oracle_path)
    env = emulated_devices_env(devices)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(out.stdout + "\n" + out.stderr)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line:\n{out.stdout}")


def _oracle_phi(graph: tuple) -> tuple[str, int]:
    """Slow-lane oracle: pure-python truss decomposition of the same
    seeded graph, phi aligned to the generator's edge order, saved to a
    temp .npy the workers load for the bitwise cross-check."""
    import numpy as np
    from repro.core import oracle
    from repro.data.synthetic import powerlaw_graph

    n, m_per, cap = graph
    edges = powerlaw_graph(n, m_per, seed=SEED, max_degree=cap)
    adj = {i: set() for i in range(n)}
    for a, b in edges:
        adj[int(a)].add(int(b))
        adj[int(b)].add(int(a))
    phi = oracle.truss_decomposition(adj)
    ref = np.asarray([phi[(int(a), int(b))] for a, b in edges],
                     dtype=np.int32)
    path = os.path.join(tempfile.mkdtemp(), "oracle_phi.npy")
    np.save(path, ref)
    return path, len(edges)


def main(rows: list, quick: bool = True):
    graph = QUICK_GRAPH if quick else FULL_GRAPH
    print(f"  oracle: pure-python decompose of the "
          f"{'quick' if quick else 'full'} graph (slow lane)...")
    oracle_path, n_edges = _oracle_phi(graph)
    print(f"  graph: n={graph[0]} m={graph[1]} cap={graph[2]} "
          f"-> {n_edges} edges")

    results = {"graph": {"n_nodes": graph[0], "m_per_node": graph[1],
                         "max_degree": graph[2], "n_edges": n_edges},
               "platform": "cpu-emulated", "points": {}}
    # decompose points: replicated baseline, partitioned same-device (the
    # 1.3x overhead criterion), partitioned multi-device (oracle-checked)
    points = [(1, "replicated", True), (1, "nodes", True), (2, "nodes", True)]
    # memory-curve completion: S=4 needs no decompose, just the slab
    points.append((4, "nodes", False))
    for devices, partition, decompose in points:
        try:
            pt = run_point(devices, partition, graph, decompose=decompose,
                           oracle_path=oracle_path if decompose else "")
        except Exception as e:  # pragma: no cover — env without headroom
            print(f"  ({devices}x {partition} skipped: {str(e)[-400:]})")
            continue
        key = f"{partition}/d{devices}"
        results["points"][key] = pt
        if decompose:
            rows.append((f"scale/decompose/{partition}/d{devices}",
                         pt["t_decompose_s"] * 1e6,
                         f"edges={pt['n_edges']};exact=True", devices,
                         {"waves": pt["waves"],
                          "mem_bytes_per_device":
                              pt["bitmap_bytes_per_device"]}))
            print(f"  {devices}x {partition}: decompose "
                  f"{pt['t_decompose_s']:.1f}s ({pt['waves']} waves), "
                  f"bitmap {pt['bitmap_bytes_per_device'] / 1e6:.1f} MB/dev"
                  + (", phi == oracle" if pt.get("oracle_exact") else ""))
        else:
            rows.append((f"scale/memory/{partition}/d{devices}",
                         0.0, f"edges={pt['n_edges']}", devices,
                         {"mem_bytes_per_device":
                              pt["bitmap_bytes_per_device"]}))
            print(f"  {devices}x {partition}: bitmap "
                  f"{pt['bitmap_bytes_per_device'] / 1e6:.1f} MB/dev")

    pts = results["points"]
    if "replicated/d1" in pts and "nodes/d1" in pts:
        ratio = (pts["nodes/d1"]["t_decompose_s"]
                 / pts["replicated/d1"]["t_decompose_s"])
        results["partition_overhead_1dev"] = round(ratio, 3)
        print(f"  partitioned/replicated wall-clock at 1 device: {ratio:.2f}x")
    rep = pts.get("replicated/d1", {}).get("bitmap_bytes_per_device")
    curve = {k.split("/d")[1]: p["bitmap_bytes_per_device"]
             for k, p in pts.items() if k.startswith("nodes/")}
    if rep and curve:
        results["memory_curve"] = {
            "replicated_bytes": rep,
            "partitioned_bytes_per_device": curve,
            "vs_replicated": {s: round(b / rep, 4)
                              for s, b in curve.items()},
        }
        for s, b in curve.items():
            if int(s) >= 2:
                assert b < rep, f"no memory win at {s} shards"
    results["oracle_exact"] = all(
        p.get("oracle_exact", True) for p in pts.values())
    if pts:
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_scale.json")
        with open(out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"  wrote {out}")
    return rows


if __name__ == "__main__":
    rows = []
    main(rows, quick="--full" not in sys.argv)
    for r in rows:
        print(",".join(map(str, r)))
