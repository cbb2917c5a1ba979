"""Distributed truss engine bench: full-bitmap psum vs delta psum.

Two measurements:
1. **Algorithmic collective volume** (host simulation): per-wave nonzero
   uint32 words that must cross the wire under (a) full psum of the N x W
   bitmap every wave vs (b) wave-0 full + per-wave removed-bit deltas.
2. **Wall time** on emulated host devices (subprocess with
   --xla_force_host_platform_device_count, like tests/test_distributed.py).
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

from repro.data.synthetic import powerlaw_graph
from repro.core import oracle
from repro.launch.mesh import emulated_devices_env


def simulate_collective_volume(n_nodes=800, m_per_node=6, seed=0):
    """Replay mask peeling on the host, counting exchanged words per wave."""
    edges = powerlaw_graph(n_nodes, m_per_node, seed=seed)
    adj = {i: set() for i in range(n_nodes)}
    for a, b in edges:
        adj[a].add(b)
        adj[b].add(a)
    n_words = (n_nodes + 31) // 32
    alive = {tuple(e) for e in map(tuple, edges)}

    def bitmap_words(edge_set):
        words = set()
        for a, b in edge_set:
            words.add((a, b // 32))
            words.add((b, a // 32))
        return words

    full_words = n_nodes * n_words
    total_full = 0
    total_delta = 0
    wave = 0
    k = 3
    prev_words = None
    while alive:
        # support within alive
        sup = {}
        live_adj = {i: set() for i in range(n_nodes)}
        for a, b in alive:
            live_adj[a].add(b)
            live_adj[b].add(a)
        for a, b in alive:
            sup[(a, b)] = len(live_adj[a] & live_adj[b])
        kill = {e for e in alive if sup[e] < k - 2}
        cur_words = bitmap_words(alive)
        total_full += full_words                       # dense psum every wave
        if prev_words is None:
            total_delta += full_words                  # wave-0 full exchange
        else:
            total_delta += len(prev_words - cur_words)  # removed words only
        prev_words = cur_words
        if kill:
            alive -= kill
        else:
            min_sup = min(sup.values())
            k = max(k + 1, min_sup + 3)
        wave += 1
    return {"waves": wave, "full_words": total_full, "delta_words": total_delta,
            "saving": total_full / max(total_delta, 1)}


def wall_time_subprocess(devices=8, n=400, deg=5, seed=1):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = f"""
import time, numpy as np
from repro.core import GraphSpec
from repro.core.distributed import distributed_decompose
from repro.launch.mesh import make_test_mesh
from repro.data.synthetic import powerlaw_graph
edges = powerlaw_graph({n}, {deg}, seed={seed})
spec = GraphSpec(n_nodes={n}, d_max={n}, e_cap=len(edges))
mesh = make_test_mesh(({devices},), ("data",))
for delta in (False, True):
    distributed_decompose(spec, mesh, np.asarray(edges), delta=delta)  # warm
    t0 = time.perf_counter()
    for _ in range(3):
        distributed_decompose(spec, mesh, np.asarray(edges), delta=delta)
    print(f"delta={{delta}} {{(time.perf_counter()-t0)/3*1e6:.0f}}")
"""
    env = emulated_devices_env(devices, PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    res = {}
    for line in out.stdout.splitlines():
        if line.startswith("delta="):
            key, us = line.split()
            res[key] = float(us)
    return res


def main(rows: list, quick: bool = True):
    sim = simulate_collective_volume()
    rows.append(("dist_truss/collective_words/full", float(sim["full_words"]),
                 f"waves={sim['waves']}"))
    rows.append(("dist_truss/collective_words/delta", float(sim["delta_words"]),
                 f"saving={sim['saving']:.1f}x"))
    print(f"  distributed truss: delta psum cuts collective words "
          f"{sim['saving']:.1f}x over {sim['waves']} waves")
    try:
        wt = wall_time_subprocess()
        for k, us in wt.items():
            # 4-tuple: the measurement ran in an 8-device subprocess, not
            # this process — stamp the real count into results.csv
            rows.append((f"dist_truss/walltime_8dev/{k}", us, "", 8))
    except Exception as e:  # pragma: no cover — env without subprocess headroom
        print(f"  (wall-time subprocess skipped: {e})")
    return rows


if __name__ == "__main__":
    rows = []
    main(rows)
    for r in rows:
        print(",".join(map(str, r)))
