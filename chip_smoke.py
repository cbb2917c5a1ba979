#!/usr/bin/env python3
"""Chip smoke: the served truss path, once, on a TPU at the paper's scale.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips: the partitioned scale tier

One chip runs three phases in this one process (a chip serves one process,
so nothing here starts a child):

1. **device** — the first JAX device must be a TPU and the kernel dispatch
   must agree (``kernels.ops.on_tpu``); nothing falls back to the CPU.
2. **cli** — ``serve_truss.main`` on its default (sorted) engine over the
   enron-like graph of ``configs/truss_paper.py`` (36,692 nodes, 5 edges
   per new node), three ticks of 1,000 updates, each flushed as one
   generation into a WAL-backed store.  Final phi must equal the
   pure-Python oracle's.
3. **kernel** — the same graph served with ``support_method="bitmap"``, the
   path that runs the Pallas ``peel_wave`` kernel: three generations of
   1,000 updates, every query kind after each, snapshot and restore, and
   the restored service must answer identically.  Final phi must equal
   the oracle's, the compiled flush program must hold ``tpu_custom_call``,
   and one flush runs under ``jax.profiler``, which must start.

Both service phases fail on any peel fault or engine fallback (the
degradation ladder would otherwise absorb a kernel the chip refused) and on
a degraded service.  ``--chips 4`` runs only the four-chip phase: the
1,049,255-edge tier of ``benchmarks/million_edge.py`` decomposed with the
adjacency bitmap node-partitioned over four chips, against the same
decomposition on the first chip alone; phi must be bitwise-equal.

Progress, compile time, wave counts and peak device memory go to earlier
lines; the last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.  No speed is claimed here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from contextlib import contextmanager

HERE = os.path.dirname(os.path.abspath(__file__))
T0 = time.perf_counter()

ENRON_NODES, ENRON_DEGREE = 36692, 5       # configs/truss_paper.py, enron-like
BATCH, GENERATIONS, KS = 1000, 3, (3, 4)   # the paper's batch size
SCALE_GRAPH = (32768, 32, 1024)            # benchmarks/million_edge.FULL_GRAPH
SCALE_SEED = 7                             # benchmarks/million_edge.SEED


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:8.1f}s] {msg}", flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums backend compile seconds per phase from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.total = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration
            self.count += 1

    def mark(self) -> tuple[float, int]:
        return self.total, self.count


@contextmanager
def phase(name: str, clock: CompileClock):
    """Log a phase's wall and compile time."""
    c0, n0 = clock.mark()
    t0 = time.perf_counter()
    log(f"phase {name}: start")
    yield
    c1, n1 = clock.mark()
    log(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s "
        f"(compile {c1 - c0:.1f}s over {n1 - n0} programs)")


def peak_memory(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def device_phase():
    """The backend must be a TPU, and the kernels must compile for it."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    require(d0.platform == "tpu",
            f"no TPU: JAX's first device is {d0.platform!r}")
    from repro.kernels import ops

    require(ops.on_tpu() and not ops._interpret(),
            "kernel dispatch does not target the TPU")
    return devices


def oracle_phi(n_nodes: int, edges) -> dict:
    from repro.core import oracle

    t0 = time.perf_counter()
    ref = oracle.scratch_phi(n_nodes, [(int(a), int(b)) for a, b in edges])
    log(f"oracle: {len(ref)} edges in {time.perf_counter() - t0:.1f}s")
    return ref


def check_service(svc, label: str) -> None:
    """Healthy service, no fault absorbed by the degradation ladder."""
    from repro.obs import metrics

    stats = svc.stats()
    require(stats["degraded"] is None and stats["breaker"]["state"] == "closed",
            f"{label}: service degraded ({stats['degraded']})")
    for name in ("truss_peel_fault_total", "truss_engine_fallback_total",
                 "truss_self_heal_total"):
        require(metrics.REGISTRY.value(name) == 0,
                f"{label}: {name} = {metrics.REGISTRY.value(name)}")
    require(svc.graph.phi_dict() == oracle_phi(svc.graph.spec.n_nodes,
                                               svc.graph.edge_list()),
            f"{label}: phi differs from the oracle")
    log(f"{label}: phi == oracle; gen {stats['gen']}, "
        f"{stats['n_edges']} edges, max truss {stats['max_truss']}, "
        f"last peel {stats['peel']}")


def cli_phase(workdir: str) -> None:
    """The default served path through the CLI entry point."""
    from repro.launch import serve_truss

    svc = serve_truss.main([
        "--nodes", str(ENRON_NODES), "--degree", str(ENRON_DEGREE),
        "--chunk", str(BATCH), "--flush-every", str(BATCH),
        "--ticks", str(GENERATIONS), "--store", os.path.join(workdir, "cli")])
    require(svc.exit_code == 0, f"serve_truss exit code {svc.exit_code}")
    require(svc.support_method == "sorted", "CLI did not serve sorted")
    require(svc.gen == GENERATIONS,
            f"CLI committed {svc.gen} generations, not {GENERATIONS}")
    check_service(svc, "cli")


def answers(svc, probes) -> list:
    """One answer of every query kind, in a comparable host form."""
    from repro.service import (COMMUNITY, MAX_K, MEMBERS, REPRESENTATIVES,
                               QueryRequest)

    (u, v), k_top = probes
    reqs = [QueryRequest(MEMBERS, k=KS[0]), QueryRequest(MEMBERS, k=k_top),
            QueryRequest(REPRESENTATIVES, k=KS[0]),
            QueryRequest(COMMUNITY, k=k_top, node=u),
            QueryRequest(MAX_K, edge=(u, v))]
    out = []
    for req in reqs:
        resp = svc.handle(req)
        out.append((req.kind, resp.value,
                    None if resp.edges is None
                    else sorted(map(tuple, resp.edges.tolist()))))
    return out


def flush_program_text(recorded) -> str:
    """Compiled HLO of the last fused flush (``batch_maintain``)."""
    from repro.core import batch

    spec, arrays, kw = recorded[-1]
    return batch.batch_maintain.lower(spec, *arrays, **kw).compile().as_text()


def kernel_phase(workdir: str) -> None:
    """The bitmap engine (Pallas ``peel_wave``) behind TrussService."""
    import glob

    import jax
    import numpy as np

    from repro.core import batch
    from repro.data.streams import GraphUpdateStream
    from repro.data.synthetic import powerlaw_graph
    from repro.obs import metrics, profiling
    from repro.service import TrussService, TrussStore

    # record the flush program's argument shapes (to compile it once more
    # and read its HLO); the flush itself runs unchanged
    recorded = []
    real = batch.batch_maintain

    def shape_of(x):
        return (jax.ShapeDtypeStruct(x.shape, x.dtype)
                if isinstance(x, jax.Array) else x)

    def recording(spec, *arrays, **kw):
        recorded.append((spec, jax.tree.map(shape_of, arrays),
                         jax.tree.map(shape_of, kw)))
        return real(spec, *arrays, **kw)

    edges = powerlaw_graph(ENRON_NODES, ENRON_DEGREE, seed=0)
    store = TrussStore(os.path.join(workdir, "kernel"))
    t0 = time.perf_counter()
    svc = TrussService(ENRON_NODES, edges, tracked_ks=KS, flush_every=BATCH,
                       store=store, support_method="bitmap")
    log(f"kernel: service up (decompose {svc.graph.last_peel_stats.waves} "
        f"waves) in {time.perf_counter() - t0:.1f}s")
    stream = GraphUpdateStream(edges, ENRON_NODES, chunk=BATCH, seed=1)
    batch.batch_maintain = recording
    try:
        for g in range(GENERATIONS):
            profiled = g == GENERATIONS - 1
            if profiled:
                profiling.configure(os.path.join(workdir, "profile"),
                                    max_traces=1)
            t0 = time.perf_counter()
            svc.submit_many([tuple(map(int, r)) for r in stream.next()])
            svc.flush()
            profiling.configure(None)
            el = svc.graph.edge_list()
            phi = svc.graph.phi_dict()
            top = max(phi.items(), key=lambda kv: (kv[1], kv[0]))
            probes = (top[0], int(top[1]))
            got = answers(svc, probes)
            log(f"kernel: gen {svc.gen} in {time.perf_counter() - t0:.1f}s "
                f"({len(el)} edges, peel {svc.stats()['peel']}"
                f"{', profiled' if profiled else ''}); "
                + " ".join(f"{k}={v if e is None else len(e)}"
                           for k, v, e in got))
    finally:
        batch.batch_maintain = real
    require(svc.gen == GENERATIONS,
            f"kernel phase committed {svc.gen} generations")
    require(len(recorded) == GENERATIONS,
            f"{len(recorded)} fused flushes for {GENERATIONS} generations")
    require(metrics.REGISTRY.value("truss_profiler_start_failures_total") == 0
            and glob.glob(os.path.join(workdir, "profile", "**", "*.xplane.pb"),
                          recursive=True),
            "the profiler did not capture the flush")
    check_service(svc, "kernel")

    t0 = time.perf_counter()
    text = flush_program_text(recorded)
    require("tpu_custom_call" in text,
            "the compiled flush program holds no Pallas TPU kernel")
    log(f"kernel: flush program holds tpu_custom_call "
        f"(re-lowered in {time.perf_counter() - t0:.1f}s)")

    svc.snapshot(stream_state=stream.state_dict())
    restored = TrussService.restore(store, flush_every=BATCH,
                                    support_method="bitmap")
    require(restored.gen == svc.gen, "restore lost generations")
    require(answers(restored, probes) == got,
            "restored service answers differently")
    require(np.array_equal(np.asarray(restored.graph.state.phi),
                           np.asarray(svc.graph.state.phi)),
            "restored phi differs")
    log(f"kernel: restore at gen {restored.gen} answers identically")


def four_chip_phase(devices) -> None:
    """Node-partitioned decompose on four chips vs the first chip alone."""
    import jax
    import numpy as np

    from repro.core import GraphSpec, from_edge_list
    from repro.core.graph import (build_bitmap, build_bitmap_partitioned,
                                  pad_state, shard_state, with_mesh)
    from repro.core.peel import peel
    from repro.data.synthetic import powerlaw_graph
    from repro.launch.mesh import make_shard_mesh

    require(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
            f"{len(devices)}")
    n, m_per, cap = SCALE_GRAPH
    edges = powerlaw_graph(n, m_per, seed=SCALE_SEED, max_degree=cap)
    spec0 = GraphSpec(n_nodes=n, d_max=cap, e_cap=len(edges))
    st0 = from_edge_list(spec0, np.asarray(edges))
    log(f"scale: {len(edges)} edges, {n} nodes")

    mesh = make_shard_mesh(4)
    spec = with_mesh(spec0, mesh, partition="nodes")
    st = shard_state(spec, pad_state(spec0, st0, spec), mesh)
    bm = build_bitmap_partitioned(spec, st, st.active, mesh)
    placed = {sh.device: sh.data.nbytes for sh in bm.addressable_shards}
    require(len(placed) == 4 and set(placed.values())
            == {spec.bitmap_bytes_per_device},
            f"bitmap slabs not one per chip: {placed}")
    t0 = time.perf_counter()
    phi4, stats4 = peel(spec, st, st.active, bitmap=bm, method="bitmap",
                        engine="delta", mesh=mesh)
    phi4 = np.asarray(phi4)[:len(edges)]
    log(f"scale: 4 chips partitioned: {int(stats4.waves)} waves in "
        f"{time.perf_counter() - t0:.1f}s (compile included)")
    for d in devices[:4]:
        ms = d.memory_stats() or {}
        log(f"scale: {d}: slab {placed[d]} B, bytes_in_use "
            f"{ms.get('bytes_in_use')}, peak {ms.get('peak_bytes_in_use')}")
    del bm, st

    t0 = time.perf_counter()
    st1 = jax.device_put(st0, devices[0])
    phi1, stats1 = peel(spec0, st1, st1.active,
                        bitmap=build_bitmap(spec0, st1, st1.active),
                        method="bitmap", engine="delta")
    phi1 = np.asarray(phi1)[:len(edges)]
    log(f"scale: 1 chip replicated: {int(stats1.waves)} waves in "
        f"{time.perf_counter() - t0:.1f}s (compile included)")
    require(np.array_equal(phi4, phi1),
            "4-chip partitioned phi differs from the 1-chip phi")
    require(int(stats4.waves) == int(stats1.waves),
            "wave counts differ across layouts")
    log(f"scale: phi bitwise-equal across layouts (max phi "
        f"{int(phi1.max())})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: device, CLI and kernel phases; 4: only the "
                         "four-chip partitioned phase")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.launch.compile_cache import configure_compile_cache
    except ImportError as exc:
        print(f"FAIL: the repository's sources are not beside this script "
              f"({exc})", file=sys.stderr)
        return 1
    try:
        import jax

        log(f"compile cache: {configure_compile_cache()}")
        clock = CompileClock()
        with phase("device", clock):
            devices = device_phase()
        with tempfile.TemporaryDirectory(prefix="chip_smoke-") as workdir:
            if args.chips == 4:
                with phase("four-chip", clock):
                    four_chip_phase(devices)
            else:
                with phase("cli", clock):
                    cli_phase(workdir)
                with phase("kernel", clock):
                    kernel_phase(workdir)
        log(f"peak bytes in use per device: "
            f"{peak_memory(devices[:args.chips])}")
    except Exception as exc:  # every failure is reported, none is absorbed
        import traceback

        traceback.print_exc()
        print(f"FAIL: {exc!r}", file=sys.stderr)
        return 1
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
