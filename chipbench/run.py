#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<config>.json``: the graph, and in ``service`` the
``TrussService`` arguments the deployment runs with) and a traffic mix
(``chipbench/traffic/<mix>.json``, read by ``traffic.py``).  The run

1. looks for the chips the cell asks for, and fails without them;
2. sets up: makes the graph (``graphs.make_graph``) and relabels it from
   the seed, starts ``repro.service.TrussService`` on it with the
   configuration's ``service`` arguments, over a mesh of the cell's chips
   where it asks for more than one (decomposition, store snapshot), and
   warms every shape the window uses with one generation of the same
   traffic;
3. measures for ``--seconds`` through the service's public calls: one
   batch per generation in a closed loop (``submit_many``); the window
   ends at the first commit at or after ``--seconds``;
4. compares what the window committed with the plain reference
   (``reference.py``), and prints each number compared beside its limit;
5. prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
   per-layer metrics, each read by ``chipbench/layers/<metric>.py``),
   ``device``, with ``--trace 1`` ``breakdown``, and last ``checks``.

Progress goes to standard error; the numbers compared are its last lines.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import reference  # noqa: E402
import traffic  # noqa: E402
from graphs import make_graph, relabelled  # noqa: E402

#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: ``TrussService`` arguments that no configuration states: the harness
#: passes the graph, the store and the mesh, the mix sets the generation
#: size, and the rest take objects, not data
NOT_CONFIGURABLE = {"n_nodes", "edges", "store", "mesh", "flush_every",
                    "chaos", "breaker", "retry"}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:8.2f}s] {msg}", file=sys.stderr,
          flush=True)


# -- what the cell is ---------------------------------------------------------

def load_cell(name: str):
    """``(bench, cell, config, mix)`` for the cell named ``name``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    return bench, cell, config, traffic.load_mix(cell["traffic"])


def cell_metrics(bench: dict, cell: dict, traced: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in moved]


def service_args(service: dict) -> dict:
    """The configuration's ``service`` entry as ``TrussService`` keyword
    arguments; a key the harness cannot pass through is refused."""
    from repro.service import TrussService

    params = inspect.signature(TrussService).parameters
    bad = sorted(k for k in service
                 if k not in params or k in NOT_CONFIGURABLE)
    if bad:
        raise ValueError(f"service keys the harness cannot pass: {bad}")
    return dict(service)


def load_reader(metric: str):
    """``read(ctx)`` of ``chipbench/layers/<metric>.py``; a metric split by
    the end-to-end metric it moves (``device_idle.read``) falls back to the
    reader of its base name (``device_idle.py``)."""
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(HERE, "layers", f"{stem}.py")
        if os.path.exists(path):
            break
    else:
        raise FileNotFoundError(f"no reader for metric {metric!r}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def require_chips(n: int) -> list:
    """The first ``n`` accelerator devices; ``NoChip`` without them."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip("JAX finds no accelerator (its devices are CPUs)")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devices)}")
    return devices[:n]


def configure_jax_cache() -> None:
    """Cache every program in the checkout, and evict none (a size limit
    from the environment would evict some and make set-up vary run to
    run).  Call it before anything compiles: JAX fixes the directory at
    its first compilation."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


# -- one run --------------------------------------------------------------------

class CompileCount:
    """Backend compilations, counted from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration


@dataclass
class Check:
    """One number compared with the reference, and its limit."""
    name: str
    value: int
    limit: int

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Ctx:
    """What a per-layer reader reads: the window's program spans
    (``repro.obs`` ``SpanEvent``s), counter deltas, committed generations,
    the reduced trace (``tracing.Summary``) and the chip's peaks."""
    generations: int
    spans: list
    counters: dict
    trace: object
    peaks: dict


def counter_totals() -> dict:
    """Every counter of the program's metrics registry, summed over its
    labels."""
    from repro.obs import metrics

    return {name: metrics.REGISTRY.value(name)
            for name, fam in metrics.REGISTRY.families().items()
            if fam.kind == "counter"}


class CellRun:
    """Set-up, window and check of one cell; the service is the program's
    ``TrussService`` over the configuration's graph, on ``devices``."""

    def __init__(self, config: dict, mix: dict, seed: int, seconds: float,
                 trace: bool, workdir: str, devices=()):
        self.config, self.mix = config, mix
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.workdir = workdir
        self.devices = list(devices)
        self.writes: list = []      # every acked write, in order
        self.failed = 0
        self.compiles = CompileCount()

    # set-up ----------------------------------------------------------------
    def mesh(self):
        """A mesh over the cell's chips where it asks for more than one
        (axis ``shard``, the program's partition axis); none on one chip."""
        if len(self.devices) < 2:
            return None
        import numpy as np
        from jax.sharding import Mesh

        return Mesh(np.asarray(self.devices), ("shard",))

    def setup(self):
        from repro.service import TrussService, TrussStore

        g = self.config["graph"]
        t = time.perf_counter()
        # one graph for every seed (the deployment's data set); the seed
        # draws its node labels, so every run does the same work in
        # another order
        edges, _ = relabelled(make_graph(g), g["n_nodes"],
                              traffic.rng_for(self.seed, 0))
        self.n_nodes = g["n_nodes"]
        self.edges0 = {(int(u), int(v)) for u, v in edges}
        log(f"graph: {len(edges)} edges over {self.n_nodes} nodes in "
            f"{time.perf_counter() - t:.2f}s")
        t = time.perf_counter()
        self.svc = TrussService(
            self.n_nodes, edges, flush_every=self.mix["batch"],
            store=TrussStore(os.path.join(self.workdir, "store")),
            mesh=self.mesh(), **service_args(self.config["service"]))
        log(f"service up in {time.perf_counter() - t:.2f}s: decompose "
            f"{self.svc.stats()['peel']}")
        self.stream = traffic.Ingest(self.mix, self.n_nodes, edges,
                                     self.seed)
        t = time.perf_counter()
        self._ingest_batch(self.stream.next())
        self._device_state()     # warms the copy the window samples with
        log(f"warm-up in {time.perf_counter() - t:.2f}s; compiled "
            f"{self.compiles.n} programs ({self.compiles.secs:.1f}s) in "
            f"set-up")

    # window ------------------------------------------------------------------
    def _ingest_batch(self, batch) -> bool:
        gen = self.svc.gen
        try:
            acks = self.svc.submit_many(batch)
        except Exception as exc:  # a refused batch is a failure, not a crash
            log(f"batch refused at gen {gen}: {exc!r}")
            self.failed += len(batch)
            return False
        self.writes.extend(batch)
        if len(acks) != len(batch) or self.svc.gen != gen + 1:
            log(f"batch of {len(batch)} gave {len(acks)} acks, gen "
                f"{gen} -> {self.svc.gen}")
            return False
        return True

    def _device_state(self) -> tuple:
        """The committed state's edge rows, live mask and phi, copied on
        the device without waiting for it (the next generation donates the
        state's buffers); ``host_state`` reads them after the window."""
        import jax

        st = self.svc.graph.state
        return tuple(jax.device_put(x, may_alias=False)
                     for x in (st.edges, st.active, st.phi))

    @staticmethod
    def host_state(edges, active, phi) -> tuple:
        """``(edges, phi)`` of the live edges, on the host."""
        import numpy as np

        act = np.asarray(active)
        return np.asarray(edges)[act], np.asarray(phi)[act]

    def window(self) -> dict:
        """Run the measured window; returns the end-to-end numbers."""
        import jax

        from repro.obs import trace as obs_trace

        obs_trace.TRACER.clear()
        jax.config.update("jax_log_compiles", True)  # name any in the window
        before = counter_totals()
        n_compiles = self.compiles.n
        # committed states compared: (writes so far, edges, active, phi)
        self.samples: list = []
        self.sample_gen = int(traffic.rng_for(self.seed, 3).integers(2))
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(os.path.join(self.workdir, "trace"),
                                     profiler_options=opts)
        ann = jax.profiler.TraceAnnotation
        with ann("chipbench.window"):
            self.window_perf_ns = time.perf_counter_ns()
            out = self._ingest_window(time.perf_counter(), ann)
        if self.trace:
            jax.profiler.stop_trace()
        jax.config.update("jax_log_compiles", False)
        self.window_compiles = self.compiles.n - n_compiles
        log(f"window: {out['attempted']} operations, {self.failed} failed, "
            f"{self.window_compiles} programs compiled inside the window")
        self.spans = [e for e in obs_trace.TRACER.events()
                      if e.t0_ns >= self.window_perf_ns]
        self.counters = {c: v - before.get(c, 0)
                         for c, v in counter_totals().items()}
        if obs_trace.TRACER.dropped():
            log(f"span ring dropped {obs_trace.TRACER.dropped()} spans")
        return out

    def _ingest_window(self, t0, ann) -> dict:
        n, gens = 0, 0
        while True:
            with ann("client.next_batch"):
                batch = self.stream.next()
            with ann("client.submit_many"):
                ok = self._ingest_batch(batch)
            t = time.perf_counter()
            if not ok:
                break
            n += len(batch)
            gens += 1
            if gens - 1 == self.sample_gen:
                self.samples.append((len(self.writes),
                                     *self._device_state()))
            if t - t0 >= self.seconds:
                break
        self.generations = gens
        log(f"window: {gens} generations of {self.mix['batch']} in "
            f"{t - t0:.3f}s")
        return {"attempted": n, "updates_per_s": n / (t - t0),
                "window_s": t - t0}

    # check -----------------------------------------------------------------
    def drain(self):
        """Commit what the window left pending, keep the committed state
        for the check, and free the service's state."""
        self.svc.flush()
        st = self.svc.graph.state
        self.samples = [(n, *self.host_state(*arrays)) for n, *arrays in
                        [(len(self.writes), st.edges, st.active, st.phi),
                         *self.samples]]
        del self.svc, st

    def check(self) -> list:
        """Compare the committed states with the plain reference; returns
        the numbers compared, each with its limit 0."""
        t = time.perf_counter()
        states = self.samples
        edge_diff = phi_diff = 0
        for n_writes, edges, phi in states:
            ref_edges = reference.apply_writes(self.edges0,
                                               self.writes[:n_writes])
            ref_phi = reference.truss_decomposition(ref_edges)
            got = {(int(u), int(v)): int(p) for (u, v), p in zip(edges, phi)}
            edge_diff += len(ref_edges ^ set(got))
            phi_diff += sum(1 for e, p in ref_phi.items() if got.get(e) != p)
        checks = [Check("edge_diff", edge_diff, 0),
                  Check("phi_diff", phi_diff, 0),
                  Check("failed_ops", self.failed, 0)]
        log(f"reference: {len(states)} committed states compared in "
            f"{time.perf_counter() - t:.2f}s")
        return checks


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def execute(bench, cell, config, mix, seed, seconds, traced, devices,
            peaks) -> tuple[dict, list]:
    """Set up, measure and check one cell; returns the result line's
    object (without ``correct``) and the checks."""
    workdir = tempfile.mkdtemp(prefix="chipbench-")
    try:
        run = CellRun(config, mix, seed, seconds, traced, workdir, devices)
        run.setup()
        setup_s = time.perf_counter() - T0
        out = run.window()
        memory = peak_bytes(devices)
        summary = None
        if traced:
            import tracing

            t = time.perf_counter()
            summary = tracing.summarize(
                tracing.load(os.path.join(workdir, "trace")), len(devices),
                run.spans, run.window_perf_ns)
            log(f"trace reduced in {time.perf_counter() - t:.2f}s: window "
                f"{summary.window_s:.3f}s, busy {summary.busy_s:.3f}s")
        ctx = Ctx(run.generations, run.spans, run.counters, summary, peaks)
        metrics = {}
        for m in cell_metrics(bench, cell, traced):
            if m["name"] == "setup_s":
                value = setup_s
            elif not traced:
                value = out.get(m["name"])
            else:
                value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": memory}
        line = {"attempted": out["attempted"], "failed": run.failed,
                "metrics": metrics, "device": device}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            line["breakdown"] = {"device_ops": summary.device_ops(),
                                 "idle_gaps": summary.gaps}
        run.drain()
        checks = run.check()
        return line, checks
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench, cell, config, mix = load_cell(args.workload)
    except (OSError, KeyError, ValueError) as exc:
        log(f"FAIL: {exc!r}")
        return 2
    configure_jax_cache()  # before the program's import can compile
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro.service  # noqa: F401 — the system under test
    except ImportError as exc:
        log(f"FAIL: the program is not beside the benchmark ({exc!r})")
        return 1
    try:
        service_args(config["service"])
    except ValueError as exc:
        log(f"FAIL: {exc}")
        return 2
    try:
        devices = require_chips(cell["chips"])
        peaks = load_peaks(devices[0].device_kind)
    except (NoChip, KeyError) as exc:
        log(f"FAIL: {exc}")
        return 1
    log(f"devices: {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}; cell {cell['name']} seed {args.seed} "
        f"seconds {args.seconds:g} trace {args.trace}")
    line, checks = execute(bench, cell, config, mix, args.seed, args.seconds,
                           bool(args.trace), devices, peaks)
    correct = all(c.ok for c in checks)
    for c in checks:
        print(f"check {c.name}: {c.value} (limit {c.limit})",
              file=sys.stderr, flush=True)
    result = {"correct": correct, **line,
              "checks": {c.name: {"value": c.value, "limit": c.limit}
                         for c in checks}}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
