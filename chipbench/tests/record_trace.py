#!/usr/bin/env python3
"""Record a small chip trace for the trace-reduction tests.

    python3 chipbench/tests/record_trace.py chipbench/tests/data/<stem>

On one chip (JAX's default device), runs the tiny stand-in cell of
``tiny.py`` (a 300-node power-law graph, the ``bulk_churn`` mix cut to
40-update batches) through ``run.CellRun`` with a 0.5 s traced window, and
writes ``<stem>.xplane.pb.gz``, the profiler trace, and
``<stem>.spans.json``: the window's program spans (name, seq, parent,
t0_ns, dur_ns), ``window_perf_ns``, ``window_s``, the state's ``e_cap`` and
bitmap ``n_words``, and how it was recorded.
"""
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (HERE, BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

import run  # noqa: E402
import tiny  # noqa: E402

SEED = 11
SECONDS = 0.5


def main(stem: str) -> int:
    device = jax.devices()[0]
    workdir = tempfile.mkdtemp(prefix="chipbench-record-")
    try:
        cell = run.CellRun(tiny.CONFIG, tiny.mix("bulk_churn"), SEED,
                           SECONDS, True, workdir, [device])
        cell.setup()
        out = cell.window()
        found = glob.glob(os.path.join(workdir, "trace", "**",
                                       "*.xplane.pb"), recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{len(found)} traces under {workdir}")
        with open(found[0], "rb") as src, \
                gzip.open(stem + ".xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
        spec = cell.svc.graph.spec
        service = tiny.CONFIG["service"]
        meta = {
            "window_perf_ns": cell.window_perf_ns,
            "spans": [[e.name, e.seq, e.parent, e.t0_ns, e.dur_ns]
                      for e in cell.spans],
            "window_s": out["window_s"],
            "e_cap": spec.e_cap,
            "n_words": spec.n_words,
            "recorded": (
                f"one {device.device_kind}, chipbench CellRun over a "
                f"{tiny.CONFIG['graph']['n_nodes']}-node power-law graph "
                f"(e_cap {service['e_cap']}, d_max {service['d_max']}), "
                f"bulk_churn mix cut to {tiny.mix('bulk_churn')['batch']}"
                f"-update batches, {SECONDS:g} s window, --trace 1, seed "
                f"{SEED}: chipbench/tests/record_trace.py"),
        }
        with open(stem + ".spans.json", "w") as f:
            json.dump(meta, f)
        print(json.dumps({"generations": cell.generations,
                          "window_s": out["window_s"],
                          "bytes": os.path.getsize(stem + ".xplane.pb.gz")}))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
