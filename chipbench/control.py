#!/usr/bin/env python3
"""The control: runs that ``correct`` has to refuse, at the cell's size.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

The configurations state exact answers: phi of every live edge equals
the from-scratch decomposition of the committed edge set.  The control
breaks that guarantee in the way a later change might be tempted to: the
reference put in the program's place, with its last generation's re-peel
skipped, so the committed edge set is right, but every surviving edge
keeps the phi of the generation before and every new edge gets phi 2.
The benchmark's own comparison (``run.CellRun.check``) must then read a
number above its limit.

For each seed the script runs the cell's window once and prints one JSON
line with the program's numbers and the control's.  The benchmark's own
runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

import numpy as np

import reference
import run


def stale_phi_state(cell_run: run.CellRun, batch: int):
    """The control's committed state in an ingest run: the right edges,
    with the phi of the generation before the last."""
    n_writes = cell_run.samples[0][0]
    before = reference.apply_writes(cell_run.edges0,
                                    cell_run.writes[:n_writes - batch])
    after = reference.apply_writes(before, cell_run.writes[n_writes - batch:
                                                           n_writes])
    old = reference.truss_decomposition(before)
    edges = np.asarray(sorted(after), np.int64).reshape(-1, 2)
    phi = np.asarray([old.get((int(u), int(v)), 2) for u, v in edges])
    return n_writes, edges, phi


def one_seed(config, mix, seed: int, seconds: float, devices) -> dict:
    """One window: the program's numbers and the control's."""
    workdir = tempfile.mkdtemp(prefix="chipbench-control-")
    try:
        r = run.CellRun(config, mix, seed, seconds, False, workdir, devices)
        r.setup()
        r.window()
        r.drain()
        checks = {c.name: c.value for c in r.check()}
        r.samples = [stale_phi_state(r, mix["batch"])]
        return {"seed": seed, "program": checks,
                "control": {c.name: c.value for c in r.check()}}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    _, cell, config, mix = run.load_cell(args.workload)
    run.configure_jax_cache()
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    devices = run.require_chips(cell["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(one_seed(config, mix, seed, args.seconds, devices)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
