"""Tiny stand-ins for a cell, and a harness run on the CPU around them."""
import contextlib
import copy
import io
import json
import os

import jax

import run
import traffic

CONFIG = {
    "graph": {"generator": "powerlaw_graph", "n_nodes": 300, "m_per_node": 4,
              "triangle_p": 0.7, "structure_seed": 0},
    "service": {"support_method": "bitmap", "partition": "replicated",
                "e_cap": 4096, "d_max": 256},
}


def mix(name: str, **over) -> dict:
    """The committed mix, made small enough for the CPU."""
    m = traffic.load_mix(name)
    small = {"batch": 40, "deletes": 20 if m["deletes"] else 0}
    return {**m, **small, **over}


def cell(mix_name: str, chips: int = 1) -> tuple:
    """``BENCHMARK.json`` and its cell on the mix ``mix_name``, asking for
    ``chips`` chips."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    c = next(w for w in bench["workloads"] if w["traffic"] == mix_name)
    return bench, {**c, "chips": chips}


def harness_run(monkeypatch, mix_name: str, seed: int = 7,
                seconds: float = 0.5, trace: int = 0, chips: int = 1,
                service: dict | None = None, **over):
    """``run.main`` end to end, with the chip look stepped around and the
    cell cut to tiny sizes (``service`` overrides the configuration's
    service arguments); returns (result line, standard error)."""
    bench, c = cell(mix_name, chips)
    m = mix(mix_name, **over)
    config = copy.deepcopy(CONFIG)
    config["service"].update(service or {})
    monkeypatch.setattr(run, "load_cell", lambda name: (
        bench, c, copy.deepcopy(config), m))
    monkeypatch.setattr(run, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(run, "load_peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", c["name"], "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)])
    assert rc == 0, err.getvalue()[-3000:]
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


if __name__ == "__main__":
    # one tiny run on every device JAX sees, partitioned over them when
    # there are several: python tiny.py <mix> (with emulated devices)
    import sys

    import pytest

    n = len(jax.devices())
    with pytest.MonkeyPatch.context() as mp:
        line, _ = harness_run(mp, sys.argv[1], seed=5, chips=n,
                              service={"partition": "nodes"} if n > 1
                              else None)
    print(json.dumps(line))
