"""The ``epinions`` deployment (Table-2 Epinions over a four-chip host, the
adjacency bitmap node-partitioned) and the per-layer metrics its cell
adds: the configuration's sizes, the readers on hand-made traces and
counters, and the cell through the harness on four emulated CPU devices."""
import json
import os
import subprocess
import sys

import numpy as np

import run
import tracing
from graphs import make_graph

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "epinions.ingest"
KERNEL_CALL = ("%bitmap_support_kernel.1 = s32[8192]{0:T(1024)} custom-call("
               "u32[8192,593]{1,0:T(8,128)} %fusion.312, "
               "u32[8192,593]{1,0:T(8,128)} %fusion.313), "
               'custom_call_target="tpu_custom_call"')
KERNEL_BYTES = 2 * 8192 * 593 * 4 + 8192 * 4


def ctx(generations=2, counters=None, trace=None):
    return run.Ctx(generations, [], counters or {}, trace,
                   {"hbm_bytes_per_s": 819e9})


def summary(ops, chips=4):
    return tracing.Summary(chips=chips, window_s=1.0, busy_s=0.5, ops=ops)


def test_configuration_meets_the_published_size_and_its_capacities():
    """507,402 edges (published 508,837) with max degree 2,129; the fixed
    capacities are twice those and split evenly over four chips."""
    _, cell, config, _ = run.load_cell(CELL)
    n = config["graph"]["n_nodes"]
    edges = make_graph(config["graph"])
    deg = np.bincount(edges.reshape(-1), minlength=n)
    assert (len(edges), int(deg.max())) == (507402, 2129)
    service = config["service"]
    assert service["e_cap"] == 2 * len(edges)
    assert service["d_max"] == 2 * int(deg.max())
    n_words = -(-n // 32)
    assert service["e_cap"] % cell["chips"] == 0
    assert n_words % cell["chips"] == 0
    assert (service["partition"], service["support_method"]) == (
        "nodes", "bitmap")
    assert config["reduced"] == []
    # the per-chip bytes the file states follow from the shapes
    word_block = n_words // cell["chips"]
    stated = config["device_bytes"]
    assert stated["bitmap_slab"] == n * word_block * 4
    assert stated["node_tables"] == n * (2 * service["d_max"] * 4 + 4)
    assert stated["edge_state"] == service["e_cap"] // cell["chips"] * 13
    assert stated["support_chunk"] == 2 * 8192 * word_block * 4
    assert stated["replicated_wave_gathers"] == (
        2 * service["e_cap"] * n_words * 4)


def test_cell_reports_the_new_metrics_and_not_the_peel_wave_kernel():
    bench, cell, _, mix = run.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "epinions", "bulk_churn", 4)
    assert mix["batch"] == 1000 and mix["deletes"] == 500
    assert [m["name"] for m in run.cell_metrics(bench, cell, False)] == [
        "updates_per_s", "setup_s"]
    traced = {m["name"] for m in run.cell_metrics(bench, cell, True)}
    assert {"bitmap_support_roofline", "collective_ms", "exchange_mb",
            "closure_bitmap_probe", "device_idle"} <= traced
    assert "peel_wave_roofline" not in traced


def test_exchange_mb_reader_gives_megabytes_per_generation():
    read = run.load_reader("exchange_mb")
    assert read(ctx(2, {"truss_exchange_bytes_total": 9_000_000})) == 4.5
    assert read(ctx(2, {"truss_exchange_bytes_total": 0})) == 0.0
    assert read(ctx(2, {})) is None             # a program without it
    assert read(ctx(0, {"truss_exchange_bytes_total": 1})) is None


def test_collective_ms_reader_sums_collectives_over_chips():
    read = run.load_reader("collective_ms")
    ms = 1_000_000
    ops = [tracing.Op("%psum.15 = s32[1014804]{0:T(1024)} all-reduce("
                      "s32[1014804]{0} %bitcast.281), channel_id=1", 0, 3 * ms),
           tracing.Op("%ag = (u32[256,593]{1,0}, u32[1024,593]{1,0}) "
                      "all-gather-start(u32[256,593]{1,0} %gather.1)",
                      0, 2 * ms),
           tracing.Op("%ag.done = u32[1024,593]{1,0} all-gather-done("
                      "(u32[256,593]{1,0}, u32[1024,593]{1,0}) %ag)",
                      5 * ms, 8 * ms),
           tracing.Op("%fusion.2 = s32[20]{0:T(128)} fusion(s32[20]{0} "
                      "%all-reduce.1), kind=kLoop", 0, 50 * ms),
           tracing.Op(KERNEL_CALL, 0, 40 * ms)]
    # (3 + 2 + 3) ms over 4 chips and 2 generations
    assert read(ctx(2, trace=summary(ops))) == 1.0
    assert read(ctx(2, trace=summary(ops[3:]))) == 0.0
    assert read(ctx(2)) is None
    assert read(ctx(0, trace=summary(ops))) is None


def test_bitmap_support_roofline_reader_counts_each_call_from_its_shapes():
    read = run.load_reader("bitmap_support_roofline")
    least_ns = KERNEL_BYTES / 819e9 * 1e9
    # two calls, each twice as long as its bytes allow: 50%
    ops = [tracing.Op(KERNEL_CALL, 0, int(round(2 * least_ns))),
           tracing.Op(KERNEL_CALL.replace(".1 =", ".2 ="), 10**9,
                      10**9 + int(round(2 * least_ns)))]
    assert abs(read(ctx(2, trace=summary(ops))) - 50.0) < 0.01
    others = [tracing.Op("%fusion.2 = s32[20]{0} fusion(s32[20]{0} %x), "
                         "kind=kLoop", 0, 10)]
    assert read(ctx(2, trace=summary(others))) is None
    assert read(ctx(2)) is None


RUN_CELL = """
import json, pytest
import tiny

committed = tiny.cell


def cell(mix_name, chips=1):
    bench, _ = committed(mix_name, chips)
    c = next(w for w in bench["workloads"] if w["name"] == "epinions.ingest")
    return bench, {**c, "chips": chips}


tiny.cell = cell
service = {k: v for k, v in json.load(open(
    "../configs/epinions.json"))["service"].items()
    if k in ("support_method", "partition")}
with pytest.MonkeyPatch.context() as mp:
    line, err = tiny.harness_run(mp, "bulk_churn", seed=2**31 + 29, chips=4,
                                 service=service)
assert "cell epinions.ingest" in err, err[-2000:]
assert "0 programs compiled inside the window" in err, err[-2000:]
print(json.dumps(line))
"""


def test_four_chip_epinions_cell_runs_partitioned_over_a_mesh():
    """The cell through the harness at a tiny size on four emulated CPU
    devices: its configuration's partition reaches the service over a mesh
    of the four, the window compiles nothing, and the run is correct
    against the reference."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [os.path.dirname(HERE), HERE, os.path.join(run.ROOT, "src")])}
    out = subprocess.run([sys.executable, "-c", RUN_CELL], env=env,
                         capture_output=True, text=True, timeout=600, cwd=HERE)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["device"]["count"] == 4
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"updates_per_s", "setup_s"}
