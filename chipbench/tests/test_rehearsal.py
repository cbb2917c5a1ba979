"""Each traffic mix through the whole harness on the CPU at a tiny size:
set-up, window, end-to-end arithmetic, the reference check, the result
line."""
import functools
import json
import math
import os
import subprocess
import sys

import pytest

import run
import traffic
from graphs import make_graph
from tiny import harness_run

MIXES = ["bulk_churn", "uniform_inserts"]
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("mix_name", MIXES)
def test_mix_runs_correct(monkeypatch, mix_name):
    line, err = harness_run(monkeypatch, mix_name, seed=2**31 + 11)
    assert line["correct"] is True
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    assert set(line["metrics"]) == {"updates_per_s", "setup_s"}
    assert all(math.isfinite(m["value"]) and m["value"] > 0
               for m in line["metrics"].values())
    assert "0 programs compiled inside the window" in err
    # the numbers compared are the last lines of standard error
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") for t in tail)


def test_same_seed_same_traffic():
    edges = [(0, 1), (1, 2), (2, 3)]
    a = traffic.Ingest(traffic.load_mix("uniform_inserts"), 300, edges, 5)
    b = traffic.Ingest(traffic.load_mix("uniform_inserts"), 300, edges, 5)
    assert a.next() == b.next()


@pytest.mark.parametrize("mix_name", MIXES)
def test_ingest_batches_net_to_fixed_counts(mix_name):
    """Every batch nets to the same deletes and inserts, whatever the seed,
    so the service pads every generation to one shape."""
    mix = traffic.load_mix(mix_name)
    for seed in (1, 2, 3):
        graph = make_graph({"generator": "powerlaw_graph", "n_nodes": 2000,
                            "m_per_node": 4, "triangle_p": 0.7,
                            "structure_seed": seed})
        s = traffic.Ingest(mix, 2000, graph, seed)
        for _ in range(3):
            batch = s.next()
            dels = {(a, b) for op, a, b in batch if op == traffic.OP_DELETE}
            ins = {(a, b) for op, a, b in batch if op == traffic.OP_INSERT}
            assert len(batch) == mix["batch"]
            assert len(dels) == mix["deletes"] and not dels & ins


def test_no_chip_fails_without_result(capsys):
    """Without an accelerator the run exits non-zero and prints no line."""
    rc = run.main(["--workload", "enron.ingest", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("key", ["flush_every", "no_such_argument", "mesh"])
def test_service_key_not_passed_is_refused(key):
    """A configuration's ``service`` key reaches ``TrussService`` as it
    stands or the run is refused: none is dropped silently."""
    with pytest.raises(ValueError, match=key):
        run.service_args({"support_method": "bitmap", key: 1})
    assert run.service_args({"partition": "nodes", "pipeline": True}) == {
        "partition": "nodes", "pipeline": True}


def test_service_arguments_reach_the_service(monkeypatch):
    """What the configuration states is what the service runs with."""
    from repro.service import TrussService

    seen = {}
    init = TrussService.__init__

    @functools.wraps(init)
    def spy(self, *a, **kw):
        seen.update(kw)
        init(self, *a, **kw)

    monkeypatch.setattr(TrussService, "__init__", spy)
    line, _ = harness_run(monkeypatch, "bulk_churn",
                          service={"strategy": "delta"})
    assert line["correct"] is True
    assert seen["strategy"] == "delta" and seen["partition"] == "replicated"
    assert seen["flush_every"] == 40 and seen["mesh"] is None


def test_mix_with_unknown_key_is_refused(tmp_path, monkeypatch):
    """A mix key the generator does not read is refused."""
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "closing.json").write_text(json.dumps(
        {"kind": "ingest", "about": "x", "batch": 10, "deletes": 0,
         "triangle_p": 0.5}))
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="triangle_p"):
        traffic.load_mix("closing")


def test_graph_with_unknown_key_is_refused():
    with pytest.raises(TypeError):
        make_graph({"generator": "powerlaw_graph", "n_nodes": 10,
                    "m_per_node": 2, "triangle_p": 0.7, "structure_seed": 0,
                    "max_degree": 4})


def test_reader_falls_back_to_its_base_name():
    """``device_idle.read`` (a split by end-to-end metric) is read by
    ``layers/device_idle.py``."""
    path = run.load_reader("device_idle.read").__code__.co_filename
    assert os.path.basename(path) == "device_idle.py"
    with pytest.raises(FileNotFoundError):
        run.load_reader("no_such_metric")


def test_four_chip_cell_runs_partitioned_over_a_mesh():
    """A cell that asks for four chips builds a mesh of them, and the
    configuration's partition reaches the service: a tiny run on four
    emulated CPU devices, node-partitioned, is correct and reports them."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [os.path.dirname(HERE), os.path.join(run.ROOT, "src")])}
    out = subprocess.run([sys.executable, os.path.join(HERE, "tiny.py"),
                          "bulk_churn"], env=env, capture_output=True,
                         text=True, timeout=600, cwd=HERE)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["device"]["count"] == 4
