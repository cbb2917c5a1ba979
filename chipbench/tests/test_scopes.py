"""Stages of device ops (the program's ``repro.obs.stages``) on a recorded
chip trace, the readers of the work counters and host spans, and the trace
reduction pinned on the first recorded trace.

``data/tiny_stages.*`` is a trace of the tiny cell recorded on one TPU v5e
by ``record_trace.py`` with the program's stage scopes and its spans as
profiler annotations; ``data/tiny_ingest.*`` is the one recorded before
either existed.
"""
import json
import os
from collections import namedtuple

import pytest

import run
import tracing
from repro.obs import stages

Span = namedtuple("Span", "name seq parent t0_ns dur_ns")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _recorded(stem):
    path = os.path.join(DATA, f"{stem}.xplane.pb.gz")
    if not os.path.exists(path):
        pytest.fail(f"recorded trace missing: {path}")
    with open(os.path.join(DATA, f"{stem}.spans.json")) as f:
        meta = json.load(f)
    return path, [Span(*s) for s in meta["spans"]], meta


# -- the trace recorded with stages ----------------------------------------------
def test_recorded_stages_cover_the_fused_batch_maintain():
    """The stages name the ops the harness reduces (its ops are keyed by
    the same HLO text), cover >= 95% of the fused batch maintain's leaf
    time, and split the same leaf time the harness counts."""
    path, _, _ = _recorded("tiny_stages")
    out = stages.summarize(path)
    rows = out["device_scopes"]
    mine = {k.split("/", 1)[1] for k, _ in rows
            if k.startswith(stages.BATCH_MODULE + "/")}
    assert set(stages.STAGES) <= mine, rows
    assert out["stage_share"] >= 0.95, rows
    pd = tracing.load(path)
    ops = [op for dev in tracing.device_ops(pd, 1).values() for op in dev]
    assert set(stages.op_scopes(stages.read_raw(path))) & {
        op.hlo for op in ops}
    leaf = sum(op.t1 - op.t0 for op in tracing.leaves(ops)) * 1e-9
    assert sum(v for _, v in rows) == pytest.approx(leaf, rel=1e-9)


def test_stages_cli_prints_the_breakdown(capsys):
    path, _, _ = _recorded("tiny_stages")
    assert stages.main([path]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out == json.loads(json.dumps(stages.summarize(path)))
    assert stages.main([]) == 2


def test_recorded_program_spans_are_on_the_trace_clock():
    """Every program span of the window is also a host annotation of the
    trace, with the same duration to within clock reads."""
    path, spans, _ = _recorded("tiny_stages")
    hosts = tracing.host_spans(tracing.load(path))
    for name in ("service.admit", "graph.net", "graph.bitmap_apply",
                 "graph.apply_batch", "gen.wait", "gen.commit", "flush"):
        mine = sorted(s.dur_ns for s in spans if s.name == name)
        traced = sorted(s.t1 - s.t0 for s in hosts if s.name == name)
        assert mine and len(traced) >= len(mine), name
        for a, b in zip(mine, traced[-len(mine):]):
            assert abs(a - b) <= max(50_000, 0.05 * a), (name, a, b)


def test_recorded_gaps_name_the_stage_not_flush():
    path, spans, meta = _recorded("tiny_stages")
    s = tracing.summarize(tracing.load(path), 1, spans,
                          meta["window_perf_ns"])
    assert s.gaps
    assert all(name != "flush" for name, sec in s.gaps if sec >= 0.002), \
        s.gaps


# -- the first recorded trace reduces as it always has ---------------------------
def test_first_recorded_trace_reduces_as_pinned():
    path, spans, meta = _recorded("tiny_ingest")
    s = tracing.summarize(tracing.load(path), 1, spans,
                          meta["window_perf_ns"])
    assert s.busy_s == pytest.approx(0.36610326600000004, rel=1e-12)
    assert s.window_s == pytest.approx(0.581432512, rel=1e-12)
    calls = s.kernel_calls("peel_wave_kernel")
    assert len(calls) == 92 and {b for _, b in calls} == {376836}
    assert sum(c for c, _ in calls) == pytest.approx(0.000645644, rel=1e-9)
    ops = s.device_ops()
    assert [k for k, _ in ops] == [
        "jit_batch_maintain/fusion s32[65536]",
        "jit_batch_maintain/fusion pred[65536]",
        "jit_batch_maintain/fusion pred[4096]",
        "jit_batch_maintain/fusion s32[8192]",
        "jit_batch_maintain/fusion u32[3000]",
        "jit_batch_maintain/fusion s32[36864]",
        "jit_batch_maintain/sort s32[65536]",
        "jit_batch_maintain/fusion s32[256]",
        "jit_batch_maintain/select_select_fusion s32[8192]",
        "jit_batch_maintain/fusion pred[8192]"]
    assert [v for _, v in ops] == pytest.approx(
        [0.2735370219999991, 0.034835520000000016, 0.025337409000000012,
         0.012143395000000005, 0.005439909000000001, 0.0037461440000000003,
         0.0022594749999999995, 0.0013760100000000004,
         0.0013636580000000007, 0.0013233490000000001], rel=1e-9)
    assert [n for n, _ in s.gaps] == ["flush"] * 10
    assert [v for _, v in s.gaps] == pytest.approx(
        [0.012049922000000001, 0.011676797000000001, 0.010768963000000001,
         0.010329183, 0.006631876, 0.004919442, 0.001386124,
         0.0013839030000000001, 0.0012911230000000001,
         0.0012823530000000002], rel=1e-9)


# -- readers ------------------------------------------------------------------------
def _ctx(generations=4, counters=None, spans=()):
    return run.Ctx(generations, list(spans), dict(counters or {}), None,
                   {"hbm_bytes_per_s": 819e9})


@pytest.mark.parametrize("metric, counter", [
    ("closure_iters", "truss_closure_iterations_total"),
    ("affected_edges", "truss_affected_edges_total"),
])
def test_counter_readers(metric, counter):
    read = run.load_reader(metric)
    assert read(_ctx(counters={counter: 3200})) == 800
    assert read(_ctx(counters={})) is None          # a program without it
    assert read(_ctx(generations=0, counters={counter: 1})) is None


def test_closure_unfiltered_reader_gives_the_share_of_generations():
    read = run.load_reader("closure_unfiltered")
    counter = "truss_closure_unfiltered_total"
    assert read(_ctx(counters={counter: 3})) == pytest.approx(75.0)
    assert read(_ctx(counters={counter: 0})) == 0.0   # every batch filtered
    assert read(_ctx(counters={})) is None          # a program without it
    assert read(_ctx(generations=0, counters={counter: 1})) is None


def test_host_path_reader_sums_the_host_stages():
    read = run.load_reader("host_path_ms")
    spans = [Span("service.admit", 0, -1, 0, 2_000_000),
             Span("graph.net", 1, 5, 0, 5_000_000),
             Span("graph.bitmap_apply", 2, 5, 0, 1_000_000),
             Span("gen.commit", 3, 5, 0, 2_000_000),
             Span("wal.fsync", 4, 5, 0, 9_000_000),     # wal_ms reads it
             Span("gen.wait", 6, 5, 0, 90_000_000),     # device time
             Span("flush", 5, -1, 0, 110_000_000)]
    assert read(_ctx(generations=2, spans=spans)) == pytest.approx(5.0)
    assert read(_ctx(spans=[s for s in spans
                            if s.name in ("flush", "wal.fsync")])) is None
