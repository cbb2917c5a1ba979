"""The trace reduction (``tracing.py``), on hand-made inputs and on a small
trace recorded on one TPU v5e (``data/``: a 0.5 s window of a tiny ingest
cell)."""
import json
import os
from collections import namedtuple

import pytest

import tracing

#: the fields of a program span the reduction reads, as recorded
Span = namedtuple("Span", "name seq parent t0_ns dur_ns")

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HLO = ("%custom-call.7 = (s32[416950]{0}, s32[416950]{0}) custom-call("
       "s32[1]{0} %p.1, u32[416950,1147]{1,0} %g.2, "
       "u32[416950,1147]{1,0} %g.3, s32[416950]{0} %c.4), "
       "custom_call_target=\"tpu_custom_call\", "
       "backend_config={\"shape\": \"u32[9,9]\"}")


def test_custom_call_shapes_and_bytes():
    results, operands = tracing.custom_call_shapes(HLO)
    assert results == [("s32", (416950,)), ("s32", (416950,))]
    assert operands == [("s32", (1,)), ("u32", (416950, 1147)),
                        ("u32", (416950, 1147)), ("s32", (416950,))]
    e, w = 416950, 1147
    assert tracing.call_bytes(results, operands) == (
        2 * e * w * 4 + 4 + e * 4 + 2 * e * 4)


def test_other_instructions_are_not_custom_calls():
    assert tracing.custom_call_shapes(
        "%fusion.3 = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop") is None


def test_leaves_drop_enclosing_ops():
    ops = [tracing.Op("%while.1 = s32[] while()", 0, 10),
           tracing.Op("%fusion.2 = s32[4] fusion()", 1, 3),
           tracing.Op("%fusion.3 = s32[4] fusion()", 4, 9),
           tracing.Op("%copy.4 = s32[4] copy()", 12, 13)]
    assert [o.instruction for o in tracing.leaves(ops)] == [
        "fusion", "fusion", "copy"]


def test_union_merges_overlaps():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3),
                                                               (5, 9)]


def _recorded():
    path = os.path.join(DATA, "tiny_ingest.xplane.pb.gz")
    if not os.path.exists(path):
        pytest.fail(f"recorded trace missing: {path}")
    with open(os.path.join(DATA, "tiny_ingest.spans.json")) as f:
        meta = json.load(f)
    spans = [Span(*s) for s in meta["spans"]]
    return tracing.load(path), spans, meta


def test_recorded_trace_reduces():
    pd, spans, meta = _recorded()
    s = tracing.summarize(pd, 1, spans, meta["window_perf_ns"])
    assert s.chips == 1
    assert 0 < s.busy_s <= s.window_s
    assert s.window_s == pytest.approx(meta["window_s"], rel=0.05)
    calls = s.kernel_calls("peel_wave_kernel")
    assert calls, "no peel_wave call found in the recorded trace"
    e, w = meta["e_cap"], meta["n_words"]
    assert {b for _, b in calls} == {2 * e * w * 4 + 4 + e * 4 + 2 * e * 4}
    assert len(s.device_ops()) == 10 and len(s.gaps) == 10
    assert all(sec > 0 for _, sec in s.gaps)
    names = {n for n, _ in s.gaps}
    assert names - {"(no host span)"}, names
