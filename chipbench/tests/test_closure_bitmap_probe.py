"""The ``closure_bitmap_probe`` reader: the share of committed generations
whose affected-set closure tested partners against the adjacency bitmap,
from the ``truss_closure_bitmap_probe_total`` counter."""
import pytest

import run


def _ctx(generations=4, counters=None):
    return run.Ctx(generations, [], dict(counters or {}), None,
                   {"hbm_bytes_per_s": 819e9})


def test_closure_bitmap_probe_reader_gives_the_share_of_generations():
    read = run.load_reader("closure_bitmap_probe")
    counter = "truss_closure_bitmap_probe_total"
    assert read(_ctx(counters={counter: 4})) == pytest.approx(100.0)
    assert read(_ctx(counters={counter: 1})) == pytest.approx(25.0)
    assert read(_ctx(counters={counter: 0})) == 0.0   # the search ran
    assert read(_ctx(counters={})) is None          # a program without it
    assert read(_ctx(generations=0, counters={counter: 1})) is None
