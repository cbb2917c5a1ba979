"""A whole run with the timed path broken underneath: ``correct`` must come
out false for each fault a cell can have: a step that returns its state
unchanged, half of each batch left out, a phi altered where it is
produced.  (The cells run on one chip, so there is no exchange between
chips to leave out.)"""
import pytest

from repro.core import batch as core_batch
from repro.core.dynamic import DynamicGraph
from tiny import harness_run

MIXES = ["bulk_churn", "uniform_inserts"]


def _unchanged(monkeypatch):
    """A step that returns its state unchanged: no write is applied."""
    monkeypatch.setattr(DynamicGraph, "apply_batch",
                        lambda self, updates, **kw: None)


def _half(monkeypatch):
    """Half of each batch left out."""
    real = DynamicGraph.apply_batch

    def half(self, updates, **kw):
        ups = list(updates)
        return real(self, ups[:len(ups) // 2], **kw)

    monkeypatch.setattr(DynamicGraph, "apply_batch", half)


def _altered_phi(monkeypatch):
    """One edge's phi altered where the fused maintain produces it."""
    real = core_batch.batch_maintain

    def altered(spec, st, *a, **kw):
        st, lo, hi, stats = real(spec, st, *a, **kw)
        slot = int(st.active.argmax())
        return st._replace(phi=st.phi.at[slot].add(1)), lo, hi, stats

    monkeypatch.setattr(core_batch, "batch_maintain", altered)


FAULTS = {"unchanged": _unchanged, "half": _half, "altered": _altered_phi}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("mix_name", MIXES)
def test_fault_is_not_correct(monkeypatch, mix_name, fault):
    FAULTS[fault](monkeypatch)
    line, _ = harness_run(monkeypatch, mix_name, seed=4, seconds=1.0)
    assert line["correct"] is False, line["checks"]
