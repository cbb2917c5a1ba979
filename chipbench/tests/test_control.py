"""The control at a size a test run holds: ``correct`` must come out
false when a guarantee the configuration states is broken
(``control.py``)."""
import pytest

import control
import run
from tiny import harness_run


@pytest.mark.parametrize("mix_name", ["bulk_churn", "uniform_inserts"])
def test_stale_phi_control_is_not_correct(monkeypatch, mix_name):
    """The reference in the program's place, last re-peel skipped."""
    drain = run.CellRun.drain

    def stale(self):
        drain(self)
        self.samples = [control.stale_phi_state(self, self.mix["batch"])]

    monkeypatch.setattr(run.CellRun, "drain", stale)
    line, _ = harness_run(monkeypatch, mix_name, seed=3)
    assert line["correct"] is False
    assert line["checks"]["phi_diff"]["value"] > 0
    assert line["checks"]["edge_diff"]["value"] == 0
