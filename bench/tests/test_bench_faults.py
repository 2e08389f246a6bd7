"""A whole run on the CPU with the timed path broken underneath: the
check has to come out false for each fault a serving cell can have."""
import pytest

import faults
import tiny


@pytest.mark.parametrize("fault", [faults.frozen_step, faults.half_mean,
                                   faults.altered_answer],
                         ids=lambda f: f"_{f.__name__}")
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    r = tiny.run()
    assert r["attempted"] > 0
    assert not r["correct"], r["checks"]
