"""Every fault a training cell can have, planted under its timed
path (bench/faults.py), turns its ``correct`` false; the harness around
it runs as it would on the chip."""
import pytest

from benchutil import rehearse

CASES = [
    ("train_stablelm_1c", "state_unchanged"),
    ("train_stablelm_1c", "half_batch"),
]


@pytest.mark.parametrize("workload,fault", CASES)
def test_planted_fault_is_not_correct(workload, fault):
    proc, result = rehearse(workload, fault=fault, seconds=0.3)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result is not None
    assert result["correct"] is False, result["checks"]
