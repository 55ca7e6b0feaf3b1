"""Every fault a serve cell can have, planted under its timed path
(bench/faults.py), turns its ``correct`` false; the harness around it
runs as it would on the chip."""
import pytest

from benchutil import rehearse

CASES = [(cell, fault) for cell in ("agtr_32k", "agtr_1k")
         for fault in ("answer_altered", "state_unchanged", "half_batch")]


@pytest.mark.parametrize("workload,fault", CASES)
def test_planted_fault_is_not_correct(workload, fault):
    proc, result = rehearse(workload, fault=fault, seconds=0.3)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result is not None
    assert result["correct"] is False, result["checks"]
