"""Every fault the MoE training cell can have, planted under its timed
path, turns its ``correct`` false: the training faults of
bench/faults.py and the expert-layer fault of bench/faults_moe.py."""
import json
import subprocess
import sys

import pytest

from benchutil import BENCH, ROOT, cell, child_env

CELL = "train_moonlight_1c"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "expert_dropped"])
def test_planted_fault_is_not_correct(fault):
    proc = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "fault_run_moe.py"), fault,
         "--workload", CELL, "--seed", "3000000019", "--seconds", "0.3",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=child_env(cell(CELL)["chips"]), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False, result["checks"]
