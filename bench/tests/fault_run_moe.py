"""Run one MoE training cell with a fault planted under its timed path:
an expert-layer fault of bench/faults_moe.py, or a training fault of
bench/faults.py.

    python bench/tests/fault_run_moe.py <fault> <bench/run.py arguments>
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import faults  # noqa: E402
import faults_moe  # noqa: E402
import run  # noqa: E402

if __name__ == "__main__":
    fault, argv = sys.argv[1], sys.argv[2:]
    if fault in faults_moe.FAULTS:
        faults_moe.install(fault)
    else:
        faults.install(fault, "train")
    sys.exit(run.main(argv))
