"""Run one benchmark cell with a fault planted under its timed path.

    python bench/tests/fault_run.py <fault> <bench/run.py arguments>
"""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import faults  # noqa: E402
import run  # noqa: E402

if __name__ == "__main__":
    fault, argv = sys.argv[1], sys.argv[2:]
    args = run.parse_args(argv)
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    kind = "serve" if cell["config"].startswith("syncagtr") else "train"
    faults.install(fault, kind)
    sys.exit(run.main(argv))
