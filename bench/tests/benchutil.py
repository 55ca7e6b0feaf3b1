"""Helpers of the benchmark's CPU tests: run a cell end to end in a child
process (its own JAX, with as many CPU devices as the cell has chips)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def cell(name: str) -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return next(w for w in spec["workloads"] if w["name"] == name)


def child_env(chips: int = 1) -> dict:
    """A CPU child with one compute thread, so that rehearsals running
    beside the rest of the suite leave its timing-sensitive tests alone."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", OMP_NUM_THREADS="1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count="
                        f"{chips} --xla_cpu_multi_thread_eigen=false").strip()
    return env


def rehearse(workload: str, *, seed: int = 3_000_000_019,
             seconds: float = 0.5, trace: int = 0, fault: str | None = None,
             timeout: float = 300) -> tuple[subprocess.CompletedProcess,
                                            dict | None]:
    """Run one cell at its toy size on the CPU; with ``fault``, under that
    planted fault (bench/faults.py). Returns the process and the parsed
    last line of its output, if it printed one."""
    args = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearse"]
    if fault:
        cmd = [sys.executable, str(BENCH / "tests" / "fault_run.py"), fault]
    else:
        cmd = [sys.executable, str(BENCH / "run.py")]
    proc = subprocess.run(cmd + args, cwd=ROOT, env=child_env(
        cell(workload)["chips"]), capture_output=True, text=True,
        timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    result = None
    if lines:
        try:
            result = json.loads(lines[-1])
        except json.JSONDecodeError:
            result = None
    return proc, result
