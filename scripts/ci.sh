#!/usr/bin/env bash
# Fast lane: the non-slow suite under a timeout, then planelint, the wire
# quorum and the example apps. Correctness only: speed is measured by
# bench/run.py on the chip.
#
#   make test-fast        (or: bash scripts/ci.sh)
#
# Exits non-zero if anything fails/errors or if collection breaks.
set -u
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

out=$(timeout "${CI_TIMEOUT:-600}" python -m pytest -q -m "not slow" 2>&1)
status=$?
echo "$out" | tail -20

if [ "$status" -ne 0 ]; then
    echo "FAST LANE: FAIL (pytest exit $status)"
    exit "$status"
fi

# planelint lane: the plane-invariant static analyzer (docs/ANALYSIS.md)
# must be clean — findings are only tolerated behind an inline pragma or
# a justified scripts/planelint_baseline.json entry, and stale baseline
# entries fail too (exit 2). The same block asserts the zero-dependency
# guarantee: importing and running repro.analysis pulls nothing outside
# the stdlib, so the lint gate runs on a stock Python (no ruff, no
# site-packages) and cannot rot with the environment.
if ! timeout 120 python - <<'EOF'
import sys
before = set(sys.modules)
from repro.analysis.cli import main
import repro.analysis
repro.analysis.analyze_source("import os\n")
stdlib = set(sys.stdlib_module_names)
bad = sorted(m for m in set(sys.modules) - before
             if m.split(".")[0] not in stdlib
             and not (m == "repro" or m.startswith("repro.analysis")))
assert not bad, f"repro.analysis pulled non-stdlib modules: {bad}"
print("planelint zero-dep: OK")
sys.exit(main(["src/repro"]))
EOF
then
    echo "FAST LANE: FAIL (planelint)"
    exit 1
fi

# wire quorum lane: a real switchd subprocess + 2 real client worker
# subprocesses voting CntFwd through a 5% lossy proxy, with one mid-run
# daemon restart-from-spool. The orchestrator verifies votes, grads
# (element-exact vs a recomputed oracle), commit count, and zero
# duplicate effects — any divergence exits non-zero.
smoke_log=$(mktemp)
if ! timeout 300 python -m repro.launch.elastic --wire-quorum --wire-loss 0.05 > "$smoke_log" 2>&1; then
    echo "FAST LANE: FAIL (wire quorum); output:"
    cat "$smoke_log"
    rm -f "$smoke_log"
    exit 1
fi
rm -f "$smoke_log"
echo "wire quorum: OK"

# examples lane: the typed-schema INC apps are the front door — an API
# regression here must fail CI, not users. Each example self-asserts its
# INC results (aggregation sums, exact counters, quorum counts, folded
# telemetry exactness).
for ex in quickstart mapreduce monitoring paxos train_telemetry; do
    ex_log=$(mktemp)
    if ! timeout 120 python -m "examples.$ex" > "$ex_log" 2>&1; then
        echo "FAST LANE: FAIL (examples.$ex); output:"
        cat "$ex_log"
        rm -f "$ex_log"
        exit 1
    fi
    rm -f "$ex_log"
    echo "examples.$ex: OK"
done
echo "FAST LANE: OK"
