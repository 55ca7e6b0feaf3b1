"""Table 4 analogue: lines of user-written code per INC application.

NetRPC's claim: INC apps in ~5% of the LoC of hand-built INC systems.
We count our examples' actual LoC (application code + NetFilter lines,
excluding blanks/comments) against the paper's prior-art numbers.

    python benchmarks/loc_table.py
"""
from __future__ import annotations

import json
import re
from pathlib import Path

EXAMPLES = Path(__file__).resolve().parents[1] / "examples"

# (endhost LoC, switch LoC) from paper Table 4
PRIOR_ART = {
    "SyncAggr": (3394, 5329),
    "AsyncAggr": (3278, 4258),
    "KeyValue": (898, 2360),
    "Agreement": (5441, 931),
}
OUR_FILES = {
    "SyncAggr": "train_mini.py",
    "AsyncAggr": "mapreduce.py",
    "KeyValue": "monitoring.py",
    "Agreement": "paxos.py",
}


def count_loc(path: Path) -> int:
    if not path.exists():
        return 0
    n = 0
    for ln in path.read_text().splitlines():
        s = ln.strip()
        if s and not s.startswith("#") and s != '"""' and not s.startswith(
                '"""'):
            n += 1
    return n


_SCHEMA_RE = re.compile(
    r"@?inc\.(service|rpc|Agg|Get|ReadMostly|CntFwd|DrainPolicy|Plain|"
    r"FPArray|IntArray|STRINTMap|Integer)\b")


def count_netfilter_loc(path: Path) -> int:
    """INC declaration lines inside an example (the 'switch code'): lines
    of the typed schema vocabulary (@inc.service/@inc.rpc decorators and
    Agg/Get/ReadMostly/CntFwd annotations), which compile into the
    NetFilter the legacy JSON blob used to spell out; legacy
    NetFilter.from_dict blocks still count for unported files."""
    if not path.exists():
        return 0
    txt = path.read_text()
    m = re.findall(r"NetFilter\.from_dict\((\{.*?\})\)", txt, re.S)
    legacy = sum(t.count("\n") + 1 for t in m)
    typed = sum(1 for ln in txt.splitlines() if _SCHEMA_RE.search(ln))
    return legacy + typed


def run():
    rows = []
    for app, fname in OUR_FILES.items():
        ours = count_loc(EXAMPLES / fname)
        nf = count_netfilter_loc(EXAMPLES / fname)
        pe, ps = PRIOR_ART[app]
        reduction = 1 - (ours + nf) / (pe + ps)
        rows.append((f"loc/{app}/ours_endhost", 0, ours))
        rows.append((f"loc/{app}/ours_netfilter", 0, nf))
        rows.append((f"loc/{app}/prior_endhost", 0, pe))
        rows.append((f"loc/{app}/prior_switch", 0, ps))
        rows.append((f"loc/{app}/reduction_pct", 0,
                     round(100 * reduction, 1)))
    return rows


if __name__ == "__main__":
    for row in run():
        print(",".join(str(x) for x in row))
