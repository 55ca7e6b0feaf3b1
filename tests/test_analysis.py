"""planelint (repro.analysis): per-rule fires/doesn't-fire fixtures,
pragma/baseline round-trips, the committed-baseline meta-test, the
zero-dependency guarantee, and the PLANE_LOCK_TIMEOUT quick-fix
regressions."""
import json
import os
import subprocess
import sys
import textwrap
import threading
from pathlib import Path

import pytest

from repro.analysis import analyze_source, baseline, run
from repro.analysis.cli import main as cli_main
from repro.analysis.rules import RULES

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src" / "repro"
BASELINE = REPO / "scripts" / "planelint_baseline.json"

HOT = "src/repro/core/inc_map.py"       # a hot-path filename for O1


def findings(source, rule=None, path="src/repro/fixture.py"):
    got = analyze_source(textwrap.dedent(source), path=path)
    if rule is not None:
        got = [f for f in got if f.rule == rule]
    return got


def rules_of(source, path="src/repro/fixture.py"):
    return {f.rule for f in findings(source, path=path)}


# ---------------------------------------------------------------------------
# L1 — stripe-locked state

L1_BAD = """
    def promote(segments):
        for seg in segments:
            seg.regs = seg.regs + 1
"""

L1_GOOD = """
    def promote(segments):
        for seg in segments:
            with seg.lock:
                seg.regs = seg.regs + 1
"""


def test_l1_fires_on_unlocked_regs_mutation():
    got = findings(L1_BAD, "L1")
    assert len(got) == 1
    assert got[0].detail == "regs" and got[0].line == 4


def test_l1_quiet_under_segment_lock():
    assert not findings(L1_GOOD, "L1")


def test_l1_quiet_in_init_and_locked_and_private():
    src = """
    class Agent:
        def __init__(self):
            self.mapping = {}
        @_locked
        def install(self, k, v):
            self.mapping[k] = v
        def _install(self, k, v):
            self.mapping[k] = v
    """
    assert not findings(src, "L1")


def test_l1_fires_on_public_unlocked_map_mutation():
    src = """
    class Agent:
        def install(self, k, v):
            self.mapping[k] = v
    """
    got = findings(src, "L1")
    assert len(got) == 1 and got[0].scope == "Agent.install"


def test_l1_fires_on_mutating_method_call():
    src = """
    def wipe(agent):
        agent.spill.clear()
    """
    assert [f.detail for f in findings(src, "L1")] == ["spill"]


# ---------------------------------------------------------------------------
# L2 — lock order and blocking under the plane

def test_l2_fires_on_untimed_plane_acquire():
    src = """
    def go(ch):
        ch.plane.acquire()
    """
    got = findings(src, "L2")
    assert len(got) == 1 and got[0].detail == "plane.acquire"


def test_l2_quiet_on_timed_plane_acquire():
    src = """
    def go(ch):
        if not ch.plane.acquire(timeout=60.0):
            raise RuntimeError("cycle")
    """
    assert not findings(src, "L2")


def test_l2_fires_on_plane_after_stripe():
    src = """
    def bad(seg, ch):
        with seg.lock:
            with ch.plane:
                pass
    """
    got = findings(src, "L2")
    assert [f.detail for f in got] == ["plane-after-stripe"]


def test_l2_quiet_on_plane_then_stripe():
    src = """
    def good(seg, ch):
        with ch.plane:
            with seg.lock:
                pass
    """
    assert not findings(src, "L2")


def test_l2_fires_on_result_wait_under_plane():
    src = """
    def bad(ch, fut):
        with ch.plane:
            fut.result()
    """
    assert [f.detail for f in findings(src, "L2")] == [".result()"]


def test_l2_fires_after_explicit_acquire_span():
    src = """
    def bad(ch, fut):
        ch.plane.acquire(timeout=5)
        try:
            fut.result()
        finally:
            ch.plane.release()
    """
    assert [f.detail for f in findings(src, "L2")] == [".result()"]


def test_l2_quiet_on_result_outside_plane():
    src = """
    def good(fut):
        return fut.result()
    """
    assert not findings(src, "L2")


# ---------------------------------------------------------------------------
# L3 — public agent mutators carry @_locked

L3_BAD = """
    class Agent:
        def __init__(self):
            self.lock = object()
            self.state = {}
        def put(self, k, v):
            self.state[k] = v
"""


def test_l3_fires_on_public_unlocked_mutator():
    got = findings(L3_BAD, "L3")
    assert len(got) == 1 and got[0].scope == "Agent.put"


def test_l3_quiet_with_locked_decorator_or_inline_lock():
    src = """
    class Agent:
        def __init__(self):
            self.lock = object()
            self.state = {}
        @_locked
        def put(self, k, v):
            self.state[k] = v
        def put2(self, k, v):
            with self.lock:
                self.state[k] = v
        def get(self, k):
            return self.state[k]
    """
    assert not findings(src, "L3")


def test_l3_quiet_without_a_lock_attribute():
    src = """
    class Stats:
        def __init__(self):
            self.n = 0
        def bump(self):
            self.n += 1
    """
    assert not findings(src, "L3")


# ---------------------------------------------------------------------------
# O1 — obs purity on hot paths

O1_BAD = """
    from repro.obs import hooks as _obs
    def step(x):
        _obs.kernel_launch("k", 1, 0.0)
        return x
"""


def test_o1_fires_on_unguarded_obs_call_in_hot_path():
    got = findings(O1_BAD, "O1", path=HOT)
    assert len(got) == 1 and got[0].detail == "_obs.kernel_launch"


def test_o1_quiet_outside_hot_paths():
    assert not findings(O1_BAD, "O1", path="src/repro/launch/steps.py")


def test_o1_quiet_when_guarded():
    src = """
    from repro.obs import hooks as _obs
    from repro.obs import trace as _trace
    def step(x):
        t0 = time.perf_counter() if _obs.METRICS else 0.0
        if _obs.METRICS:
            _obs.kernel_launch("k", 1, t0)
        y = _obs.d2h(x, "site") if _obs.ON else np.asarray(x)
        if _obs.ON:
            with _trace.span("inc.step"):
                y = y + 1
        return y
    """
    assert not findings(src, "O1", path=HOT)


def test_o1_tracks_guard_variables_and_boolops():
    src = """
    from repro.obs import hooks as _obs
    from repro.obs import trace as _trace
    def step(x):
        on = _obs.ON and x is not None
        if on:
            _trace.span("p")
        ann = _trace.batch("s", app="app") if _obs.ON else None
        if ann is not None:
            _trace.span("q")
        return x
    """
    assert not findings(src, "O1", path=HOT)


def test_o1_exempts_observed_variants():
    src = """
    from repro.obs import trace as _trace
    def _run_pipeline_observed(x):
        _trace.span("inc.plane_lock")
        return x
    """
    assert not findings(src, "O1", path=HOT)


def test_o1_fires_outside_guard_branch():
    src = """
    from repro.obs import hooks as _obs
    from repro.obs import trace as _trace
    def step(x):
        if _obs.ON:
            pass
        _trace.span("p")
        return x
    """
    assert len(findings(src, "O1", path=HOT)) == 1


def test_o1_accepts_guard_early_exit():
    src = """
    from repro.obs import hooks as _obs
    from repro.obs import trace as _trace
    def step(x):
        if not _obs.ON:
            return x
        with _trace.span("inc.step"):
            return x + 1
    """
    assert not findings(src, "O1", path=HOT)
    # an early exit on some other condition guards nothing
    assert len(findings(src.replace("not _obs.ON", "not x"), "O1",
                        path=HOT)) == 1


def test_o1_guard_is_a_switchboard_bool():
    # any other attribute of an obs module is no guard: only the hooks'
    # ON / METRICS bools compile the off path down to one branch
    src = """
    from repro.obs import hooks as _obs
    from repro.obs import trace as _trace
    def step(x):
        if _obs.enabled_somehow:
            _trace.span("p")
        if _trace.pending:
            _trace.span("q")
        return x
    """
    assert len(findings(src, "O1", path=HOT)) == 2


# ---------------------------------------------------------------------------
# E1 — env vars read once at import

def test_e1_fires_on_per_call_env_read():
    src = """
    import os
    def use_pallas():
        return os.environ.get("REPRO_PALLAS_INTERPRET") == "1"
    """
    got = findings(src, "E1")
    assert len(got) == 1 and got[0].detail == "REPRO_PALLAS_INTERPRET"


def test_e1_resolves_module_constants():
    src = """
    import os
    _ENV = "REPRO_PALLAS_INTERPRET"
    def resolve():
        return os.getenv(_ENV)
    """
    assert [f.detail for f in findings(src, "E1")] \
        == ["REPRO_PALLAS_INTERPRET"]


def test_e1_quiet_at_module_level_and_on_writes():
    src = """
    import os
    _GPV = os.environ.get("REPRO_GPV", "1") != "0"
    def enable():
        os.environ["REPRO_FLASH_ATTN"] = "1"
    """
    assert not findings(src, "E1")


def test_e1_quiet_on_non_repro_vars():
    src = """
    import os
    def home():
        return os.environ.get("HOME")
    """
    assert not findings(src, "E1")


# ---------------------------------------------------------------------------
# S1 — schema options handled or rejected

def test_s1_fires_on_unhandled_option():
    src = """
    class SchemaError(ValueError):
        pass
    class _FieldSpec:
        _OPTIONS = {"agg": ("precision", "frobnicate")}
        def __call__(self, **kw):
            if "precision" in kw:
                pass
            raise SchemaError("unknown")
    """
    got = findings(src, "S1")
    assert [f.detail for f in got] == ["frobnicate"]


def test_s1_fires_when_nothing_rejects():
    src = """
    class _FieldSpec:
        _OPTIONS = {"agg": ("precision",)}
        def __call__(self, **kw):
            if "precision" in kw:
                pass
    """
    assert [f.detail for f in findings(src, "S1")] == ["<no-rejection>"]


def test_s1_quiet_when_all_options_handled():
    src = """
    class SchemaError(ValueError):
        pass
    class _FieldSpec:
        _OPTIONS = {"agg": ("precision", "clear")}
        def __call__(self, **kw):
            for opt in kw:
                if opt not in ("precision", "clear"):
                    raise SchemaError(opt)
    """
    assert not findings(src, "S1")


def test_s1_fires_on_surfaced_but_unhandled_local_accum():
    """An _OPTIONS entry advertising ``local_accum`` without any code
    mentioning it is exactly the drift S1 exists for: the option would
    validate at the schema surface and then silently do nothing."""
    src = """
    class SchemaError(ValueError):
        pass
    class _FieldSpec:
        _OPTIONS = {"agg": ("precision", "local_accum")}
        def __call__(self, **kw):
            if "precision" in kw:
                pass
            raise SchemaError("unknown")
    """
    assert [f.detail for f in findings(src, "S1")] == ["local_accum"]


def test_s1_quiet_on_handled_local_accum():
    """The real schema.py shape: ``local_accum`` surfaced in _OPTIONS and
    handled by name in the option-validation body."""
    src = """
    class SchemaError(ValueError):
        pass
    class _FieldSpec:
        _OPTIONS = {"agg": ("precision", "local_accum")}
        def __call__(self, **kw):
            if "precision" in kw:
                pass
            if "local_accum" in kw:
                if int(kw["local_accum"]) < 1:
                    raise SchemaError("local_accum must be >= 1")
            raise SchemaError("unknown")
    """
    assert not findings(src, "S1")


# ---------------------------------------------------------------------------
# D1 — dead code

def test_d1_fires_on_unused_import():
    src = """
    import os
    import sys
    print(sys.argv)
    """
    assert [f.detail for f in findings(src, "D1")] == ["os"]


def test_d1_honors_noqa_and_all_and_init():
    src = """
    import os  # noqa: F401 (re-export)
    from x import y
    __all__ = ["y"]
    """
    assert not findings(src, "D1")
    used = """
    import os
    print(os.sep)
    """
    assert not findings(used, "D1")
    anything = "import os\nimport sys\n"
    assert not findings(anything, "D1",
                        path="src/repro/analysis/__init__.py")


def test_d1_fires_on_unreachable_statement():
    src = """
    def f():
        return 1
        print("never")
    """
    got = findings(src, "D1")
    assert [f.detail for f in got] == ["unreachable"] and got[0].line == 4


# ---------------------------------------------------------------------------
# pragmas

def test_pragma_with_reason_suppresses():
    src = """
    import os
    def f():
        # planelint: allow(E1) — fixture wants the dynamic read
        return os.environ.get("REPRO_X")
    """
    assert not findings(src)


def test_pragma_same_line_and_star():
    src = """
    import os
    def f():
        return os.environ.get("REPRO_X")  # planelint: allow(*) — testing
    """
    assert not findings(src)


def test_pragma_without_reason_does_not_suppress():
    src = """
    import os
    def f():
        # planelint: allow(E1)
        return os.environ.get("REPRO_X")
    """
    got = findings(src)
    assert {f.rule for f in got} == {"E1", "P1"}


# ---------------------------------------------------------------------------
# baseline round-trip + CLI exit codes

BAD_FILE = textwrap.dedent("""
    import os
    def f():
        return os.environ.get("REPRO_X")
""")


def test_baseline_round_trip(tmp_path):
    fx = tmp_path / "fixture.py"
    fx.write_text(BAD_FILE)
    bl = tmp_path / "baseline.json"
    assert cli_main([str(fx), "--write-baseline", str(bl)]) == 0
    data = json.loads(bl.read_text())
    assert len(data["entries"]) == 1
    data["entries"][0]["reason"] = "kept on purpose for the round-trip"
    bl.write_text(json.dumps(data))
    res = run([str(fx)], str(bl))
    assert not res["new"] and not res["stale"]
    assert len(res["baselined"]) == 1
    assert cli_main([str(fx), "--baseline", str(bl)]) == 0


def test_baseline_requires_reasons(tmp_path):
    fx = tmp_path / "fixture.py"
    fx.write_text(BAD_FILE)
    bl = tmp_path / "baseline.json"
    cli_main([str(fx), "--write-baseline", str(bl)])
    # --write-baseline leaves a TODO reason; load() accepts any nonempty
    # string, but an emptied reason must fail loudly
    data = json.loads(bl.read_text())
    data["entries"][0]["reason"] = ""
    bl.write_text(json.dumps(data))
    with pytest.raises(baseline.BaselineError):
        baseline.load(str(bl))
    assert cli_main([str(fx), "--baseline", str(bl)]) == 2


def test_stale_baseline_fails(tmp_path):
    fx = tmp_path / "fixture.py"
    fx.write_text("x = 1\n")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"entries": [
        {"rule": "E1", "file": "fixture.py", "scope": "f",
         "detail": "REPRO_GONE", "reason": "was fixed"}]}))
    assert cli_main([str(fx), "--baseline", str(bl)]) == 2


def test_cli_exit_one_on_new_finding(tmp_path):
    fx = tmp_path / "fixture.py"
    fx.write_text(BAD_FILE)
    assert cli_main([str(fx), "--no-baseline"]) == 1
    assert cli_main([str(fx), "--no-baseline", "--json"]) == 1


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in RULES:
        assert rule in out


# ---------------------------------------------------------------------------
# the tree itself

def test_repo_tree_is_clean_against_committed_baseline():
    """The tier-1 gate: src/repro must produce exactly the committed
    baseline — no new findings, no stale entries. A violating diff fails
    here even without CI."""
    res = run([str(SRC)], str(BASELINE))
    assert not res["errors"], res["errors"]
    new = "\n".join(f"{f.location()}: {f.rule} {f.message}"
                    for f in res["new"])
    assert not res["new"], f"non-baselined planelint findings:\n{new}"
    assert not res["stale"], (
        f"stale baseline entries (fix the baseline): {res['stale']}")


def test_committed_baseline_entries_all_carry_reasons():
    entries = baseline.load(str(BASELINE))
    assert entries, "baseline unexpectedly empty — update this test"
    for e in entries:
        assert len(e["reason"]) > 10, e
    # the D1 sweep landed: no dead-code grandfathering
    assert not [e for e in entries if e["rule"] == "D1"]


def test_analyzer_is_stdlib_only():
    """Zero-dependency guarantee: importing and running the analyzer
    pulls nothing outside the stdlib and repro.analysis itself."""
    prog = (
        "import sys\n"
        "before = set(sys.modules)\n"
        "import repro.analysis.cli\n"
        "import repro.analysis\n"
        "repro.analysis.analyze_source('import os\\n')\n"
        "stdlib = set(sys.stdlib_module_names)\n"
        "bad = sorted(m for m in set(sys.modules) - before\n"
        "             if m.split('.')[0] not in stdlib\n"
        "             and not (m == 'repro' "
        "or m.startswith('repro.analysis')))\n"
        "assert not bad, bad\n")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run([sys.executable, "-c", prog], env=env,
                          capture_output=True, text=True, cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# quick-fix regressions: PLANE_LOCK_TIMEOUT

def test_plane_lock_timeout_env_override():
    """REPRO_PLANE_LOCK_TIMEOUT is honored once, at import (E1)."""
    env = dict(os.environ, REPRO_PLANE_LOCK_TIMEOUT="7.5",
               PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run(
        [sys.executable, "-c",
         "import repro.core.rpc as r; print(r.PLANE_LOCK_TIMEOUT)"],
        env=env, capture_output=True, text=True, cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "7.5"


def test_cyclic_handler_diagnostic_still_names_the_channel(monkeypatch):
    """The timeout stays a call-time module-global read, so rebinding it
    still works and the cyclic-handler RuntimeError names the blocked
    channel."""
    from repro.core import rpc as rpc_mod
    from repro.core.netfilter import NetFilter
    from repro.core.rpc import Field, NetRPC, Service

    svc = Service("Mon")
    svc.rpc("Bump", [Field("kvs", "STRINTMap")], [Field("msg")],
            NetFilter.from_dict({"AppName": "CYCLE-1",
                                 "addTo": "Req.kvs"}))
    rt = NetRPC()
    stub = rt.make_stub(svc)
    stub.call("Bump", {"kvs": {"a": 1}})
    ch = rt.controller.lookup("CYCLE-1")
    monkeypatch.setattr(rpc_mod, "PLANE_LOCK_TIMEOUT", 0.05)
    acquired, release = threading.Event(), threading.Event()

    def holder():
        ch.plane.acquire()
        acquired.set()
        release.wait(10)
        ch.plane.release()

    t = threading.Thread(target=holder)
    t.start()
    try:
        assert acquired.wait(10)
        with pytest.raises(RuntimeError, match="CYCLE-1") as exc:
            stub.call("Bump", {"kvs": {"a": 1}})
        assert "cyclic" in str(exc.value)
    finally:
        release.set()
        t.join(timeout=10)
