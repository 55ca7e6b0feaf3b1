"""Sharded concurrent data plane (ISSUE 5): worker pool, per-channel
plane locks, weighted-fair drain loop.

The load-bearing property: a sharded run (``IncRuntime(workers=4)``)
produces per-channel results **equal** to the ``workers=1`` sequential
oracle — replies, final INC map state, CntFwd quorum decisions,
mid-batch-failure chaining, and the audited stats split — because one
channel's pipeline stays strictly serial no matter how many workers the
pool has. Plus: channels genuinely drain in parallel, strict-priority /
DRR picking behaves as configured, the per-channel ServerAgent window
knob threads through, shutdown is idempotent, and handlers calling
``drain()`` raise instead of deadlocking under a 4-worker stress mix.
"""
import threading
import time

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from repro.core.netfilter import NetFilter
from repro.core.rpc import Field, NetRPC, Service
from repro.core.runtime import DrainPolicy, IncRuntime, _ChannelQueue
from repro.core import rpc as rpc_mod


def nf(d):
    return NetFilter.from_dict(d)


def monitor_service(app="MON"):
    svc = Service("Monitor")
    svc.rpc("Push", [Field("kvs", "STRINTMap"), Field("payload")],
            [Field("payload")],
            nf({"AppName": app, "addTo": "R.kvs"}))
    svc.rpc("Query", [Field("kvs", "STRINTMap")],
            [Field("kvs", "STRINTMap")],
            nf({"AppName": app, "get": "Y.kvs"}))
    svc.rpc("QueryClear", [Field("kvs", "STRINTMap")],
            [Field("kvs", "STRINTMap")],
            nf({"AppName": app, "get": "Y.kvs", "clear": "copy"}))
    return svc


def vote_service(app="VOTE"):
    svc = Service("Vote")
    svc.rpc("Cast", [Field("kvs", "STRINTMap")], [Field("msg")],
            nf({"AppName": app,
                "CntFwd": {"to": "SRC", "threshold": 2, "key": "b"}}))
    return svc


def tensor_service(app="TEN"):
    svc = Service("Tensor")
    svc.rpc("Accum", [Field("tensor", "FPArray")], [],
            nf({"AppName": app, "addTo": "R.tensor", "Precision": 4}))
    svc.rpc("Pull", [Field("tensor", "FPArray")],
            [Field("tensor", "FPArray")],
            nf({"AppName": app, "get": "Y.tensor", "Precision": 4}))
    return svc


def _policy(**kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_delay", 30.0)
    kw.setdefault("eager_window", False)
    return DrainPolicy(**kw)


def _mk(workers):
    rt = IncRuntime(policy=_policy(), workers=workers)
    rt.server.register("Cast", lambda r: {"msg": "committed"})
    stubs = {"mon": rt.make_stub(monitor_service()),
             "vote": rt.make_stub(vote_service()),
             "ten": rt.make_stub(tensor_service())}
    return rt, stubs


def _apply(ops, workers):
    """Run the generated op stream on a fresh runtime; returns (replies,
    final-state probes) with every future resolved."""
    rt, stubs = _mk(workers)
    try:
        futs = []
        for kind, a, kvs in ops:
            if kind == 0:
                method = ("Push", "Query", "QueryClear")[a % 3]
                payload = ({f"k{k}": v for k, v in kvs} if method == "Push"
                           else {f"k{k}": 0 for k, _ in kvs})
                futs.append(stubs["mon"].call_async(method,
                                                    {"kvs": payload}))
            elif kind == 1:
                futs.append(stubs["vote"].call_async(
                    "Cast", {"kvs": {f"b{a % 4}": 1}}))
            else:
                method = ("Accum", "Pull")[a % 2]
                arr = np.array([v / 7.0 for _, v in kvs], np.float64)
                futs.append(stubs["ten"].call_async(method,
                                                    {"tensor": arr}))
        got = [f.result(timeout=30) for f in futs]
        state = ([stubs["mon"].agents["Push"].read(f"k{i}")
                  for i in range(8)]
                 + [stubs["ten"].agents["Accum"].server.read(i)
                    for i in range(6)])
        rt.scheduling_report()      # runs the per-channel stats audit
        return got, state
    finally:
        rt.close()


# ---- sharded == sequential oracle (results, state, quorums, stats) ----------

@settings(max_examples=6)
@given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 5),
                          st.lists(st.tuples(st.integers(0, 7),
                                             st.integers(-50, 50)),
                                   min_size=1, max_size=4)),
                min_size=1, max_size=18))
def test_sharded_results_equal_sequential_oracle(ops):
    want, want_state = _apply(ops, workers=1)
    got, got_state = _apply(ops, workers=4)
    for w, g in zip(want, got):
        assert type(w) is type(g)
        if isinstance(w, dict) and "tensor" in w:
            assert w.keys() == g.keys()
            assert w["tensor"] == g["tensor"]
        else:
            assert w == g
    assert want_state == got_state


def _apply_failure(n, bad, workers):
    """One deterministic single-batch burst with call ``bad`` failing:
    returns (per-call outcome tags, final state)."""
    rt = IncRuntime(policy=_policy(max_batch=max(n, 1)), workers=workers)
    try:
        def handler(req):
            if req.get("payload") == "bad":
                raise RuntimeError("handler down")
            return {"payload": "ok"}
        rt.server.register("Push", handler)
        stub = rt.make_stub(monitor_service())
        reqs = [{"kvs": {f"k{i % 4}": i + 1},
                 "payload": "bad" if i == bad else "good"}
                for i in range(n)]
        futs = stub.call_batch_async("Push", reqs)
        out = []
        for f in futs:
            exc = f.exception(timeout=30)
            if exc is None:
                out.append(("ok", f.result()))
            elif "abandoned" in str(exc):
                out.append(("abandoned", str(exc.__cause__)))
            else:
                out.append(("raised", str(exc)))
        state = [stub.agents["Push"].read(f"k{i}") for i in range(4)]
        return out, state
    finally:
        rt.close(flush=False)


@settings(max_examples=8)
@given(st.integers(1, 8), st.integers(0, 7))
def test_mid_batch_failure_chaining_matches_oracle(n, bad):
    bad = bad % n
    want = _apply_failure(n, bad, workers=1)
    got = _apply_failure(n, bad, workers=4)
    assert got == want
    # and the chaining shape itself: calls before the failure resolve,
    # the failing call raises, trailing calls are abandoned
    outcomes = [tag for tag, _ in got[0]]
    assert outcomes[:bad] == ["ok"] * bad
    assert outcomes[bad] == "raised"
    assert outcomes[bad + 1:] == ["abandoned"] * (n - bad - 1)


def test_gpv_equals_dict_under_concurrent_drains():
    """The PR 4 GPV==dict equivalence must survive 4-worker drains."""
    ops = [(2, i, [(j, (i * 7 + j) % 23 - 11) for j in range(4)])
           for i in range(24)]
    prev = rpc_mod.set_gpv(True)
    try:
        want = _apply(ops, workers=4)
        rpc_mod.set_gpv(False)
        got = _apply(ops, workers=4)
    finally:
        rpc_mod.set_gpv(prev)
    assert want == got


# ---- true concurrency across channels ---------------------------------------

def test_independent_channels_drain_concurrently():
    """Two channels' handlers observe each other mid-flight: only possible
    when their pipeline passes genuinely overlap on distinct workers."""
    ev = {"A": threading.Event(), "B": threading.Event()}
    seen = {}

    def mk_handler(me, other):
        def handler(req):
            ev[me].set()
            seen[me] = ev[other].wait(timeout=10.0)
            return {"payload": "ok"}
        return handler

    rt = IncRuntime(policy=_policy(max_delay=0.001), workers=2)
    try:
        svc_a, svc_b = monitor_service("CC-A"), monitor_service("CC-B")
        rt.server.register("Push", None)     # replaced below per channel

        # one shared method name would collide; use two services with
        # distinct methods via two runtimes' worth of handlers instead
        sa = rt.make_stub(svc_a)
        sb = rt.make_stub(svc_b)

        def route(req):
            return mk_handler(*(("A", "B") if req.get("payload") == "A"
                                else ("B", "A")))(req)
        rt.server.register("Push", route)
        fa = sa.call_async("Push", {"kvs": {"a": 1}, "payload": "A"})
        fb = sb.call_async("Push", {"kvs": {"b": 1}, "payload": "B"})
        assert fa.result(timeout=30) == {"payload": "ok"}
        assert fb.result(timeout=30) == {"payload": "ok"}
        assert seen == {"A": True, "B": True}, \
            "handlers never overlapped: the plane is still serial"
    finally:
        rt.close()


def test_backpressure_wakeups_with_worker_pool():
    """Admission blocking + wakeups still work when 4 workers drain: the
    submitter unblocks as soon as any worker frees room."""
    pol = DrainPolicy(max_batch=8, max_delay=0.001, backlog_factor=1,
                      ecn_threshold=8, service_rate=200.0)
    rt = IncRuntime(policy=pol, workers=4)
    try:
        rt.server.register(
            "Push", lambda r: (time.sleep(0.002), {"payload": "ok"})[1])
        stub = rt.make_stub(monitor_service())
        futs = [stub.call_async("Push", {"kvs": {"k": 1}, "payload": "p"})
                for _ in range(48)]
        for f in futs:
            assert f.result(timeout=30) == {"payload": "ok"}
        ch = stub.channels["Push"]
        assert ch.stats.admission_waits > 0
        assert stub.agents["Push"].read("k") == 48
        rep = rt.scheduling_report()["MON"]
        assert rep["drained_calls"] == 48
        assert rep["queue_depth"] == 0
    finally:
        rt.close()


# ---- weighted-fair picking (strict tiers + DRR) -----------------------------

def _fake_queue(rt, app, now, **pol_kw):
    ch = rt.controller.register(nf({"AppName": app, "addTo": "R.kvs"}),
                                n_slots=64)
    q = _ChannelQueue(ch, _policy(**pol_kw), now)
    q.demand = True                   # always drain-eligible
    rt._queues[ch.gaid] = q
    return q


def _fill(q, n, now):
    while len(q.entries) < n:
        q.entries.append((None, None, now))


def test_pick_prefers_higher_priority_tier():
    rt = IncRuntime()                 # no stubs -> no worker threads
    now = time.monotonic()
    lo = _fake_queue(rt, "P-lo", now, priority=0, weight=100.0)
    hi = _fake_queue(rt, "P-hi", now, priority=3, weight=0.1)
    _fill(lo, 3, now)
    _fill(hi, 3, now)
    with rt._work:
        for _ in range(5):
            q, trigger, take = rt._pick(time.monotonic())
            assert q is hi, "strict priority must beat any weight"
            assert trigger in ("size", "flush") and take == 3


def test_drr_shares_follow_weights_within_a_tier():
    rt = IncRuntime()
    now = time.monotonic()
    heavy = _fake_queue(rt, "W-heavy", now, weight=3.0, max_batch=4)
    light = _fake_queue(rt, "W-light", now, weight=1.0, max_batch=4)
    served = {"W-heavy": 0, "W-light": 0}
    _fill(heavy, 4, now)
    _fill(light, 4, now)
    with rt._work:
        for _ in range(400):
            q, _, take = rt._pick(time.monotonic())
            served[q.channel.netfilter.app_name] += take
            for _ in range(take):     # honor the pick, then refill
                q.entries.popleft()
            _fill(heavy, 4, now)
            _fill(light, 4, now)
    ratio = served["W-heavy"] / served["W-light"]
    assert 2.0 < ratio < 4.5, served   # ~3:1 by weight
    assert served["W-light"] > 0       # DRR guarantees progress


def test_pick_weight_validation():
    rt = IncRuntime()
    now = time.monotonic()
    with pytest.raises(ValueError, match="weight"):
        _fake_queue(rt, "W-bad", now, weight=0.0)
    with pytest.raises(ValueError, match="weight"):
        _fake_queue(rt, "W-nan", now, weight=float("nan"))


def test_drr_debt_is_bounded_for_a_solo_channel():
    """A channel draining alone pays its take with nobody to share with;
    its deficit must bottom out at the symmetric floor — otherwise a
    sibling joining the tier later would starve it for as long as it had
    previously run solo."""
    rt = IncRuntime()
    now = time.monotonic()
    solo = _fake_queue(rt, "W-solo", now, weight=1.0, max_batch=4)
    _fill(solo, 4, now)
    with rt._work:
        for _ in range(100):
            q, _, take = rt._pick(time.monotonic())
            for _ in range(take):
                q.entries.popleft()
            _fill(solo, 4, now)
    from repro.core.runtime import _DEFICIT_CAP_BATCHES
    floor = -_DEFICIT_CAP_BATCHES * 4 * 1.0
    assert solo.deficit >= floor, solo.deficit


def test_drr_deficit_resets_when_queue_empties():
    """Classic DRR: credit/debt is only meaningful while backlogged — a
    drained-empty channel restarts at 0 instead of carrying stale debt."""
    rt = IncRuntime(policy=_policy(max_batch=4), workers=1)
    try:
        stub = rt.make_stub(monitor_service("DR-1"))
        futs = [stub.call_async("Push", {"kvs": {"a": 1}})
                for _ in range(8)]
        for f in futs:
            f.result(timeout=30)
        with rt._work:
            q = rt._queues[stub.channels["Push"].gaid]
            assert not q.entries
            assert q.deficit == 0.0
    finally:
        rt.close()


# ---- schema lowering: priority / weight / window knobs ----------------------

def test_schema_priority_weight_and_window_lower_to_channel():
    import repro.api as inc

    @inc.service(app="SW-1")
    class Svc:
        @inc.rpc(request_msg="R", priority=2, weight=3.5,
                 drain=DrainPolicy(max_batch=16, window=4096))
        def Push(self, kvs: inc.Agg[inc.STRINTMap]): ...

    rt = IncRuntime()
    try:
        stub = rt.make_stub(Svc)
        ch = stub.channels["Push"]
        assert ch.drain_policy.priority == 2
        assert ch.drain_policy.weight == 3.5
        assert ch.drain_policy.max_batch == 16
        # the satellite knob: DrainPolicy.window threads down to the
        # channel's ServerAgent LRU window
        assert ch.server.window == 4096
    finally:
        rt.close()


def test_unannotated_service_keeps_default_agent_window():
    rt = IncRuntime()
    try:
        stub = rt.make_stub(monitor_service("DW-1"))
        assert stub.channels["Push"].server.window == 1024
    finally:
        rt.close()


def test_schema_priority_weight_validation():
    import repro.api as inc
    with pytest.raises(inc.SchemaError, match="priority"):
        @inc.service(app="SV-1")
        class Bad1:
            @inc.rpc(priority="high")
            def Push(self, kvs: inc.Agg[inc.STRINTMap]): ...
    with pytest.raises(inc.SchemaError, match="weight"):
        @inc.service(app="SV-2", weight=0)
        class Bad2:
            @inc.rpc
            def Push(self, kvs: inc.Agg[inc.STRINTMap]): ...


def test_bad_window_override_is_rejected():
    import repro.api as inc

    @inc.service(app="BW-1", drain=DrainPolicy(window=0))
    class Svc:
        @inc.rpc
        def Push(self, kvs: inc.Agg[inc.STRINTMap]): ...

    rt = IncRuntime()
    try:
        with pytest.raises(ValueError, match="window"):
            rt.make_stub(Svc)
    finally:
        rt.close()


# ---- observability ----------------------------------------------------------

def test_scheduling_report_plane_section():
    rt = IncRuntime(policy=_policy(max_batch=4), workers=2)
    try:
        stub = rt.make_stub(monitor_service("RPT-1"))
        futs = [stub.call_async("Push", {"kvs": {"a": 1}})
                for _ in range(8)]
        for f in futs:
            f.result(timeout=30)
        rep = rt.scheduling_report()
        chan = rep["RPT-1"]
        assert chan["priority"] == 0 and chan["weight"] == 1.0
        assert chan["mean_drain_wait_us"] >= 0.0
        plane = rep["__plane__"]
        assert set(plane["workers"]) == {"w0", "w1"}
        total_drains = sum(w["drains"] for w in plane["workers"].values())
        assert total_drains >= 1
        assert 0 in plane["priorities"]
        assert plane["priorities"][0]["calls"] >= 8
        assert plane["priorities"][0]["mean_wait_us"] >= 0.0
        assert isinstance(plane["pick_contention"], int)
    finally:
        rt.close()


def test_workers_param_validation():
    with pytest.raises(ValueError, match="workers"):
        IncRuntime(workers=0)


# ---- shutdown: idempotence + no-deadlock stress -----------------------------

def test_close_is_idempotent():
    rt = IncRuntime(workers=4)
    stub = rt.make_stub(monitor_service("CL-1"))
    stub.call_async("Push", {"kvs": {"a": 1}}).result(timeout=30)
    rt.close()
    rt.close()                        # second close must be a no-op
    with pytest.raises(RuntimeError, match="closed"):
        stub.call_async("Push", {"kvs": {"a": 1}})
    # context-manager form over a fresh runtime
    with IncRuntime(workers=2) as rt2:
        s2 = rt2.make_stub(monitor_service("CL-2"))
        assert s2.call_async("Push", {"kvs": {"a": 1}}).result(
            timeout=30) == {}
    rt2.close()                       # after __exit__: still a no-op


def test_shutdown_stress_handlers_drain_raises_never_hangs():
    """4 workers x 6 channels x 4 submitter threads, with handlers that
    (a) call rt.drain() — must raise, not deadlock — and (b) make nested
    inline calls onto a shared leaf channel (star topology). The whole
    mix must complete and close cleanly inside the deadline."""
    rt = IncRuntime(policy=_policy(max_batch=4, max_delay=0.001),
                    workers=4)
    drain_errors = []
    try:
        leaf_svc = Service("Leaf")
        leaf_svc.rpc("LeafPut", [Field("kvs", "STRINTMap")],
                     [Field("msg")],
                     nf({"AppName": "ST-leaf", "addTo": "R.kvs"}))
        leaf = rt.make_stub(leaf_svc)

        def handler(req):
            mode = req.get("payload")
            if mode == "drain":
                try:
                    rt.drain()
                except RuntimeError as e:
                    drain_errors.append(str(e))
            elif mode == "nest":
                leaf.call("LeafPut", {"kvs": {"n": 1}})
            return {"payload": "ok"}
        rt.server.register("Push", handler)

        stubs = [rt.make_stub(monitor_service(f"ST-{i}"))
                 for i in range(6)]

        def submitter(tid):
            futs = []
            for i in range(60):
                stub = stubs[(tid + i) % len(stubs)]
                mode = ("plain", "drain", "nest")[i % 3]
                futs.append(stub.call_async(
                    "Push", {"kvs": {f"k{tid}": 1}, "payload": mode}))
            for f in futs:
                assert f.result(timeout=60) == {"payload": "ok"}

        threads = [threading.Thread(target=submitter, args=(t,))
                   for t in range(4)]
        deadline = time.monotonic() + 120
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
            assert not t.is_alive(), "stress mix deadlocked"
        assert drain_errors and all("deadlock" in e for e in drain_errors)
        assert leaf.agents["LeafPut"].read("n") == 4 * 20
        rt.scheduling_report()        # audit every channel's stats split
    finally:
        rt.close()
        rt.close()
