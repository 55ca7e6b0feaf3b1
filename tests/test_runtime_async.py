"""Auto-drain scheduler + IncFuture semantics (core/runtime.py).

Covers the three drain triggers (size / time / AIMD window), admission
backpressure, off-thread future resolution with the PR-1 mid-batch-failure
semantics, inline-call ordering, and a property test that async results
are byte-equal to an independently built sequential runtime.
"""
import time

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from repro.core.netfilter import NetFilter
from repro.core.rpc import Field, NetRPC, Service
from repro.core.runtime import DrainPolicy, IncRuntime


def nf(d):
    return NetFilter.from_dict(d)


def monitor_service():
    svc = Service("Monitor")
    svc.rpc("Push", [Field("kvs", "STRINTMap"), Field("payload")],
            [Field("payload")],
            nf({"AppName": "MON", "addTo": "R.kvs"}))
    svc.rpc("Query", [Field("kvs", "STRINTMap")], [Field("kvs", "STRINTMap")],
            nf({"AppName": "MON", "get": "Y.kvs"}))
    svc.rpc("QueryClear", [Field("kvs", "STRINTMap")],
            [Field("kvs", "STRINTMap")],
            nf({"AppName": "MON", "get": "Y.kvs", "clear": "copy"}))
    return svc


def wait_done(futs, timeout=5.0):
    """Poll done() — never result(), which would demand-flush and mask
    which trigger actually fired."""
    deadline = time.monotonic() + timeout
    while not all(f.done() for f in futs):
        assert time.monotonic() < deadline, "futures never resolved"
        time.sleep(0.002)


# ---- triggers ---------------------------------------------------------------

def test_size_trigger_drains_at_max_batch():
    rt = IncRuntime(policy=DrainPolicy(max_batch=4, max_delay=30.0,
                                       eager_window=False))
    try:
        stub = rt.make_stub(monitor_service())
        futs = [stub.call_async("Push", {"kvs": {"a": 1}}) for _ in range(4)]
        wait_done(futs)
        ch = stub.channels["Push"]
        assert ch.stats.drain_triggers["size"] == 1
        assert ch.stats.drain_triggers["flush"] == 0
        assert ch.stats.drained_batches == 1
        assert ch.stats.mean_drained_batch == 4.0
        assert stub.agents["Push"].read("a") == 4
    finally:
        rt.close()


def test_time_trigger_bounds_delay():
    rt = IncRuntime(policy=DrainPolicy(max_batch=1000, max_delay=0.05,
                                       eager_window=False))
    try:
        stub = rt.make_stub(monitor_service())
        t0 = time.monotonic()
        futs = [stub.call_async("Push", {"kvs": {"x": 1}}) for _ in range(3)]
        wait_done(futs)
        elapsed = time.monotonic() - t0
        ch = stub.channels["Push"]
        assert ch.stats.drain_triggers["time"] >= 1
        assert ch.stats.drain_triggers["size"] == 0
        assert elapsed >= 0.04          # not before the deadline
        assert stub.agents["Push"].read("x") == 3
    finally:
        rt.close()


def test_window_trigger_drains_when_window_has_room():
    # defaults: eager AIMD window. The clock stands still, so the call
    # never ages past max_delay and the time trigger cannot fire first
    rt = IncRuntime(clock=lambda: 0.0)
    try:
        stub = rt.make_stub(monitor_service())
        f = stub.call_async("Push", {"kvs": {"w": 1}})
        wait_done([f])
        triggers = stub.channels["Push"].stats.drain_triggers
        assert triggers["window"] >= 1
        assert triggers["time"] == 0
    finally:
        rt.close()


def test_backpressure_blocks_admission_and_bounds_queue():
    # slow handler + tiny service rate: sustained overload -> ECN shrinks
    # the AIMD window -> producers block instead of growing the queue
    pol = DrainPolicy(max_batch=8, max_delay=0.001, backlog_factor=1,
                      ecn_threshold=8, service_rate=200.0)
    rt = IncRuntime(policy=pol)
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
        assert ch.stats.max_queue_depth <= 8 + pol.w_max
        assert stub.agents["Push"].read("k") == 48
        rep = rt.scheduling_report()["MON"]
        assert rep["drained_calls"] == 48
        assert rep["queue_depth"] == 0
    finally:
        rt.close()


# ---- future semantics -------------------------------------------------------

def test_future_exception_and_abandonment():
    """PR-1 mid-batch-failure semantics, delivered through futures:
    completed calls keep effects and resolve; the failing call re-raises
    the handler exception; trailing calls get a chained abandoned error."""
    rt = IncRuntime(policy=DrainPolicy(max_batch=3, max_delay=30.0,
                                       eager_window=False))
    try:
        def handler(req):
            if req.get("payload") == "bad":
                raise RuntimeError("handler down")
            return {"payload": "ok"}
        rt.server.register("Push", handler)
        stub = rt.make_stub(monitor_service())
        f1 = stub.call_async("Push", {"kvs": {"a": 1}, "payload": "good"})
        f2 = stub.call_async("Push", {"kvs": {"b": 2}, "payload": "bad"})
        f3 = stub.call_async("Push", {"kvs": {"c": 3}, "payload": "good"})
        assert f1.result(timeout=5) == {"payload": "ok"}
        with pytest.raises(RuntimeError, match="handler down"):
            f2.result(timeout=5)
        with pytest.raises(RuntimeError, match="abandoned") as ei:
            f3.result(timeout=5)
        assert "handler down" in str(ei.value.__cause__)
        assert isinstance(f3.exception(), RuntimeError)
        # effects up to and including the failing call's addTo are kept
        assert stub.agents["Push"].read("a") == 1
        assert stub.agents["Push"].read("b") == 2
    finally:
        rt.close(flush=False)


def test_result_demand_flushes_before_time_trigger():
    rt = IncRuntime(policy=DrainPolicy(max_batch=1000, max_delay=30.0,
                                       eager_window=False))
    try:
        stub = rt.make_stub(monitor_service())
        t0 = time.monotonic()
        f = stub.call_async("Push", {"kvs": {"d": 1}})
        assert f.result(timeout=5) == {}
        assert time.monotonic() - t0 < 5.0   # did not wait out max_delay
        assert stub.channels["Push"].stats.drain_triggers["flush"] >= 1
    finally:
        rt.close()


def test_result_timeout_raises():
    rt = IncRuntime(policy=DrainPolicy(max_batch=1000, max_delay=30.0,
                                       eager_window=False))
    try:
        rt.server.register(
            "Push", lambda r: (time.sleep(0.5), {"payload": "ok"})[1])
        stub = rt.make_stub(monitor_service())
        f = stub.call_async("Push", {"kvs": {"t": 1}, "payload": "p"})
        with pytest.raises(TimeoutError):
            f.result(timeout=0.05)
        assert f.result(timeout=5) == {"payload": "ok"}
    finally:
        rt.close()


def test_close_resolves_leftovers_and_rejects_new_work():
    rt = IncRuntime(policy=DrainPolicy(max_batch=1000, max_delay=30.0,
                                       eager_window=False))
    stub = rt.make_stub(monitor_service())
    f = stub.call_async("Push", {"kvs": {"z": 1}})
    rt.close(flush=False)
    with pytest.raises(RuntimeError, match="closed"):
        f.result(timeout=1)
    with pytest.raises(RuntimeError, match="closed"):
        stub.call_async("Push", {"kvs": {"z": 1}})


def test_drain_flushes_everything_synchronously():
    rt = IncRuntime(policy=DrainPolicy(max_batch=1000, max_delay=30.0,
                                       eager_window=False))
    try:
        stub = rt.make_stub(monitor_service())
        futs = [rt.submit(stub, "Push", {"kvs": {"s": 1}}) for _ in range(5)]
        assert not any(f.done() for f in futs)
        assert rt.drain() == 5
        assert all(f.done() for f in futs)
        assert stub.channels["Push"].stats.drain_triggers["flush"] == 1
    finally:
        rt.close()


def test_trailing_flush_failure_surfaces_on_last_future():
    """If the pipeline raises after every call completed (the trailing
    buffer flush), the last call's future carries it — it must not vanish
    into the scheduler loop."""
    rt = IncRuntime(policy=DrainPolicy(max_batch=2, max_delay=30.0,
                                       eager_window=False))
    try:
        stub = rt.make_stub(monitor_service())
        ch = stub.channels["Push"]
        boom = RuntimeError("flush exploded")

        def bad_addto(logs, vals):
            raise boom
        ch.server.addto_batch = bad_addto     # the final flush will raise
        f1 = stub.call_async("Push", {"kvs": {"a": 1}})
        f2 = stub.call_async("Push", {"kvs": {"b": 2}})
        assert f1.result(timeout=5) == {}     # completed before the flush
        with pytest.raises(RuntimeError, match="flush exploded"):
            f2.result(timeout=5)
    finally:
        rt.close(flush=False)


def test_handler_inline_call_on_own_channel_does_not_deadlock():
    """A handler making a synchronous follow-up call on its own channel
    must work from both drain paths: a main-thread inline drain (the busy
    flag is ours — recurse) and the scheduler thread."""
    svc = monitor_service()
    rt = IncRuntime(policy=DrainPolicy(max_batch=1000, max_delay=30.0,
                                       eager_window=False))
    try:
        stub = rt.make_stub(svc)

        def handler(req):
            if req.get("payload") == "nest":
                inner = stub.call("Query", {"kvs": {"n": 0}})
                return {"payload": f"saw-{int(inner['kvs']['n'])}"}
            return {"payload": "ok"}
        rt.server.register("Push", handler)
        # queue an async call, then trigger a main-thread inline drain via
        # call(): the drained handler re-enters run_direct on this channel.
        # The nested Query's entry flush applies the enclosing batch's
        # buffered updates — including this call's own addTo — so it sees
        # everything issued before it: 5 (queued) + 2 (this call).
        stub.call_async("Push", {"kvs": {"n": 5}, "payload": "plain"})
        out = stub.call("Push", {"kvs": {"n": 2}, "payload": "nest"})
        assert out == {"payload": "saw-7"}
        # and from the scheduler thread: result() demand-flushes, so the
        # drain (and the nested handler call) runs on the worker
        f = stub.call_async("Push", {"kvs": {"n": 1}, "payload": "nest"})
        assert f.result(timeout=5) == {"payload": "saw-8"}
        assert stub.agents["Push"].read("n") == 8
    finally:
        rt.close()


def test_nested_get_clear_does_not_double_clear():
    """A handler's nested inline get+clear must observe the enclosing
    batch's buffered (deferred) clear — not pre-clear state — or the key
    is decremented twice and goes negative."""
    svc = monitor_service()
    rt = IncRuntime(policy=DrainPolicy(max_batch=1000, max_delay=30.0,
                                       eager_window=False))
    try:
        stub = rt.make_stub(svc)
        seen = []

        def handler(req):
            if req.get("payload") == "nest":
                out = stub.call("QueryClear", {"kvs": {"k": 0}})
                seen.append(int(out["kvs"]["k"]))
            return {"payload": "ok"}
        rt.server.register("Push", handler)
        stub.call("Push", {"kvs": {"k": 5}, "payload": "plain"})   # k = 5
        # one batch: QueryClear(k) buffers the deferred clear (k, -5);
        # then Push's handler runs a nested QueryClear, which must see the
        # already-cleared k == 0 — not stale 5 (double-clear -> k == -5)
        f1 = rt.submit(stub, "QueryClear", {"kvs": {"k": 0}})
        rt.submit(stub, "Push", {"kvs": {"z": 1}, "payload": "nest"})
        rt.drain()
        assert f1.result()["kvs"]["k"] == 5            # the real clear
        assert seen == [0]                             # nested saw cleared
        assert stub.agents["Push"].read("k") == 0      # not -5
    finally:
        rt.close()


def test_handler_inline_call_on_other_channel_does_not_deadlock():
    """Cross-channel nesting: a handler on channel A makes a synchronous
    call on channel B while the scheduler is busy with B — the in-pipeline
    caller must not wait on B's busy flag (deadlock cycle via the plane
    lock)."""
    svc_a = monitor_service()
    svc_b = Service("Other")
    svc_b.rpc("Put", [Field("kvs", "STRINTMap")], [Field("msg")],
              nf({"AppName": "OTHER", "addTo": "R.kvs"}))
    rt = IncRuntime(policy=DrainPolicy(max_batch=1000, max_delay=0.01,
                                       eager_window=False))
    try:
        sb = rt.make_stub(svc_b)

        def handler(req):
            sb.call("Put", {"kvs": {"x": 1}})          # cross-channel
            return {"payload": "ok"}
        rt.server.register("Push", handler)
        sa = rt.make_stub(svc_a)
        # keep channel B's queue active so the scheduler touches it too
        for _ in range(20):
            rt.submit(sb, "Put", {"kvs": {"y": 1}})
            out = sa.call("Push", {"kvs": {"a": 1}, "payload": "p"})
            assert out == {"payload": "ok"}
        rt.drain()
        assert sb.agents["Put"].read("x") == 20
        assert sb.agents["Put"].read("y") == 20
    finally:
        rt.close()


def test_drain_inside_handler_raises_instead_of_deadlocking():
    """A handler calling rt.drain() would wait forever on the busy flag
    its own (blocked) thread holds — the guard must convert that into a
    RuntimeError on the inline user-thread path too, not just on the
    scheduler thread."""
    rt = IncRuntime(policy=DrainPolicy(max_batch=1000, max_delay=30.0,
                                       eager_window=False))
    try:
        caught = []

        def handler(req):
            try:
                rt.drain()
            except RuntimeError as e:
                caught.append(str(e))
            return {"payload": "ok"}
        rt.server.register("Push", handler)
        stub = rt.make_stub(monitor_service())
        out = stub.call("Push", {"kvs": {"a": 1}, "payload": "p"})
        assert out == {"payload": "ok"}
        assert caught and "deadlock" in caught[0]
    finally:
        rt.close()


def test_close_completes_when_flush_raises():
    rt = IncRuntime(policy=DrainPolicy(max_batch=1000, max_delay=30.0,
                                       eager_window=False))
    rt.server.register("Push", lambda r: (_ for _ in ()).throw(
        RuntimeError("handler down")))
    stub = rt.make_stub(monitor_service())
    f = stub.call_async("Push", {"kvs": {"a": 1}, "payload": "p"})
    rt.close()                      # must not re-raise the handler error
    with pytest.raises(RuntimeError, match="handler down"):
        f.result(timeout=1)
    with pytest.raises(RuntimeError, match="closed"):
        stub.call_async("Push", {"kvs": {"a": 1}})


# ---- ordering + stats split -------------------------------------------------

def test_inline_call_drains_queued_async_calls_first():
    """Issue order is preserved across fronts: async votes queued before a
    direct call() reach the CntFwd counter first."""
    svc = Service("Vote")
    svc.rpc("Cast", [Field("kvs", "STRINTMap")], [Field("msg")],
            nf({"AppName": "VOTE",
                "CntFwd": {"to": "SRC", "threshold": 2, "key": "b"}}))
    rt = IncRuntime(policy=DrainPolicy(max_batch=1000, max_delay=30.0,
                                       eager_window=False))
    try:
        rt.server.register("Cast", lambda r: {"msg": "committed"})
        stub = rt.make_stub(svc)
        f = stub.call_async("Cast", {"kvs": {"b1": 1}})   # vote 1 (queued)
        out = stub.call("Cast", {"kvs": {"b1": 1}})       # vote 2 (direct)
        assert f.result(timeout=5) == {}      # queued vote ran first, cnt=1
        assert out == {"msg": "committed"}    # direct call hit the quorum
        assert stub.channels["Cast"].stats.drain_triggers["inline"] == 1
    finally:
        rt.close()


def test_explicit_and_drained_counters_are_split():
    """The satellite fix: N=1 Stub.call passes must not dilute the
    coalescing efficiency reported for runtime drains."""
    rt = IncRuntime(policy=DrainPolicy(max_batch=4, max_delay=30.0,
                                       eager_window=False))
    try:
        stub = rt.make_stub(monitor_service())
        for _ in range(6):                    # six explicit N=1 passes
            stub.call("Push", {"kvs": {"e": 1}})
        futs = [stub.call_async("Push", {"kvs": {"e": 1}}) for _ in range(4)]
        wait_done(futs)
        st_ = stub.channels["Push"].stats
        assert st_.explicit_batches == 6 and st_.explicit_calls == 6
        assert st_.drained_batches == 1 and st_.drained_calls == 4
        assert st_.mean_explicit_batch == 1.0
        assert st_.mean_drained_batch == 4.0
        # the blended mean still exists but under-reports coalescing
        assert st_.mean_batch == 10 / 7
    finally:
        rt.close()


# ---- property: async == sequential -----------------------------------------

_METHODS = ("Push", "Query", "QueryClear")


@settings(max_examples=8)
@given(st.lists(st.tuples(st.integers(0, 2),
                          st.lists(st.tuples(st.integers(0, 7),
                                             st.integers(-50, 50)),
                                   min_size=1, max_size=4)),
                min_size=1, max_size=12))
def test_async_results_equal_sequential(ops):
    reqs = []
    for mi, kvs in ops:
        method = _METHODS[mi]
        if method == "Push":
            payload = {f"k{ki}": v for ki, v in kvs}
        else:
            payload = {f"k{ki}": 0 for ki, _ in kvs}
        reqs.append((method, {"kvs": payload}))
    probe = [f"k{i}" for i in range(8)]

    seq_rt = NetRPC()
    seq_stub = seq_rt.make_stub(monitor_service())
    want = [seq_stub.call(m, dict(r)) for m, r in reqs]
    want_state = [seq_stub.agents["Push"].read(k) for k in probe]

    rt = IncRuntime(policy=DrainPolicy(max_batch=3, max_delay=30.0,
                                       eager_window=False))
    try:
        stub = rt.make_stub(monitor_service())
        futs = [stub.call_async(m, dict(r)) for m, r in reqs]
        got = [f.result(timeout=10) for f in futs]
        got_state = [stub.agents["Push"].read(k) for k in probe]
    finally:
        rt.close()
    assert got == want
    assert got_state == want_state
