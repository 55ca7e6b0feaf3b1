"""Typed declarative schema layer (core/schema.py + repro/api.py).

Four angles:

  golden        compiling the four example services produces byte-identical
                ``NetFilter.to_dict()`` output to the legacy hand-written
                JSON blobs they replaced — the schema is sugar, not a new
                wire semantic.
  validation    schema mistakes raise SchemaError at class-definition time
                with the offending Class.method named.
  equivalence   property test: for random schemas and payloads, typed-stub
                calls == legacy ``Stub.call``/``call_batch`` results —
                including mid-batch-failure and CntFwd-threshold semantics.
  bulk async    ``stub.Rpc.batch`` / ``call_batch_async`` rides the same
                scheduler triggers and backpressure as ``call_async``; the
                ChannelStats attribution check stays green throughout.
"""
import os
import sys

import numpy as np
import pytest

from hypothesis import example, given, settings
from hypothesis import strategies as st

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import repro.api as inc
from repro.core.netfilter import NetFilter
from repro.core.rpc import Field, NetRPC, Service
from repro.core.runtime import DrainPolicy, IncRuntime


# ---- golden: schema compilation == the legacy example NetFilters -----------

GOLDEN = {
    # examples/quickstart.py (SyncAgtr, paper Fig. 3)
    ("Gradient", "Update"): {
        "AppName": "DT-1", "Precision": 8,
        "get": "AgtrGrad.tensor", "addTo": "NewGrad.tensor",
        "clear": "copy", "modify": "nop",
        "CntFwd": {"to": "ALL", "threshold": 2, "key": "ClientID"},
    },
    # examples/monitoring.py (KeyValue)
    ("Monitor", "MonitorCall"): {
        "AppName": "MON-1", "Precision": 0,
        "addTo": "MonitorRequest.kvs",
    },
    ("Monitor", "Query"): {
        "AppName": "MON-1", "Precision": 0, "get": "QueryReply.kvs",
    },
    # examples/mapreduce.py (AsyncAgtr)
    ("MapReduce", "ReduceByKey"): {
        "AppName": "MR-1", "Precision": 0, "addTo": "ReduceRequest.kvs",
    },
    ("MapReduce", "Query"): {
        "AppName": "MR-1", "Precision": 0, "get": "QueryReply.kvs",
    },
    # examples/paxos.py (Agreement; one class, two channels)
    ("Paxos", "Prepare"): {
        "AppName": "paxos-prepare",
        "CntFwd": {"to": "SRC", "threshold": 1, "key": "kvs"},
    },
    ("Paxos", "Accept"): {
        "AppName": "paxos-accept",
        "CntFwd": {"to": "ALL", "threshold": 2, "key": "kvs"},
    },
}


def _example_schemas():
    from examples.mapreduce import MapReduce
    from examples.monitoring import Monitor
    from examples.paxos import Paxos
    from examples.quickstart import Gradient
    return {c.__inc_schema__.name: c.__inc_schema__
            for c in (Gradient, Monitor, MapReduce, Paxos)}


def test_golden_example_netfilters_byte_identical():
    schemas = _example_schemas()
    for (svc, rpc_name), legacy in GOLDEN.items():
        compiled = schemas[svc].rpcs[rpc_name].netfilter.to_dict()
        want = NetFilter.from_dict(legacy).to_dict()
        assert compiled == want, (svc, rpc_name, compiled, want)


def test_example_schemas_classify_like_table1():
    schemas = _example_schemas()
    assert schemas["Gradient"].rpcs["Update"].netfilter.app_type() \
        == "SyncAgtr"
    assert schemas["MapReduce"].rpcs["ReduceByKey"].netfilter.app_type() \
        == "AsyncAgtr"
    assert schemas["Monitor"].rpcs["Query"].netfilter.app_type() \
        == "KeyValue"
    assert schemas["Paxos"].rpcs["Accept"].netfilter.app_type() \
        == "Agreement"


# ---- validation: definition-site SchemaError --------------------------------

def test_two_agg_fields_rejected():
    with pytest.raises(inc.SchemaError, match=r"Bad\.P.*one Map\.addTo"):
        @inc.service(app="X")
        class Bad:
            @inc.rpc
            def P(self, a: inc.Agg[inc.STRINTMap],
                  b: inc.Agg[inc.STRINTMap]): ...


def test_get_on_request_side_rejected():
    with pytest.raises(inc.SchemaError, match=r"Bad\.P.*reply-side"):
        @inc.service(app="X")
        class Bad:
            @inc.rpc
            def P(self, a: inc.Get[inc.STRINTMap]): ...


def test_agg_on_reply_side_rejected():
    with pytest.raises(inc.SchemaError, match=r"Bad\.P.*request-side"):
        @inc.service(app="X")
        class Bad:
            @inc.rpc
            def P(self, a: inc.Plain) -> {"t": inc.Agg[inc.FPArray]}: ...


def test_unknown_annotation_option_rejected():
    with pytest.raises(inc.SchemaError, match=r"precison"):
        inc.Agg[inc.FPArray](precison=8)        # typo'd 'precision'


def test_precision_out_of_range_rejected():
    with pytest.raises(inc.SchemaError, match=r"\[0, 9\]"):
        inc.Agg[inc.FPArray](precision=11)


def test_bad_clear_policy_rejected():
    with pytest.raises(inc.SchemaError, match=r"clear"):
        inc.ReadMostly[inc.STRINTMap](clear="wipe")


def test_bad_modify_op_rejected():
    with pytest.raises(inc.SchemaError, match=r"divide"):
        inc.Agg[inc.STRINTMap](modify=("divide", 3))


def test_cntfwd_threshold_without_key_rejected():
    with pytest.raises(inc.SchemaError, match=r"vote\s+key"):
        inc.CntFwd(to="ALL", threshold=2)


def test_cntfwd_bad_target_rejected():
    with pytest.raises(inc.SchemaError, match=r"EVERYONE"):
        inc.CntFwd(to="EVERYONE", threshold=1, key="k")


def test_conflicting_clear_between_annotations_rejected():
    with pytest.raises(inc.SchemaError, match=r"Bad\.P.*conflicting"):
        @inc.service(app="X")
        class Bad:
            @inc.rpc
            def P(self, a: inc.Agg[inc.FPArray](clear="copy")
                  ) -> {"a": inc.Get[inc.FPArray](clear="lazy")}: ...


def test_missing_app_rejected():
    with pytest.raises(inc.SchemaError, match=r"Bad\.P.*AppName"):
        @inc.service
        class Bad:
            @inc.rpc
            def P(self, a: inc.Plain): ...


def test_service_without_rpcs_rejected():
    with pytest.raises(inc.SchemaError, match=r"at least one RPC"):
        @inc.service(app="X")
        class Bad:
            def helper(self):
                return 1


def test_conflicting_drain_overrides_on_shared_channel_rejected():
    with pytest.raises(inc.SchemaError, match=r"conflicting DrainPolicy"):
        @inc.service(app="X")
        class Bad:
            @inc.rpc(drain=DrainPolicy(max_batch=2))
            def P(self, a: inc.Agg[inc.STRINTMap]): ...

            @inc.rpc(drain=DrainPolicy(max_batch=8), reply_msg="Y")
            def Q(self, a: inc.ReadMostly[inc.STRINTMap]): ...


def test_readmostly_plus_agg_rejected():
    with pytest.raises(inc.SchemaError, match=r"either a write stream"):
        @inc.service(app="X")
        class Bad:
            @inc.rpc
            def P(self, a: inc.Agg[inc.STRINTMap],
                  b: inc.ReadMostly[inc.STRINTMap]): ...


def test_unknown_request_field_at_call_site():
    @inc.service(app="CALLCHK")
    class Svc:
        @inc.rpc
        def Push(self, kvs: inc.Agg[inc.STRINTMap]): ...
    stub = NetRPC().make_stub(Svc)
    with pytest.raises(inc.SchemaError, match=r"Svc\.Push.*kv_typo"):
        stub.Push(kv_typo={"a": 1})


# ---- equivalence: typed stub == legacy Stub ---------------------------------

CLEARS = ("nop", "copy")
MODIFIES = ("nop", ("max", 40), ("add", 3))


def _legacy_service(app, precision, clear, modify, threshold):
    svc = Service("Rand")
    mod = ("nop" if modify == "nop"
           else {"op": modify[0], "para": modify[1]})
    svc.rpc("Push", [Field("kvs", "STRINTMap"), Field("payload")],
            [Field("payload")],
            NetFilter.from_dict({"AppName": app, "Precision": precision,
                                 "addTo": "Req.kvs", "modify": mod}))
    svc.rpc("Query", [Field("kvs", "STRINTMap")],
            [Field("kvs", "STRINTMap")],
            NetFilter.from_dict({"AppName": app, "Precision": precision,
                                 "get": "QueryReply.kvs", "clear": clear}))
    svc.rpc("Cast", [Field("kvs", "STRINTMap")], [Field("msg")],
            NetFilter.from_dict({"AppName": f"{app}-vote", "CntFwd":
                                 {"to": "SRC", "threshold": threshold,
                                  "key": "b"}}))
    return svc


def _typed_service(app, precision, clear, modify, threshold):
    """The same random schema, spelled declaratively.  Built function-by-
    function so the property test can parameterize annotations."""
    def Push(self, kvs, payload): ...
    Push.__annotations__ = {
        "kvs": inc.Agg[inc.STRINTMap](precision=precision, modify=modify),
        "payload": inc.Plain,
        "return": {"payload": inc.Plain}}
    Push = inc.rpc(request_msg="Req")(Push)

    def Query(self, kvs): ...
    Query.__annotations__ = {
        "kvs": inc.ReadMostly[inc.STRINTMap](precision=precision,
                                             clear=clear)}
    Query = inc.rpc(Query)

    def Cast(self, kvs): ...
    Cast.__annotations__ = {"kvs": inc.STRINTMap,
                            "return": {"msg": inc.Plain}}
    Cast = inc.rpc(app=f"{app}-vote",
                   cnt_fwd=inc.CntFwd(to="SRC", threshold=threshold,
                                      key="b"))(Cast)

    cls = type("Rand", (), {"Push": Push, "Query": Query, "Cast": Cast})
    return inc.service(app=app, name="Rand")(cls)


def _handlers(rt):
    def push_handler(req):
        if req.get("payload") == "bad":
            raise RuntimeError("handler down")
        return {"payload": "ok"}
    rt.server.register("Push", push_handler)
    rt.server.register("Cast", lambda r: {"msg": "committed"})


_METHODS = ("Push", "Query", "Cast")


def _reqs_from_ops(ops):
    reqs = []
    for mi, fail, kvs in ops:
        m = _METHODS[mi % 3]
        if m == "Push":
            reqs.append((m, {"kvs": {f"k{ki % 6}": v for ki, v in kvs},
                             "payload": "bad" if fail == 3 else "p"}))
        elif m == "Query":
            reqs.append((m, {"kvs": {f"k{ki % 6}": 0 for ki, _ in kvs}}))
        else:
            reqs.append((m, {"kvs": {f"b{ki % 3}": 1 for ki, _ in kvs}}))
    return reqs


# deadline=None: the first example compiles its programs (~0.9 s on a
# CPU), so hypothesis's 200 ms deadline fails on the compile, not on a case
@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2),                       # precision
       st.sampled_from(CLEARS),
       st.sampled_from(MODIFIES),
       st.integers(1, 3),                       # CntFwd threshold
       st.lists(st.tuples(st.integers(0, 2), st.integers(0, 3),
                          st.lists(st.tuples(st.integers(0, 7),
                                             st.integers(-40, 40)),
                                   min_size=1, max_size=4)),
                min_size=1, max_size=10))
@example(precision=2, clear="copy", modify=("add", 3), threshold=3,
         ops=[(0, 0, [(6, 15), (6, 15)]), (1, 1, [(6, 37), (2, -27)])])
def test_typed_stub_equals_legacy_stub(precision, clear, modify, threshold,
                                       ops):
    """Same random schema + request stream through (a) the legacy string
    front and (b) the generated typed stub: positional replies, raised
    exceptions, and final observable map state must agree — including
    mid-batch handler failures (fail==3 payloads) and CntFwd quorums."""
    mod_tag = modify if isinstance(modify, str) else f"{modify[0]}{modify[1]}"
    app = f"EQ-{precision}-{clear}-{mod_tag}-{threshold}"
    reqs = _reqs_from_ops(ops)
    probe = [f"k{i}" for i in range(6)]

    lrt = NetRPC()
    _handlers(lrt)
    lstub = lrt.make_stub(_legacy_service(app, precision, clear, modify,
                                          threshold))
    want, want_err = [], []
    for m, r in reqs:
        try:
            want.append(lstub.call(m, dict(r)))
            want_err.append(None)
        except RuntimeError as e:
            want.append(None)
            want_err.append(str(e))
    want_state = [lstub.agents["Push"].read(k) for k in probe]

    trt = NetRPC()
    _handlers(trt)
    tstub = trt.make_stub(_typed_service(app, precision, clear, modify,
                                         threshold))
    got, got_err = [], []
    for m, r in reqs:
        f = getattr(tstub, m)(**dict(r))
        if f.exception() is None:
            got.append(f.result())
            got_err.append(None)
        else:
            got.append(None)
            got_err.append(str(f.exception()))
    got_state = [tstub.agents["Push"].read(k) for k in probe]

    assert got == want
    assert got_err == want_err
    assert got_state == want_state

    # the bulk front: typed .batch() against legacy call_batch, per method
    # stream (mid-batch failures surface through the futures with the
    # sequential abandoned-semantics, so compare outcome-by-outcome)
    push_reqs = [dict(r) for m, r in reqs if m == "Push"]
    if push_reqs:
        l2 = NetRPC()
        _handlers(l2)
        ls = l2.make_stub(_legacy_service(app, precision, clear, modify,
                                          threshold))
        t2 = NetRPC()
        _handlers(t2)
        ts = t2.make_stub(_typed_service(app, precision, clear, modify,
                                         threshold))
        try:
            lwant = ls.call_batch("Push", [dict(r) for r in push_reqs])
            lerr = None
        except RuntimeError as e:
            lwant, lerr = None, str(e)
        futs = ts.Push.batch([dict(r) for r in push_reqs])
        if lerr is None:
            assert [f.result() for f in futs] == lwant
        else:
            errs = [f.exception() for f in futs]
            assert any(str(e) == lerr for e in errs if e is not None)
        assert ([ts.agents["Push"].read(k) for k in probe]
                == [ls.agents["Push"].read(k) for k in probe])


def test_batch_mid_failure_future_semantics():
    """stub.Rpc.batch on the scheduler runtime: completed calls resolve
    and keep effects, the failing call re-raises, trailing calls get the
    chained abandoned error (same contract as call_async)."""
    @inc.service(app="BF-1",
                 drain=DrainPolicy(max_batch=3, max_delay=30.0,
                                   eager_window=False))
    class Svc:
        @inc.rpc(request_msg="R")
        def Push(self, kvs: inc.Agg[inc.STRINTMap], payload: inc.Plain
                 ) -> {"payload": inc.Plain}: ...

    rt = IncRuntime()
    try:
        def handler(req):
            if req.get("payload") == "bad":
                raise RuntimeError("handler down")
            return {"payload": "ok"}
        rt.server.register("Push", handler)
        stub = rt.make_stub(Svc)
        futs = stub.Push.batch([
            {"kvs": {"a": 1}, "payload": "good"},
            {"kvs": {"b": 2}, "payload": "bad"},
            {"kvs": {"c": 3}, "payload": "good"},
        ])
        assert futs[0].result(timeout=5) == {"payload": "ok"}
        with pytest.raises(RuntimeError, match="handler down"):
            futs[1].result(timeout=5)
        with pytest.raises(RuntimeError, match="abandoned") as ei:
            futs[2].result(timeout=5)
        assert "handler down" in str(ei.value.__cause__)
        assert stub.agents["Push"].read("a") == 1
        assert stub.agents["Push"].read("b") == 2
    finally:
        rt.close(flush=False)


def test_batch_async_rides_scheduler_triggers():
    """One .batch(list) submission is carved into pipeline batches by the
    channel's size trigger — not executed as one monolithic pass."""
    @inc.service(app="BT-1",
                 drain=DrainPolicy(max_batch=4, max_delay=30.0,
                                   eager_window=False))
    class Svc:
        @inc.rpc(request_msg="R")
        def Push(self, kvs: inc.Agg[inc.STRINTMap]): ...

    rt = IncRuntime()
    try:
        stub = rt.make_stub(Svc)
        futs = stub.Push.batch([{"kvs": {"x": 1}} for _ in range(12)])
        for f in futs:
            f.result(timeout=5)
        ch = stub.channels["Push"]
        assert stub.agents["Push"].read("x") == 12
        assert ch.stats.drain_triggers["size"] == 3
        assert ch.stats.mean_drained_batch == 4.0
        rep = rt.scheduling_report()["BT-1"]    # also runs the stats audit
        assert rep["drained_calls"] == 12
    finally:
        rt.close()


def test_batch_async_backpressure_bounds_queue():
    """A huge .batch() list cannot bypass admission control: the submitter
    blocks mid-list once the backlog limit is hit, so the queue stays
    bounded while the scheduler drains."""
    @inc.service(app="BP-1",
                 drain=DrainPolicy(max_batch=8, max_delay=0.001,
                                   backlog_factor=1, ecn_threshold=8,
                                   service_rate=500.0))
    class Svc:
        @inc.rpc(request_msg="R")
        def Push(self, kvs: inc.Agg[inc.STRINTMap], payload: inc.Plain
                 ) -> {"payload": inc.Plain}: ...

    rt = IncRuntime()
    try:
        rt.server.register(
            "Push", lambda r: (__import__("time").sleep(0.001),
                               {"payload": "ok"})[1])
        stub = rt.make_stub(Svc)
        futs = stub.Push.batch([{"kvs": {"k": 1}, "payload": "p"}
                                for _ in range(64)])
        for f in futs:
            assert f.result(timeout=30) == {"payload": "ok"}
        ch = stub.channels["Push"]
        assert ch.stats.admission_waits > 0
        assert ch.stats.max_queue_depth <= 8 + rt.policy.w_max
        assert stub.agents["Push"].read("k") == 64
    finally:
        rt.close()


# ---- per-channel DrainPolicy override ---------------------------------------

def test_schema_drain_policy_applies_per_channel():
    """Two services on one runtime: each channel drains by its own
    schema-declared trigger config, not the runtime default."""
    @inc.service(app="PC-small",
                 drain=DrainPolicy(max_batch=2, max_delay=30.0,
                                   eager_window=False))
    class Small:
        @inc.rpc(request_msg="R")
        def Push(self, kvs: inc.Agg[inc.STRINTMap]): ...

    @inc.service(app="PC-big",
                 drain=DrainPolicy(max_batch=6, max_delay=30.0,
                                   eager_window=False))
    class Big:
        @inc.rpc(request_msg="R")
        def Push(self, kvs: inc.Agg[inc.STRINTMap]): ...

    rt = IncRuntime(policy=DrainPolicy(max_batch=1000, max_delay=30.0,
                                       eager_window=False))
    try:
        s, b = rt.make_stub(Small), rt.make_stub(Big)
        sf = [s.Push(kvs={"a": 1}) for _ in range(2)]
        bf = [b.Push(kvs={"a": 1}) for _ in range(6)]
        for f in sf + bf:
            f.result(timeout=5)
        assert s.channels["Push"].stats.drain_triggers["size"] == 1
        assert b.channels["Push"].stats.drain_triggers["size"] == 1
        assert s.channels["Push"].stats.mean_drained_batch == 2.0
        assert b.channels["Push"].stats.mean_drained_batch == 6.0
    finally:
        rt.close()


# ---- ChannelStats attribution audit (satellite regression) ------------------

def test_channelstats_attribution_audit():
    """Mixed explicit + drained traffic keeps drained+explicit == total;
    a corrupted split is caught by scheduling_report()."""
    @inc.service(app="CS-1",
                 drain=DrainPolicy(max_batch=4, max_delay=30.0,
                                   eager_window=False))
    class Svc:
        @inc.rpc(request_msg="R")
        def Push(self, kvs: inc.Agg[inc.STRINTMap]): ...

    rt = IncRuntime()
    try:
        stub = rt.make_stub(Svc)
        for _ in range(3):                  # explicit N=1 passes
            stub.Push(kvs={"e": 1}).result(timeout=5)
        futs = [stub.Push(kvs={"e": 1}) for _ in range(4)]
        for f in futs:
            f.result(timeout=5)
        st_ = stub.channels["Push"].stats
        st_.check_consistent()              # green on real traffic
        rep = rt.scheduling_report()["CS-1"]
        assert rep["calls"] == rep["explicit_calls"] + rep["drained_calls"]
        st_.drained_calls += 1              # inject a double-count
        with pytest.raises(AssertionError, match="attribution drift"):
            rt.scheduling_report()
        st_.drained_calls -= 1
    finally:
        rt.close()


# ---- inline (NetRPC) futures-first surface ----------------------------------

def test_netrpc_futures_resolve_inline():
    @inc.service(app="NF-1")
    class Svc:
        @inc.rpc(request_msg="R")
        def Push(self, kvs: inc.Agg[inc.STRINTMap]): ...
        @inc.rpc(reply_msg="Y")
        def Query(self, kvs: inc.ReadMostly[inc.STRINTMap]): ...

    rt = NetRPC()
    stub = rt.make_stub(Svc)
    f = stub.Push(kvs={"a": 2})
    assert f.done()                          # resolved before return
    assert f.result() == {}
    assert stub.Query(kvs={"a": 0}).result()["kvs"] == {"a": 2}


def test_netrpc_batch_runs_pending_submissions_first():
    """Issue order across fronts holds for the inline bulk path too."""
    @inc.service(app="NF-2")
    class Svc:
        @inc.rpc(request_msg="R")
        def Push(self, kvs: inc.Agg[inc.STRINTMap]): ...
        @inc.rpc(reply_msg="Y")
        def Query(self, kvs: inc.ReadMostly[inc.STRINTMap]): ...

    rt = NetRPC()
    stub = rt.make_stub(Svc)
    t = rt.submit(stub.legacy, "Push", {"kvs": {"x": 5}})
    futs = stub.Query.batch([{"kvs": {"x": 0}}])
    assert futs[0].result()["kvs"] == {"x": 5}   # saw the queued push
    assert t.done


def test_quickstart_flow_through_typed_stub():
    """The paper's Fig. 2-4 flow end-to-end on the typed surface."""
    from examples.quickstart import Gradient
    rt = NetRPC()
    a, b = rt.make_stub(Gradient), rt.make_stub(Gradient)
    g1 = np.array([0.5, -1.25, 2.0])
    g2 = np.array([1.5, 0.25, -1.0])
    assert a.Update(tensor=g1).result() == {}
    got = b.Update(tensor=g2).result()["tensor"]
    np.testing.assert_allclose(np.array([got[i] for i in range(3)]),
                               g1 + g2, atol=1e-6)
