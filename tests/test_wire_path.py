"""Array-native GPV wire path (ISSUE 4): equivalence vs the dict path.

Four angles:

  quantize      the vectorized ``np.rint``-based quantize/dequantize is
                element-exact vs the scalar ``int(round(x * s))`` oracle
                across signs, halfway cases, and precisions 0-8 — for both
                the resolve path and the phase-1 modify path (which keeps
                fixed point through the dict path's dequantize->requantize
                round trip).
  end-to-end    same tensor request stream through the GPV path and the
                per-element dict path (``set_gpv``): replies, final map
                state, and every data-plane stat (hits/misses/bytes/spill)
                must agree — including Stream.modify fusion, clear="copy"
                reply clears, and client-side collisions.
  spill batch   the folded ``spill_host`` update == the old per-item
                Python loop, stats included (satellite regression).
  reply shape   schema-bound stubs return request-shaped ndarrays for
                FPArray Map.get replies; legacy ``Service`` stubs and
                map-typed fields keep dict replies.
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import example, given, settings
from hypothesis import strategies as st

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import repro.api as inc
from repro.core import rpc as rpc_mod
from repro.core.inc_map import (ClientAgent, ServerAgent, SwitchMemory,
                                quantize_scalar_ref, quantize_stream,
                                quantize_values)
from repro.core.netfilter import NetFilter
from repro.core.rpc import Field, NetRPC, Service, TensorSegment
from repro.kernels import ops


@pytest.fixture
def gpv_on():
    prev = rpc_mod.set_gpv(True)
    yield
    rpc_mod.set_gpv(prev)


# ---- quantize/dequantize: vectorized == scalar oracle ------------------------

@settings(max_examples=30)
@given(st.integers(0, 8),
       st.lists(st.floats(-2e4, 2e4), min_size=1, max_size=40))
def test_quantize_stream_matches_scalar(precision, xs):
    scale = 10 ** precision
    for dtype in (np.float64, np.float32):
        arr = np.array(xs, dtype)
        want = quantize_scalar_ref(list(arr), scale)
        got = quantize_stream(arr, scale)
        assert got.tolist() == want, (dtype, precision)


@pytest.mark.parametrize("precision", range(0, 9))
def test_quantize_halfway_cases_round_to_even(precision):
    scale = 10 ** precision
    # products that land exactly (or as near as floats allow) on k + 0.5,
    # both signs — the round-half-even cliff
    ks = np.arange(-25, 25)
    xs = (ks + 0.5) / scale
    want = quantize_scalar_ref(list(xs), scale)
    assert quantize_stream(xs, scale).tolist() == want


def test_quantize_int_values_pass_through():
    vals = [0, 1, -7, 123456, -2**31 + 1]
    assert quantize_stream(np.array(vals), 1).tolist() == \
        quantize_scalar_ref(vals, 1)
    assert quantize_stream(np.array(vals), 100).tolist() == \
        quantize_scalar_ref(vals, 100)
    # heterogeneous (object) payloads fall back to the oracle itself
    mixed = [1, 2.5, -3]
    assert quantize_values(mixed, 10).tolist() == \
        quantize_scalar_ref(mixed, 10)


@settings(max_examples=20)
@given(st.integers(0, 8), st.lists(st.integers(-2**31 + 1, 2**31 - 1),
                                   min_size=1, max_size=40))
def test_phase1_fixed_point_carry_is_identity(precision, qs):
    """The dict path dequantizes a post-modify int32 stream to floats and
    re-quantizes it in resolve; the GPV path carries the ints directly.
    For every int32-range value the round trip is the identity, so both
    paths agree — this is the invariant that lets phase 1 skip the float
    detour."""
    scale = 10 ** precision
    q = np.array(qs, np.int64)
    floats = q / scale                       # what the dict path stores
    requant = quantize_stream(floats, scale)
    assert requant.tolist() == q.tolist()
    # and the scalar path agrees with itself
    assert quantize_scalar_ref(list(floats), scale) == q.tolist()


def test_reply_dequantize_matches_scalar_division():
    raw = np.array([-10**9, -3, 0, 7, 10**9], np.int64)
    for precision in range(0, 9):
        scale = 10 ** precision
        want = [int(r) / scale for r in raw]
        assert (raw / scale).tolist() == want


def test_quantize_nonfinite_raises_like_scalar_oracle():
    """The scalar path raises on NaN/inf (int(round(...)) cannot convert
    them); the vectorized path must stay as loud instead of silently
    emitting int64-min garbage — e.g. a float16 stream whose product
    overflows in the input dtype."""
    import warnings
    half = np.array([0.5, 300.0], np.float16)       # 300e6 overflows f16
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(OverflowError):
            quantize_stream(half, 10 ** 6)
        with pytest.raises((OverflowError, ValueError)):
            quantize_scalar_ref(list(half), 10 ** 6)
        with pytest.raises(ValueError):
            quantize_stream(np.array([np.nan]), 10)
        with pytest.raises(ValueError):
            quantize_scalar_ref([float("nan")], 10)


def test_quantize_overflow_stays_loud():
    """Out-of-range products raise instead of silently wrapping — int64
    overflow in the integer branch, int32 overflow at the Stream.modify
    narrowing, and >2**53 ints in a float-coerced mixed list all kept the
    scalar path's exactness/loudness."""
    from repro.core.rpc import _int32_checked
    with pytest.raises(OverflowError):
        quantize_stream(np.array([2 ** 60], np.int64), 100)
    with pytest.raises(OverflowError):
        _int32_checked(np.array([10 ** 10], np.int64))
    big = 2 ** 53 + 1
    assert quantize_values([big, 0.5], 1).tolist() == \
        quantize_scalar_ref([big, 0.5], 1)          # exact, not float64
    with pytest.raises(OverflowError):              # finite float > int64
        quantize_stream(np.array([1e19]), 1)
    with pytest.raises(OverflowError):              # uint64 >= 2**63
        quantize_stream(np.array([2 ** 63], np.uint64), 1)


def test_spill_map_version_tracks_every_mutation():
    """read_batch's spill snapshot invalidates on ANY mutation path —
    including setdefault/popitem, the hole the versioned dict exists to
    close."""
    from repro.core.inc_map import _SpillMap
    s = _SpillMap()
    v = s.version
    s[3] += 5                      # missing-key insert + store
    assert s.version > v and s[3] == 5
    for mutate in (lambda: s.setdefault(9, 2), lambda: s.pop(9),
                   lambda: s.update({4: 1}), lambda: s.popitem(),
                   lambda: s.clear()):
        v = s.version
        mutate()
        assert s.version > v


# ---- device-lane quantize: fused kernel == host oracle (ISSUE 6) -------------

@pytest.mark.parametrize("precision", range(0, 9))
def test_device_quantize_matches_host_across_signs_and_halfway(precision):
    """The fused device kernel quantizes with jnp.round on a float32
    product; the host lane uses np.rint on the same float32 product. For
    every float32 input whose scaled value fits int32 the two are
    element-identical — across signs, the round-half-to-even cliff, and
    precisions 0-8. (float64 streams never reach the kernel: the phase-2
    router host-quantizes them, pinned end-to-end in
    tests/test_device_path.py.)"""
    from repro.kernels.fused_gpv import fused_addto_pallas
    scale = 10 ** precision
    ks = np.arange(-25, 25)
    rng = np.random.RandomState(precision)
    xs = np.concatenate([
        (ks + 0.5) / scale,                  # the halfway cliff, both signs
        ks / scale,                          # exact integers
        rng.uniform(-20.0, 20.0, 64),        # |x*scale| < 2**31 at p=8
    ]).astype(np.float32)
    want = quantize_stream(xs, scale)
    # adding into a zeroed segment leaves exactly quantize(xs) behind
    got = np.asarray(fused_addto_pallas(
        jnp.zeros(len(xs), jnp.int32), 0, jnp.asarray(xs), scale,
        interpret=True))
    np.testing.assert_array_equal(got, want.astype(np.int32))


def test_device_read_dequant_contract_and_sentinel_mask():
    """The fused read reply is raw * (1/float32(scale)) — the reciprocal
    multiply, NOT float division — with the overflow sentinels masked.
    The host fallback in read_batch_dev computes the same formula, so the
    two reply flavors are bit-identical."""
    from repro.kernels.constants import INT32_MAX, INT32_MIN, SAT_MAX, \
        SAT_MIN
    from repro.kernels.fused_gpv import fused_read_pallas
    raw = np.array([0, 5, -7, 123456789, INT32_MAX, INT32_MIN,
                    SAT_MAX, SAT_MIN], np.int32)
    vals, mask = fused_read_pallas(jnp.asarray(raw), 0, len(raw), 10 ** 6,
                                   interpret=True)
    inv = np.float32(1.0) / np.float32(10.0 ** 6)
    np.testing.assert_array_equal(np.asarray(vals),
                                  raw.astype(np.float32) * inv)
    assert np.asarray(mask).tolist() == [False] * 4 + [True, True,
                                                       False, False]


# ---- fold_stream_host: one pass == Counter reference -------------------------

@settings(max_examples=20)
@given(st.lists(st.tuples(st.integers(0, 12), st.integers(-50, 50)),
                min_size=1, max_size=60))
def test_fold_stream_host_matches_counter(pairs):
    logs = np.array([l for l, _ in pairs], np.uint32)
    vals = np.array([v for _, v in pairs], np.int64)
    keys, counts, sums = ops.fold_stream_host(logs, vals)
    # first-occurrence order (Counter insertion order)
    seen, order_ref = set(), []
    for l, _ in pairs:
        if l not in seen:
            seen.add(l)
            order_ref.append(l)
    assert keys.tolist() == order_ref
    from collections import Counter
    cnt_ref = Counter(l for l, _ in pairs)
    sum_ref = Counter()
    for l, v in pairs:
        sum_ref[l] += v
    assert counts.tolist() == [cnt_ref[l] for l in order_ref]
    assert sums.tolist() == [sum_ref[l] for l in order_ref]


# ---- end-to-end: GPV path == dict path ---------------------------------------

def _tensor_service(app, precision, clear, modify):
    svc = Service("T")
    mod = ("nop" if modify == "nop"
           else {"op": modify[0], "para": modify[1]})
    svc.rpc("Update", [Field("tensor", "FPArray")],
            [Field("tensor", "FPArray")],
            NetFilter.from_dict({"AppName": app, "Precision": precision,
                                 "get": "A.tensor", "addTo": "N.tensor",
                                 "clear": clear, "modify": mod}))
    return svc


def _run_stream(gpv, app, precision, clear, modify, tensors, collide):
    prev = rpc_mod.set_gpv(gpv)
    try:
        rt = NetRPC()
        stub = rt.make_stub(_tensor_service(app, precision, clear, modify))
        if collide:
            # an int key >= 2**32 hashes to a small address, claiming it
            # as a foreign key: the same-address tensor index must detour
            # via the collision host path on BOTH marshalling paths
            stub.agents["Update"].logical(2**32 + 2)
        replies = [stub.call("Update", {"tensor": t}) for t in tensors]
        srv = stub.agents["Update"].server
        n = max(len(np.ravel(t)) for t in tensors)
        state = srv.read_batch(np.arange(n, dtype=np.uint32)).tolist()
        stats = {"hits": srv.hits, "misses": srv.misses,
                 "inc_bytes": srv.inc_bytes, "host_bytes": srv.host_bytes,
                 "spill": dict(srv.spill), "mapped": set(srv.mapping)}
        return replies, state, stats
    finally:
        rpc_mod.set_gpv(prev)


CLEARS = ("nop", "copy")
MODIFIES = ("nop", ("max", 30), ("add", 5))


# deadline=None: the first example compiles its programs (~0.6-0.9 s on a
# CPU), so hypothesis's 200 ms deadline fails on the compile, not on a case
@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2),                       # precision
       st.sampled_from(CLEARS),
       st.sampled_from(MODIFIES),
       st.integers(0, 1),                       # collide?
       st.lists(st.lists(st.floats(-60.0, 60.0), min_size=1, max_size=9),
                min_size=1, max_size=6))
# precision 0 with a -0.5 payload: a halfway case at the coarsest scale
@example(precision=0, clear="copy", modify=("add", 5), collide=0,
         payloads=[[1.1771700249927793e-124, -0.5, 46.74120342387266],
                   [-2.506903900182774e-254, 3.4416725526901153e-75,
                    42.086081749086176, 2.2250738585e-313,
                    48.307492998586966, 41.595659675524075,
                    21.77819980854528],
                   [57.63833550374572, -10.363782002275961]])
# the SyncAgtr shape at precision 6: the same chunk pushed twice, copy clear
@example(precision=6, clear="copy", modify="nop", collide=0,
         payloads=[[5.292157, 1.200472, 2.936214, 6.72268, 5.602674,
                    -2.931834, 2.850265, -0.454072, -0.309657]] * 2)
def test_gpv_equals_dict_path_end_to_end(precision, clear, modify, collide,
                                         payloads):
    tag = modify if isinstance(modify, str) else f"{modify[0]}{modify[1]}"
    app = f"WPEQ-{precision}-{clear}-{tag}-{collide}"
    tensors = [np.array(p, np.float32) for p in payloads]
    r_gpv, s_gpv, st_gpv = _run_stream(True, app + "-g", precision, clear,
                                       modify, tensors, collide)
    r_dict, s_dict, st_dict = _run_stream(False, app + "-d", precision,
                                          clear, modify, tensors, collide)
    for got, want, t in zip(r_gpv, r_dict, tensors):
        want_vec = [want["tensor"][i] for i in range(len(t))]
        got_vec = [got["tensor"][i] for i in range(len(t))]
        assert got_vec == want_vec          # element-exact, not allclose
    assert s_gpv == s_dict                  # final map state
    assert st_gpv == st_dict                # full data-plane stats


def test_gpv_batch_and_cntfwd_match_dict_path(gpv_on):
    """call_batch + CntFwd gating over tensor payloads: the GPV pipeline
    preserves the batched sequential semantics and the sub-RTT drop."""
    def build(gpv):
        prev = rpc_mod.set_gpv(gpv)
        try:
            svc = Service("G")
            svc.rpc("Update", [Field("tensor", "FPArray")],
                    [Field("tensor", "FPArray")],
                    NetFilter.from_dict({
                        "AppName": f"WPCF-{int(gpv)}", "Precision": 4,
                        "get": "A.tensor", "addTo": "N.tensor",
                        "clear": "copy",
                        "CntFwd": {"to": "ALL", "threshold": 2,
                                   "key": "ClientID"}}))
            rt = NetRPC()
            stub = rt.make_stub(svc)
            rng = np.random.RandomState(7)
            reqs = [{"tensor": rng.randn(16).astype(np.float32)}
                    for _ in range(4)]
            return stub.call_batch("Update", reqs), reqs
        finally:
            rpc_mod.set_gpv(prev)

    got, reqs = build(True)
    want, _ = build(False)
    assert got[0] == {} and got[2] == {}       # below threshold: dropped
    for g, w in zip(got, want):
        if not w:
            assert g == w
            continue
        assert [g["tensor"][i] for i in range(16)] == \
            [w["tensor"][i] for i in range(16)]


# ---- spill batching: folded update == per-item loop --------------------------

def test_spill_host_matches_per_item_loop():
    def fresh():
        return ServerAgent(SwitchMemory(2, 64), gaid=1, n_slots=8)

    pairs = [(3, 5), (9, -2), (3, 7), (40, 0), (9, 1)]
    a, b = fresh(), fresh()
    a.spill_host(list(pairs))
    for l, v in pairs:                      # the pre-batching reference
        b.spill[l] += v
        b.host_bytes += 8
    assert dict(a.spill) == dict(b.spill)
    assert a.host_bytes == b.host_bytes
    assert a.misses == b.misses == 0        # collision spill is not a miss


def test_addto_batch_folded_stats_match_reference():
    """Duplicate-heavy update stream: the folded miss/grant path keeps
    byte-for-byte stats with a scalar one-update-at-a-time replay."""
    rng = np.random.RandomState(3)
    logs = (rng.zipf(1.4, 200) % 24).astype(np.uint32)
    vals = rng.randint(-9, 9, 200)
    batched = ServerAgent(SwitchMemory(2, 64), gaid=1, n_slots=8, window=64)
    for i in range(0, 200, 40):             # five 40-element flushes
        batched.addto_batch(logs[i:i + 40], vals[i:i + 40])
    # routing invariants the folded path must keep: every stream element is
    # attributed to exactly one path, bytes follow the 8-byte-per-item rule,
    # and no value is lost whichever side it landed on
    assert batched.hits + batched.misses == 200
    assert batched.inc_bytes == 8 * batched.hits
    assert batched.host_bytes == 8 * batched.misses
    total = {int(k): 0 for k in set(logs.tolist())}
    for l, v in zip(logs.tolist(), vals.tolist()):
        total[l] += v
    for l, want in total.items():
        assert batched.read(l) == want, l


# ---- reply shapes ------------------------------------------------------------

@inc.service(app="WPSH-1")
class GradShape:
    @inc.rpc(request_msg="N", reply_msg="A")
    def Update(self, tensor: inc.Agg[inc.FPArray](precision=6, clear="copy")
               ) -> {"tensor": inc.Get[inc.FPArray]}: ...


def test_typed_stub_returns_request_shaped_ndarray(gpv_on):
    rt = NetRPC()
    stub = rt.make_stub(GradShape)
    g = np.arange(12, dtype=np.float32).reshape(3, 4) / 8
    out = stub.Update(tensor=g).result()["tensor"]
    assert isinstance(out, np.ndarray) and out.shape == (3, 4)
    np.testing.assert_allclose(out, g, atol=1e-6)
    # map-typed dict request on the same channel stays a dict reply
    out2 = stub.Update(tensor={0: 1.0, 1: 2.0}).result()["tensor"]
    assert isinstance(out2, dict)


def test_legacy_service_stub_keeps_dict_reply(gpv_on):
    rt = NetRPC()
    stub = rt.make_stub(_tensor_service("WPSH-2", 6, "copy", "nop"))
    out = stub.call("Update", {"tensor": np.array([1.5, -2.25])})["tensor"]
    assert isinstance(out, dict)
    assert out == {0: 1.5, 1: -2.25}
    # ... while the inbound side still took the array fast path
    assert stub.channels["Update"].stats.gpv_calls == 1
    assert stub.channels["Update"].stats.gpv_elems == 2


def test_set_gpv_false_forces_dict_marshalling():
    prev = rpc_mod.set_gpv(False)
    try:
        rt = NetRPC()
        stub = rt.make_stub(GradShape)
        out = stub.Update(tensor=np.array([0.5, 1.5])).result()["tensor"]
        assert isinstance(out, dict)        # no TensorSegment, no ndarray
        assert stub.channels["Update"].stats.gpv_calls == 0
    finally:
        rpc_mod.set_gpv(prev)


def test_stream_items_shapes_fast_vs_dict_path(gpv_on):
    from repro.core.rpc import _stream_items
    assert isinstance(_stream_items({"t": np.zeros(3)}, "M.t"),
                      TensorSegment)
    assert isinstance(_stream_items({"t": [1, 2, 3]}, "M.t"), TensorSegment)
    assert _stream_items({"t": {"a": 1}}, "M.t") == {"a": 1}
    assert _stream_items({"t": 3.5}, "M.t") == {0: 3.5}     # 0-d: dict path
    assert _stream_items({"t": ["a", "b"]}, "M.t") == {0: "a", 1: "b"}
    assert _stream_items({}, "M.t") == {}


# ---- dense collision table ---------------------------------------------------

def test_dense_then_foreign_key_collides(gpv_on):
    srv = ServerAgent(SwitchMemory(2, 64), gaid=1, n_slots=32)
    cl = ClientAgent(srv)
    logs, vals, spills = cl.resolve_dense(8, np.arange(8, dtype=np.int64))
    assert spills == [] and len(logs) == 8
    # a foreign key hashing into the claimed dense range must detour
    assert cl.logical(2**32 + 3) is None
    assert cl.collisions[2**32 + 3] == 3


def test_foreign_then_dense_index_collides(gpv_on):
    srv = ServerAgent(SwitchMemory(2, 64), gaid=1, n_slots=32)
    cl = ClientAgent(srv)
    assert cl.logical(2**32 + 3) == 3       # foreign key claims address 3
    logs, vals, spills = cl.resolve_dense(8, np.arange(10, 18,
                                                       dtype=np.int64))
    assert spills == [(3, 13)]              # index 3 spills its value
    assert 3 not in logs.tolist()
    assert len(logs) == 7


def test_dense_table_grows_and_caches(gpv_on):
    srv = ServerAgent(SwitchMemory(2, 64), gaid=1, n_slots=32)
    cl = ClientAgent(srv)
    a = cl.dense_addrs(4)
    b = cl.dense_addrs(16)
    assert a.tolist() == list(range(4))
    assert b.tolist() == list(range(16))
    # plain int keys are identity-canonical: no collision with the table
    assert cl.logical(5) == 5


# ---- pure-query (ReadMostly) array requests (ISSUE 5 satellite) -------------

@inc.service(app="WPRQ-1")
class ReadSvc:
    @inc.rpc(request_msg="Accum")
    def Accum(self, tensor: inc.Agg[inc.FPArray](precision=4)): ...

    @inc.rpc(request_msg="FetchReq", reply_msg="FetchReply")
    def Fetch(self, tensor: inc.ReadMostly[inc.FPArray](precision=4)): ...


def test_pure_query_array_rides_gpv_path(gpv_on):
    rt = NetRPC()
    stub = rt.make_stub(ReadSvc, n_slots=64)
    g = np.arange(12, dtype=np.float32).reshape(3, 4) / 8
    stub.Accum(tensor=g).result()
    stub.Accum(tensor=g).result()
    ch = stub.channels["Fetch"]
    before = (ch.stats.gpv_calls, ch.stats.gpv_elems)
    out = stub.Fetch(tensor=np.zeros((3, 4), np.float32)).result()["tensor"]
    # ndarray reply, request-shaped, and the query itself counted as GPV
    assert isinstance(out, np.ndarray) and out.shape == (3, 4)
    np.testing.assert_allclose(out, 2 * g, atol=1e-3)
    assert ch.stats.gpv_calls == before[0] + 1
    assert ch.stats.gpv_elems == before[1] + 12


@settings(max_examples=10)
@given(st.integers(1, 24), st.integers(0, 4),
       st.lists(st.floats(-100.0, 100.0), min_size=1, max_size=24))
# a full-width read at precision 6
@example(n=24, precision=6,
         xs=[48.730361, -18.352692, -15.845153, -32.189059, 25.962229,
             -69.046161, 52.344353, -22.836207, 9.571173, -7.481111,
             43.863238, -61.804221, -9.672516, -11.521631, 34.013083,
             -32.996738, -5.172846, -26.335753, 1.266412, 17.484456,
             -33.018575, 34.341711, 27.047722, 15.07483])
def test_pure_query_gpv_equals_dict_reference(n, precision, xs):
    """Array-shaped ReadMostly requests: the TensorSegment read must be
    element-identical to the {i: x} dict reference path (REPRO_GPV=0),
    including accumulated state from prior array writes."""
    arr = np.array((xs * ((n // len(xs)) + 1))[:n], np.float64)

    @inc.service(app="WPRQ-prop")
    class Svc:
        @inc.rpc(request_msg="Accum")
        def Accum(self, tensor: inc.Agg[inc.FPArray](
            precision=precision)): ...

        @inc.rpc(request_msg="F", reply_msg="FR")
        def Fetch(self, tensor: inc.ReadMostly[inc.FPArray](
            precision=precision)): ...

    legs = {}
    for gpv in (True, False):
        prev = rpc_mod.set_gpv(gpv)
        try:
            rt = NetRPC()
            stub = rt.make_stub(Svc, n_slots=64)
            stub.Accum(tensor=arr).result()
            stub.Accum(tensor=-2 * arr).result()
            out = stub.Fetch(tensor=np.zeros(n)).result()["tensor"]
            vals = (out.tolist() if isinstance(out, np.ndarray)
                    else [out[i] for i in range(n)])
            legs[gpv] = vals
            assert (stub.channels["Fetch"].stats.gpv_calls > 0) == gpv
        finally:
            rpc_mod.set_gpv(prev)
    assert legs[True] == legs[False]


def test_pure_query_dict_request_still_dict_everywhere(gpv_on):
    """A dict-keyed query keeps the historical dict path and reply even
    with GPV on (explicit key maps are not dense tensors)."""
    rt = NetRPC()
    stub = rt.make_stub(ReadSvc, n_slots=64)
    stub.Accum(tensor=np.array([1.0, 2.0, 3.0])).result()
    out = stub.Fetch(tensor={0: 0, 2: 0}).result()["tensor"]
    assert isinstance(out, dict)
    assert out == {0: 1.0, 2: 3.0}
    assert stub.channels["Fetch"].stats.gpv_calls == 1   # the Accum only


def test_pure_query_clear_applies_once(gpv_on):
    """Get+clear on an array-shaped pure query: the read returns the
    accumulated values and the buffered clear empties the map exactly
    once (no double-decrement), matching the dict reference."""

    @inc.service(app="WPRQ-clr")
    class Svc:
        @inc.rpc(request_msg="Accum")
        def Accum(self, tensor: inc.Agg[inc.FPArray](precision=2)): ...

        @inc.rpc(request_msg="F", reply_msg="FR")
        def Drain(self, tensor: inc.ReadMostly[inc.FPArray](
            precision=2, clear="copy")): ...

    rt = NetRPC()
    stub = rt.make_stub(Svc, n_slots=64)
    g = np.array([1.25, -2.5, 3.75])
    stub.Accum(tensor=g).result()
    first = stub.Drain(tensor=np.zeros(3)).result()["tensor"]
    np.testing.assert_allclose(first, g, atol=1e-2)
    second = stub.Drain(tensor=np.zeros(3)).result()["tensor"]
    np.testing.assert_allclose(second, np.zeros(3))


def test_pure_query_empty_array_matches_dict_fallback(gpv_on):
    """A zero-length query array must behave like an empty dict on BOTH
    legs: fall back to dumping every spilled key, not silently return an
    empty GPV reply (the n=0 edge of GPV==dict)."""
    legs = {}
    for gpv in (True, False):
        prev = rpc_mod.set_gpv(gpv)
        try:
            rt = NetRPC()
            stub = rt.make_stub(ReadSvc, n_slots=0)   # no switch slots:
            stub.Accum(tensor={"spilled": 7.0}).result()   # -> host spill
            out = stub.Fetch(tensor=np.zeros(0)).result()["tensor"]
            legs[gpv] = out
        finally:
            rpc_mod.set_gpv(prev)
    assert legs[True] == legs[False]
    assert legs[True]                     # the spill dump, not {}
