"""Device-resident GPV data plane (ISSUE 6): differential device-vs-host.

The contract under test: ``device=True`` on an Agg/Get annotation changes
WHERE the registers live and HOW the quantize/addto/read verbs execute
(fused Pallas kernels over a jax int32 segment vs numpy over a host
segment) but never WHAT they compute. Every test here runs the same
stream down both lanes and asserts element-exact agreement:

  registers      identical int32 contents after any addto/clear sequence,
                 including misses, spills, and duplicate addresses;
  replies        the device Get reply is a float32 jax array equal to
                 ``raw.astype(f32) * (1 / float32(scale))`` — the shared
                 reciprocal-dequant formula of kernels/fused_gpv.py and
                 the host fallback in inc_map.read_batch_dev;
  stats          hits/misses/inc_bytes/host_bytes/spill parity, so the
                 device lane cannot silently re-route traffic;
  scheduling     a sharded runtime (``IncRuntime(workers=4)``) over a
                 device channel equals the ``workers=1`` sequential run.

The compiled-kernel lane is xfail-not-skip on CPU: the test body is the
same differential check, it just needs a TPU/GPU backend to lower — on an
accelerator container it activates (and must pass) without edits.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.api as inc
from repro import obs
from repro.core import rpc as rpc_mod
from repro.core.channel import Controller
from repro.core.inc_map import ServerAgent, SwitchMemory, quantize_stream
from repro.core.rpc import NetRPC
from repro.core.runtime import DrainPolicy, IncRuntime
from repro.kernels.backend import (accelerator_present, pallas_mode,
                                   resolve_interpret)
from repro.kernels.constants import INT32_MAX, INT32_MIN, SAT_MAX
from repro.kernels.fused_gpv import (fused_addto_pallas, fused_read_pallas,
                                     fused_scatter_pallas)


def _grad_pair(app, *, precision=6, clear="nop", n_slots=64):
    """(host stub, device stub) over identical schemas modulo device=."""
    stubs = []
    for device in (False, True):
        @inc.service(app=f"{app}-{'dev' if device else 'host'}")
        class Svc:
            @inc.rpc(request_msg="NewGrad", reply_msg="AgtrGrad")
            def Update(self, tensor: inc.Agg[inc.FPArray](
                    precision=precision, clear=clear, device=device)
                    ) -> {"tensor": inc.Get[inc.FPArray]}: ...
        stubs.append(NetRPC().make_stub(Svc, n_slots=n_slots))
    return stubs[0], stubs[1]


def _raw_state(stub, n):
    srv = stub.agents["Update"].server
    return srv.read_batch(np.arange(n, dtype=np.uint32)).tolist()


def _stats(stub):
    srv = stub.agents["Update"].server
    return {"hits": srv.hits, "misses": srv.misses,
            "inc_bytes": srv.inc_bytes, "host_bytes": srv.host_bytes,
            "spill": dict(srv.spill)}


# ---- end-to-end: device lane == host lane ------------------------------------

@pytest.mark.parametrize("clear", ["nop", "copy"])
@pytest.mark.parametrize("precision", [0, 4, 6])
def test_device_registers_and_replies_match_host(precision, clear):
    host, dev = _grad_pair(f"DP-eq-{precision}-{clear}",
                           precision=precision, clear=clear, n_slots=48)
    rng = np.random.RandomState(11)
    inv = np.float32(1.0) / np.float32(10.0 ** precision)
    for _ in range(3):
        g = (rng.randn(48) * 5).astype(np.float32)
        r_host = host.Update(tensor=g).result()["tensor"]
        r_dev = dev.Update(tensor=g).result()["tensor"]
        # the device reply is a float32 jax array...
        assert isinstance(r_dev, jnp.ndarray) and r_dev.dtype == jnp.float32
        assert r_dev.shape == g.shape
        # ... whose values are the reciprocal dequantize of the exact
        # host-lane registers (raw/scale in f64, exactly invertible)
        raw = np.rint(np.asarray(r_host) * 10.0 ** precision).astype(
            np.int64)
        np.testing.assert_array_equal(np.asarray(r_dev),
                                      raw.astype(np.float32) * inv)
    assert _raw_state(host, 48) == _raw_state(dev, 48)
    assert _stats(host) == _stats(dev)


def test_float64_stream_routes_to_host_quantize_and_still_matches():
    """float64 payloads must NOT ride the f32 device kernels (the fused
    quantize computes in f32, which is lossy for f64): the phase-2 router
    host-quantizes them, and the device registers stay element-exact vs
    the host lane anyway."""
    host, dev = _grad_pair("DP-f64", precision=6, clear="copy", n_slots=32)
    g = np.linspace(-3.0, 3.0, 32, dtype=np.float64) + 1e-9
    for stub in (host, dev):
        stub.Update(tensor=g).result()
    assert _raw_state(host, 32) == _raw_state(dev, 32)
    assert _stats(host) == _stats(dev)
    # the reply still comes back device-resident under the same contract
    out = dev.Update(tensor=np.zeros(32)).result()["tensor"]
    assert isinstance(out, jnp.ndarray) and out.dtype == jnp.float32


def test_gpv_off_dict_path_equals_device_lane():
    """With GPV marshalling forced off, a device channel's updates travel
    as per-element dicts and land through the int addto lane — the final
    registers must equal the array-native device path."""
    host, dev = _grad_pair("DP-dict", precision=4, n_slots=16)
    g = np.array([1.25, -2.5, 0.0, 3.75] * 4, np.float32)
    dev.Update(tensor=g).result()
    prev = rpc_mod.set_gpv(False)
    try:
        host.Update(tensor=g).result()
    finally:
        rpc_mod.set_gpv(prev)
    assert _raw_state(host, 16) == _raw_state(dev, 16)


def test_empty_batch_both_lanes():
    host, dev = _grad_pair("DP-empty", precision=2, n_slots=8)
    for stub in (host, dev):
        out = stub.Update(tensor=np.zeros(0, np.float32)).result()["tensor"]
        assert len(np.ravel(np.asarray(out))) == 0
    assert _stats(host) == _stats(dev)


# ---- agent-level parity: misses, spill, duplicate addresses ------------------

def _agent(device, n_slots=8):
    return ServerAgent(SwitchMemory(2, 64), gaid=1, n_slots=n_slots,
                       device=device)


def test_addto_f32_miss_and_spill_stats_parity():
    """A duplicate-heavy stream over more keys than the partition holds:
    the device lane's hit/miss routing, spill contents, and byte counters
    must match the host lane exactly — misses host-quantize into the same
    spill dict either way."""
    rng = np.random.RandomState(5)
    logs = (rng.zipf(1.4, 300) % 24).astype(np.uint32)
    fvals = (rng.randn(300) * 10).astype(np.float32)
    agents = {d: _agent(d) for d in (False, True)}
    for i in range(0, 300, 50):
        for a in agents.values():
            a.addto_batch_f32(logs[i:i + 50], fvals[i:i + 50], 10 ** 4)
    host, dev = agents[False], agents[True]
    assert (host.hits, host.misses) == (dev.hits, dev.misses)
    assert host.inc_bytes == dev.inc_bytes
    assert host.host_bytes == dev.host_bytes
    assert dict(host.spill) == dict(dev.spill)
    probe = np.unique(logs)
    np.testing.assert_array_equal(host.read_batch(probe),
                                  dev.read_batch(probe))


def test_read_batch_dev_fallback_equals_fast_path(monkeypatch):
    """read_batch_dev's single-segment contiguous fast path and its
    general fallback (spill present / partial hits) obey the same
    reciprocal-dequant contract, and clear alike: the fused program and
    the clear verb after the fallback's read leave the same registers."""
    verb_clears = []
    clear_batch = ServerAgent.clear_batch
    monkeypatch.setattr(ServerAgent, "clear_batch",
                        lambda self, l, r: verb_clears.append(len(l))
                        or clear_batch(self, l, r))
    inv = np.float32(1.0) / np.float32(10.0 ** 4)
    logs = np.arange(16, dtype=np.uint32)
    fast, slow = _agent(True, n_slots=16), _agent(True, n_slots=16)
    for a in (fast, slow):
        a.addto_batch_f32(logs, np.arange(16, dtype=np.float32) / 3,
                          10 ** 4)
    want_raw = fast.read_batch(logs)
    vals = fast.read_batch_dev(logs, 10 ** 4, True)
    assert isinstance(vals, jnp.ndarray) and verb_clears == []
    np.testing.assert_array_equal(np.asarray(vals),
                                  want_raw.astype(np.float32) * inv)
    # force the fallback: a spilled key in the probe
    slow.spill_host([(999, 7)])
    probe = np.append(logs, np.uint32(999))
    vals2 = slow.read_batch_dev(probe, 10 ** 4, True)
    assert verb_clears == [17]
    np.testing.assert_array_equal(np.asarray(vals2)[:16], np.asarray(vals))
    assert np.asarray(vals2)[16] == np.float32(7) * inv
    phys = fast.base + np.arange(16)
    np.testing.assert_array_equal(fast.switch.get(phys),
                                  slow.switch.get(phys))
    assert not fast.read_batch(probe).any()
    assert not slow.read_batch(probe).any()
    # without a clear the fallback reads and leaves the state alone
    slow.spill_host([(999, 5)])
    slow.addto_batch_f32(logs[3:4], np.ones(1, np.float32), 10 ** 4)
    vals3 = slow.read_batch_dev(np.array([999, 3], np.uint32), 10 ** 4)
    np.testing.assert_array_equal(
        np.asarray(vals3),
        slow.read_batch(np.array([999, 3], np.uint32)).astype(np.float32)
        * inv)
    assert slow.read_batch(np.array([999, 3], np.uint32)).tolist() == [
        5, 10 ** 4]


@pytest.mark.parametrize("device", [False, True])
def test_clear_batch_takes_off_what_was_read(device):
    """The clear after a read takes off the value that was read, so an
    update landing between the two survives it, in a register as in the
    spill; a sentinel stays, and the clear moves no addTo count."""
    a = _agent(device, n_slots=4)
    logs = np.array([0, 1, 2, 3, 9], np.uint32)   # key 9 finds no slot
    a.addto_batch(logs, np.array([5, INT32_MAX, -7, 0, 11], np.int64))
    assert 9 not in a.mapping and a.spill[9] == 11
    raw = a.read_batch(logs)
    assert raw.tolist() == [5, INT32_MAX, -7, 0, 11]
    a.addto_batch(logs[[0, 4]], np.array([3, 4], np.int64))
    counts = (a.hits, a.misses, a.inc_bytes, a.host_bytes,
              a.seen_this_window)
    a.clear_batch(np.append(logs, logs[:1]), np.append(raw, raw[:1]))
    assert a.read_batch(logs).tolist() == [3, INT32_MAX, 0, 0, 4]
    assert (a.hits, a.misses, a.inc_bytes, a.host_bytes,
            a.seen_this_window) == counts


def test_device_kernel_duplicate_addresses_match_host():
    """Duplicate physical addresses inside ONE fused-scatter batch apply
    serially in stream order — exactly the host fast path's semantics
    (the satellite-2 sweep found zero divergence; this pins it)."""
    regs0 = np.zeros(8, np.int32)
    idx = np.array([3, 3, 5, 3, 5], np.int32)
    fv = np.array([1.5, -0.25, 2.0, 1.0, -2.0], np.float32)
    got = np.asarray(fused_scatter_pallas(
        jnp.asarray(regs0), jnp.asarray(idx), jnp.asarray(fv), 100,
        interpret=True))
    want = regs0.copy().astype(np.int64)
    q = quantize_stream(fv, 100)
    for j, v in zip(idx, q):
        want[j] += v
    np.testing.assert_array_equal(got, want.astype(np.int32))


# ---- the copy clear: device lane == host lane == numpy oracle ----------------

def _copy_lanes(app, n, *, lead=0, tail=0, seg_slots=64):
    """{False: host stub, True: device stub}, each with ``clear="copy"``
    on its own 2-segment register file, its partition behind ``lead``
    slots of another and followed by ``tail`` slots of a third."""
    stubs = {}
    for device in (False, True):
        @inc.service(app=f"{app}-{'dev' if device else 'host'}")
        class Svc:
            @inc.rpc(request_msg="NewGrad", reply_msg="AgtrGrad")
            def Update(self, tensor: inc.Agg[inc.FPArray](
                    precision=6, clear="copy", device=device)
                    ) -> {"tensor": inc.Get[inc.FPArray]}: ...
        switch = SwitchMemory(2, seg_slots)
        if lead:
            assert switch.reserve(1000, lead)
        stubs[device] = NetRPC(Controller(switch)).make_stub(Svc,
                                                             n_slots=n)
        if tail:
            assert switch.reserve(1001, tail)
    return stubs


def _oracle_pass(regs, chunks):
    """The numpy oracle of one pass: per call, the saturating sticky add
    of the chunk's fixed-point values (scale 10**6), the reply read, then
    the clear (zero, a sentinel kept). Returns the replies' raw ints."""
    out = []
    for g in chunks:
        q = np.rint(np.asarray(g, np.float32) * 10 ** 6).astype(np.int64)
        for i, v in enumerate(q.tolist()):
            if abs(regs[i]) != INT32_MAX:
                regs[i] = (INT32_MAX if regs[i] + v > SAT_MAX else
                           INT32_MIN if regs[i] + v < -SAT_MAX else
                           regs[i] + v)
        out.append(regs.copy())
        regs[np.abs(regs) != INT32_MAX] = 0
    return out


def _check_copy_pass(stubs, chunks, regs):
    """One pass of ``chunks`` down both lanes, against the oracle."""
    inv = np.float32(1.0) / np.float32(10.0 ** 6)
    want = _oracle_pass(regs, chunks)
    req = [{"tensor": g} for g in chunks]
    got_host = [f.result()["tensor"] for f in stubs[False].Update.batch(req)]
    got_dev = [f.result()["tensor"] for f in stubs[True].Update.batch(req)]
    for w, h, d in zip(want, got_host, got_dev):
        np.testing.assert_array_equal(np.asarray(h), w / 10.0 ** 6)
        np.testing.assert_array_equal(np.asarray(d),
                                      w.astype(np.float32) * inv)
    n = len(regs)
    assert _raw_state(stubs[False], n) == _raw_state(stubs[True], n) \
        == regs.tolist()


def _clear_lanes():
    snap = obs.registry().snapshot()["counters"]
    return {k.split('"')[1]: v for k, v in snap.items()
            if k.startswith("inc_regfile_clear_elems_total{")}


COPY_CASES = {
    # name: (lead, n, tail, calls a pass, the device lane's clear)
    "pass1": (0, 48, 0, 1, "device"),
    "pass4": (0, 48, 0, 4, "device"),
    "pass8": (0, 48, 0, 8, "device"),
    # [16, 64): the bucket of 64 runs past the segment, so exact length
    "run_ends_at_segment_end": (16, 48, 0, 4, "device"),
    # [0, 40) read in a bucket of 64 over the next partition's slots,
    # which the clear must leave alone
    "bucket_past_run": (0, 40, 24, 4, "device"),
    # [16, 80) over both segments: the read gathers, then clears
    "run_over_two_segments": (16, 64, 0, 4, "host"),
}


@pytest.mark.parametrize("case", list(COPY_CASES))
def test_copy_clear_matches_host_and_oracle(case):
    lead, n, tail, k, lane = COPY_CASES[case]
    stubs = _copy_lanes(f"DP-copy-{case}", n, lead=lead, tail=tail)
    neighbor = np.arange(lead + n, lead + n + tail)
    for dev in (False, True):
        stubs[dev].agents["Update"].server.switch.addto(
            neighbor, np.arange(1, tail + 1, dtype=np.int32) * 7)
    rng = np.random.RandomState(31)
    regs = np.zeros(n, np.int64)
    obs.enable()
    try:
        for _ in range(3):
            _check_copy_pass(stubs, [(rng.randn(n) * 3).astype(np.float32)
                                     for _ in range(k)], regs)
        lanes = _clear_lanes()
    finally:
        obs.disable()
        obs.reset()
    # every call cleared its n registers on each lane, the device lane
    # inside the read's program where the run allows
    assert lanes == ({"host": 2 * 3 * k * n} if lane == "host" else
                     {"host": 3 * k * n, "device": 3 * k * n})
    for dev in (False, True):
        srv = stubs[dev].agents["Update"].server
        assert srv.switch.get(neighbor).tolist() == \
            (np.arange(1, tail + 1) * 7).tolist()
        # a clear is not addTo traffic
        assert srv.hits + srv.misses == 3 * k * n
        assert srv.inc_bytes == 8 * srv.hits
    assert _stats(stubs[False]) == _stats(stubs[True])


def test_copy_clear_keeps_a_sentinel_on_both_lanes():
    stubs = _copy_lanes("DP-copy-sentinel", 32)
    regs = np.zeros(32, np.int64)
    g = np.linspace(-2, 2, 32).astype(np.float32)
    _check_copy_pass(stubs, [g], regs)       # maps every key
    # two registers near the ends of the range, which the next update
    # carries past them
    near = np.array([2_000_000_000, -2_000_000_000], np.int64)
    for stub in stubs.values():
        stub.agents["Update"].server.addto_batch(
            np.array([3, 17], np.uint32), near)
    regs[[3, 17]] = near
    hot = g.copy()
    hot[[3, 17]] = [500.0, -500.0]
    _check_copy_pass(stubs, [hot, g], regs)
    for stub in stubs.values():
        raw = stub.agents["Update"].server.read_batch(
            np.array([3, 17, 4], np.uint32))
        assert raw.tolist() == [INT32_MAX, INT32_MIN, 0]
    _check_copy_pass(stubs, [g, g], regs)    # the sentinels stay sticky
    assert _stats(stubs[False]) == _stats(stubs[True])


def test_copy_clear_in_a_nested_handler_pass():
    """A handler's inline call on its own channel flushes the enclosing
    call's update, reads both and clears them; the enclosing call then
    reads the cleared registers — alike on both lanes."""
    stubs = _copy_lanes("DP-copy-nested", 16)
    g = np.linspace(0.5, 2.0, 16).astype(np.float32)
    inner = {}
    for dev, stub in stubs.items():
        seen = []

        def handler(req, stub=stub, seen=seen):
            if not seen:
                seen.append(None)            # the inner call's own handler
                seen[0] = np.asarray(
                    stub.Update(tensor=2 * g).result()["tensor"])
            return {}
        stub.legacy.runtime.server.register("Update", handler)
        outer = np.asarray(stub.Update(tensor=g).result()["tensor"])
        assert not outer.any()
        inner[dev] = seen[0]
    want = (np.rint(g * 10 ** 6).astype(np.int64)
            + np.rint(2 * g * 10 ** 6).astype(np.int64))
    np.testing.assert_array_equal(inner[False], want / 10.0 ** 6)
    np.testing.assert_array_equal(
        inner[True], want.astype(np.float32)
        * (np.float32(1.0) / np.float32(10.0 ** 6)))
    assert _raw_state(stubs[False], 16) == _raw_state(stubs[True], 16) \
        == [0] * 16
    assert _stats(stubs[False]) == _stats(stubs[True])


# ---- sharded runtime: device channel under concurrent drains -----------------

def _run_sharded(workers, n=32, rounds=10):
    @inc.service(app=f"DP-shard-{workers}")
    class Svc:
        @inc.rpc(request_msg="NewGrad", reply_msg="AgtrGrad")
        def Update(self, tensor: inc.Agg[inc.FPArray](
                precision=6, clear="copy", device=True)
                ) -> {"tensor": inc.Get[inc.FPArray]}: ...

    rt = IncRuntime(policy=DrainPolicy(max_batch=3, max_delay=30.0,
                                       eager_window=False), workers=workers)
    try:
        stub = rt.make_stub(Svc, n_slots=n)
        rng = np.random.RandomState(17)
        futs = [stub.Update(tensor=(rng.randn(n) * 2).astype(np.float32))
                for _ in range(rounds)]
        outs = [np.asarray(f.result(timeout=30)["tensor"]).tolist()
                for f in futs]
        state = _raw_state(stub, n)
        rt.scheduling_report()          # per-channel stats audit
        return outs, state
    finally:
        rt.close()


def test_sharded_device_channel_equals_sequential():
    want = _run_sharded(1)
    got = _run_sharded(4)
    assert got == want


# ---- mode selection (satellite 1) --------------------------------------------

def test_mode_resolution_param_env_backend(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    # backend default: CPU interprets, TPU/GPU compile
    assert resolve_interpret(None) is (not accelerator_present())
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert pallas_mode() == "interpret"
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert pallas_mode() == "compiled"
    # an explicit parameter beats the env override
    assert pallas_mode(True) == "interpret"


def test_fused_kernels_honor_env_override(monkeypatch):
    """REPRO_PALLAS_INTERPRET=1 must reach the fused kernels' default
    lane — the process-wide CI knob that forces the interpret oracle."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    regs = jnp.zeros(8, jnp.int32)
    out = fused_addto_pallas(regs, 2, jnp.asarray([1.5, -2.0], jnp.float32),
                             10)
    assert np.asarray(out).tolist() == [0, 0, 15, -20, 0, 0, 0, 0]


# ---- compiled lane: activates on an accelerator, xfail (not skip) on CPU -----

@pytest.mark.xfail(not accelerator_present(), strict=False,
                   reason="compiled Pallas lowering needs a TPU/GPU "
                          "backend; xfail-not-skip so this lane runs and "
                          "gates green on an accelerator container")
def test_compiled_fused_kernels_match_interpret_oracle():
    rng = np.random.RandomState(23)
    regs = jnp.asarray(rng.randint(-1000, 1000, 256).astype(np.int32))
    fv = jnp.asarray((rng.randn(64) * 7).astype(np.float32))
    a_int = fused_addto_pallas(regs, 32, fv, 10 ** 4, interpret=True)
    a_cmp = fused_addto_pallas(regs, 32, fv, 10 ** 4, interpret=False)
    np.testing.assert_array_equal(np.asarray(a_cmp), np.asarray(a_int))
    v_int, m_int = fused_read_pallas(a_int, 32, 64, 10 ** 4, interpret=True)
    v_cmp, m_cmp = fused_read_pallas(a_cmp, 32, 64, 10 ** 4,
                                     interpret=False)
    np.testing.assert_array_equal(np.asarray(v_cmp), np.asarray(v_int))
    np.testing.assert_array_equal(np.asarray(m_cmp), np.asarray(m_int))
    assert pallas_mode(False) == "compiled"


# ---- schema gating -----------------------------------------------------------

def test_device_schema_on_host_channel_raises():
    rt = NetRPC()

    @inc.service(app="DP-gate", name="Host")
    class HostSvc:
        @inc.rpc(request_msg="N", reply_msg="A")
        def Update(self, tensor: inc.Agg[inc.FPArray](precision=4)
                   ) -> {"tensor": inc.Get[inc.FPArray]}: ...

    @inc.service(app="DP-gate", name="Dev")
    class DevSvc:
        @inc.rpc(request_msg="N", reply_msg="A")
        def Update(self, tensor: inc.Agg[inc.FPArray](
                precision=4, device=True)
                ) -> {"tensor": inc.Get[inc.FPArray]}: ...

    rt.make_stub(HostSvc, n_slots=16)
    with pytest.raises(ValueError, match="device"):
        rt.make_stub(DevSvc, n_slots=16)
    # ... while the reverse order is fine: a device channel serves host
    # schemas (the registers are a superset capability)
    rt2 = NetRPC()
    rt2.make_stub(DevSvc, n_slots=16)
    rt2.make_stub(HostSvc, n_slots=16)


# ---- residency follows the declaration, never the backend --------------------

def test_host_segment_stays_numpy_whatever_the_backend(monkeypatch):
    """A device=False segment is a writable numpy array after addTo even
    where the Pallas kernels would run (use_pallas() true, as on a TPU):
    the host plane the tests check is the one the chip runs."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    host, _ = _grad_pair("DP-resid", precision=3, n_slots=40)
    g = np.linspace(-3, 3, 40, dtype=np.float32)
    host.Update(tensor=g).result()
    switch = host.agents["Update"].server.switch
    mem = SwitchMemory(n_segments=2, seg_slots=32)
    mem.addto(np.array([1, 1, 40], np.int64), np.array([5, 6, 7], np.int32))
    for seg in switch.segments + mem.segments:
        assert not seg.device
        assert isinstance(seg.regs, np.ndarray) and seg.regs.flags.writeable
    assert mem.get(np.array([1, 40], np.int64)).tolist() == [11, 7]
    assert _raw_state(host, 40) == quantize_stream(g, 10 ** 3).tolist()


def test_compile_cache_dir_from_env_else_checkout(monkeypatch, tmp_path):
    from pathlib import Path

    from repro.kernels import backend
    before = jax.config.jax_compilation_cache_dir
    assert backend.CHECKOUT == Path(__file__).resolve().parents[1]
    try:
        outside = str(tmp_path / "outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        assert backend.configure_compile_cache(tmp_path) == outside
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(tmp_path / ".jax_cache")
        assert backend.configure_compile_cache(tmp_path) == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_device_option_requires_array_iedt():
    from repro.core.schema import SchemaError
    with pytest.raises(SchemaError):
        inc.Agg[inc.STRINTMap](device=True)


# ---- train-step integration (launch/steps.py) --------------------------------

def test_train_telemetry_gradient_aggregation_device_resident():
    from repro.launch.steps import TrainTelemetry
    tel = TrainTelemetry(app_prefix="DP-train", grad_slots=64)
    try:
        grads = {"w": jnp.asarray(np.linspace(-1, 1, 12, dtype=np.float32)
                                  .reshape(3, 4)),
                 "b": jnp.asarray(np.array([0.5, -0.25, 0.125],
                                           np.float32))}
        out = tel.aggregate_gradients(grads)
        # structure and residency preserved; values follow the dequant
        # contract (raw = rint(g * scale) exactly, reciprocal multiply)
        assert set(out) == {"w", "b"} and out["w"].shape == (3, 4)
        assert isinstance(out["w"], jnp.ndarray)
        scale = 10.0 ** 6
        inv = np.float32(1.0) / np.float32(scale)
        for k in out:
            g = np.asarray(grads[k], np.float32)
            raw = np.rint(g * np.float32(scale)).astype(np.int64)
            np.testing.assert_array_equal(
                np.asarray(out[k]), raw.astype(np.float32) * inv)
    finally:
        tel.rt.close()
