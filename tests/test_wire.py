"""Real-wire exactly-once: run_flow's contract over actual sockets.

test_transport.py proves the §5.1 flip-bit property on the in-process
simulator; these tests port the same contract to the real wire — a
``SwitchServer`` behind a deterministic ``FaultProxy`` injecting seeded
loss / duplication / reordering, plus daemon crash/restart. The
properties are identical: no side effect is ever double-applied
(``duplicate_effects == {}``), registers match an in-process oracle
element-exactly, and no call ever hangs past its deadline.
"""
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.inc_map import ServerAgent, SwitchMemory
from repro.kernels.constants import INT32_MAX
from repro.net import (FaultProxy, FaultSpec, RemoteSwitchMemory,
                       SwitchServer, WireTransport)
from repro.net import protocol as proto

GEO = dict(n_segments=4, seg_slots=256)


def _stack(spec=None, spool=None, **kw):
    """server [+ proxy] + transport + memory; returns (srv, px, t, mem)."""
    srv = SwitchServer(state_spool=spool, track_effects=True, **GEO).start()
    px = FaultProxy(srv.address, spec).start() if spec else None
    addr = px.address if px else srv.address
    t = WireTransport(addr, flow_id=kw.pop("flow_id", 1), w_max=8,
                      rto_base=kw.pop("rto_base", 0.02),
                      call_timeout=kw.pop("call_timeout", 30.0), **kw)
    mem = RemoteSwitchMemory(t, **GEO)
    return srv, px, t, mem


def _teardown(srv, px, t):
    t.close()
    if px:
        px.stop()
    srv.stop()


def _respawn(address, spool):
    """A new daemon on a stopped one's TCP address, restored from its spool."""
    host, port = address
    return SwitchServer(host=host, port=port, state_spool=spool,
                        track_effects=True, **GEO).start()


# -- exactly-once under chaos -------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(st.floats(0.0, 0.2), st.integers(0, 2**16))
def test_exactly_once_under_chaos(loss, seed):
    """Any seeded loss/dup/reorder pattern: every addto lands exactly
    once, element-exact against local accumulation."""
    spec = FaultSpec(seed=seed, loss=loss, dup=loss / 2, reorder=loss / 2)
    srv, px, t, mem = _stack(spec)
    try:
        assert mem.reserve(1, 32)
        phys = np.arange(32, dtype=np.int64)
        expect = np.zeros(32, dtype=np.int64)
        rng = np.random.default_rng(seed)
        for _ in range(24):
            vals = rng.integers(-50, 50, size=32).astype(np.int32)
            mem.addto(phys, vals)
            expect += vals
        got = mem.get(phys).astype(np.int64)
        assert np.array_equal(got, expect)
        assert t.ctrl("stats")["duplicate_effects"] == {}
    finally:
        _teardown(srv, px, t)


@pytest.mark.parametrize("restart", ["crash", "spool"])
def test_crash_restart_replay(restart, tmp_path):
    """Daemon crash mid-stream, or a graceful stop and a new daemon
    restored from the state spool on the same address: clients reconnect
    and replay; state survives; still exactly-once."""
    spec = FaultSpec(seed=3, loss=0.2, dup=0.1, reorder=0.1)
    spool = str(tmp_path / "switchd.pkl")
    srv, px, t, mem = _stack(spec, spool=spool if restart == "spool" else None)
    try:
        assert mem.reserve(1, 64)
        phys = np.arange(64, dtype=np.int64)
        expect = np.zeros(64, dtype=np.int64)
        rng = np.random.default_rng(7)
        for _ in range(30):
            vals = rng.integers(-50, 50, size=64).astype(np.int32)
            mem.addto(phys, vals)
            expect += vals
        if restart == "crash":
            srv.crash(0.3)                  # refuse service; state survives
        else:
            srv.stop()                      # spools registers + seqs
            srv = _respawn(srv.address, spool)
        for _ in range(30):
            vals = rng.integers(-50, 50, size=64).astype(np.int32)
            mem.addto(phys, vals)
            expect += vals
        got = mem.get(phys).astype(np.int64)
        assert np.array_equal(got, expect)
        stats = t.ctrl("stats")
        assert stats["duplicate_effects"] == {}
        assert t.report()["reconnects"] >= 1
        assert not mem.fallback_active
    finally:
        _teardown(srv, px, t)


def test_stop_spools_the_op_it_is_applying(tmp_path):
    """A graceful stop that lands while an addTo is being applied spools
    that addTo too: the daemon restarted from the spool holds it, though
    the old one may have ACKed it after the stop began."""
    spool = str(tmp_path / "switchd.pkl")
    srv = SwitchServer(state_spool=spool, track_effects=True, **GEO).start()
    t = WireTransport(srv.address, flow_id=1, w_max=8, rto_base=0.02,
                      call_timeout=30.0)
    mem = RemoteSwitchMemory(t, **GEO)
    entered, release = threading.Event(), threading.Event()
    apply = srv._apply

    def held_apply(op, meta, arrays):
        if op == proto.OP_ADDTO:
            entered.set()
            release.wait(10)
        return apply(op, meta, arrays)

    try:
        assert mem.reserve(1, 32)
        phys = np.arange(32, dtype=np.int64)
        srv._apply = held_apply
        mem.addto(phys, np.full(32, 7, np.int32))
        assert entered.wait(10)
        stopper = threading.Thread(target=srv.stop)
        stopper.start()
        time.sleep(0.2)         # time enough for a stop that did not wait
        release.set()
        stopper.join(10)
        assert not stopper.is_alive()
        srv = _respawn(srv.address, spool)
        assert mem.get(phys).tolist() == [7] * 32
        assert t.ctrl("stats")["duplicate_effects"] == {}
    finally:
        release.set()
        t.close()
        srv.stop(spool=False)


def test_oracle_equivalence_mixed_ops():
    """Mixed addto/addto_f32/clear stream under reorder+dup faults must
    match an in-process SwitchMemory oracle bit-for-bit (including the
    f32 quantization scale math), and so must a read that clears."""
    spec = FaultSpec(seed=5, loss=0.1, dup=0.15, reorder=0.15)
    srv, px, t, mem = _stack(spec)
    oracle = SwitchMemory(**GEO)
    try:
        assert mem.reserve(2, 48) and oracle.reserve(2, 48)
        phys = np.arange(48, dtype=np.int64)
        rng = np.random.default_rng(11)
        scale = 1 << 16
        for i in range(20):
            kind = rng.integers(0, 10)
            if kind < 5:
                vals = rng.integers(-99, 99, size=48).astype(np.int32)
                mem.addto(phys, vals)
                oracle.addto(phys, vals)
            elif kind < 9:
                fvals = rng.standard_normal(48).astype(np.float32)
                mem.addto_f32(phys, fvals, scale)
                oracle.addto_f32(phys, fvals, scale)
            else:
                mem.clear(phys[:16])
                oracle.clear(phys[:16])
        assert np.array_equal(mem.get(phys), oracle.get(phys))
        wire_f, _ = mem.read_f32(phys, scale)
        orac_f, _ = oracle.read_f32(phys, scale)
        assert np.array_equal(np.asarray(wire_f), np.asarray(orac_f))
        # the reply path's clear keeps a sentinel, over the wire too
        for m in (mem, oracle):
            m.addto(phys[5:6], np.array([INT32_MAX], np.int32))
            m.clear_read(phys[30:40], m.get(phys[30:40]))
        assert np.array_equal(mem.get(phys), oracle.get(phys))
        wire_c, _ = mem.read_f32(phys, scale, True)
        orac_c, _ = oracle.read_f32(phys, scale, True)
        assert np.array_equal(np.asarray(wire_c), np.asarray(orac_c))
        assert mem.get(phys).tolist() == oracle.get(phys).tolist() == \
            [INT32_MAX if i == 5 else 0 for i in range(48)]
        assert t.ctrl("stats")["duplicate_effects"] == {}
    finally:
        _teardown(srv, px, t)


def test_reserve_mirrors_daemon_placement():
    """Two clients reserving in opposite order still agree on physical
    placement: the daemon's FCFS start is authoritative."""
    srv = SwitchServer(track_effects=True, **GEO).start()
    t1 = WireTransport(srv.address, flow_id=1, w_max=8)
    t2 = WireTransport(srv.address, flow_id=2, w_max=8)
    m1 = RemoteSwitchMemory(t1, **GEO)
    m2 = RemoteSwitchMemory(t2, **GEO)
    try:
        assert m1.reserve(10, 20) and m1.reserve(11, 30)
        assert m2.reserve(11, 30) and m2.reserve(10, 20)
        assert m1.partitions == m2.partitions
        # and a write through one client is visible through the other
        start = m1.partitions[10][0]
        phys = start + np.arange(20, dtype=np.int64)
        m1.addto(phys, np.full(20, 7, np.int32))
        t1.barrier()                         # m2's read fences only flow 2
        assert np.array_equal(m2.get(phys), np.full(20, 7, np.int32))
    finally:
        t1.close()
        t2.close()
        srv.stop()


@pytest.mark.parametrize("path", ["read_f32", "agent"])
def test_clear_keeps_another_writers_update_after_the_read(path):
    """A reply's clear takes off what the read returned, not what the
    registers hold when it lands: an addTo that a second client process
    sends to the same registers between the read and the clear survives
    it, and a sentinel stays. Both clears are covered: the one inside
    ``read_f32`` and the agent's ``clear_batch`` after ``read_batch``
    (the host lane's reply path)."""
    srv = SwitchServer(track_effects=True, **GEO).start()
    t1 = WireTransport(srv.address, flow_id=1, w_max=8)
    t2 = WireTransport(srv.address, flow_id=2, w_max=8)
    m1 = RemoteSwitchMemory(t1, **GEO)
    m2 = RemoteSwitchMemory(t2, **GEO)
    try:
        logs = np.arange(16, dtype=np.uint32)
        first = np.arange(1, 17, dtype=np.int32) * 5
        first[3] = INT32_MAX
        agent = ServerAgent(m1, gaid=10, n_slots=16)
        assert agent.granted and m2.reserve(10, 16)
        agent.addto_batch(logs, first)        # maps every key
        assert agent.read_batch(logs).tolist() == first.tolist()
        start = m1.partitions[10][0]
        phys = start + np.arange(16, dtype=np.int64)
        read, writes = m1.get, [3]

        def read_then_other_writes(p):
            out = read(p)
            while writes:                     # once, right after the read
                m2.addto(phys, np.full(16, writes.pop(), np.int32))
                t2.barrier()
            return out

        m1.get = read_then_other_writes
        if path == "read_f32":
            slots = np.array([agent.mapping[int(l)] for l in logs])
            got, _ = m1.read_f32(agent.base + slots, 100, True)
            got = np.asarray(got)
        else:
            raw = agent.read_batch(logs)
            agent.clear_batch(logs, raw)
            got = raw
        del m1.get
        np.testing.assert_array_equal(
            got, first if path == "agent" else
            first.astype(np.float32) * np.float32(0.01))
        assert agent.read_batch(logs).tolist() == \
            [INT32_MAX if i == 3 else 3 for i in range(16)]
        assert (agent.hits, agent.misses) == (0, 16)   # the first addTo
    finally:
        t1.close()
        t2.close()
        srv.stop()


# -- failure semantics --------------------------------------------------------

def test_deadline_never_hangs():
    """An op against a daemon that stays down raises TimeoutError at
    (about) the call deadline — never a hang, never silence."""
    srv, px, t, mem = _stack(call_timeout=0.6, unreachable_after=30.0)
    try:
        assert mem.reserve(1, 8)
        phys = np.arange(8, dtype=np.int64)
        srv.crash(10.0)                      # much longer than the deadline
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            mem.get(phys)                    # barrier or read must trip
        took = time.monotonic() - t0
        assert took < 5.0                    # bounded, not hung
    finally:
        _teardown(srv, px, t)


def test_degrades_to_local_plane():
    """Past unreachable_after the transport degrades and the memory
    falls back to its host-side plane: ops keep working locally and the
    report says so."""
    srv, px, t, mem = _stack(call_timeout=2.0, unreachable_after=0.3)
    try:
        assert mem.reserve(1, 16)
        phys = np.arange(16, dtype=np.int64)
        mem.addto(phys, np.ones(16, np.int32))
        assert np.array_equal(mem.get(phys), np.ones(16, np.int32))
        srv.crash(30.0)
        deadline = time.monotonic() + 10.0
        while not t.degraded and time.monotonic() < deadline:
            try:
                mem.addto(phys, np.ones(16, np.int32))
            except TimeoutError:
                pass
            time.sleep(0.05)
        assert t.degraded
        mem.addto(phys, np.ones(16, np.int32))   # served by the fallback
        assert mem.fallback_active
        rep = mem.report()
        assert rep["degraded"] and rep["fallback_active"]
        assert rep["fallback_activations"] >= 1
        assert len(mem.get(phys)) == 16          # local reads still work
    finally:
        _teardown(srv, px, t)


def test_close_fails_pending_ops():
    srv, px, t, mem = _stack()
    assert mem.reserve(1, 8)
    _teardown(srv, px, t)
    with pytest.raises((TimeoutError, ConnectionError)):
        t.call(proto.OP_READ, {}, [np.arange(8, dtype=np.int64)])


# -- runtime integration ------------------------------------------------------

@pytest.fixture
def wire_runtime():
    import repro.api as inc
    from repro.core.channel import Controller

    srv = SwitchServer(track_effects=True, **GEO).start()
    t = WireTransport(srv.address, flow_id=1, w_max=8)
    sw = RemoteSwitchMemory(t, **GEO)
    rt = inc.IncRuntime(controller=Controller(switch=sw))
    yield rt, t, srv
    rt.close()
    t.close()
    srv.stop()


def test_runtime_typed_stubs_over_wire(wire_runtime):
    """The whole point of the plug-in seam: typed stubs work unchanged
    when the switch plane lives in another process, and the snapshot
    exports (and validates) a 'wire' section."""
    import repro.api as inc
    from repro.obs import schema as obs_schema

    rt, t, srv = wire_runtime

    @inc.service(app="WIRE-T")
    class WireProbe:
        @inc.rpc(request_msg="R")
        def Push(self, kvs: inc.Agg[inc.STRINTMap],
                 payload: inc.Plain) -> {"payload": inc.Plain}: ...

        @inc.rpc(reply_msg="Q")
        def Query(self, kvs: inc.ReadMostly[inc.STRINTMap]): ...

    rt.server.register("Push", lambda req: {"payload": "ack"})
    stub = rt.make_stub(WireProbe, n_slots=128)
    truth = {}
    for i in range(24):
        kvs = {f"k-{(i * 5 + j) % 9}": j + 1 for j in range(3)}
        for k, v in kvs.items():
            truth[k] = truth.get(k, 0) + v
        assert stub.Push(kvs=kvs, payload=f"p{i}").result()
    rt.drain()
    q = stub.Query(kvs={k: 0 for k in truth}).result()
    assert q["kvs"] == truth                 # aggregated in the daemon
    report = rt.scheduling_report()
    assert report["__wire__"]["connected"]
    snap = rt.metrics_snapshot()
    assert snap["wire"]["acked"] >= 1
    assert snap["wire"]["fallback_active"] is False
    obs_schema.validate(snap,
                        obs_schema.load(obs_schema.repo_schema_path()))


# -- codec properties (pure, no sockets) --------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 2000),
       st.integers(0, 2**16))
def test_op_codec_roundtrip(seq, n, seed):
    rng = np.random.default_rng(seed)
    arrays = [rng.integers(-2**31, 2**31 - 1, size=n).astype(np.int32),
              rng.standard_normal(n).astype(np.float32)]
    meta = {"scale": 65536.0, "seq": seq}
    blob = proto.encode_op("addto_f32", meta, arrays)
    op2, meta2, arrays2 = proto.decode_op(blob)
    assert op2 == "addto_f32" and meta2 == meta
    for a, b in zip(arrays, arrays2):
        assert a.dtype == b.dtype and np.array_equal(a, b)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 5000), st.integers(64, 512), st.integers(0, 2**16))
def test_fragmentation_roundtrip(nbytes, mtu, seed):
    rng = np.random.default_rng(seed)
    blob = rng.bytes(nbytes)
    frags = proto.fragment(blob, mtu)
    assert all(len(f) <= mtu for f in frags)
    re = proto.Reassembler()
    out = None
    for i, f in enumerate(frags):
        out = re.add(7, 3, i, len(frags), f)
    assert out == blob
