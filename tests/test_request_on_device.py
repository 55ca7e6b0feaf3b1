"""A device request reaches the register file as the caller's buffer.

A SyncAgtr call whose chunk is a float32 ``jax.Array`` on the registers'
device, all of whose addresses hit one device segment's contiguous run,
makes no host<->device transfer: the chunk is staged as it is
(``SwitchMemory.addto_f32``), and the fused program's scalar operands
come from ``kernels/ops.py``'s device-scalar cache. Every other request
takes the host path it took before, element for element, and the
register file counts both under ``inc_request_elems_total{lane}``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as inc
from repro import obs
from repro.core.channel import Controller
from repro.core.inc_map import SwitchMemory
from repro.core.rpc import NetRPC
from repro.core.runtime import IncRuntime

PRECISION = 6
SCALE = 10 ** PRECISION
N = 48


def _grad(app, *, get=True, modify="nop", cnt_fwd=None):
    """A device-lane SyncAgtr service: an fp32 ``Agg`` with a ``copy``
    clear, and a ``Get`` reply unless ``get`` is False."""
    ret = {"tensor": inc.Get[inc.FPArray]} if get else {}

    def Update(self, tensor): ...
    Update.__annotations__ = {
        "tensor": inc.Agg[inc.FPArray](precision=PRECISION,
                                       clear="copy" if get else "nop",
                                       modify=modify, device=True),
        "return": ret}
    opts = {"request_msg": "NewGrad"}
    if get:
        opts["reply_msg"] = "AgtrGrad"
    if cnt_fwd is not None:
        opts["cnt_fwd"] = cnt_fwd
    cls = type("Grad", (), {"Update": inc.rpc(**opts)(Update)})
    return inc.service(app=app, name="Grad")(cls)


def _oracle(x: np.ndarray) -> np.ndarray:
    """The host integer oracle of a ``copy`` call's reply: the chunk's
    fixed point, rounded half to even in its own precision, times the
    fp32 reciprocal of the scale."""
    q = np.rint(x * x.dtype.type(SCALE))
    return q.astype(np.float32) * (np.float32(1.0) / np.float32(SCALE))


def _transfer_guard(mode):
    """Set the transfer guard for every thread (a pass runs on the
    runtime's drain thread); returns the setting to restore."""
    prev = jax.config.jax_transfer_guard
    jax.config.update("jax_transfer_guard", mode)
    return prev


# -- the steady call makes no transfer -------------------------------------------

@pytest.mark.parametrize("obs_on", [False, True], ids=["obs_off", "obs_on"])
@pytest.mark.parametrize("n", [1024, 32768])
def test_steady_device_call_makes_no_host_device_transfer(n, obs_on):
    """``disallow_explicit`` refuses every host-to-device transfer, the
    explicit ``jnp.asarray`` that once pushed the request back as well as
    a jit's implicit copy of a Python scalar. The CPU backend guards no
    device-to-host pull and keeps no host copy of an array it pulled, so
    with obs on the copy counters stand in for the pull's guard."""
    sw = SwitchMemory(2, 40_000)
    with IncRuntime(Controller(sw)) as rt:
        stub = rt.make_stub(_grad(f"ONDEV-{n}-{obs_on}"), n_slots=n)
        keys = jax.random.split(jax.random.key(n), 7)
        chunks = [jax.random.normal(k, (n,), jnp.float32) * 3.0
                  for k in keys]
        for c in chunks[:3] * 2:           # grants, compiles, caches
            stub.Update(tensor=c).result()["tensor"].block_until_ready()
        fresh = chunks[3:]
        jax.block_until_ready(fresh)
        if obs_on:
            obs.enable()
        prev = _transfer_guard("disallow_explicit")
        try:
            futs = [stub.Update(tensor=c) for c in fresh]
            replies = [f.result()["tensor"] for f in futs]
            jax.block_until_ready(replies)
        finally:
            jax.config.update("jax_transfer_guard", prev)
            snap = obs.registry().snapshot()
            obs.disable()
            obs.reset()
        # no host copy of a fresh chunk was made
        assert all(getattr(c, "_npy_value", None) is None for c in fresh)
        for c, r in zip(fresh, replies):
            np.testing.assert_array_equal(np.asarray(r),
                                          _oracle(np.asarray(c)))
        regs = stub.agents["Update"].server.read_batch(
            np.arange(n, dtype=np.uint32))
        assert not np.count_nonzero(regs)
    if obs_on:
        assert not [k for k in snap["counters"]
                    if k.startswith("inc_xfer_bytes_total")]
        assert {k: v for k, v in snap["counters"].items()
                if k.startswith("inc_request_elems_total")} == {
            'inc_request_elems_total{lane="device"}': 4 * n}


# -- a push-only call lends its buffer for the call alone ------------------------

@pytest.mark.parametrize("kind", ["device", "numpy"])
def test_push_only_call_holds_no_request_buffer_past_its_call(kind):
    """A device request that no read of its pass runs is added before the
    call returns, so the caller may delete its buffer at once: the
    registers then hold what the same calls leave from host copies."""
    def play(to_req):
        stub = NetRPC().make_stub(_grad(f"PUSH-{kind}", get=False),
                                  n_slots=N)
        for x in _chunks(k=4):
            r = to_req(x)
            stub.Update(tensor=r).result()
            if isinstance(r, jax.Array):
                r.delete()
        srv = stub.agents["Update"].server
        staged = [s._staged for s in srv.switch.segments
                  if getattr(s, "_staged", None)]
        assert not any(st[1].is_deleted() for st in staged)
        return srv.read_batch(np.arange(N, dtype=np.uint32))

    obs.enable()
    try:
        got = play(jnp.asarray if kind == "device" else np.asarray)
        counted = {k: v for k, v in obs.registry().snapshot()[
            "counters"].items() if k.startswith("inc_request_elems_total")}
    finally:
        obs.disable()
        obs.reset()
    np.testing.assert_array_equal(got, play(np.asarray))
    # the first call's keys miss (to the host spill) and are granted;
    # the other three reach the register file
    lane = "device" if kind == "device" else "host"
    assert counted == {f'inc_request_elems_total{{lane="{lane}"}}': 3 * N}


# -- every fallback: today's path, counted as host -------------------------------

def _chunks(dtype=np.float32, k=3):
    rng = np.random.default_rng(5)
    return [(rng.standard_normal(N) * 3.0).astype(dtype) for _ in range(k)]


def _calls(stub, reqs):
    return [np.asarray(stub.Update(tensor=r).result()["tensor"])
            for r in reqs]


def _numpy(to_req):
    stub = NetRPC().make_stub(_grad("FB-np"), n_slots=N)
    xs = _chunks()
    got = _calls(stub, [x for x in xs])          # host arrays either way
    for x, r in zip(xs[1:], got[1:]):
        np.testing.assert_array_equal(r, _oracle(x))
    return got, stub


def _float64(to_req):
    stub = NetRPC().make_stub(_grad("FB-f64"), n_slots=N)
    xs = _chunks(np.float64)       # no float64 jax array without x64
    got = _calls(stub, xs)
    for x, r in zip(xs[1:], got[1:]):
        np.testing.assert_array_equal(r, _oracle(x))
    return got, stub


def _bfloat16(to_req):
    stub = NetRPC().make_stub(_grad("FB-bf16"), n_slots=N)
    return _calls(stub, [to_req(x, jnp.bfloat16) for x in _chunks()]), stub


def _collision(to_req):
    stub = NetRPC().make_stub(_grad("FB-coll"), n_slots=N)
    # an int key >= 2**32 claims address 5 first: element 5 of every
    # chunk rides the collision host path
    assert stub.agents["Update"].logical(2 ** 32 + 5) == 5
    return _calls(stub, [to_req(x) for x in _chunks()]), stub


def _cntfwd_mixed(to_req):
    svc = _grad("FB-cnt", cnt_fwd=inc.CntFwd(to="ALL", threshold=2,
                                              key="ClientID"))
    stub = NetRPC().make_stub(svc, n_slots=N)
    got = []
    for x in _chunks(k=4):
        reply = stub.Update(tensor=to_req(x)).result()
        got.append(np.asarray(reply.get("tensor", np.zeros(0))))
    return got, stub


def _modify(to_req):
    stub = NetRPC().make_stub(_grad("FB-mod", modify=("max", -1_000_000)),
                              n_slots=N)
    return _calls(stub, [to_req(x) for x in _chunks()]), stub


def _two_chunks(to_req):
    stub = NetRPC().make_stub(_grad("FB-two", get=False), n_slots=N)
    for _ in range(2):          # the first batch grants the registers
        for f in stub.Update.batch([{"tensor": to_req(x)}
                                    for x in _chunks(k=2)]):
            f.result()
    return [], stub


def _host_segment(to_req):
    sw = SwitchMemory(2, 64)
    for x in _chunks():
        sw.addto_f32(np.arange(10, 10 + N), to_req(x), SCALE)
    return [sw.get(np.arange(128))], None


CASES = {"numpy": _numpy, "float64": _float64, "bfloat16": _bfloat16,
         "collision": _collision, "cntfwd_mixed": _cntfwd_mixed,
         "host_segment": _host_segment, "modify": _modify,
         "two_chunks": _two_chunks}


def _state(stub):
    if stub is None:
        return []
    srv = stub.agents["Update"].server
    return [srv.read_batch(np.arange(N, dtype=np.uint32)),
            *[np.asarray(s.regs) for s in srv.switch.segments[:1]]]


@pytest.mark.parametrize("name", list(CASES))
def test_fallback_takes_todays_path_counted_as_host(name):
    """The request as a device array (as a numpy array where jax has no
    such array) against its host copy, the form every request took on its
    way in before: replies and registers agree element for element, and
    every element that reached the register file counts as ``host``."""
    case = CASES[name]

    def on_device(x, dtype=jnp.float32):
        return jnp.asarray(x, dtype)

    def on_host(x, dtype=jnp.float32):
        return np.asarray(jnp.asarray(x, dtype))

    obs.enable()
    try:
        got, stub = case(on_device)
        counted = {k: v for k, v in obs.registry().snapshot()[
            "counters"].items() if k.startswith("inc_request_elems_total")}
        state = _state(stub)
    finally:
        obs.disable()
        obs.reset()
    want, want_stub = case(on_host)
    assert len(got) == len(want)
    for g, w in zip(got + state, want + _state(want_stub)):
        np.testing.assert_array_equal(g, w)
    assert list(counted) == ['inc_request_elems_total{lane="host"}']
    assert counted['inc_request_elems_total{lane="host"}'] > 0
