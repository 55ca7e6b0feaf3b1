"""A dense fp32 addTo of a device segment runs inside the program of the
read that follows it on the same run (``DeviceSegment.stage``), and any
other access to the segment first runs it alone.

Every case runs one sequence of register-file operations twice: staged,
and down the unstaged path (``stage`` patched to launch
``ops.device_addto_dense`` at once, which is how every dense addTo ran
before staging). The replies, the values read, and every register of the
file afterwards must agree element for element, and the staged run's
``inc_regfile_adds_total{lane}`` and ``fused_addto_read`` launches must
say which adds ran inside a read.
"""
import numpy as np
import pytest

import repro.api as inc
from repro import obs
from repro.core.channel import Controller
from repro.core.inc_map import DeviceSegment, SwitchMemory
from repro.core.rpc import NetRPC
from repro.kernels import ops
from repro.kernels.constants import INT32_MAX, INT32_MIN

SLOTS = 128
SCALE = 10 ** 6


def _switch(parts) -> SwitchMemory:
    """Two segments of ``SLOTS``: with ``parts``, device partitions of
    those sizes reserved in order and one more over the rest; without,
    none (a case's service reserves its own)."""
    sw = SwitchMemory(2, SLOTS)
    if parts is None:
        return sw
    for gaid, n in enumerate(parts):
        assert sw.reserve(gaid, n, device=True)
    sw.reserve(99, 2 * SLOTS - sum(parts), device=True)
    return sw


def _chunk(rng, n, mag=3.0):
    return (rng.standard_normal(n) * mag).astype(np.float32)


def _run(start, n):
    return np.arange(start, start + n, dtype=np.int64)


class Ops:
    """The operations of a case on one register file, each recording
    what it reads."""

    def __init__(self, sw):
        self.sw = sw
        self.rng = np.random.default_rng(7)
        self.seen: list[np.ndarray] = []

    def add(self, start, n, mag=3.0, scale=SCALE):
        self.sw.addto_f32(_run(start, n), _chunk(self.rng, n, mag), scale)

    def read(self, start, n, clear=True, scale=SCALE):
        vals, _ = self.sw.read_f32(_run(start, n), scale, clear)
        assert vals.shape == (n,)
        self.seen.append(np.asarray(vals))

    def get(self, start, n):
        self.seen.append(self.sw.get(_run(start, n)))

    def addto_int(self, start, n):
        self.sw.addto(_run(start, n),
                      self.rng.integers(-999, 999, n).astype(np.int32))

    def regs(self, s=0):
        self.seen.append(np.asarray(self.sw.segments[s].regs))

    def overwrite(self, s=0):
        self.sw.segments[s].regs = ops.zeros_regs(SLOTS, device=True)


def _fused(o, clear):
    for _ in range(3):
        o.add(0, 64)
        o.read(0, 64, clear)


def _saturating(o):
    # half the chunk lands beyond int32 at this scale: sentinels, which
    # the clear keeps, and which stay sticky through the next add
    for _ in range(2):
        o.add(0, 64, mag=4000.0)
        o.read(0, 64)
    o.regs()
    assert {INT32_MAX, INT32_MIN} <= set(o.seen[-1].tolist())


def _push_only(o):
    for _ in range(3):
        o.add(0, 64)
    o.regs()


def _settle_by_other_run(o):
    o.add(0, 32)
    o.read(32, 32)
    o.read(0, 32)


def _settle_by_get(o):
    o.add(0, 32)
    o.get(0, 32)
    o.read(0, 32)


def _settle_by_int_addto(o):
    o.add(0, 32)
    o.addto_int(0, 32)
    o.read(0, 32)


def _settle_by_second_addto_f32(o):
    o.add(0, 32)
    o.add(0, 32)
    o.read(0, 32)


def _settle_by_regs(o):
    o.add(0, 32)
    o.regs()
    o.read(0, 32)


def _overwritten(o):
    # registers written whole: an add staged before is overwritten too
    o.add(0, 32)
    o.overwrite()
    o.regs()


def _settle_by_other_scale(o):
    o.add(0, 32)
    o.read(0, 32, scale=10 ** 4)


def _two_partitions_interleaved(o):
    for _ in range(2):
        o.add(0, 32)
        o.read(0, 32)
        o.add(32, 32)
        o.read(32, 32)


def _two_partitions_crossed(o):
    o.add(0, 32)
    o.add(32, 32)
    o.read(0, 32)
    o.read(32, 32)


def _bucket_past_run(o):
    # [0, 40) is read in a bucket of 64 over the next partition's slots,
    # which neither the add nor the clear may touch
    o.addto_int(40, 24)
    for clear in (True, False, True):
        o.add(0, 40)
        o.read(0, 40, clear)
    o.get(40, 24)


def _run_at_segment_end(o):
    # [100, 128): the bucket of 32 runs past the segment, so exact length
    for _ in range(2):
        o.add(100, 28)
        o.read(100, 28)


def _push_only_service(o):
    @inc.service(app="STAGE-push")
    class Push:
        @inc.rpc(request_msg="NewGrad")
        def Update(self, tensor: inc.Agg[inc.FPArray](
                precision=6, device=True)) -> {}: ...

    stub = NetRPC(Controller(o.sw)).make_stub(Push, n_slots=48)
    for _ in range(3):
        stub.Update(tensor=_chunk(o.rng, 48)).result()
    o.regs()


def _served_calls(o):
    @inc.service(app="STAGE-serve")
    class Grad:
        @inc.rpc(request_msg="NewGrad", reply_msg="AgtrGrad")
        def Update(self, tensor: inc.Agg[inc.FPArray](
                precision=6, clear="copy", device=True)
                ) -> {"tensor": inc.Get[inc.FPArray]}: ...

    stub = NetRPC(Controller(o.sw)).make_stub(Grad, n_slots=48)
    for k in (1, 4):
        futs = stub.Update.batch([{"tensor": _chunk(o.rng, 48)}
                                  for _ in range(k)])
        o.seen += [np.asarray(f.result()["tensor"]) for f in futs]


# name: (partitions, the case, {lane: adds}, fused_addto_read launches)
CASES = {
    "fused_clear": ((64,), lambda o: _fused(o, True), {"read": 3}, 3),
    "fused_no_clear": ((64,), lambda o: _fused(o, False), {"read": 3}, 3),
    "saturating": ((64,), _saturating, {"read": 2}, 2),
    "push_only": ((64,), _push_only, {"alone": 3}, 0),
    "settle_by_other_run": ((32, 32), _settle_by_other_run,
                            {"alone": 1}, 0),
    "settle_by_get": ((32,), _settle_by_get, {"alone": 1}, 0),
    "settle_by_int_addto": ((32,), _settle_by_int_addto, {"alone": 1}, 0),
    "settle_by_second_addto_f32": ((32,), _settle_by_second_addto_f32,
                                   {"alone": 1, "read": 1}, 1),
    "settle_by_regs": ((32,), _settle_by_regs, {"alone": 1}, 0),
    "settle_by_other_scale": ((32,), _settle_by_other_scale,
                              {"alone": 1}, 0),
    "overwritten": ((32,), _overwritten, {}, 0),
    "two_partitions_interleaved": ((32, 32), _two_partitions_interleaved,
                                   {"read": 4}, 4),
    "two_partitions_crossed": ((32, 32), _two_partitions_crossed,
                               {"alone": 2}, 0),
    "bucket_past_run": ((40, 24), _bucket_past_run, {"read": 3}, 3),
    "run_at_segment_end": ((100, 28), _run_at_segment_end, {"read": 2}, 2),
    # a service's first call finds its keys unmapped: its add goes to
    # the host spill, and it is granted the registers the next call uses
    "push_only_service": (None, _push_only_service, {"alone": 2}, 0),
    "served_calls": (None, _served_calls, {"read": 4}, 4),
}


def _play(parts, case):
    o = Ops(_switch(parts))
    case(o)
    return o.seen, [np.asarray(s.regs) for s in o.sw.segments], o.sw


def _labels(section, name, value=lambda v: v):
    snap = obs.registry().snapshot()[section]
    return {k.split('"')[1]: value(v) for k, v in snap.items()
            if k.startswith(name + "{")}


@pytest.mark.parametrize("name", list(CASES))
def test_staged_add_equals_unstaged_path(name, monkeypatch):
    parts, case, lanes, fused = CASES[name]
    obs.enable()
    try:
        seen, regs, sw = _play(parts, case)
        adds = _labels("counters", "inc_regfile_adds_total")
        launches = _labels("histograms", "inc_kernel_launch_us",
                           lambda h: h["count"])
    finally:
        obs.disable()
        obs.reset()
    # no staged add outlives the final read of every register above
    assert not any(getattr(s, "_staged", None) for s in sw.segments)
    assert adds == lanes
    assert launches.get("fused_addto_read", 0) == fused
    assert launches.get("fused_addto", 0) == lanes.get("alone", 0)

    def at_once(seg, start, fv, scale):
        seg.regs = ops.device_addto_dense(seg.regs, start, fv, scale)
    monkeypatch.setattr(DeviceSegment, "stage", at_once)
    want_seen, want_regs, _ = _play(parts, case)
    assert len(seen) == len(want_seen)
    for got, want in zip(seen + regs, want_seen + want_regs):
        np.testing.assert_array_equal(got, want)
