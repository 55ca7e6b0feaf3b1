"""The reduction from a profiler trace to the per-layer numbers: on
hand-made intervals, and on a short trace recorded on a TPU v5e (eight
``agtr_1k`` calls, bench/testdata/)."""
import pytest

from benchutil import BENCH

import trace_reduce as tr  # noqa: E402

RECORDED = BENCH / "testdata" / "agtr_1k_8calls.xplane.pb"


def test_union_and_subtract():
    a = tr.union([(0, 10), (5, 20), (30, 40)])
    assert a == [(0, 20), (30, 40)]
    b = tr.union([(2, 4), (15, 35)])
    assert tr.subtract(a, b) == [(0, 2), (4, 15), (35, 40)]
    assert tr.length(a) == 30


def test_self_time_removes_nested_ops():
    evs = [("while", 0, 100), ("fusion", 10, 30), ("dot", 40, 90),
           ("copy", 100, 110)]
    st = tr.self_times(evs)
    assert st == {"while": 30, "fusion": 20, "dot": 50, "copy": 10}


def test_op_names_are_shortened():
    assert tr.op_name("%fusion.12 = f32[8]{0} fusion(%p), kind=kLoop") \
        == "fusion.12"
    assert tr.op_name("jit__fused_addto_jit(1234567)") \
        == "jit__fused_addto_jit"


def test_busy_exposed_and_gaps_on_hand_made_planes():
    ns = 1_000_000                       # 1 ms in ns
    dev = {tr.OPS_LINE: [("%fusion.1 = ...", 0, 2 * ns),
                         ("%collective-permute-done.3 = ...", 2 * ns,
                          3 * ns),
                         ("%fusion.2 = ...", 8 * ns, 9 * ns)],
           tr.ASYNC_LINE: [("%collective-permute-start.3 = ...", ns,
                            3 * ns)],
           tr.MODULES_LINE: [("jit_body(1)", 0, 9 * ns)]}
    host = {"main": [("bench.step", 0, 10 * ns),
                     ("np.asarray(jax.Array)", 3 * ns, 8 * ns)]}
    out = tr.reduce_planes([dev, dev], host)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(4e-3)
    assert out["modules"] == {"jit_body": pytest.approx(9e-3)}
    # the permute runs 1..3 ms; compute covers 0..2 ms: 1 ms exposed
    assert out["exposed_s"] == pytest.approx(1e-3)
    assert out["collectives_s"]["collective-permute"] == \
        pytest.approx(3e-3)
    assert out["idle_gaps"] == [["bench.step > np.asarray(jax.Array)",
                                 pytest.approx(5e-3)]]


def test_recorded_chip_trace():
    out = tr.reduce_file(RECORDED, n_devices=1)
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < 0.5
    assert {"jit__fused_addto_jit", "jit__fused_read_jit"} \
        <= set(out["modules"])
    assert out["device_ops"][0][1] >= out["device_ops"][-1][1] > 0
    assert out["idle_gaps"] and all(
        isinstance(name, str) and secs > 0 for name, secs in out["idle_gaps"])
    assert any(name.startswith("bench.call") for name, _ in out["idle_gaps"])
