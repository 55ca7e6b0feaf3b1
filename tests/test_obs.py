"""repro.obs (ISSUE 7): metrics registry, span tracing, and the
exportable telemetry surface.

The load-bearing properties:

  - Histogram arithmetic is exact: le-bucket boundary semantics,
    ``observe_many`` ≡ a loop of ``observe``, ``merge`` preserves every
    moment, and concurrent writers (4 threads on one striped lock) lose
    nothing.
  - Spans ride the JAX profiler: a capture of device-lane passes holds
    ``inc.drain > inc.pass > inc.execute > inc.regfile.addto_f32 >
    inc.xfer.h2d`` nested on the drain worker's host line, one pass
    number per batch; with obs off no span is built at all.
  - The served path's counters are exact where they happen: bytes of
    every host<->device copy by site, every call's queue wait, and the
    register file's host time less the copies inside it.
  - Identity: the SAME workload run with obs off and obs fully on
    produces byte-identical replies and final INC-map state — the
    instrumentation observes the data plane, it never steers it.
  - Disabled is really off: handles record nothing, snapshots carry no
    quantile keys, and flipping enable/disable reuses live handles.
  - The exports hold their published shape: ``metrics_snapshot()``
    validates against scripts/obs_schema.json (workers=4 included, with
    per-channel p99s readable), scheduling_report() carries the
    ``"__switch__"`` section, and ``prometheus_text()`` emits cumulative
    bucket series.
"""
import contextlib
import importlib.util
import json
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.api as inc
from repro import obs
from repro.core.channel import DRAIN_TRIGGERS, ChannelStats
from repro.obs import schema as obs_schema
from repro.obs import trace as trace_mod
from repro.obs.metrics import (COUNT_BUCKETS, Counter, Histogram,
                               MetricsRegistry, metric_key)


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with obs off and empty — the module
    globals (registry, hook bools) are process-wide."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# -- histogram arithmetic ----------------------------------------------------

def test_histogram_bucket_boundaries():
    h = Histogram("h", buckets=(10.0, 20.0, 40.0))
    # le semantics: a sample equal to a bound lands in that bound's bucket
    for v in (5.0, 10.0, 10.5, 20.0, 39.9, 40.0, 41.0):
        h.observe(v)
    assert h.bounds == (10.0, 20.0, 40.0, float("inf"))
    assert h.counts == [2, 2, 2, 1]
    assert h.count == 7
    assert h.sum == pytest.approx(5 + 10 + 10.5 + 20 + 39.9 + 40 + 41)
    assert h.min == 5.0 and h.max == 41.0


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        Histogram("h", buckets=())
    with pytest.raises(ValueError):
        Histogram("h", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        Histogram("h", buckets=(2.0, 1.0))


def test_observe_many_equals_observe_loop():
    vals = [0.5, 1.0, 3.14, 17.0, 1e4, 2e6, 7.0, 7.0, 0.0]
    a = Histogram("a")
    b = Histogram("b")
    for v in vals:
        a.observe(v)
    b.observe_many(vals)
    assert a.counts == b.counts
    assert a.count == b.count
    assert a.sum == pytest.approx(b.sum)
    assert a.min == b.min and a.max == b.max
    b.observe_many([])                      # empty batch is a no-op
    assert b.count == len(vals)


def test_histogram_merge_exact_and_bound_checked():
    a = Histogram("a", buckets=(1.0, 10.0, 100.0))
    b = Histogram("b", buckets=(1.0, 10.0, 100.0))
    a.observe_many([0.5, 5.0, 50.0])
    b.observe_many([7.0, 500.0])
    a.merge(b)
    assert a.count == 5
    assert a.counts == [1, 2, 1, 1]
    assert a.sum == pytest.approx(562.5)
    assert a.min == 0.5 and a.max == 500.0
    with pytest.raises(ValueError):
        a.merge(Histogram("c", buckets=(2.0, 20.0)))


def test_quantiles_clamp_to_observed_range():
    h = Histogram("h", buckets=(100.0, 200.0))
    h.observe(150.0)
    # single sample: every quantile is that sample (interpolation clamps)
    assert h.quantile(0.0) == 150.0
    assert h.quantile(0.5) == 150.0
    assert h.quantile(0.99) == 150.0
    # +inf bucket: the observed max is the only finite estimate
    h2 = Histogram("h2", buckets=(1.0,))
    h2.observe_many([5.0, 7.0, 9.0])
    assert h2.quantile(0.99) == 9.0
    assert Histogram("h3").quantile(0.5) == 0.0      # empty -> 0
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_quantile_interpolates_monotonically():
    h = Histogram("h", buckets=tuple(float(b) for b in COUNT_BUCKETS))
    h.observe_many(list(range(1, 1001)))
    qs = [h.quantile(q) for q in (0.1, 0.25, 0.5, 0.75, 0.9, 0.99)]
    assert qs == sorted(qs)
    assert 400 <= h.quantile(0.5) <= 600      # coarse but centered
    s = h.summary()
    assert set(s) == {"count", "sum", "min", "max", "mean",
                      "p50", "p90", "p99"}
    assert s["count"] == 1000 and s["mean"] == pytest.approx(500.5)


def test_concurrent_writers_lose_nothing():
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("lat_us")
    c = reg.counter("total")
    n_threads, per = 4, 2000

    def work(seed):
        for i in range(per):
            h.observe(float((seed * per + i) % 997))
            c.inc()

    ts = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert h.count == n_threads * per
    assert sum(h.counts) == n_threads * per
    assert c.value == n_threads * per


# -- registry behavior -------------------------------------------------------

def test_registry_dedupes_and_type_checks():
    reg = MetricsRegistry(enabled=True)
    assert reg.counter("x", app="a") is reg.counter("x", app="a")
    assert reg.counter("x", app="a") is not reg.counter("x", app="b")
    with pytest.raises(TypeError):
        reg.gauge("x", app="a")
    assert metric_key("x", {"b": 1, "a": 2}) == 'x{a="2",b="1"}'


def test_disabled_registry_records_nothing():
    reg = MetricsRegistry(enabled=False)
    c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
    c.inc()
    g.set(3.0)
    h.observe(1.0)
    h.observe_many([1.0, 2.0])
    assert c.value == 0 and g.value == 0.0 and h.count == 0
    # the same handles start recording after the flip — no re-lookup
    reg.enabled = True
    c.inc(5)
    g.set(2.5)
    h.observe(1.0)
    assert c.value == 5 and g.value == 2.5 and h.count == 1


def test_snapshot_and_collectors():
    reg = MetricsRegistry(enabled=True)
    reg.counter("c", app="a").inc(3)
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(10.0)
    reg.register_collector("agents", lambda: {"hits": 7})
    reg.register_collector("broken", lambda: 1 / 0)
    snap = reg.snapshot()
    assert snap["schema"] == "repro.obs/v1" and snap["enabled"]
    assert snap["counters"]['c{app="a"}'] == 3
    assert snap["gauges"]["g"] == 1.5
    assert snap["histograms"]["h"]["count"] == 1
    assert snap["collected"]["agents"] == {"hits": 7}
    assert "error" in snap["collected"]["broken"]   # must not kill export
    reg.reset()
    assert reg.snapshot()["counters"] == {}


def test_prometheus_text_cumulative_buckets():
    reg = MetricsRegistry(enabled=True)
    reg.counter("reqs", app="a").inc(2)
    h = reg.histogram("lat", buckets=(10.0, 100.0))
    h.observe_many([5.0, 50.0, 500.0])
    text = reg.prometheus_text()
    assert "# TYPE lat histogram" in text
    assert 'lat_bucket{le="10.0"} 1' in text
    assert 'lat_bucket{le="100.0"} 2' in text
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert "lat_count 3" in text
    assert 'reqs{app="a"} 2' in text


# -- spans ---------------------------------------------------------------------

class _SpanRecorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps every span
    the data plane builds, as (name, args)."""

    def __init__(self):
        self.built = []

    def __call__(self, name, **args):
        self.built.append((name, args))
        return contextlib.nullcontext()


@pytest.fixture
def spans(monkeypatch):
    rec = _SpanRecorder()
    monkeypatch.setattr(trace_mod, "TraceAnnotation", rec)
    return rec


def test_spans_are_noops_when_off(spans):
    with obs.trace_span("user"):                 # off: a null context
        pass
    assert not obs.enabled() and spans.built == []
    obs.enable(metrics=False)                    # spans alone
    assert obs.enabled() and not obs.metrics_enabled()
    with obs.trace_span("user", step=1):
        pass
    assert spans.built == [("user", {"step": 1})]
    obs.enable()
    assert obs.metrics_enabled()


def test_pass_takes_its_batch_number(spans):
    with trace_mod.batch("inc.drain", n=2) as b:
        trace_mod.pass_span("inc.pass")
        trace_mod.pass_span("inc.pass")          # a handler's nested pass
    trace_mod.pass_span("inc.pass")              # an inline call's pass
    drain, first, nested, alone = [a["seq"] for _, a in spans.built]
    assert drain == b.seq and first == drain
    assert len({first, nested, alone}) == 3


# -- strict trigger accounting (satellite b) ---------------------------------

def test_note_trigger_rejects_unknown_trigger():
    st = ChannelStats()
    for t in DRAIN_TRIGGERS:
        st.note_trigger(t)
    assert sum(st.drain_triggers.values()) == len(DRAIN_TRIGGERS)
    with pytest.raises(ValueError, match="unknown drain trigger"):
        st.note_trigger("typo")


# -- data-plane integration --------------------------------------------------

@inc.service(app="OBS-T", drain=inc.DrainPolicy(max_batch=8, max_delay=0.05,
                                                eager_window=False))
class ObsProbe:
    @inc.rpc(request_msg="R")
    def Push(self, kvs: inc.Agg[inc.STRINTMap],
             payload: inc.Plain) -> {"payload": inc.Plain}: ...

    @inc.rpc(reply_msg="Q")
    def Query(self, kvs: inc.ReadMostly[inc.STRINTMap]): ...


def _workload(n_calls=48):
    """Deterministic probe stream; returns every observable output as one
    JSON-serializable object (replies + aggregated map state)."""
    rt = inc.NetRPC()
    rt.server.register("Push", lambda req: {"payload": "ack"})
    stub = rt.make_stub(ObsProbe, n_slots=256)
    truth = {}
    replies = []
    for i in range(n_calls):
        kvs = {f"k-{(i * 7 + j) % 13}": j + 1 for j in range(4)}
        for k, v in kvs.items():
            truth[k] = truth.get(k, 0) + v
        replies.append(stub.Push(kvs=kvs, payload=f"p{i}").result())
    query = stub.Query(kvs={k: 0 for k in truth}).result()
    return {"replies": replies, "query": query["kvs"], "truth": truth}


def test_identity_obs_off_vs_on():
    """The whole point of the guard structure: enabling metrics+tracing
    must not change a single byte of what the data plane computes."""
    base = json.dumps(_workload(), sort_keys=True)
    obs.enable()
    traced = json.dumps(_workload(), sort_keys=True)
    obs.disable()
    again = json.dumps(_workload(), sort_keys=True)
    assert traced == base
    assert again == base
    d = json.loads(base)
    assert d["query"] == d["truth"]


def test_disabled_runtime_snapshot_has_no_quantiles():
    with inc.IncRuntime() as rt:
        rt.server.register("Push", lambda req: {"payload": "ack"})
        stub = rt.make_stub(ObsProbe, n_slots=256)
        futs = [stub.Push(kvs={"a": 1}, payload="x") for _ in range(16)]
        rt.drain()
        for f in futs:
            f.result()
        snap = rt.metrics_snapshot()
    assert snap["enabled"] is False
    ch = snap["channels"]["OBS-T"]
    assert "latency_p50_us" not in ch and "drain_wait_p99_us" not in ch
    assert snap["metrics"]["counters"] == {}    # nothing recorded


def test_workers4_snapshot_validates_and_reports_quantiles(spans):
    obs.enable()
    with inc.IncRuntime(workers=4) as rt:
        rt.server.register("Push", lambda req: {"payload": "ack"})
        stub = rt.make_stub(ObsProbe, n_slots=256)
        futs = [stub.Push(kvs={f"k-{i % 11}": 1, f"k-{i % 7}": 2},
                          payload="x") for i in range(64)]
        rt.drain()
        for f in futs:
            f.result()
        report = rt.scheduling_report()
        snap = rt.metrics_snapshot()
    # satellite a: the switch section rides the scheduling report
    assert "__switch__" in report
    assert report["__switch__"]["apps"]["OBS-T"]["cache_hit_ratio"] >= 0.0
    # the checked-in schema is the contract CI holds the export to
    obs_schema.validate(snap,
                        obs_schema.load(obs_schema.repo_schema_path()))
    ch = snap["channels"]["OBS-T"]
    for key in ("latency_p50_us", "latency_p99_us",
                "drain_wait_p50_us", "drain_wait_p99_us"):
        assert key in ch, key
    assert ch["latency_p99_us"] >= ch["latency_p50_us"]
    assert ch["acks"] >= 1
    assert snap["switch"]["total_slots"] > 0
    assert "cache_hit_ratio" in snap["switch"]["apps"]["OBS-T"]
    assert snap["switch"]["segments"]
    hists = snap["metrics"]["histograms"]
    assert any(k.startswith("inc_pipeline_pass_us") for k in hists)
    # every drained batch's pass carries the batch's number
    drains = [a["seq"] for n, a in spans.built if n == "inc.drain"]
    passes = {a["seq"] for n, a in spans.built if n == "inc.pass"}
    assert drains and len(set(drains)) == len(drains)
    assert set(drains) <= passes


def test_per_runtime_histograms_are_isolated():
    """Two runtimes must not share latency distributions: the per-channel
    histograms live on the scheduler queue, not in the global registry."""
    obs.enable()

    def one_runtime():
        with inc.IncRuntime() as rt:
            rt.server.register("Push", lambda req: {"payload": "ack"})
            stub = rt.make_stub(ObsProbe, n_slots=256)
            futs = [stub.Push(kvs={"a": 1}, payload="x") for _ in range(8)]
            rt.drain()
            for f in futs:
                f.result()
            return rt.metrics_snapshot()["channels"]["OBS-T"]

    a = one_runtime()
    b = one_runtime()
    # same workload, fresh histograms: counts reflect ONE runtime's calls
    assert a["drained_calls"] == b["drained_calls"] == 8


# -- the served path's spans and counters -------------------------------------

@inc.service(app="OBS-DEV")
class DevGrad:
    """The device lane with the ``copy`` clear, as SyncAgtr serves it."""
    @inc.rpc(request_msg="NewGrad", reply_msg="AgtrGrad")
    def Update(self, tensor: inc.Agg[inc.FPArray](
            precision=6, clear="copy", device=True)
            ) -> {"tensor": inc.Get[inc.FPArray]}: ...


def _chunks(n_elems: int, k: int) -> list:
    """Fresh device chunks with no element that quantizes to zero, so a
    ``copy`` clear writes back every register."""
    base = jnp.linspace(0.5, 1.5, n_elems, dtype=jnp.float32)
    return [base * (i + 1) for i in range(k)]


def _device_calls(rt, n_elems: int, k: int) -> None:
    stub = rt.make_stub(DevGrad, n_slots=n_elems)
    for f in [stub.Update(tensor=c) for c in _chunks(n_elems, k)]:
        f.result()["tensor"].block_until_ready()


def _sums(snap: dict, name: str) -> dict:
    """{labels: sum} of a histogram (or value of a counter) by label set."""
    out = {}
    for section in ("counters", "histograms"):
        for k, v in snap[section].items():
            if k.startswith(name + "{"):
                out[k[len(name):]] = v["sum"] if isinstance(v, dict) else v
    return out


def test_profiler_capture_nests_device_lane_on_the_drain_worker(tmp_path):
    n = 128
    obs.enable()
    with inc.IncRuntime() as rt:
        _device_calls(rt, n, 2)               # compiles outside the capture
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _device_calls(rt, n, 6)
        finally:
            jax.profiler.stop_trace()
    lines = trace_mod.captured_spans(tmp_path)
    holding = [name for name, evs in lines.items()
               if any(e[0] == "inc.drain" for e in evs)]
    assert holding == ["inc-drain-0"]
    evs = lines["inc-drain-0"]

    def chain(inner):
        return sorted((e for e in evs
                       if e[1] <= inner[1] and inner[2] <= e[2]),
                      key=lambda e: (e[1], -e[2]))

    # every call's update is one fused addTo of the caller's device
    # chunk: no copy crosses the host link anywhere in the calls
    pushes = [e for e in evs if e[0] == "inc.regfile.addto_f32"]
    assert len(pushes) == 6
    for push in pushes:
        c = chain(push)
        assert [e[0] for e in c] == [
            "inc.drain", "inc.pass", "inc.execute", "inc.regfile.addto_f32"]
        assert c[0][3]["seq"] == c[1][3]["seq"]
    assert not [e for e in evs if e[0].startswith("inc.xfer.")]
    # every call's read clears on the device: nothing crosses inside it
    reads = [e for e in evs if e[0] == "inc.regfile.read_dev"]
    assert len(reads) == 6
    for read in reads:
        assert [e[0] for e in chain(read)] == [
            "inc.drain", "inc.pass", "inc.execute", "inc.regfile.read_dev"]
        assert not [e for e in evs if e[0].startswith("inc.xfer.")
                    and read[1] <= e[1] and e[2] <= read[2]]
    drains = sorted(e[3]["seq"] for e in evs if e[0] == "inc.drain")
    passes = sorted(e[3]["seq"] for e in evs if e[0] == "inc.pass")
    assert drains == passes and len(set(drains)) == len(drains)


def test_obs_off_builds_no_span_and_moves_no_counter(spans):
    with inc.IncRuntime() as rt:
        _device_calls(rt, 64, 3)
    assert spans.built == []
    snap = obs.registry().snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}
    obs.enable()
    with inc.IncRuntime() as rt:
        _device_calls(rt, 64, 3)
    built = {name for name, _ in spans.built}
    assert {"inc.drain", "inc.pass", "inc.plane_lock", "inc.stream_modify",
            "inc.resolve_addrs", "inc.cntfwd_gate", "inc.execute",
            "inc.regfile.addto_f32", "inc.regfile.read_dev",
            "inc.xfer.d2h", "inc.xfer.h2d", "inc.resolve"} <= built
    # the clear is inside the read: no int write-back, no clear verb
    assert not {"inc.regfile.addto", "inc.regfile.clear"} & built


def test_copy_clear_call_moves_twenty_bytes_an_element():
    """A copy-clear call of a device request moves nothing across the
    host link. Of the 20 B an element such a call once moved, the 12 B of
    a clear that pulled the registers and wrote their negation back went
    first; the request's pull to the host and its push back into the
    fused addTo (8 B) are gone too: the caller's chunk reaches the
    register file as it is."""
    n = 256
    with inc.IncRuntime() as rt:
        _device_calls(rt, n, 1)               # compiles with obs off
        obs.enable()
        _device_calls(rt, n, 1)
    snap = obs.registry().snapshot()
    assert _sums(snap, "inc_xfer_bytes_total") == {}
    assert not [k for k in snap["histograms"] if k.startswith("inc_xfer_us")]
    assert snap["counters"]['inc_gpv_elems_total{app="OBS-DEV"}'] == n
    assert _sums(snap, "inc_request_elems_total") == {'{lane="device"}': n}


def test_queue_wait_sums_pick_minus_submit():
    now = [100.0]
    policy = inc.DrainPolicy(max_batch=64, max_delay=1e9,
                             eager_window=False)
    obs.enable()
    with inc.IncRuntime(policy=policy, clock=lambda: now[0]) as rt:
        stub = rt.make_stub(DevGrad, n_slots=32)
        submits = [100.0 + 0.25 * i for i in range(8)]
        futs = []
        for t, c in zip(submits, _chunks(32, 8)):
            now[0] = t
            futs.append(stub.Update(tensor=c))
        now[0] = 110.0                         # the batch is picked here
        assert rt.drain() == 8
        for f in futs:
            f.result()
    h = obs.registry().snapshot()["histograms"][
        'inc_queue_wait_us{app="OBS-DEV"}']
    assert h["count"] == 8
    assert h["sum"] == pytest.approx(sum((110.0 - t) * 1e6
                                         for t in submits))


def test_regfile_time_less_its_copies_fits_in_the_pass():
    obs.enable()
    with inc.IncRuntime() as rt:
        _device_calls(rt, 128, 6)
    snap = obs.registry().snapshot()
    verbs = sum(_sums(snap, "inc_regfile_us").values())
    copies = sum(v for k, v in _sums(snap, "inc_xfer_us").items()
                 if 'site="regfile.' in k)
    passes = sum(_sums(snap, "inc_pipeline_pass_us").values())
    assert set(_sums(snap, "inc_regfile_us")) == {
        '{op="addto_f32"}', '{op="read_dev"}'}
    assert copies > 0
    assert 0.0 <= verbs - copies <= passes


# -- the benchmark's readers of these counters ---------------------------------

_BENCH = Path(__file__).resolve().parents[1] / "bench"
_READERS = ("sched.queue_wait_us_per_call", "pipeline.xfer_bytes_per_elem",
            "pipeline.device_wait_us_per_call", "regfile.host_us_per_call",
            "regfile.device_clear_share", "regfile.add_in_read_share",
            "pipeline.request_on_device_share")
# copy readers: no array crosses the host link in a steady call, so their
# counters never appear in the window and the line leaves them out
_SILENT = ("pipeline.xfer_bytes_per_elem", "pipeline.device_wait_us_per_call")


def _reader(name: str):
    if str(_BENCH) not in sys.path:
        sys.path.insert(0, str(_BENCH))
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), _BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def traced_rehearsal():
    sys.path.insert(0, str(_BENCH / "tests"))
    try:
        from benchutil import rehearse
    finally:
        sys.path.remove(str(_BENCH / "tests"))
    proc, result = rehearse("agtr_1k", trace=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return result


@pytest.mark.parametrize("name", _READERS)
def test_served_path_readers_read_a_traced_rehearsal(name, traced_rehearsal):
    if name in _SILENT:
        assert name not in traced_rehearsal["metrics"]
    else:
        value = traced_rehearsal["metrics"][name]["value"]
        assert value > 0
    if name in ("regfile.device_clear_share", "regfile.add_in_read_share",
                "pipeline.request_on_device_share"):
        assert value == 100.0
    rec = {"obs": None, "trace": None, "window": {}, "peaks": None,
           "chips": 1}
    assert _reader(name).read(rec) is None


@inc.service(app="OBS-HOST")
class HostGrad:
    """``DevGrad`` on host registers: its clear is the clear verb's."""
    @inc.rpc(request_msg="NewGrad", reply_msg="AgtrGrad")
    def Update(self, tensor: inc.Agg[inc.FPArray](
            precision=6, clear="copy")
            ) -> {"tensor": inc.Get[inc.FPArray]}: ...


def test_clear_counts_its_lane_and_the_share_reads_them():
    n = 64
    obs.enable()
    with inc.IncRuntime() as rt:
        _device_calls(rt, n, 3)
    dev_snap = obs.registry().snapshot()
    assert _sums(dev_snap, "inc_regfile_clear_elems_total") == {
        '{lane="device"}': 3 * n}
    with inc.IncRuntime() as rt:
        stub = rt.make_stub(HostGrad, n_slots=n)
        for c in _chunks(n, 2):
            stub.Update(tensor=np.asarray(c)).result()
    both = obs.registry().snapshot()
    assert _sums(both, "inc_regfile_clear_elems_total") == {
        '{lane="device"}': 3 * n, '{lane="host"}': 2 * n}
    share = _reader("regfile.device_clear_share").read
    assert share({"obs": dev_snap}) == 100.0
    assert share({"obs": both}) == pytest.approx(60.0)
    obs.reset()
    assert share({"obs": obs.registry().snapshot()}) is None
    assert share({"obs": None}) is None
