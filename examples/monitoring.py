"""Network monitoring / flow counting (paper Figs. 22-24, KeyValue type).

Probes increment per-flow counters in the INC map at line rate (the
ElasticSketch analogue); a monitor process queries hot flows at any time.
The cache-replacement policy keeps hot flows on the 'switch' and spills
the long tail to the server agent.

The typed schema declares the whole app: ``MonitorCall`` streams a
``STRINTMap`` through Map.addTo (plus a pass-through payload the server
handler sees), ``Query`` is a ``ReadMostly`` RPC — the request carries
the keys, their aggregated counts come back via Map.get.  The service's
``drain=`` option sets the channel's schedule: every 16 queued probes
become one INC-map kernel batch; application code never schedules (or
drains) anything.  The Query future is issued on the same channel, so
FIFO order guarantees it observes every probe issued before it.

This example also demonstrates the observability front door
(docs/OBSERVABILITY.md): ``inc.obs.enable()`` turns the data-plane
metrics and spans on, ``inc.metrics()`` records an application-level
counter next to the built-in ones, ``inc.trace(...)`` wraps the probe
loop in a user span, a ``jax.profiler`` capture holds that span and the
data plane's own, and the run ends with the per-channel p99 latency
from ``rt.metrics_snapshot()``.

    PYTHONPATH=src python -m examples.monitoring
"""
import tempfile

import numpy as np

import jax

import repro.api as inc
from repro.obs.trace import captured_spans


@inc.service(app="MON-1",
             drain=inc.DrainPolicy(max_batch=16, max_delay=0.05,
                                   eager_window=False))
class Monitor:
    @inc.rpc(request_msg="MonitorRequest")
    def MonitorCall(self, kvs: inc.Agg[inc.STRINTMap],
                    payload: inc.Plain) -> {"payload": inc.Plain}: ...

    @inc.rpc(reply_msg="QueryReply")
    def Query(self, kvs: inc.ReadMostly[inc.STRINTMap]): ...


def main():
    # observability on for the whole run: data-plane metrics + spans
    # (they land in the profiler capture around the probe burst)
    inc.obs.enable()
    rt = inc.IncRuntime()
    rt.server.register("MonitorCall", lambda req: {"payload": "ack"})
    probe = rt.make_stub(Monitor, n_slots=512)
    probes_sent = inc.metrics().counter("mon_probes_total")

    # synthetic zipf traffic: a few elephant flows, many mice. Probes go
    # through the futures front; the schema's size trigger turns every 16
    # of them into one INC-map kernel batch.
    rng = np.random.RandomState(0)
    truth = {}
    futures = []
    trace_dir = tempfile.TemporaryDirectory()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # the spans, not every frame
    with jax.profiler.trace(trace_dir.name, profiler_options=opts), \
            inc.trace("probe_burst", n=200):
        for _ in range(200):
            flows = rng.zipf(1.4, 64) % 2000
            kvs = {}
            for f in flows:
                key = f"flow-{f}"
                kvs[key] = kvs.get(key, 0) + 1
                truth[key] = truth.get(key, 0) + 1
            probes_sent.inc()
            futures.append(probe.MonitorCall(kvs=kvs, payload="probe"))

        # the monitor reads at any time; the Query rides the same channel
        # queue, so it drains behind all 200 probes (.result()
        # demand-flushes)
        reply = probe.Query(kvs={k: 0 for k in truth}).result()
    spans = captured_spans(trace_dir.name, prefix="")
    trace_dir.cleanup()
    drains = sum(nm.startswith("inc.drain") for evs in spans.values()
                 for nm, *_ in evs)
    user = sum(nm == "probe_burst" for evs in spans.values()
               for nm, *_ in evs)
    assert all(f.result()["payload"] == "ack" for f in futures)
    got = {k: int(v) for k, v in reply["kvs"].items()}
    assert got == truth
    hot = sorted(got.items(), key=lambda kv: -kv[1])[:5]
    srv = probe.agents["MonitorCall"].server
    sched = rt.scheduling_report()["MON-1"]
    print("hot flows:", hot)
    print(f"flows tracked: {len(truth)}; switch slots: {srv.capacity}; "
          f"cache hit ratio: {srv.cache_hit_ratio:.3f}")
    print(f"auto-drain: {sched['drained_calls']} calls in "
          f"{sched['drained_batches']} batches (triggers {sched['drains']}), "
          f"mean batch {sched['mean_drained_batch']}")

    # the obs exports: per-channel latency quantiles + the app counter
    snap = rt.metrics_snapshot()
    mon = snap["channels"]["MON-1"]
    probes = snap["metrics"]["counters"]["mon_probes_total"]
    print(f"obs: {probes} probes; submit->resolve "
          f"p50={mon.get('latency_p50_us', 0.0)}us "
          f"p99={mon.get('latency_p99_us', 0.0)}us; "
          f"CHR={snap['switch']['apps']['MON-1']['cache_hit_ratio']:.3f}; "
          f"capture: {drains} inc.drain spans on "
          f"{len([ln for ln in spans if ln.startswith('inc-drain')])} "
          f"drain worker line(s), {user} user span")
    assert drains >= 1 and user == 1
    print("== every counter exact (switch + host-spill fallback)")
    rt.close()
    inc.obs.disable()
    inc.obs.reset()


if __name__ == "__main__":
    main()
