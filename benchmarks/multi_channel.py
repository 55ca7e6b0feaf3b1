"""Sharded-plane sweep: M independent channels under offered load,
``IncRuntime(workers=N)`` for N in {1, 2, 4}.

ISSUE 5's question: does the worker pool + per-channel plane locking
actually let independent channels drain in parallel, and does the
weighted-fair loop (strict priority tiers, DRR within a tier) keep every
tenant progressing under saturation?

Topology: one strict-priority latency channel (``priority=1``) plus four
bulk channels at ``priority=0`` with DRR weights 8/4/2/1 — five
independent GAIDs sharing one runtime and one host server. The bulk
channels get open-loop submitter threads (admission backpressure is the
only throttle); the latency channel is *paced* at a fixed modest rate —
a saturated strict-priority tier would correctly monopolize the plane,
which is the deployment's misconfiguration, not the scheduler's job to
fix. The server handler models per-call *blocking* work
(``--service-us`` of sleep, floor'd by the OS timer at ~1.2ms: a
downstream I/O or device-kernel wait) — the component concurrent drain
workers overlap. Pure-Python marshalling cost cannot scale past the core
count under the GIL and is measured by bench-wire/bench-batch instead;
the regression guard for those single-channel paths is their own
unchanged gates.

Reported per worker count: aggregate calls/sec (completions inside the
measurement window / window), per-priority-tier p99 completion latency,
and the starvation check — the lowest-weight bulk channel must complete
calls (> 0) while the plane is saturated, which is exactly what DRR
guarantees and a naive hottest-first loop does not.

Acceptance (the ISSUE 5 gate): with 4 workers over the 5 channels,
aggregate calls/sec >= 2.5x the same-session ``workers=1`` baseline
(median of within-repeat ratios). Box-weather guard like async_latency:
when the gate fails, the workers=1 config is re-run against itself,
interleaved; if identical code cannot hold a 0.8 self-ratio the row
reports PASS-BASELINE-ALSO-FAILS (+ ``baseline_self_ratio=``) instead of
a bare FAIL.

    PYTHONPATH=src python -m benchmarks.multi_channel [--smoke] [--csv]
"""
from __future__ import annotations

if __package__ in (None, ""):            # executed as a bare script
    import sys
    from pathlib import Path
    _root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(_root))
    sys.path.insert(0, str(_root / "src"))

import threading
import time

import numpy as np

import repro.api as inc
from repro.api import DrainPolicy, IncRuntime
from benchmarks._util import write_bench_json

BULK_WEIGHTS = (8.0, 4.0, 2.0, 1.0)   # priority-0 tier, DRR shares
WORKER_SWEEP = (1, 2, 4)
GATE_X = 2.5                          # ISSUE 5: 4 workers >= 2.5x 1 worker
SERVICE_US = 500.0                    # per-call blocking handler work
HI_RATE = 100.0                       # paced latency-tier arrivals, calls/s
KEYS_PER_CALL = 8


def mk_services() -> list:
    """(label, schema class, priority, weight) per channel: one strict
    tier-1 latency channel + the weighted tier-0 bulk channels. Exercises
    the new ``@inc.service(priority=..., weight=...)`` annotations."""
    svcs = []

    @inc.service(app="shard-hi", name="ShardHi", priority=1,
                 drain=DrainPolicy(max_batch=8, max_delay=0.001,
                                   eager_window=False))
    class Hi:
        @inc.rpc(request_msg="R")
        def Push(self, kvs: inc.Agg[inc.STRINTMap], payload: inc.Plain
                 ) -> {"payload": inc.Plain}: ...

    svcs.append(("hi", Hi, 1, 1.0))
    for i, w in enumerate(BULK_WEIGHTS):
        @inc.service(app=f"shard-b{i}", name=f"ShardBulk{i}", weight=w,
                     drain=DrainPolicy(max_batch=16, max_delay=0.001,
                                       eager_window=False, weight=w))
        class Bulk:
            @inc.rpc(request_msg="R")
            def Push(self, kvs: inc.Agg[inc.STRINTMap], payload: inc.Plain
                     ) -> {"payload": inc.Plain}: ...

        svcs.append((f"b{i}", Bulk, 0, w))
    return svcs


def _requests(n: int, seed: int) -> list[dict]:
    rng = np.random.RandomState(seed)
    return [{"kvs": {f"f-{int(k)}": 1
                     for k in rng.zipf(1.3, KEYS_PER_CALL) % 512},
             "payload": "p"} for _ in range(n)]


def _drive(svcs: list, workers: int, duration: float,
           service_us: float) -> dict:
    """One measurement window: open-loop submitters on every channel for
    ``duration`` seconds; returns aggregate cps, per-priority p99, and
    per-channel completion counts (all restricted to completions inside
    the window — the drain tail after the deadline is excluded)."""
    service_s = service_us / 1e6
    rt = IncRuntime(workers=workers)
    rt.server.register(
        "Push", lambda r: (time.sleep(service_s), {"payload": "ok"})[1])
    stubs = [(label, rt.make_stub(svc), prio, w)
             for label, svc, prio, w in svcs]
    reqs = {label: _requests(256, seed=i)
            for i, (label, _, _, _) in enumerate(stubs)}
    records = {label: [] for label, _, _, _ in stubs}   # (done_ts, latency)

    # warm every channel (spawns the pool, grants map slots) off-clock
    for label, stub, _, _ in stubs:
        stub.Push(**reqs[label][0]).result()

    start = time.perf_counter()
    deadline = start + duration

    def submit_loop(label, stub, rate):
        """Open loop (rate=None: admission backpressure is the throttle)
        or paced arrivals at ``rate`` calls/s (the latency tier)."""
        rec = records[label]
        rs = reqs[label]
        i = 0
        while True:
            if rate is not None:
                target = start + i / rate
                delay = target - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
            if time.perf_counter() >= deadline:
                break
            arr = time.perf_counter()
            f = stub.Push(**rs[i % len(rs)])    # blocks on admission
            f.add_done_callback(
                lambda fut, a=arr, r=rec:
                r.append((time.perf_counter(), time.perf_counter() - a)))
            i += 1

    threads = [threading.Thread(target=submit_loop,
                                args=(label, stub,
                                      HI_RATE if prio > 0 else None),
                                daemon=True)
               for label, stub, prio, _ in stubs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rt.drain()                      # flush the tail so close() is quick
    report = rt.scheduling_report()
    # --trace runs export the full obs snapshot before the runtime goes
    # away (per-channel drain-wait p99, switch CHR); None when obs is off
    snap = rt.metrics_snapshot() if inc.obs.enabled() else None
    rt.close()

    done_in_window = {label: [lat for ts, lat in records[label]
                              if ts <= deadline]
                      for label, _, _, _ in stubs}
    total = sum(len(v) for v in done_in_window.values())
    by_prio: dict[int, list] = {}
    for label, _, prio, _ in stubs:
        by_prio.setdefault(prio, []).extend(done_in_window[label])
    p99 = {p: (float(np.percentile(np.array(v) * 1e6, 99)) if v else 0.0)
           for p, v in by_prio.items()}
    return {"cps": total / duration,
            "p99_us_by_prio": p99,
            "completed": {label: len(v)
                          for label, v in done_in_window.items()},
            "plane": report.get("__plane__", {}),
            "snapshot": snap}


def run(duration: float = 0.8, repeats: int = 3,
        service_us: float = SERVICE_US) -> tuple[list, dict]:
    svcs_for = {w: mk_services() for w in WORKER_SWEEP}
    # schema classes hold compiled NetFilters keyed by AppName; channels
    # themselves are per-runtime (fresh Controller each _drive), so one
    # schema set per worker count is enough for the whole sweep
    samples = {w: [] for w in WORKER_SWEEP}
    low_label = f"b{len(BULK_WEIGHTS) - 1}"         # lowest DRR weight
    low_done = {w: [] for w in WORKER_SWEEP}        # per repeat
    detail = {}                                     # last repeat (p99s)
    for _ in range(repeats):
        # interleave worker counts per repeat so box jitter lands on
        # every config alike; the gate uses within-repeat ratios
        for w in WORKER_SWEEP:
            res = _drive(svcs_for[w], w, duration, service_us)
            samples[w].append(res["cps"])
            low_done[w].append(res["completed"].get(low_label, 0))
            detail[w] = res
    rows = []
    for w in WORKER_SWEEP:
        best = max(samples[w])
        res = detail[w]
        rows.append((f"t_shard/thr/workers{w}",
                     round(1e6 / best, 1) if best else 0,
                     f"agg_calls_per_sec={best:.0f}"
                     f" channels={len(svcs_for[w])}"))
        for p in sorted(res["p99_us_by_prio"], reverse=True):
            rows.append((f"t_shard/lat/workers{w}/prio{p}",
                         round(res["p99_us_by_prio"][p], 1),
                         f"p99_us={res['p99_us_by_prio'][p]:.0f}"))
        # starvation is judged over EVERY repeat, not whichever run the
        # other columns happen to report: the lowest-weight channel must
        # make progress in each saturated window
        starved = min(low_done[w]) == 0
        rows.append((f"t_shard/starvation/workers{w}", 0,
                     f"lowest_weight_completed_per_repeat={low_done[w]}"
                     f" ({'FAIL' if starved else 'PASS'})"
                     f" last_per_channel={res['completed']}"))
    ratios = [b / a for a, b in zip(samples[1], samples[4]) if a > 0]
    ratio = float(np.median(ratios)) if ratios else 0.0
    verdict = "PASS" if ratio >= GATE_X else "FAIL"
    baseline_note = ""
    self_ratio = None
    if verdict == "FAIL":
        # box-weather guard (see async_latency): identical workers=1 code
        # re-run against its own replay, interleaved — if the baseline
        # cannot hold steady against itself, the box failed the leg
        ctrl = {0: [], 1: []}
        for _ in range(max(2, repeats)):
            for leg in (0, 1):
                ctrl[leg].append(
                    _drive(svcs_for[1], 1, duration, service_us)["cps"])
        pairs = [a / b for a, b in zip(ctrl[0], ctrl[1]) if b > 0]
        self_ratio = float(np.median(pairs)) if pairs else 0.0
        stable = min(self_ratio, 1.0 / self_ratio) if self_ratio else 0.0
        baseline_note = f" baseline_self_ratio={self_ratio:.2f}"
        if stable < 0.8:
            verdict = "PASS-BASELINE-ALSO-FAILS"
    starvation_ok = all(min(low_done[w]) > 0 for w in WORKER_SWEEP)
    rows.append(("t_shard/acceptance", 0,
                 f"workers4_vs_workers1={ratio:.2f}x"
                 f" (need >= {GATE_X:.1f}x: {verdict})"
                 f" starvation_check={'PASS' if starvation_ok else 'FAIL'}"
                 f"{baseline_note}"))
    acceptance = {
        "workers4_vs_workers1": round(ratio, 3),
        "target": GATE_X,
        "verdict": verdict,
        "starvation_check": "PASS" if starvation_ok else "FAIL",
    }
    if self_ratio is not None:
        acceptance["baseline_self_ratio"] = round(self_ratio, 3)
    return rows, acceptance


def _traced_window(duration: float, service_us: float) -> None:
    """``--trace``: one fully-observed workers=4 saturation window under
    a ``jax.profiler`` capture written to benchmarks/TRACE_multi_channel/
    (open it in TensorBoard's profile plugin, or load its
    ``.trace.json.gz`` in ui.perfetto.dev): each drain worker's line
    holds its ``inc.drain > inc.pass > ...`` spans beside the device
    ops, and the per-channel drain-wait p99 + switch CHR come straight
    out of ``metrics_snapshot()``."""
    from pathlib import Path

    import jax

    from repro.obs.trace import captured_spans
    out = Path(__file__).resolve().parent / "TRACE_multi_channel"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # the spans, not every frame
    inc.obs.enable()
    try:
        with jax.profiler.trace(str(out), create_perfetto_trace=True,
                                profiler_options=opts):
            res = _drive(mk_services(), 4, duration, service_us)
        snap = res["snapshot"]
        for line, evs in sorted(captured_spans(out).items()):
            drains = sum(nm == "inc.drain" for nm, *_ in evs)
            print(f"trace: {line}: {drains} inc.drain, {len(evs)} inc.* "
                  f"spans -> {out}")
        for app, ch in sorted(snap["channels"].items()):
            print(f"{app}: drain_wait_p99_us="
                  f"{ch.get('drain_wait_p99_us', 0.0)}"
                  f" latency_p99_us={ch.get('latency_p99_us', 0.0)}"
                  f" CHR="
                  f"{snap['switch']['apps'][app]['cache_hit_ratio']:.3f}")
    finally:
        inc.obs.disable()
        inc.obs.reset()


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run for CI (correct plumbing, noisy numbers)")
    ap.add_argument("--csv", action="store_true",
                    help="append the rows to benchmarks/results.csv")
    ap.add_argument("--trace", action="store_true",
                    help="one traced workers=4 window instead of the sweep:"
                         " a profiler capture in benchmarks/"
                         "TRACE_multi_channel/"
                         " (Perfetto-loadable) + the obs snapshot summary")
    ap.add_argument("--duration", type=float, default=0.8)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--service-us", type=float, default=SERVICE_US)
    args = ap.parse_args()
    duration = 0.4 if args.smoke else args.duration
    repeats = 1 if args.smoke else args.repeats
    if args.trace:
        _traced_window(duration, args.service_us)
        return
    rows, acceptance = run(duration, repeats, args.service_us)
    lines = [",".join(str(x) for x in row) for row in rows]
    for ln in lines:
        print(ln)
    # smoke runs export under a separate (gitignored) name so CI never
    # overwrites the committed full-run trajectory with tiny-n noise
    write_bench_json("smoke_multi_channel" if args.smoke
                     else "multi_channel",
                     {"duration": duration, "repeats": repeats,
                      "service_us": args.service_us,
                      "workers": list(WORKER_SWEEP),
                      "bulk_weights": list(BULK_WEIGHTS),
                      "smoke": args.smoke},
                     rows, acceptance)
    if args.csv:
        from pathlib import Path
        out = Path(__file__).resolve().parent / "results.csv"
        with out.open("a") as f:
            f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
