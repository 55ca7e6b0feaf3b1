#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name, so adding a cell, a traffic
mix, a configuration or a per-layer metric means adding files and
``BENCHMARK.json`` entries, never editing this one:

  BENCHMARK.json           the cell (``workloads``), its configuration
                           (``configs``) and its metrics
  bench/configs/<c>.json   the configuration's sizes (``file`` in configs)
  bench/traffic/<cell>.json  the traffic mix; its ``kind`` names the driver
  bench/drivers/<kind>.py  ``run(ctx) -> record``: set-up, window, check
  bench/metrics/<m>.py     ``read(rec) -> value | None`` per per-layer metric
  bench/peaks.json         published peaks, keyed by JAX's ``device_kind``

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the
window under the JAX profiler and prints the per-layer metrics, the
device's busy and window seconds and a breakdown. Both check the timed
path's output against a plain reference and print every compared number
beside its limit, last on standard error and last in the result line.

Without a TPU, or with fewer chips than the cell asks for, the run fails
with no result. ``--rehearse`` is for tests only: toy sizes from the
``rehearse`` blocks of the configuration and the traffic, on the CPU with
the kernels interpreted; its result names the CPU as its platform.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """A run that cannot give a result: wrong machine, unknown device,
    missing file. It exits non-zero and prints no result line."""


def load_module(path: Path, name: str | None = None):
    """Import a benchmark file by path (metric and driver files are named
    after metrics and traffic kinds, which may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    mod_name = name or "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def seed_words(seed: int, n: int = 8) -> list[int]:
    """``n`` independent 31-bit seeds from one benchmark seed of any
    size (JAX keys and numpy generators take them whole)."""
    import numpy as np
    words = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(w) & 0x7FFFFFFF for w in words]


def _overlay(base: dict, rehearse: bool) -> dict:
    out = {k: v for k, v in base.items() if k != "rehearse"}
    if rehearse:
        out.update(base.get("rehearse", {}))
    return out


class Ctx:
    """What a driver gets: the cell's files, the run's arguments and the
    helpers every driver shares (seeds, spans, compile counting, the
    profiler)."""

    def __init__(self, args, spec: dict, cell: dict):
        self.args = args
        self.cell = cell
        self.name = cell["name"]
        self.chips = int(cell["chips"])
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = bool(args.rehearse)
        conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
        self.config = _overlay(json.loads((ROOT / conf["file"]).read_text()),
                               self.rehearse)
        tpath = BENCH / "traffic" / f"{cell['traffic']}.json"
        if not tpath.is_file():
            raise BenchError(f"missing {tpath.relative_to(ROOT)}")
        self.traffic = _overlay(json.loads(tpath.read_text()), self.rehearse)
        self.seeds = seed_words(self.seed)
        self.compiles = 0
        self.t_start = T_START
        self.trace_dir = ROOT / ".bench_trace" / self.name
        self.devices = None
        self.peaks = None

    # -- spans and compiles -------------------------------------------------

    def span(self, name: str):
        """A host span on the profiler's clock (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def listen_compiles(self) -> None:
        import jax

        def on_duration(event, duration, **_):
            if event == COMPILE_EVENT:
                self.compiles += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def memory_peak(self) -> int | None:
        """Peak bytes in use on the fullest chip of the cell."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    # -- profiler -----------------------------------------------------------

    def start_trace(self) -> None:
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans, not every frame
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)

    def stop_trace(self) -> dict:
        """Stop the profiler and reduce its trace (bench/trace_reduce.py);
        the raw trace is deleted."""
        import jax
        jax.profiler.stop_trace()
        import trace_reduce
        try:
            return trace_reduce.reduce_dir(self.trace_dir,
                                           n_devices=self.chips)
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def check_machine(ctx: Ctx) -> None:
    import jax
    devs = jax.devices()
    if ctx.rehearse:
        if devs[0].platform != "cpu":
            raise BenchError("--rehearse is the CPU rehearsal; drop it on a "
                             "chip")
        ctx.devices = devs[:ctx.chips]
        if len(ctx.devices) < ctx.chips:
            raise BenchError(f"rehearsal needs {ctx.chips} CPU devices "
                             "(XLA_FLAGS=--xla_force_host_platform_"
                             "device_count)")
        return
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < ctx.chips:
        raise BenchError(f"cell {ctx.name} needs {ctx.chips} chips, JAX "
                         f"found {len(devs)}")
    ctx.devices = devs[:ctx.chips]
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    kind = devs[0].device_kind
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    ctx.peaks = table[kind]


def configure_jax(ctx: Ctx) -> None:
    import jax
    ctx.listen_compiles()
    if ctx.rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    # every program, however quick to compile, comes from the cache on
    # the second run of a cell
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def build_result(ctx: Ctx, spec: dict, rec: dict) -> dict:
    metrics = {}
    if not ctx.trace:
        for m in spec["end_to_end"]:
            # a window that completed nothing has no rate or tail; its
            # run is not correct
            if applies(m, ctx.name) and m["name"] in rec["e2e"]:
                metrics[m["name"]] = {"value": rec["e2e"][m["name"]],
                                      "unit": m["unit"]}
    else:
        rec = dict(rec, peaks=ctx.peaks, chips=ctx.chips)
        for m in spec["per_layer"]:
            if not applies(m, ctx.name):
                continue
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": rec.get("memory_peak_bytes")}
    out = {"correct": bool(rec["correct"]), "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if ctx.trace and rec.get("trace"):
        tr = rec["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"][:10],
                            "idle_gaps": tr["idle_gaps"][:10]}
    out["window_compiles"] = rec.get("window_compiles")
    out["checks"] = rec["checks"]
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU, kernels interpreted "
                         "(tests only)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
        cell = next((w for w in spec["workloads"]
                     if w["name"] == args.workload), None)
        if cell is None:
            raise BenchError(f"no workload {args.workload!r} in "
                             "BENCHMARK.json")
        if not (ROOT / "src" / "repro").is_dir():
            raise BenchError("no program under src/repro: run from a "
                             "checkout of the repository")
        sys.path.insert(0, str(ROOT / "src"))
        sys.path.insert(0, str(BENCH))
        ctx = Ctx(args, spec, cell)
        check_machine(ctx)
        configure_jax(ctx)
        driver = load_module(BENCH / "drivers" / f"{ctx.traffic['kind']}.py")
        rec = driver.run(ctx)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = build_result(ctx, spec, rec)
    for name, c in rec["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
