"""Reduce a JAX profiler trace of one window to the numbers the
per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
On a TPU each chip is a plane ``/device:TPU:<i>`` whose lines hold the
device's own events: ``XLA Ops`` (every HLO op that ran, nested where an
op contains others, such as a ``while``), ``Async XLA Ops`` (copies and
collectives running beside them), ``XLA Modules`` (one event per jitted
program run) and ``Steps``. The host plane ``/host:CPU`` holds the host
threads' spans, the benchmark's own ``bench.*`` annotations among them,
on the same clock.

What comes out, per chip and averaged over the chips of the cell:

  busy_s        the union of the intervals in which an ``XLA Ops`` event
                ran on the device
  device_ops    self time (the op's time less that of ops nested in it)
                by op name, largest first
  modules       device time by jitted program name
  collectives_s device time of collective ops (permutes, all-reduces,
                all-gathers, reduce-scatters) by kind
  exposed_s     collective time during which no other op ran
  idle_gaps     the longest gaps between busy intervals of chip 0, each
                named by the ``bench.*`` host span and the innermost other
                host event open at its middle
"""
from __future__ import annotations

import re
from pathlib import Path

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all")
MIN_GAP_NS = 1_000


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``; module
    names ``jit_body(1234)`` -> ``jit_body``."""
    name = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return name.split("(", 1)[0]


def collective_kind(name: str) -> str | None:
    for kind in COLLECTIVES:
        if kind in name:
            return kind
    return None


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list[tuple[float, float]], b: list[tuple[float, float]]
             ) -> list[tuple[float, float]]:
    """The parts of union ``a`` not covered by union ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: list[tuple[str, float, float]]) -> dict[str, float]:
    """Self time by name of possibly nested events (name, start, end)."""
    out: dict[str, float] = {}
    stack: list[list] = []        # [name, (start, end), child time]

    def close(item):
        name, (s, e), child = item
        out[name] = out.get(name, 0.0) + (e - s) - child

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([name, (s, e), 0.0])
    while stack:
        close(stack.pop())
    return out


def _lines(plane) -> dict[str, list[tuple[str, float, float]]]:
    out = {}
    for line in plane.lines:
        out[line.name] = [(ev.name, float(ev.start_ns),
                           float(ev.start_ns + ev.duration_ns))
                          for ev in line.events]
    return out


def reduce_planes(device_lines: list[dict], host_lines: dict) -> dict:
    """The reduction proper, over plain lists: ``device_lines`` has one
    ``{line name: [(event name, start ns, end ns)]}`` per chip,
    ``host_lines`` the same for the host plane."""
    n = len(device_lines)
    busy, exposed = 0.0, 0.0
    ops: dict[str, float] = {}
    modules: dict[str, float] = {}
    coll: dict[str, float] = {}
    busy0: list[tuple[float, float]] = []
    for i, lines in enumerate(device_lines):
        evs = [(op_name(nm), s, e) for nm, s, e in lines.get(OPS_LINE, [])]
        busy_iv = union([(s, e) for _, s, e in evs])
        busy += length(busy_iv)
        if i == 0:
            busy0 = busy_iv
        for nm, t in self_times(evs).items():
            ops[nm] = ops.get(nm, 0.0) + t
        for nm, s, e in lines.get(MODULES_LINE, []):
            key = op_name(nm)
            modules[key] = modules.get(key, 0.0) + (e - s)
        all_evs = evs + [(op_name(nm), s, e)
                         for nm, s, e in lines.get(ASYNC_LINE, [])]
        coll_iv, comp_iv = [], []
        for nm, s, e in all_evs:
            kind = collective_kind(nm)
            if kind is not None:
                coll[kind] = coll.get(kind, 0.0) + (e - s)
                coll_iv.append((s, e))
        for nm, s, e in evs:
            if collective_kind(nm) is None:
                comp_iv.append((s, e))
        exposed += length(subtract(union(coll_iv), union(comp_iv)))
    ns = 1e-9 / max(n, 1)
    return {
        "devices": n,
        "busy_s": busy * ns,
        "device_ops": [[k, v * ns] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])],
        "modules": {k: v * ns for k, v in modules.items()},
        "collectives_s": {k: v * ns for k, v in coll.items()},
        "exposed_s": exposed * ns,
        "idle_gaps": idle_gaps(busy0, host_lines),
    }


def idle_gaps(busy: list[tuple[float, float]], host_lines: dict,
              top: int = 10) -> list[list]:
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)
            if busy[i + 1][0] - busy[i][1] >= MIN_GAP_NS]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        out.append([host_activity(host_lines, (s + e) / 2), (e - s) * 1e-9])
    return out


def host_activity(host_lines: dict, t: float) -> str:
    """What the host was doing at ``t``: the ``bench.*`` span open then
    (innermost, on any thread), and the innermost other host event open
    then on any thread, such as a transfer or a dispatch."""
    spans, inner = [], []
    for events in host_lines.values():
        for nm, s, e in events:
            if s <= t < e:
                (spans if nm.startswith("bench.") else inner).append(
                    (e - s, nm))
    label = min(spans)[1] if spans else "outside bench spans"
    if inner:
        label += " > " + min(inner)[1]
    return label


def reduce_file(path, n_devices: int) -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    dev, host = {}, {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev[int(m.group(1))] = _lines(plane)
        elif plane.name == "/host:CPU":
            host = _lines(plane)
    chips = [dev[i] for i in sorted(dev)[:n_devices]]
    return reduce_planes(chips, host)


def reduce_dir(trace_dir, n_devices: int) -> dict:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(files[-1], n_devices)
