"""repro.obs.trace — spans on the JAX profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation`` opened on the thread that
does the work, so in a profiler capture it lands on that thread's line of
the ``/host:CPU`` plane, on the same clock as the device's ops::

    obs.enable()
    jax.profiler.start_trace("/tmp/inc-trace")
    ... workload ...
    jax.profiler.stop_trace()      # load the directory in TensorBoard's
                                   # profile plugin or in Perfetto

Outside a capture a span costs about a microsecond. The call sites build
none while obs is off (repro/obs/hooks.py), so everything here may assume
it is on.

A batch keeps one number across its spans: the drain worker's ``batch``
(``inc.drain``) draws it, and the pipeline pass it runs (``pass_span``,
``inc.pass``) takes the same number as its ``seq``. A pass with no batch
around it (an inline call, or a handler's nested call inside a pass) draws
its own.
"""
from __future__ import annotations

import itertools
import threading

from jax.profiler import TraceAnnotation

_tls = threading.local()
_seq = itertools.count(1)


def span(name: str, **args) -> TraceAnnotation:
    """``with span("inc.execute"):`` — one span on the calling thread."""
    return TraceAnnotation(name, **args)


class batch:
    """The span of one drained batch, whose number the pass it runs
    takes (``pass_span``)."""

    __slots__ = ("seq", "_ann")

    def __init__(self, name: str, **args):
        self.seq = next(_seq)
        self._ann = TraceAnnotation(name, seq=self.seq, **args)

    def __enter__(self):
        _tls.pending = self.seq
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        _tls.pending = None
        return self._ann.__exit__(*exc)


def pass_span(name: str, **args) -> TraceAnnotation:
    """A pipeline pass's span, numbered by the batch that runs it, or
    with a number of its own when no batch is waiting for its pass."""
    seq = getattr(_tls, "pending", None)
    if seq is None:
        seq = next(_seq)
    else:
        _tls.pending = None
    return TraceAnnotation(name, seq=seq, **args)


def captured_spans(trace_dir, prefix: str = "inc.") -> dict[str, list]:
    """The spans named ``prefix*`` on the host plane of the newest
    profiler capture under ``trace_dir``, by host line (thread) name:
    ``{line: [(name, start_ns, end_ns, {arg: value})]}``."""
    from pathlib import Path

    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no profiler capture under {trace_dir}")
    out: dict[str, list] = {}
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    {k: v for k, v in ev.stats})
                   for ev in line.events if ev.name.startswith(prefix)]
            if evs:
                out.setdefault(line.name, []).extend(evs)
    return out
