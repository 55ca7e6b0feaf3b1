"""repro.obs — end-to-end observability for the INC data plane.

One import gives three things:

  metrics   a lock-striped registry of counters / gauges / fixed-bucket
            histograms with a no-op-when-disabled fast path
            (repro/obs/metrics.py). The data plane records per-channel
            submit→resolve latency and queue wait, drain-trigger mix,
            AIMD cw / ECN marks, switch hit/miss/spill, GPV coverage,
            host↔device copies, register-file host time and Pallas
            kernel launch timings into it — but ONLY while enabled.
  spans     the served path's layers as ``jax.profiler`` annotations
            (``inc.drain``, ``inc.pass``, its phases, ``inc.regfile.*``,
            ``inc.xfer.*``), on the device trace's clock in any profiler
            capture (repro/obs/trace.py).
  export    ``IncRuntime.metrics_snapshot()`` (stable schema
            ``repro.obs/v1``, validated by scripts/obs_schema.json) and
            ``registry().prometheus_text()``.

Everything is OFF by default: the instrumented hot paths compile down to
one module-global bool branch per site (repro/obs/hooks.py), so the
pre-obs data plane is the disabled mode. Turn it on with::

    from repro import obs
    obs.enable()                       # metrics and spans
    with jax.profiler.trace("/tmp/inc-trace"):
        ... workload ...
    snap = rt.metrics_snapshot()
    obs.disable()

or set ``REPRO_OBS=1`` in the environment. ``enable(metrics=False)``
opens the spans alone. The chip benchmark (``bench/run.py``) measures
with obs off and reads its per-layer metrics from a separate traced run.
"""
from __future__ import annotations

import contextlib
import os

from repro.obs import hooks as _hooks
from repro.obs import metrics as _metrics
from repro.obs import schema
from repro.obs import trace as _trace
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               SCHEMA_VERSION)

__all__ = [
    "SCHEMA_VERSION", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "registry", "enable", "disable", "enabled", "metrics_enabled",
    "trace_span", "reset", "schema",
]

_NULL_SPAN = contextlib.nullcontext()


def registry() -> MetricsRegistry:
    """The process-wide metrics registry (the ``inc.metrics()`` front
    door). Handles obtained while disabled stay valid and start
    recording once ``enable()`` flips the switch."""
    return _metrics.REGISTRY


def enable(metrics: bool = True) -> None:
    """Turn observability on: the data plane's spans always, the
    metrics registry too unless ``metrics`` is False."""
    _metrics.REGISTRY.enabled = bool(metrics)
    _hooks.sync(True)


def disable() -> None:
    """Back to the zero-overhead default: the data-plane call sites fall
    through their single-bool guards again. Recorded metrics are
    retained (use ``reset()`` to drop them)."""
    _metrics.REGISTRY.enabled = False
    _hooks.sync(False)


def enabled() -> bool:
    return _hooks.ON


def metrics_enabled() -> bool:
    return _hooks.METRICS


def trace_span(name: str, **args):
    """User-level span (the ``inc.trace(...)`` front door)::

        with inc.trace("train_step", step=i):
            ...

    A ``jax.profiler`` annotation on the calling thread while obs is
    enabled; a no-op context manager otherwise."""
    if not _hooks.ON:
        return _NULL_SPAN
    return _trace.span(name, **args)


def reset() -> None:
    """Drop recorded metrics (bench legs / tests)."""
    _metrics.REGISTRY.reset()


if os.environ.get("REPRO_OBS") == "1":
    enable()
