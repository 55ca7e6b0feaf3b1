"""repro.obs.hooks — the switchboard the instrumented hot paths read.

``ON`` and ``METRICS`` are plain module globals, flipped only by
``repro.obs.enable()/disable()``: ``ON`` while obs is enabled at all (the
data plane then opens its spans, repro/obs/trace.py), ``METRICS`` while
the registry records too. Every data-plane call site guards its
instrumentation with one of them::

    if _obs.METRICS:
        t0 = time.perf_counter()
    ...
    if _obs.METRICS:
        _obs.pipeline_pass(app, n, source, t0)

    out = _obs.d2h(x, "regfile.get") if _obs.ON else np.asarray(x)

so the *disabled* build — the default — pays exactly one module-global
load + bool branch per site: there is no hook object to call, no registry
lookup, no lock, no span. That is the structural entirety of
disabled-mode overhead.

The record functions below run only when obs is enabled; they are
batch-granular (one histogram observe / counter inc per pipeline pass,
copy or kernel launch, never per element).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

ON = False
METRICS = False

# pipeline-pass durations are typically tens of us to tens of ms;
# element-count instruments use the power-of-two buckets
_US = _metrics.LATENCY_BUCKETS_US
_N = _metrics.COUNT_BUCKETS


def sync(on: bool) -> None:
    """Mirror the enable state into the call-site bools (called by
    repro.obs.enable/disable)."""
    global ON, METRICS
    ON = bool(on)
    METRICS = ON and _metrics.REGISTRY.enabled


# -- record functions (enabled mode only) -----------------------------------

def pipeline_pass(app: str, n_calls: int, source: str, t0: float) -> None:
    """One completed pipeline batch on a channel (rpc._run_pipeline)."""
    reg = _metrics.REGISTRY
    dur_us = (time.perf_counter() - t0) * 1e6
    reg.histogram("inc_pipeline_pass_us", buckets=_US, app=app).observe(
        dur_us)
    reg.histogram("inc_pipeline_batch_calls", buckets=_N, app=app).observe(
        n_calls)
    reg.counter("inc_pipeline_calls_total", app=app, source=source).inc(
        n_calls)
    reg.counter("inc_pipeline_batches_total", app=app, source=source).inc()


def plane_wait(app: str, wait_us: float) -> None:
    """Channel plane-lock acquisition wait (contention signal)."""
    _metrics.REGISTRY.histogram("inc_plane_lock_wait_us", buckets=_US,
                                app=app).observe(wait_us)


def gpv_coverage(app: str, gpv_calls: int, gpv_elems: int,
                 dict_calls: int) -> None:
    """GPV vs dict wire-path coverage for one batch."""
    reg = _metrics.REGISTRY
    if gpv_calls:
        reg.counter("inc_gpv_calls_total", app=app).inc(gpv_calls)
        reg.counter("inc_gpv_elems_total", app=app).inc(gpv_elems)
    if dict_calls:
        reg.counter("inc_dict_calls_total", app=app).inc(dict_calls)


def aimd_update(app: str, cw: int, ecn: bool) -> None:
    """Post-drain AIMD ack: cw evolution gauge + ECN mark counter."""
    reg = _metrics.REGISTRY
    reg.gauge("inc_aimd_cw", app=app).set(cw)
    reg.counter("inc_aimd_acks_total", app=app).inc()
    if ecn:
        reg.counter("inc_ecn_marks_total", app=app).inc()


def drain_trigger(app: str, trigger: str) -> None:
    _metrics.REGISTRY.counter("inc_drain_total", app=app,
                              trigger=trigger).inc()


def local_fold(app: str, depth: int) -> None:
    """One local-aggregation flush (Agg[...](local_accum=N)): ``depth``
    calls left the client as ONE switch-bound update, so depth-1 pipeline
    traversals were saved."""
    reg = _metrics.REGISTRY
    reg.counter("inc_local_folds_total", app=app).inc(depth - 1)
    reg.histogram("inc_local_fold_depth", buckets=_N, app=app).observe(depth)


def kernel_launch(kernel: str, n: int, t0: float) -> None:
    """One fused Pallas kernel launch (kernels/fused_gpv.py). Wall time
    of the pallas_call invocation: dispatch latency when compiled,
    execution time under interpret mode."""
    reg = _metrics.REGISTRY
    dur_us = (time.perf_counter() - t0) * 1e6
    reg.histogram("inc_kernel_launch_us", buckets=_US,
                  kernel=kernel).observe(dur_us)
    reg.counter("inc_kernel_elems_total", kernel=kernel).inc(n)


def queue_wait(app: str, waits_us: list[float]) -> None:
    """Every call of one drained batch: submit-to-pick wait."""
    _metrics.REGISTRY.histogram("inc_queue_wait_us", buckets=_US,
                                app=app).observe_many(waits_us)


def regfile_op(op: str, t0: float) -> None:
    """Host time of one register-file data-path verb (ServerAgent)."""
    _metrics.REGISTRY.histogram("inc_regfile_us", buckets=_US,
                                op=op).observe(
        (time.perf_counter() - t0) * 1e6)


def regfile_clear(lane: str, n: int) -> None:
    """Registers a reply's clear emptied: ``lane="device"`` inside the
    program that read them, ``"host"`` by a clear after the read."""
    _metrics.REGISTRY.counter("inc_regfile_clear_elems_total",
                              lane=lane).inc(n)


def regfile_add(lane: str) -> None:
    """One dense fp32 addTo of a device segment: ``lane="read"`` run
    inside the program of the read that followed it, ``"alone"`` in a
    launch of its own."""
    _metrics.REGISTRY.counter("inc_regfile_adds_total", lane=lane).inc(1)


def request_elems(lane: str, n: int) -> None:
    """Request elements of an addTo flush, by how they reached the
    register file: ``lane="device"`` as the caller's device buffer (no
    copy), ``"host"`` through a host copy or the host quantizer."""
    _metrics.REGISTRY.counter("inc_request_elems_total", lane=lane).inc(n)


# -- host<->device copies ----------------------------------------------------
#
# Array buffers that cross the host link in the served path, recorded at
# the site that moves them: an ``inc.xfer.<dir>`` span carrying ``site``
# and ``bytes``, and with metrics on ``inc_xfer_bytes_total{dir,site}``
# and ``inc_xfer_us{dir,site}``. Sites inside the register file are named
# ``regfile.*``. Scalar jit arguments are not counted.

def _xfer(direction: str, site: str, nbytes: int, t0: float) -> None:
    reg = _metrics.REGISTRY
    reg.counter("inc_xfer_bytes_total", dir=direction, site=site).inc(nbytes)
    reg.histogram("inc_xfer_us", buckets=_US, dir=direction,
                  site=site).observe((time.perf_counter() - t0) * 1e6)


def d2h(x, site: str) -> np.ndarray:
    """``np.asarray(x)``, recorded as one device-to-host copy. A value
    that crosses no link is not recorded: a host array, or a jax array
    whose host copy jax kept from an earlier pull."""
    if not isinstance(x, jax.Array) \
            or getattr(x, "_npy_value", None) is not None:
        return np.asarray(x)
    nbytes = int(x.nbytes)
    t0 = time.perf_counter()
    with _trace.span("inc.xfer.d2h", site=site, bytes=nbytes):
        out = np.asarray(x)
    if METRICS:
        _xfer("d2h", site, nbytes, t0)
    return out


def h2d(x, site: str, dtype=None) -> jax.Array:
    """``jnp.asarray(x, dtype)``, recorded as one host-to-device copy; a
    jax array is passed through unrecorded."""
    if isinstance(x, jax.Array):
        return jnp.asarray(x, dtype)
    x = np.asarray(x, dtype)
    nbytes = x.size * jax.dtypes.canonicalize_dtype(x.dtype).itemsize
    t0 = time.perf_counter()
    with _trace.span("inc.xfer.h2d", site=site, bytes=nbytes):
        out = jnp.asarray(x)
    if METRICS:
        _xfer("h2d", site, nbytes, t0)
    return out


# -- real-wire transport (repro.net) -----------------------------------------

def wire_retx(flow: int, rto_s: float) -> None:
    """One RTO-driven retransmission on the real wire; ``rto_s`` is the
    backed-off timeout that just fired (the backoff histogram)."""
    reg = _metrics.REGISTRY
    reg.counter("net_retx_total", flow=str(flow)).inc()
    reg.histogram("net_rto_backoff_us", buckets=_US,
                  flow=str(flow)).observe(rto_s * 1e6)


def wire_ack(flow: int, cw: int, ecn: bool) -> None:
    """One real-wire ACK: AIMD cw gauge + ECN mark counter per flow."""
    reg = _metrics.REGISTRY
    reg.gauge("net_aimd_cw", flow=str(flow)).set(cw)
    reg.counter("net_acks_total", flow=str(flow)).inc()
    if ecn:
        reg.counter("net_ecn_marks_total", flow=str(flow)).inc()


def wire_reconnect(flow: int) -> None:
    _metrics.REGISTRY.counter("net_reconnects_total", flow=str(flow)).inc()


def wire_fallback(flow: int) -> None:
    """The channel gave up on the switch and fell back to the host-side
    execution path (graceful degradation)."""
    _metrics.REGISTRY.counter("net_fallback_activations_total",
                              flow=str(flow)).inc()
