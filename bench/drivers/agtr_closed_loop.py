"""SyncAgtr aggregation served through the typed stub, closed loop.

Set-up builds the register file of the configuration, the ``DeviceGrad``
service on ``IncRuntime``, and a pool of seeded fp32 gradient chunks per
client, made on the device in one jitted call. Every client then calls
``stub.Update(tensor=g)`` and waits for its ``Get`` reply to be ready on
the device before it issues the next call, cycling through its pool; one
thread drives all clients, each with one call in flight.
Warm-up runs the same loop until every shape the window uses has
compiled; the window then runs it for ``--seconds``.

``correct``: with ``clear="copy"`` every reply is the dequantized
fixed-point value of its own call's chunk, whatever the interleaving of
clients. A sample of replies, drawn from the seed by reservoir over all
calls of the window, is compared element for element with the numpy
integer oracle (``bench/reference/agtr_oracle.py``); after the window
every register of the partition must read zero again.
"""
# no ``from __future__ import annotations``: the schema compiler reads the
# service's annotations evaluated, and they name function-local values
import sys
import time
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp


def device_grad_service(conf: dict):
    """The repository's SyncAgtr declaration on the device lane
    (``benchmarks/device_path.py:DeviceGrad``), with the precision and
    clear policy of the configuration."""
    import repro.api as inc
    svc = conf["service"]

    @inc.service(app=svc["app"], name="DeviceGrad")
    class DeviceGrad:
        @inc.rpc(request_msg="NewGrad", reply_msg="AgtrGrad")
        def Update(self, tensor: inc.Agg[inc.FPArray](
                precision=svc["precision"], clear=svc["clear"],
                device=svc["device"])
                ) -> {"tensor": inc.Get[inc.FPArray]}: ...
    return DeviceGrad


def make_pools(key_seed: int, workers: int, pool: int, n: int,
               scale: float) -> list[list[jax.Array]]:
    """``workers`` x ``pool`` distinct fp32 chunks of ``n`` elements, drawn
    on the device in one jitted call."""
    def draw(key):
        keys = jax.random.split(key, workers * pool)
        return [jax.random.normal(k, (n,), jnp.float32) * scale
                for k in keys]
    flat = jax.jit(draw)(jax.random.key(key_seed))
    jax.block_until_ready(flat)
    return [flat[w * pool:(w + 1) * pool] for w in range(workers)]


class Client:
    """One closed-loop client: its pool, its latencies and a reservoir
    sample of its replies for the check."""

    def __init__(self, stub, pool, sample: int, rng):
        self.stub = stub
        self.pool = pool
        self.sample = sample
        self.rng = rng
        self.i = 0
        self.seen = 0
        self.lat: list[float] = []
        self.kept: list[tuple[int, jax.Array]] = []
        self.issued = 0
        self.failed = 0
        self.error: BaseException | None = None

    def issue(self, record: bool):
        """Send the next chunk of the pool: (client, chunk, future, t0)."""
        k = self.i % len(self.pool)
        self.i += 1
        self.issued += record
        t0 = time.perf_counter()
        return self, k, self.stub.Update(tensor=self.pool[k]), t0

    def finish(self, k: int, fut, t0: float, record: bool) -> None:
        """Wait for a reply to be ready on the device and record it."""
        try:
            reply = fut.result()["tensor"]
            reply.block_until_ready()
        except Exception as e:            # counted; the check then fails
            self.failed += record
            self.error = e
            return
        if not record:
            return
        self.lat.append(time.perf_counter() - t0)
        # reservoir sampling: every call of the window is equally likely
        # to be compared
        if self.seen < self.sample:
            self.kept.append((k, reply))
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.sample:
                self.kept[j] = (k, reply)
        self.seen += 1


def run_clients(clients, seconds: float, record: bool, ctx) -> float:
    """Keep one call of every client in flight from this one thread until
    ``seconds`` have passed, then wait for the calls still in flight.
    Passes complete in the order their calls were queued, so the oldest
    call is the one to wait for."""
    t0 = time.perf_counter()
    deadline = t0 + seconds
    inflight = deque(c.issue(record) for c in clients)
    while inflight:
        c, k, fut, t_issue = inflight.popleft()
        with ctx.span("bench.wait"):
            c.finish(k, fut, t_issue, record)
        if time.perf_counter() < deadline:
            with ctx.span("bench.issue"):
                inflight.append(c.issue(record))
    return time.perf_counter() - t0


def warm_zero_runs(stub, switch, chunk, seg_slots: int, zmax: int) -> None:
    """Warm the clear write-back lengths that exact zeros make. An element
    whose fixed-point value is 0 leaves its register at zero, so the
    write-back that clears it is one entry shorter in that segment, and the
    device int lane pads each length to its bucket in a program of its own.
    Calls whose chunk holds 1..``zmax`` zeros at the head of every segment
    run, as a pass's middle call and as its last, compile those lengths
    here and not in the window."""
    srv = stub.agents["Update"].server
    start, n = switch.partitions[srv.gaid]
    head = jnp.asarray((start + np.arange(n)) % seg_slots)
    for z in range(1, zmax + 1):
        gz = jnp.where(head < z, jnp.float32(0.0), chunk)
        for _ in range(3):
            for f in stub.Update.batch([{"tensor": gz}, {"tensor": chunk}]):
                f.result()["tensor"].block_until_ready()
            stub.Update(tensor=gz).result()["tensor"].block_until_ready()


def run(ctx) -> dict:
    from repro import obs
    from repro.core.channel import Controller
    from repro.core.inc_map import SwitchMemory
    from repro.core.runtime import IncRuntime

    from reference import agtr_oracle as oracle

    conf, tr = ctx.config, ctx.traffic
    n, workers, pool = tr["elems"], tr["workers"], tr["pool"]
    svc = conf["service"]
    scale_fp = 10 ** svc["precision"]

    switch = SwitchMemory(conf["n_segments"], conf["seg_slots"])
    rt = IncRuntime(Controller(switch))
    stub = rt.make_stub(device_grad_service(conf), n_slots=n)
    pools = make_pools(ctx.seeds[0], workers, pool, n, tr["chunk_std"])
    clients = [Client(stub, pools[w], tr["sample_per_worker"],
                      np.random.default_rng(ctx.seeds[2] + w))
               for w in range(workers)]
    # warm-up: the same closed loop, long enough that every pass shape
    # (a pass's first call, its later calls, its closing flush) compiled
    run_clients(clients, tr["warmup_seconds"], record=False, ctx=ctx)
    for c in clients:
        if c.error is not None:
            raise c.error
    warm_zero_runs(stub, switch, pools[0][0], conf["seg_slots"],
                   tr["warm_zero_runs"])
    setup_s = time.perf_counter() - ctx.t_start

    if ctx.trace:
        obs.reset()
        obs.enable(metrics=True)
        ctx.start_trace()
    c0 = ctx.compiles
    stats = stub.channels["Update"].stats
    passes0 = stats.batches
    # a program compiled inside the window is named on standard error
    jax.config.update("jax_log_compiles", True)
    window_s = run_clients(clients, ctx.seconds, record=True, ctx=ctx)
    jax.config.update("jax_log_compiles", False)
    window_compiles = ctx.compiles - c0
    passes = stats.batches - passes0
    for c in clients:
        if c.error is not None:
            print(f"bench: a call failed: {c.error!r}", file=sys.stderr)
            break
    trace = None
    if ctx.trace:
        snap = obs.registry().snapshot()
        obs.disable()
        trace = dict(ctx.stop_trace(), window_s=window_s)
    else:
        snap = None
    mem = ctx.memory_peak()

    # -- check: sampled replies against the integer oracle ----------------
    lat = np.array([x for c in clients for x in c.lat])
    attempted = sum(c.issued for c in clients)
    failed = sum(c.failed for c in clients)
    completed = len(lat)
    per_client = [len(c.lat) for c in clients]
    print(f"bench: {completed} calls in {passes} pipeline passes, "
          f"{min(per_client)}-{max(per_client)} a client, "
          f"{window_compiles} compiles in the window", file=sys.stderr)
    mismatched = 0
    compared = 0
    for w, c in enumerate(clients):
        for k, reply in c.kept:
            want = oracle.reply_f32(np.asarray(pools[w][k]), scale_fp)
            got = np.asarray(reply)
            mismatched += oracle.mismatches(got, want)
            compared += want.size
    srv = stub.agents["Update"].server
    regs = srv.read_batch(np.arange(n, dtype=np.uint32))
    nonzero = int(np.count_nonzero(regs))
    rt.close()

    checks = {
        "reply_elems_compared": {"value": compared, "limit": ">= 1"},
        "reply_elems_mismatched": {"value": mismatched, "limit": 0},
        "registers_nonzero_after": {"value": nonzero, "limit": 0},
        "calls_failed": {"value": failed, "limit": 0},
    }
    correct = (compared >= 1 and mismatched == 0 and nonzero == 0
               and failed == 0)
    e2e = {"setup_s": setup_s}
    if completed:
        e2e["agtr_elems_per_s"] = completed * n / window_s
        e2e["agtr_call_p99_ms"] = float(np.quantile(lat, 0.99)) * 1e3
    return {
        "correct": correct, "attempted": attempted, "failed": failed,
        "checks": checks, "e2e": e2e, "memory_peak_bytes": mem,
        "window": {"seconds": window_s, "calls": completed, "elems": n},
        "window_compiles": window_compiles,
        "obs": snap, "trace": trace,
    }

