"""Replica pool: N executors of one net sharing one `KernelCache`.

The paper's pre-transformed kernels are the expensive shared state --
the whole point of the cache is that transforms are prepared ONCE and
served everywhere, so replicas must share it (the cache is internally
locked).  Each replica owns its jit-compiled program table; waves are
dispatched to the least-loaded replica on a thread pool, with
per-replica in-flight and dispatch accounting.  `workers=0` runs waves
inline on the caller's thread -- the deterministic mode the simulated-
clock tests use (no thread interleaving, same results, same counters).
"""

from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import dataclasses

import jax
import numpy as np

from repro.convserve.obs.trace import CAT_HOST, NULL_TRACER
from repro.convserve.runtime.clock import Clock, RealClock
from repro.convserve.runtime.scheduler import Wave


@dataclasses.dataclass
class WaveResult:
    """One executed wave: per-request outputs plus where/how long.
    `compute_s` runs from the host-to-device put through the fetch of
    the output.  `compiled` marks a cold wave (the replica jitted a new
    program for this shape): its wall time is compile + compute, so the
    runtime keeps it out of the deadline-slack service estimate."""

    wave: Wave
    outputs: Dict[int, np.ndarray]  # rid -> (H', W', C')
    replica: int
    compute_s: float
    compiled: bool = False


class ReplicaPool:
    """Dispatches waves across replicas of one compiled net.

    `executors` are callables ``ex(batch, sizes)`` exposing ``spec`` and
    ``cache`` (both `NetExecutor` and `engine.CompiledNet` qualify) that
    were built against the SAME `KernelCache` -- asserted here, because
    separate caches would silently re-transform every kernel per
    replica.

    Each wave's host path is traced on `tracer` as a
    `convserve.replica.run` span, under the wave's span, holding
    `assemble`, `put`, `compute`, `fetch` and `crop` children (see
    `obs.trace`).
    """

    def __init__(self, executors: Sequence, *, workers: Optional[int] = None,
                 clock: Optional[Clock] = None):
        if not executors:
            raise ValueError("replica pool needs at least one executor")
        cache = executors[0].cache
        spec = executors[0].spec
        for ex in executors[1:]:
            if ex.cache is not cache:
                raise ValueError(
                    "replicas must share one KernelCache (pass the same "
                    "cache/Engine when compiling each replica)"
                )
            if ex.spec is not spec and ex.spec != spec:
                raise ValueError("replicas must serve the same NetSpec")
        self.spec = spec
        self.cache = cache
        self.clock = clock or RealClock()
        self.tracer = NULL_TRACER  # ServeRuntime attaches its own
        self.workers = len(executors) if workers is None else workers
        self._pool = (
            ThreadPoolExecutor(
                max_workers=self.workers, thread_name_prefix="replica"
            )
            if self.workers > 0
            else None
        )
        self._lock = threading.Lock()
        self.executors = list(executors)  # guarded-by: _lock
        self.in_flight = [0] * len(executors)  # guarded-by: _lock
        self.dispatched = [0] * len(executors)  # guarded-by: _lock

    @classmethod
    def build(cls, engine, spec, weights, n: int, *,
              workers: Optional[int] = None,
              clock: Optional[Clock] = None, **compile_kwargs):
        """Compile `n` replicas of one net on one engine (hence one
        shared cache) and pool them.  The net is PLANNED once; replicas
        2..n bind the first replica's plan -- planning n times would be
        redundant roofline work, and with measurement-backed knobs
        (``tune_r=True``) could even hand different replicas different
        programs, breaking the pool's shared-shape assumption."""
        first = engine.compile(spec, weights, **compile_kwargs)
        fuse = compile_kwargs.get("fuse", True)
        nets = [first] + [
            engine.compile(spec, weights, plan=first.plan, fuse=fuse)
            for _ in range(n - 1)
        ]
        return cls(nets, workers=workers, clock=clock)

    # ------------------------------------------------------- dispatch

    def _pick(self):
        """Least-loaded replica; dispatch count breaks ties so the
        synchronous mode still spreads waves across replicas.  Returns
        ``(index, executor)`` -- the executor is read under the same
        lock, so a concurrent `swap` cannot slip between pick and run."""
        with self._lock:
            i = min(
                range(len(self.executors)),
                key=lambda j: (self.in_flight[j], self.dispatched[j], j),
            )
            self.in_flight[i] += 1
            self.dispatched[i] += 1
            return i, self.executors[i]

    def _run(self, i: int, ex, wave: Wave) -> WaveResult:
        tr = self.tracer
        try:
            with tr.span("convserve.replica.run", CAT_HOST,
                         parent=wave.trace_parent, replica=i,
                         **wave.trace_args):
                with tr.span("convserve.replica.assemble", CAT_HOST):
                    batch, sizes = wave.assemble()
                before = ex.compile_count
                t0 = self.clock.now()
                with tr.span("convserve.replica.put", CAT_HOST):
                    batch, sizes = jax.block_until_ready(
                        jax.device_put((batch, sizes))
                    )
                with tr.span("convserve.replica.compute", CAT_HOST):
                    y = jax.block_until_ready(ex(batch, sizes))
                with tr.span("convserve.replica.fetch", CAT_HOST):
                    y = np.asarray(y)
                dt = self.clock.now() - t0
                with tr.span("convserve.replica.crop", CAT_HOST):
                    outputs = wave.crop(self.spec, y)
            return WaveResult(
                wave=wave, outputs=outputs, replica=i, compute_s=dt,
                compiled=ex.compile_count > before,
            )
        finally:
            with self._lock:
                self.in_flight[i] -= 1

    def submit(self, wave: Wave) -> "Future[WaveResult]":
        """Run the wave on the least-loaded replica.  Returns a Future;
        with ``workers=0`` it is already completed (inline execution)."""
        i, ex = self._pick()
        if self._pool is None:
            fut: Future = Future()
            try:
                fut.set_result(self._run(i, ex, wave))
            except BaseException as e:  # mirror executor.submit semantics
                fut.set_exception(e)
            return fut
        return self._pool.submit(self._run, i, ex, wave)

    def run(self, wave: Wave) -> WaveResult:
        """Synchronous convenience wrapper."""
        return self.submit(wave).result()

    def swap(self, executors: Sequence, *, timeout_s: float = 5.0) -> list:
        """Atomically replace every replica's executor with `executors`
        (the hot-swap path).  Waits for all in-flight waves to drain on
        the OLD program first -- the drain check and the flip happen
        under the dispatch lock, so no wave can be picked between them.
        Returns the outgoing executors (the caller diffs their cache
        keys against the new ones to invalidate stale transforms).
        """
        new = list(executors)
        if len(new) != len(self.executors):
            raise ValueError(
                f"swap needs {len(self.executors)} executors, got {len(new)}"
            )
        for ex in new:
            if ex.cache is not self.cache:
                raise ValueError(
                    "swapped-in replicas must share the pool's KernelCache"
                )
            if ex.spec is not self.spec and ex.spec != self.spec:
                raise ValueError("swapped-in replicas must serve the same NetSpec")
        deadline = self.clock.now() + timeout_s
        while True:
            with self._lock:
                if sum(self.in_flight) == 0:
                    old = self.executors
                    self.executors = new
                    return old
            if self.clock.now() > deadline:
                raise TimeoutError(
                    f"in-flight waves did not drain within {timeout_s}s"
                )
            self.clock.sleep(0.001)

    def has_capacity(self) -> bool:
        """Whether a dispatched wave would start immediately.  The
        runtime gates wave formation on this: dispatching into a
        saturated pool would just move the queue somewhere batching
        can no longer reach it."""
        if self._pool is None:
            return True
        with self._lock:
            return sum(self.in_flight) < self.workers

    def warmup(self, buckets: Sequence[int],
               batch_sizes: Sequence[int]) -> None:
        """Compile every (bucket, batch size) program on EVERY replica
        and prepare the shared transforms, using all-padding waves
        (batch rows of extent 0 are fully masked, so warmup computes
        zeros and cannot affect any served output)."""
        c0 = self.spec.conv_layers()[0][1].c_in
        for ex in self.executors:
            for b in buckets:
                for s in batch_sizes:
                    x = np.zeros((s, b, b, c0), np.float32)
                    jax.block_until_ready(ex(x, np.zeros((s, 2), np.int32)))

    # ---------------------------------------------------------- stats

    def profile_stages(self, side: int, batch: int = 1) -> List[tuple]:
        """Per-stage wall times on replica 0 at a bucket geometry (the
        telemetry snapshot's stage rollup)."""
        c0 = self.spec.conv_layers()[0][1].c_in
        x = np.zeros((batch, side, side, c0), np.float32)
        return self.executors[0].profile_stages(x)

    def stats(self) -> dict:
        with self._lock:
            per_replica = {
                "dispatched": list(self.dispatched),
                "in_flight": list(self.in_flight),
            }
            executors = list(self.executors)
        return {
            "replicas": len(executors),
            "workers": self.workers,
            **per_replica,
            "compiled_programs": sum(
                ex.compile_count for ex in executors
            ),
            "cache": self.cache.stats(),
        }

    def shutdown(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True)
