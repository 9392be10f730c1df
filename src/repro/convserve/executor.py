"""Planned-net executor: a thin driver over the `ExecProgram` IR.

The net -- every stage in its planned algorithm plus the epilogue glue
lowered into it -- runs as ONE XLA program per concrete input shape, so
serving a bucket is a single dispatch.  The executor interprets nothing
per layer: `program.lower` already resolved the net into stages, each
stage's elementwise glue is folded into the owning algorithm's task loop
(`Algorithm.fuse_epilogue`), and fusion-group stages run whole chains of
convs through `Algorithm.execute_staged` without materializing the full
intermediate activation.  Pre-transformed kernels come from the
`KernelCache` and enter the program as arguments (not constants): a new
bucket shape recompiles the program but reuses the cached transforms,
and the cache counters are visible per-request because the fetch happens
outside the jit boundary.

Ragged batches: images smaller than their bucket ride in zero-padded.
Zero padding alone is NOT enough for correctness -- the first conv writes
nonzero values into the padded margin (its taps reach real pixels), and
later same-padded convs bleed those back across the true-image edge.  So
when per-sample extents are supplied, every stage re-zeroes everything
beyond each sample's true extent before handing to the next (`sizes` is
data, not shape: masking costs one compare+multiply and never
recompiles).  Inside a fusion group the intermediate masks are applied
tile-position-aware (the epilogue callables carry the super-tile's row
offset), so fused serving stays exact.  With true dims divisible by the
pool windows, pooling windows never straddle the mask edge, which makes
the padded run exactly equal to running each image unpadded.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import registry
from repro.convserve.cache import KernelCache, weights_fingerprint
from repro.convserve.graph import NetSpec
from repro.convserve.obs.trace import (
    CAT_HOST,
    CAT_PROFILE,
    CAT_STAGE,
    NULL_TRACER,
)
from repro.convserve.runtime.clock import Clock, RealClock
from repro.convserve.plan import NetPlan
from repro.convserve.program import EpilogueOp, ExecProgram, Stage, lower


def _mask_to_extent(
    x: jnp.ndarray, hs: jnp.ndarray, ws: jnp.ndarray, row0: int = 0
) -> jnp.ndarray:
    """Zero rows >= hs[b] and cols >= ws[b] of an NHWC batch.  `row0` is
    the global row offset of `x` when it is a super-tile of a larger
    tensor (fusion-group interiors)."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
    keep = (rows < hs[:, None, None, None]) & (cols < ws[:, None, None, None])
    return jnp.where(keep, x, jnp.zeros((), x.dtype))


def _split_epilogue(
    ops: Tuple[EpilogueOp, ...]
) -> Tuple[Tuple[EpilogueOp, ...], Tuple[EpilogueOp, ...]]:
    """(elementwise prefix, rest): the prefix folds into the algorithm's
    task loop; pools (and anything after them) run on assembled output."""
    for i, op in enumerate(ops):
        if not op.elementwise:
            return ops[:i], ops[i:]
    return ops, ()


class _Extent:
    """Traced per-sample true extents (ragged batches), or inert when the
    batch is dense.  Geometry updates mirror the ops applied."""

    def __init__(self, hs, ws):
        self.hs, self.ws = hs, ws

    @property
    def live(self) -> bool:
        return self.hs is not None

    def after_conv(self, spec) -> "_Extent":
        if not self.live:
            return self
        return _Extent(
            (self.hs + 2 * spec.pad - spec.k) // spec.stride + 1,
            (self.ws + 2 * spec.pad - spec.k) // spec.stride + 1,
        )

    def after_pool(self, window: int) -> "_Extent":
        if not self.live:
            return self
        return _Extent(self.hs // window, self.ws // window)

    def mask(self, x, row0: int = 0):
        if not self.live:
            return x
        with jax.named_scope("mask"):
            return _mask_to_extent(x, self.hs, self.ws, row0)


def _maxpool(x: jnp.ndarray, window: int) -> jnp.ndarray:
    b, h, w, c = x.shape
    v = window
    return x.reshape(b, h // v, v, w // v, v, c).max(axis=(2, 4))


class NetExecutor:
    """Runs a `NetSpec` lowered to an `ExecProgram` with cached kernel
    transforms."""

    def __init__(
        self,
        spec: NetSpec,
        weights: Dict[int, jnp.ndarray],
        plan: NetPlan,
        *,
        cache: Optional[KernelCache] = None,
        dtype=jnp.float32,
        clock: Optional[Clock] = None,
        tracer=None,
    ):
        missing = [i for i, _ in spec.param_layers() if i not in weights]
        if missing:
            raise ValueError(f"weights missing for parameter layers {missing}")
        # lower() validates plan-vs-spec coverage, geometry, and the
        # fusion groups' structural legality
        self.program: ExecProgram = lower(spec, plan)
        self.spec = spec
        self.plan = plan
        self.dtype = jnp.dtype(dtype)
        self.cache = cache if cache is not None else KernelCache()
        self.clock = clock or RealClock()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.weights = {i: jnp.asarray(w, dtype) for i, w in weights.items()}
        # hash once here, not per request: the fingerprint keys the cache
        # to these parameter values (shared caches stay collision-free)
        self._weights_fp = {
            i: weights_fingerprint(w) for i, w in self.weights.items()
        }
        self._plans = {p.layer: p for p in plan.layers}
        self._compiled: Dict[tuple, object] = {}
        self.calls = 0  # batches served through __call__
        self.images = 0  # batch rows served (padding rows included)

    @property
    def compile_count(self) -> int:
        """How many programs have been lowered (bounded by bucketing)."""
        return len(self._compiled)

    def compiles_by_bucket(self) -> Dict[int, int]:
        """Compiled-program count per spatial bucket (input H)."""
        out: Dict[int, int] = {}
        for shape, *_ in self._compiled:
            out[shape[1]] = out.get(shape[1], 0) + 1
        return out

    def cache_keys(self) -> list:
        """Every `KernelCache` key this executor's plan can touch (one
        per transform-consuming layer).  The hot-swap path diffs the
        outgoing and incoming executors' key sets to invalidate only
        what the new program no longer needs."""
        return [
            KernelCache.key(
                self.plan.net, p, self.dtype, self._weights_fp[i]
            )
            for i, p in self._plans.items()
            if registry.get(p.algo).consumes_wt
        ]

    def stats(self) -> dict:
        """Compile counts + kernel-cache counters, one dict -- the single
        source the engine and serving front-ends extend."""
        return {
            "compiled_programs": self.compile_count,
            "compiles_per_bucket": self.compiles_by_bucket(),
            "calls": self.calls,
            "images": self.images,
            "cache": self.cache.stats(),
        }

    # ------------------------------------------------------ stage driver

    def _elementwise_fn(self, ops: Tuple[EpilogueOp, ...], ws):
        """Fold bias/relu ops into a structured `registry.ElementwiseOps`
        (None when empty): still a plain ``y -> y`` callable, but fused
        algorithms can read its static op list and fold the glue into
        their kernel's scatter phase instead of a separate pass."""
        if not ops:
            return None
        return registry.ElementwiseOps(
            [
                ("bias", ws[op.layer]) if op.kind == "bias" else ("relu",)
                for op in ops
            ]
        )

    def _apply_tail(
        self, x, ops: Tuple[EpilogueOp, ...], ext: _Extent, ws
    ) -> Tuple[jnp.ndarray, _Extent]:
        """Pools and any post-pool elementwise ops, on assembled output.
        True dims divide the pool windows (validated at admission), so no
        window straddles the mask edge; masked stays masked garbage-free
        after the end-of-stage re-mask."""
        for op in ops:
            if op.kind == "maxpool":
                with jax.named_scope("pool"):
                    x = _maxpool(x, op.window)
                ext = ext.after_pool(op.window)
            elif op.kind == "bias":
                x = x + ws[op.layer]
            else:
                x = jax.nn.relu(x)
        return x, ext

    def _run_single(self, stage: Stage, x, ws, wts, ext: _Extent):
        u = stage.units[0]
        aplan = u.plan.algo_plan()
        alg = registry.get(aplan.algo)
        pre, tail = _split_epilogue(u.epilogue)
        runner = alg.fuse_epilogue(aplan, self._elementwise_fn(pre, ws))
        x = runner(x, ws[u.layer], wts.get(u.layer))
        ext = ext.after_conv(aplan.spec)
        x, ext = self._apply_tail(x, tail, ext, ws)
        return ext.mask(x), ext

    def _run_fused(self, stage: Stage, x, ws, wts, ext: _Extent):
        chain: List[registry.ChainLink] = []
        cur = ext
        tail_ops: Tuple[EpilogueOp, ...] = ()
        for j, u in enumerate(stage.units):
            aplan = u.plan.algo_plan()
            nxt = cur.after_conv(aplan.spec)
            last = j == len(stage.units) - 1
            pre, tail = _split_epilogue(u.epilogue)
            if last:
                tail_ops = tail
            # elementwise glue (bias/relu) folds into the owning
            # algorithm's task loop inside the chain, exactly as in a
            # single stage; only the position-dependent extent re-mask
            # (ragged batches) runs on the assembled intermediate --
            # tile-position-aware so the next conv of the chain never
            # taps across a true-image edge
            epi = (
                (lambda y, row0, _e=nxt: _e.mask(y, row0))
                if nxt.live and not last
                else None
            )
            chain.append(
                registry.ChainLink(
                    w=ws[u.layer], wt=wts.get(u.layer), plan=aplan,
                    epilogue=epi,
                    elementwise=self._elementwise_fn(pre, ws),
                )
            )
            cur = nxt
        alg = registry.get(stage.units[0].plan.algo)
        x = alg.execute_staged(x, chain, tile_rows=stage.tile_rows)
        x, cur = self._apply_tail(x, tail_ops, cur, ws)
        return cur.mask(x), cur

    def _forward(self, x, ws, wts, sizes):
        """The wave program.  Named scopes put `prologue`, each
        `stage<i>.<label>` and each `mask` and `pool` into the ops'
        metadata, for profiles and lowered text."""
        ext = _Extent(
            sizes[:, 0] if sizes is not None else None,
            sizes[:, 1] if sizes is not None else None,
        )
        with jax.named_scope("prologue"):
            x = ext.mask(x)
            if self.program.prologue:
                x, ext = self._apply_tail(x, self.program.prologue, ext, ws)
                x = ext.mask(x)
        for i, stage in enumerate(self.program.stages):
            run = self._run_fused if stage.fused else self._run_single
            with jax.named_scope(f"stage{i}.{stage.label}"):
                x, ext = run(stage, x, ws, wts, ext)
        return x

    # -------------------------------------------------------- public API

    def _fetch_transforms(self) -> Dict[int, jnp.ndarray]:
        """Per-request cache fetch: first request per layer transforms and
        stores; later requests (any bucket) count as hits.  The cache
        itself knows (via the registry) which algorithms have nothing to
        prepare and returns None for those."""
        wts = {}
        for i, _ in self.spec.conv_layers():
            wt = self.cache.get(
                self.plan.net, self._plans[i], self.weights[i], self.dtype,
                w_fp=self._weights_fp[i],
            )
            if wt is not None:
                wts[i] = wt
        return wts

    def _validate_call(self, x, sizes):
        if x.ndim != 4:
            raise ValueError(f"expected NHWC input, got shape {x.shape}")
        self.spec.infer_shapes(x.shape[1], x.shape[2], x.shape[3])  # validate
        if sizes is not None:
            sizes = jnp.asarray(sizes, jnp.int32)
            if sizes.shape != (x.shape[0], 2):
                raise ValueError(
                    f"sizes shape {sizes.shape} != ({x.shape[0]}, 2)"
                )
        return sizes

    def _program(self, x, sizes, mesh):
        """The jitted wave program for this batch shape: the whole net
        on one device, or -- with `mesh` -- one copy per device of the
        mesh's data axis, each running its rows of the wave (weights
        and transforms replicated).  Rows are independent, so the
        per-device program is the single-device one at a smaller batch,
        and every kernel runs whole on its own device."""
        key = (tuple(x.shape), sizes is not None, mesh)
        fn = self._compiled.get(key)
        if fn is None:
            fwd = self._forward
            if mesh is not None:
                rows = P("data")
                fwd = jax.shard_map(
                    fwd, mesh=mesh,
                    in_specs=(rows, P(), P(), None if sizes is None else rows),
                    out_specs=rows,
                    # Pallas outputs carry no varying-axes annotation
                    check_vma=False,
                )
            fn = jax.jit(fwd)
            self._compiled[key] = fn
        return fn

    def __call__(
        self, x: jnp.ndarray, sizes: Optional[jnp.ndarray] = None,
        *, mesh=None,
    ) -> jnp.ndarray:
        """Run one batch.

        x: (B, H, W, C); defines the bucket.  sizes: optional (B, 2) int32
        true (h, w) per sample for ragged batches -- samples are zeroed
        beyond their true extent stage by stage so padded serving is
        exact (see module docstring).  mesh: split the batch over the
        mesh's ``data`` axis, one shard per device (B must divide it).
        """
        x = jnp.asarray(x, self.dtype)
        sizes = self._validate_call(x, sizes)
        with self.tracer.span("convserve.exec.transforms", CAT_HOST):
            wts = self._fetch_transforms()
        with self.tracer.span("convserve.exec.launch", CAT_HOST):
            fn = self._program(x, sizes, mesh)
            self.calls += 1
            self.images += int(x.shape[0])
            return fn(x, self.weights, wts, sizes)

    def lower(self, x: jnp.ndarray, sizes: Optional[jnp.ndarray] = None,
              *, mesh=None):
        """The wave program for this batch, lowered but not compiled or
        run: `as_text()` is what the compiler receives."""
        x = jnp.asarray(x, self.dtype)
        sizes = self._validate_call(x, sizes)
        wts = self._fetch_transforms()
        return self._program(x, sizes, mesh).lower(
            x, self.weights, wts, sizes
        )

    def profile_stages(
        self, x: jnp.ndarray, sizes: Optional[jnp.ndarray] = None
    ) -> List[Tuple[str, float]]:
        """Per-stage wall times (seconds), each stage jitted and timed
        separately -- the benchmark surface; serving always runs the
        whole net as one program."""
        x = jnp.asarray(x, self.dtype)
        sizes = self._validate_call(x, sizes)
        wts = self._fetch_transforms()
        b_h, b_w, b_c = int(x.shape[1]), int(x.shape[2]), int(x.shape[3])
        ext0 = _Extent(
            sizes[:, 0] if sizes is not None else None,
            sizes[:, 1] if sizes is not None else None,
        )
        x = ext0.mask(x)
        if self.program.prologue:  # mirror _forward: pre-conv glue first
            x, ext0 = self._apply_tail(
                x, self.program.prologue, ext0, self.weights
            )
            x = ext0.mask(x)
        x = jax.block_until_ready(x)
        rows: List[Tuple[str, float]] = []
        tr = self.tracer
        with tr.span(
            "profile_stages", CAT_PROFILE,
            net=self.plan.net, bucket=b_h, batch=int(x.shape[0]),
        ):
            for stage in self.program.stages:
                run = self._run_fused if stage.fused else self._run_single

                def step(x, ws, wts, hs, ws_cols, _run=run, _stage=stage):
                    y, ext = _run(_stage, x, ws, wts, _Extent(hs, ws_cols))
                    return y, ext.hs, ext.ws

                fn = jax.jit(step)
                args = (x, self.weights, wts, ext0.hs, ext0.ws)
                with tr.span(
                    f"stage:{stage.label}", CAT_STAGE,
                    stage=stage.label, fused=stage.fused,
                ):
                    jax.block_until_ready(fn(*args))  # compile untimed
                    t0 = self.clock.now()
                    y, hs, ws_cols = fn(*args)
                    x = jax.block_until_ready(y)
                    dt = self.clock.now() - t0
                    rows.append((stage.label, dt))
                ext0 = _Extent(hs, ws_cols)
        want = self.spec.out_shape(b_h, b_w, b_c)
        if tuple(x.shape[1:]) != want:
            raise AssertionError(
                f"profiled stage chain produced {tuple(x.shape[1:])}, net "
                f"expects {want} -- stage driver out of sync with _forward"
            )
        return rows
