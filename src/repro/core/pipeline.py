"""The transform-generic tile-pipeline engine.

One engine, every transform family.  `fused.py`, `three_stage.py`,
`fft_conv.py` and the Pallas wrapper used to each hand-roll their own
OLA gather -> transform -> matmul -> inverse -> scatter loop; this module
is the single implementation they all drive with a `Transform` object
(core.transforms) instead of inlined math:

  * `fused_tile_conv` -- the paper's L3-fused task structure: a
    `lax.scan` over tasks of R tiles, each task gathering, forward-
    transforming, channel-mixing against the stationary right-hand
    matrices, inverse-transforming, and (optionally) running the fused
    elementwise epilogue while the tiles are still task-resident.  The
    per-task working set follows the shared-buffer layout accounting of
    `core.sharedbuf` (`shared_buffer_plan`); the R bound the planner
    derives from it is family-exact through `TileAlgebra`.
  * `staged_tile_conv` -- the vendor 3-stage structure: every stage runs
    over ALL tiles before the next begins, materializing the transformed
    tensors (what DNNL/ZNN/LIBXSMM do, and the paper's baseline).
    `staged_stage_fns` exposes the three stages separately for honest
    stage-boundary benchmarking.

Grouped convolutions are handled once, here, for every family: tiles are
gathered with full channel width and the channel mix runs block-diagonal
(`Transform.multiply(groups=...)`), so registering a transform family
never re-implements groups.

`TransformedAlgorithm` is the registry face of the engine: a shared
plan/prepare/execute/fuse_epilogue lifecycle parameterized only by a
transform factory, so a concrete algorithm (`l3_fused`, `fft_fused`,
`three_stage`) is little more than a family + tier declaration.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import analysis, registry, tiling, transforms
from repro.core.sharedbuf import SharedBufferPlan


def _tile_offsets(plan: tiling.TilePlan, batch: int) -> np.ndarray:
    """(N_tile, 3) int32: (batch, row0, col0) of every input tile, flat order."""
    b_idx, h_idx, w_idx = np.meshgrid(
        np.arange(batch),
        np.arange(plan.n_tiles_h) * plan.t_out,
        np.arange(plan.n_tiles_w) * plan.t_out,
        indexing="ij",
    )
    return np.stack(
        [b_idx.ravel(), h_idx.ravel(), w_idx.ravel()], axis=1
    ).astype(np.int32)


def _gather_tiles(x_padded: jnp.ndarray, offsets: jnp.ndarray, t: int) -> jnp.ndarray:
    """Gather R overlapping (T, T, C) tiles given (R, 3) offsets."""

    def one(off):
        return jax.lax.dynamic_slice(
            x_padded,
            (off[0], off[1], off[2], 0),
            (1, t, t, x_padded.shape[3]),
        )[0]

    return jax.vmap(one)(offsets)  # (R, T, T, C)


def _assemble(y_tiles, plan: tiling.TilePlan, batch: int, n_tile: int, dtype):
    """(n_pad, T', T', C') task output -> assembled, cropped NHWC output."""
    c_out = y_tiles.shape[-1]
    y_tiles = y_tiles.reshape(-1, plan.t_out, plan.t_out, c_out)[:n_tile]
    y_tiles = y_tiles.reshape(
        batch, plan.n_tiles_h, plan.n_tiles_w, plan.t_out, plan.t_out, c_out
    )
    return tiling.assemble_tiles(y_tiles, plan).astype(dtype)


def shared_buffer_plan(
    transform: transforms.Transform, r: int, c_in: int, c_out: int
) -> SharedBufferPlan:
    """The paper-S4.2 shared-buffer layout of one task's working set, in
    the transform's own domain (rfft half-spectrum, complex width for
    FFT).  The Pallas kernel materializes this layout in VMEM; the
    analytic R bound (`analysis.max_r_ta`) prices it."""
    ta = transform.algebra
    return SharedBufferPlan(
        r=r, c_in=c_in, c_out=c_out,
        t2=ta.domain_points, elem_bytes=ta.elem_bytes,
    )


def fused_tile_conv(
    x: jnp.ndarray,
    w: Optional[jnp.ndarray],
    transform: transforms.Transform,
    *,
    pad: int = 0,
    r_tiles: int = 24,
    wt: Optional[jnp.ndarray] = None,
    groups: int = 1,
    epilogue=None,
    blocks=None,
    backend: Optional[str] = None,
) -> jnp.ndarray:
    """NHWC L3-fused transformed convolution, any transform family.

    Dispatches to the parametric tile engine (`repro.kernels.fused_tile`)
    whenever the family lowers to a `TileKernelSpec`: the same gather ->
    forward GEMM -> batched mix -> inverse GEMM -> scatter program either
    as a Pallas kernel (TPU / interpret) or as the XLA matrix path (CPU).
    Families without a spec -- and f64 inputs, whose basis matrices would
    lose precision in f32 -- run the interpreting `scan_tile_conv` below.

    `blocks` (a `kernels.fused_tile.BlockConfig`) carries the autotuned
    block shape; `r_tiles` alone seeds an unchunked default.  `backend`
    overrides the engine backend (see `fused_tile.resolve_backend`).
    """
    from repro.kernels import fused_tile as _ft  # deferred: jax warm-up

    b = _ft.resolve_backend(backend)
    if b != "scan" and _ft.engine_supported(transform, x.dtype):
        try:
            return _ft.conv2d_fused_tile(
                x, w, transform,
                pad=pad,
                blocks=blocks or _ft.BlockConfig(r=int(r_tiles)),
                wt=wt, groups=groups, epilogue=epilogue, backend=b,
            )
        except _ft.UnsupportedSpec:
            pass
    return scan_tile_conv(
        x, w, transform,
        pad=pad, r_tiles=r_tiles, wt=wt, groups=groups, epilogue=epilogue,
    )


def scan_tile_conv(
    x: jnp.ndarray,
    w: Optional[jnp.ndarray],
    transform: transforms.Transform,
    *,
    pad: int = 0,
    r_tiles: int = 24,
    wt: Optional[jnp.ndarray] = None,
    groups: int = 1,
    epilogue=None,
) -> jnp.ndarray:
    """The interpreting task-scan engine (the oracle the parametric
    kernel is tested against, and the fallback for families/dtypes it
    cannot lower).

    Tiles are processed in N_task = ceil(N_tile / R) independent tasks;
    each task's intermediates stay in fast private memory while the
    right-hand matrices -- re-read by every task -- stay hot in the fast
    shared level (the paper's contribution).  `epilogue`, when given, is
    an elementwise callable applied to each task's (R, T', T', C') output
    tiles inside the scan: output tiles abut, so this equals applying it
    to the assembled output, but the glue runs on task-resident data.
    """
    t = transform.t
    plan = tiling.TilePlan.build(x.shape[1], x.shape[2], transform.k, pad, t)
    if wt is None:
        wt = transform.kernel_transform(w)
    batch = x.shape[0]

    xp = tiling.pad_input(x, plan)
    n_tile = plan.n_tiles(batch)
    r = min(r_tiles, n_tile)
    n_task = -(-n_tile // r)
    n_pad = n_task * r

    offsets = _tile_offsets(plan, batch)
    if n_pad > n_tile:  # pad the task list by repeating the last tile
        offsets = np.concatenate(
            [offsets, np.repeat(offsets[-1:], n_pad - n_tile, axis=0)], axis=0
        )
    offsets = jnp.asarray(offsets).reshape(n_task, r, 3)

    def task(carry, off_r):
        tiles = _gather_tiles(xp, off_r, t)  # (R, T, T, C)
        u = transform.forward(tiles)  # step 1: basis change
        # the declared compute domain is a checked contract: the
        # working-set algebra (elem_bytes) and the cached right-hand
        # matrices' dtype both key off it, so a transform whose forward
        # diverges from its declaration must fail here, at trace time
        assert u.dtype == transform.domain_dtype(x.dtype), (
            f"{transform.family} forward produced {u.dtype}, "
            f"declared domain {transform.domain_dtype(x.dtype)}"
        )
        mm = transform.multiply(u, wt, groups)  # step 2: channel mix
        y = transform.inverse(mm)  # step 3: back to (R, T', T', C')
        if epilogue is not None:
            y = epilogue(y)
        return carry, y

    _, y_tiles = jax.lax.scan(task, jnp.zeros((), x.dtype), offsets)
    return _assemble(y_tiles, plan, batch, n_tile, x.dtype)


def staged_stage_fns(
    transform: transforms.Transform,
    plan: tiling.TilePlan,
    groups: int = 1,
):
    """The three materializing stages as separate callables.

    stage 1: padded input -> all transformed tiles (N_tile, domain, C)
    stage 2: channel mix against the right-hand matrices
    stage 3: inverse transform + assembly -> (B, H', W', C')

    Used whole by `staged_tile_conv` and separately jitted by
    `ThreeStageStaged` so U and M demonstrably round-trip main memory at
    stage boundaries, mirroring the vendor libraries.
    """

    def stage1(xp):
        tiles = tiling.extract_tiles(xp, plan)  # (B, nH, nW, T, T, C)
        b = tiles.shape[0]
        tiles = tiles.reshape(
            b * plan.tiles_per_image, plan.t, plan.t, tiles.shape[-1]
        )
        return transform.forward(tiles)

    def stage2(u, wt):
        return transform.multiply(u, wt, groups)

    def stage3(mm, batch):
        y_tiles = transform.inverse(mm)  # (N_tile, T', T', C')
        n_tile = y_tiles.shape[0]
        return _assemble(y_tiles, plan, batch, n_tile, y_tiles.dtype)

    return stage1, stage2, stage3


def staged_tile_conv(
    x: jnp.ndarray,
    w: Optional[jnp.ndarray],
    transform: transforms.Transform,
    *,
    pad: int = 0,
    wt: Optional[jnp.ndarray] = None,
    groups: int = 1,
) -> jnp.ndarray:
    """The non-fused 3-stage structure (each stage over ALL tiles),
    single-jit form."""
    plan = tiling.TilePlan.build(
        x.shape[1], x.shape[2], transform.k, pad, transform.t
    )
    if wt is None:
        wt = transform.kernel_transform(w)
    s1, s2, s3 = staged_stage_fns(transform, plan, groups)
    xp = tiling.pad_input(x, plan)
    return s3(s2(s1(xp), wt), x.shape[0]).astype(x.dtype)


# ------------------------------------------------------------------------
# Registry face: the shared lifecycle of every transformed algorithm.
# ------------------------------------------------------------------------


def resolve_r(
    spec: registry.ConvSpec,
    hw: analysis.HardwareModel,
    transform: transforms.Transform,
    *,
    hints,
    tune_r: bool = False,
    wisdom_path=None,
):
    """R for a transformed plan: explicit hint > measured (tune_r) >
    wisdom-file lookup > analytic prediction.  Wisdom entries are keyed
    by transform family + tile size + geometry, so Winograd-R and FFT-T
    tunes for the same layer never collide.  Returns (r, tuned) where
    `tuned` marks an R that came from measurement (fresh or cached in
    the wisdom file) rather than the model."""
    from repro.core import tune  # deferred: tune times this module's conv

    r_hint = hints.get("r_tiles")
    if r_hint is not None:
        return int(r_hint), False
    if tune_r:
        r = tune.tuned_r(
            spec.h, spec.w, spec.c_in, spec.c_out,
            transform=transform, wisdom_path=wisdom_path,
        )
        return int(r), True
    r = tune.lookup_r(
        spec.h, spec.w, spec.c_in, spec.c_out,
        transform=transform, wisdom_path=wisdom_path,
    )
    if r is not None:
        # clamp a wisdom R measured elsewhere into this hw's feasible range
        r_max = analysis.max_r_ta(hw, spec.c_in, spec.c_out, transform.algebra)
        return (max(1, min(int(r), r_max)) if r_max >= 1 else int(r)), True
    return (
        tune.predict_r(spec.c_in, spec.c_out, transform=transform, hw=hw),
        False,
    )


class TransformedAlgorithm(registry.Algorithm):
    """Base class for algorithms realized by the shared tile engine.

    A subclass declares its transform family (`make_transform` + the
    name of its tile-size param) and its registry identity; planning,
    weight pre-transforms, execution, grouped support, stride-decimation
    and in-task epilogue fusion are all inherited.  `execute_staged`
    (cross-layer fusion groups) comes from `registry.Algorithm` and is
    generic over any engine-backed execute, which makes every transform
    family a first-class fusion-group citizen.
    """

    consumes_wt = True
    tile_param: str = ""  # "m" (Winograd) or "t_fft" (FFT)
    default_tile: int = 0  # default value of that param
    r_floor_base: int = 8  # family floor on a useful task width

    def make_transform(
        self, spec: registry.ConvSpec, params
    ) -> transforms.Transform:
        """The family's Transform at this plan's tile size."""
        raise NotImplementedError

    def supports(self, spec: registry.ConvSpec) -> bool:
        # the engine handles stride (decimation), groups (block-diagonal
        # mix) and ragged geometry for every family; dtype domains may
        # narrow this in subclasses.  Temporal (1-D causal) specs have
        # left-only pad semantics outside the 2-D tiling engine.
        return not spec.temporal

    def r_floor(self, hw: analysis.HardwareModel) -> int:
        return max(self.r_floor_base, analysis.min_r(hw) // 2)

    def plan(self, spec, hw, *, hints=None, tune_r=False, wisdom_path=None):
        hints = hints or {}
        tile = int(hints.get(self.tile_param) or self.default_tile)
        params = {self.tile_param: tile}
        tr = self.make_transform(spec, params)
        r, tuned = resolve_r(
            spec, hw, tr, hints=hints, tune_r=tune_r, wisdom_path=wisdom_path
        )
        ta = tr.algebra
        util = analysis.predicted_utilization(
            hw, r, spec.c_in, spec.c_out, ta.t, ta.t_out, ta.alpha,
            spec.groups,
        )
        params = {**params, "r_tiles": int(r)}
        from repro.core import tune  # deferred: tune times this module

        blocks = tune.lookup_blocks(
            spec.h, spec.w, spec.c_in, spec.c_out,
            transform=tr, wisdom_path=wisdom_path,
        )
        if blocks is not None:
            params["blocks"] = blocks.to_wisdom()
        cost = registry.fused_auto_cost(
            spec, hw, ta, self.r_floor(hw), blocks=blocks
        )
        if hw.tile_backend == "pallas" and not self._kernel_fits(
            tr, spec, r, blocks
        ):
            cost = math.inf  # the compiled kernel cannot hold this layer
        return registry.AlgoPlan(
            self.name, spec, params,
            predicted_util=util, cost=cost, tuned=tuned,
        )

    @staticmethod
    def _kernel_fits(tr: transforms.Transform, spec, r: int, blocks) -> bool:
        """Whether the Pallas tile kernel can run this layer at the blocks
        it will be launched with (`blocks` from wisdom, else R tiles and
        one task per program): its channel counts must fill one partial
        or whole lane tiles, and its blocks (stationary right-hand
        matrices above all) must fit the kernel's VMEM budget.  Families
        without a kernel spec run the scan engine and always fit."""
        from repro.kernels.fused_tile import kernel as _kernel

        ks = tr.kernel_spec()
        if ks is None:
            return True
        if blocks is not None:
            r, tpp = blocks.r, max(1, blocks.tasks_per_program)
        else:
            tpp = 1
        return _kernel.kernel_fits(ks, spec.c_in, spec.c_out, r, tpp)

    def tile_algebra(self, plan: registry.AlgoPlan):
        return self.make_transform(plan.spec, plan.params).algebra

    def prepare_weights(self, w, plan):
        if self.tile_param not in plan.params:
            raise ValueError(
                f"{self.name} plan without {self.tile_param}: {plan.params}"
            )
        return self.make_transform(plan.spec, plan.params).kernel_transform(w)

    def _run(self, x, w, wt, plan, epilogue):
        tr = self.make_transform(plan.spec, plan.params)
        blocks = None
        if "blocks" in plan.params:
            from repro.kernels.fused_tile import BlockConfig

            blocks = BlockConfig.from_wisdom(plan.params["blocks"])
        return fused_tile_conv(
            x, w, tr,
            pad=plan.spec.pad,
            r_tiles=int(plan.params.get("r_tiles", 24)),
            wt=wt,
            groups=plan.spec.groups,
            epilogue=epilogue,
            blocks=blocks,
        )

    def execute(self, x, w, wt, plan):
        return registry.decimate(
            self._run(x, w, wt, plan, None), plan.spec.stride
        )

    def fuse_epilogue(self, plan, epilogue):
        # fold the elementwise glue into the task scan: it runs on the
        # (R, T', T', C') tiles while they are still task-resident,
        # instead of as a separate pass over the assembled output
        def run(x, w, wt):
            return registry.decimate(
                self._run(x, w, wt, plan, epilogue), plan.spec.stride
            )

        return run
