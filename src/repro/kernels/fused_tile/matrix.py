"""XLA matrix path of the parametric tile kernel (the CPU fast path).

The exact same math as the Pallas kernel body -- forward basis GEMM,
batched channel mix, inverse basis GEMM, all from one `TileKernelSpec`
-- spelled as three wide GEMMs over the whole tile population instead of
a per-task grid.  On CPUs this is the fastest formulation we measured:
one (P*S, T^2) x (T^2, N*C) forward GEMM keeps Eigen at full rate where
separable per-axis transforms and per-task scans run an order of
magnitude below peak.

`chunk` bounds the transform-domain working set exactly like R*tasks
bound it in the on-chip kernel: tiles are processed in chunks of that
many (lax.map over equal chunks), which is what the block autotuner
trades off against per-chunk overhead on cache-constrained geometries.
Chunk 0 (the default) runs the whole population in one sweep.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import tiling, transforms
from repro.kernels.fused_tile.kernel import GEMM_PRECISION


def _mm(a, b):
    return jnp.matmul(a, b, precision=GEMM_PRECISION)


def _run_tiles(
    d: jnp.ndarray,  # (N, T*T, C) f32 flattened spatial tiles
    rhs: jnp.ndarray,  # (S, g, P*C/g, P*C'/g)
    kf: jnp.ndarray,  # (P*S, T*T)
    ki: jnp.ndarray,  # (T'^2, P*S)
    spec: transforms.TileKernelSpec,
    groups: int,
    epilogue,
) -> jnp.ndarray:
    """One sweep: (N, T*T, C) -> (N, T', T', C') output tiles."""
    n, _, c_in = d.shape
    t, t_out, p, s = spec.t, spec.t_out, spec.planes, spec.s_mix
    cgi = c_in // groups
    c_out = rhs.shape[1] * rhs.shape[3] // p
    cgo = c_out // groups

    t1 = d.transpose(1, 0, 2).reshape(t * t, n * c_in)
    u = _mm(kf, t1).reshape(p, s, n, groups, cgi)
    lhs = u.transpose(1, 3, 2, 0, 4).reshape(s, groups, n, p * cgi)
    mm = jnp.einsum(  # (S, g, N, P*C'/g)
        "sgnc,sgcd->sgnd", lhs, rhs, precision=GEMM_PRECISION
    )
    z = (
        mm.reshape(s, groups, n, p, cgo)
        .transpose(3, 0, 2, 1, 4)
        .reshape(p * s, n * c_out)
    )
    y = _mm(ki, z).reshape(t_out, t_out, n, c_out).transpose(2, 0, 1, 3)
    if epilogue is not None:
        # output tiles abut, so elementwise glue on tiles == on the
        # assembled output -- same contract as the task-scan engine
        y = epilogue(y)
    return y


def matrix_tile_conv(
    xp: jnp.ndarray,
    rhs: jnp.ndarray,
    plan: tiling.TilePlan,
    spec: transforms.TileKernelSpec,
    *,
    groups: int = 1,
    epilogue=None,
    chunk: int = 0,
) -> jnp.ndarray:
    """(B, H_pad, W_pad, C) padded input -> (B, H_out, W_out, C')."""
    batch = xp.shape[0]
    c_in = xp.shape[-1]
    t, t_out = spec.t, spec.t_out
    tiles = tiling.extract_tiles(xp, plan)  # (B, nH, nW, T, T, C)
    n = batch * plan.tiles_per_image
    d = tiles.reshape(n, t * t, c_in).astype(jnp.float32)

    if chunk and chunk < n:
        n_chunks = -(-n // chunk)
        n_pad = n_chunks * chunk
        if n_pad > n:
            d = jnp.concatenate(
                [d, jnp.zeros((n_pad - n, t * t, c_in), d.dtype)], axis=0
            )
        y = jax.lax.map(
            lambda blk: _run_tiles(blk, rhs, jnp.asarray(spec.fwd),
                                   jnp.asarray(spec.inv), spec, groups,
                                   epilogue),
            d.reshape(n_chunks, chunk, t * t, c_in),
        ).reshape(n_pad, t_out, t_out, -1)[:n]
    else:
        y = _run_tiles(
            d, rhs, jnp.asarray(spec.fwd), jnp.asarray(spec.inv), spec,
            groups, epilogue,
        )

    c_out = y.shape[-1]
    y6 = y.reshape(
        batch, plan.n_tiles_h, plan.n_tiles_w, t_out, t_out, c_out
    )
    return tiling.assemble_tiles(y6, plan)


def staged_matrix_fns(
    plan: tiling.TilePlan,
    spec: transforms.TileKernelSpec,
    groups: int = 1,
) -> Tuple:
    """The vendor three-stage structure through the same kernel math:
    stage 1 = gather + forward basis GEMM (materializes U), stage 2 =
    packed channel mix (materializes M), stage 3 = inverse basis GEMM +
    assembly.  Each stage runs over ALL tiles -- the materializing
    baseline the fused path is measured against -- yet all three consume
    the same `TileKernelSpec` as the fused kernel.

    Returned stage signatures mirror `pipeline.staged_stage_fns`:
    stage2 takes the *family-native* wt and packs it, so cached kernel
    transforms stay backend-agnostic.
    """
    t, t_out, p, s = spec.t, spec.t_out, spec.planes, spec.s_mix
    kf = jnp.asarray(spec.fwd)
    ki = jnp.asarray(spec.inv)

    def stage1(xp):
        tiles = tiling.extract_tiles(xp, plan)
        b = tiles.shape[0]
        c_in = tiles.shape[-1]
        n = b * plan.tiles_per_image
        d = tiles.reshape(n, t * t, c_in).astype(jnp.float32)
        u = _mm(kf, d.transpose(1, 0, 2).reshape(t * t, n * c_in))
        return u.reshape(p * s, n, c_in)  # transformed tiles, plane-major

    def stage2(u, wt):
        rhs = spec.pack_rhs(wt, groups)
        _, n, c_in = u.shape
        cgi = c_in // groups
        lhs = (
            u.reshape(p, s, n, groups, cgi)
            .transpose(1, 3, 2, 0, 4)
            .reshape(s, groups, n, p * cgi)
        )
        return jnp.einsum(
            "sgnc,sgcd->sgnd", lhs, rhs, precision=GEMM_PRECISION
        )

    def stage3(mm, batch):
        s_, g, n, pcgo = mm.shape
        cgo = pcgo // p
        c_out = g * cgo
        z = (
            mm.reshape(s, g, n, p, cgo)
            .transpose(3, 0, 2, 1, 4)
            .reshape(p * s, n * c_out)
        )
        y = _mm(ki, z).reshape(t_out, t_out, n, c_out).transpose(2, 0, 1, 3)
        y6 = y.reshape(
            batch, plan.n_tiles_h, plan.n_tiles_w, t_out, t_out, c_out
        )
        return tiling.assemble_tiles(y6, plan)

    return stage1, stage2, stage3


def pallas_block_geometry(
    plan: tiling.TilePlan, r: int, tasks_per_program: int
) -> Optional[tiling.TilePlan]:
    """Extended plan whose column tile count divides r*tasks_per_program
    (the Pallas grid requirement); None when already aligned."""
    span = r * max(1, tasks_per_program)
    n_tw = -(-plan.n_tiles_w // span) * span
    if n_tw == plan.n_tiles_w:
        return None
    return tiling.TilePlan(
        h=plan.h, w=plan.w, k=plan.k, pad=plan.pad, t=plan.t,
        t_out=plan.t_out, h_out=plan.h_out, w_out=plan.w_out,
        n_tiles_h=plan.n_tiles_h, n_tiles_w=n_tw,
        h_pad=plan.h_pad, w_pad=n_tw * plan.t_out + plan.k - 1,
    )
