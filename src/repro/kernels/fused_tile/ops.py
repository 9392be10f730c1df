"""Entry points of the parametric fused tile engine.

`conv2d_fused_tile` runs one transformed convolution through a
`TileKernelSpec` on the backend of choice:

  * ``xla``               -- the matrix path (`matrix_tile_conv`): the
                             same kernel math as three wide GEMMs, the
                             CPU fast path
  * ``pallas``            -- the on-chip task-loop kernel (`kernel.py`),
                             compiled (TPU and friends)
  * ``pallas_interpret``  -- the identical Pallas kernel in interpret
                             mode, so CPU CI executes the exact program
                             the accelerator runs

Backend resolution: explicit argument > ``REPRO_TILE_BACKEND`` env var >
``pallas`` on TPU, ``xla`` elsewhere.  The interpreter is refused on a
TPU: there it would stand in for the compiled kernel without a trace.
f64 inputs have no f32 basis matrices and raise `UnsupportedSpec`,
which the pipeline catches to fall back to the interpreting scan engine.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import registry, tiling, transforms
from repro.kernels.fused_tile import kernel as _kernel
from repro.kernels.fused_tile import matrix as _matrix
from repro.kernels.fused_tile.blocks import BlockConfig

_BACKENDS = ("xla", "pallas", "pallas_interpret")
_ENV_BACKEND = "REPRO_TILE_BACKEND"

class UnsupportedSpec(Exception):
    """The parametric engine cannot run this problem; callers fall back
    to the interpreting scan engine."""


def resolve_backend(backend: Optional[str] = None) -> str:
    b = backend or os.environ.get(_ENV_BACKEND)
    on_tpu = jax.default_backend() == "tpu"
    if b is None:
        return "pallas" if on_tpu else "xla"
    if b not in _BACKENDS + ("scan",):
        raise ValueError(f"unknown tile backend {b!r}, expected {_BACKENDS}")
    if on_tpu and b == "pallas_interpret":
        raise ValueError(
            "tile backend 'pallas_interpret' on a TPU: the interpreter "
            "would stand in for the compiled kernel"
        )
    return b


def engine_supported(transform: transforms.Transform, dtype) -> bool:
    """Can the parametric engine (any backend) run this family/dtype?"""
    if transform.kernel_spec() is None:
        return False
    # the f32 basis matrices would silently downgrade f64 precision
    return jnp.dtype(dtype) != jnp.float64


def conv2d_fused_tile(
    x: jnp.ndarray,
    w: Optional[jnp.ndarray],
    transform: transforms.Transform,
    *,
    pad: int = 0,
    blocks: Optional[BlockConfig] = None,
    wt: Optional[jnp.ndarray] = None,
    groups: int = 1,
    epilogue=None,
    backend: Optional[str] = None,
) -> jnp.ndarray:
    """NHWC fused transformed convolution through the parametric kernel.

    `wt` is the *family-native* transformed kernel (what
    `Transform.kernel_transform` returns and the kernel cache stores);
    packing into the engine's real mix layout happens here.  `epilogue`
    may be a `registry.ElementwiseOps` (folded into the kernel's scatter
    phase on the Pallas paths) or any elementwise callable (applied to
    output tiles on the matrix path, post-pass otherwise).
    """
    spec = transform.kernel_spec()
    if spec is None:
        raise UnsupportedSpec(f"{transform.family} has no TileKernelSpec")
    if jnp.dtype(x.dtype) == jnp.float64:
        raise UnsupportedSpec("f64 inputs: basis matrices are f32")
    b = resolve_backend(backend)
    if b == "scan":
        raise UnsupportedSpec("scan backend requested")
    if wt is None:
        wt = transform.kernel_transform(w)
    blocks = blocks or BlockConfig(r=24)

    plan = tiling.TilePlan.build(x.shape[1], x.shape[2], spec.k, pad, spec.t)

    if b == "xla":
        xp = tiling.pad_input(x, plan)
        y = _matrix.matrix_tile_conv(
            xp, spec.pack_rhs(wt, groups), plan, spec, groups=groups,
            epilogue=epilogue,
            chunk=blocks.chunk(),
        )
        return y.astype(x.dtype)

    # Pallas paths: align the column tile count to R * tasks_per_program
    # (extra zero columns, cropped after assembly) and lower the epilogue
    # to its kernel form.  R runs as whole sublane tiles.
    r = _kernel.row_block(min(blocks.r, plan.n_tiles_w))
    tpp = max(1, blocks.tasks_per_program)
    while plan.n_tiles_w < r * tpp and tpp > 1:
        tpp -= 1
    ext = _matrix.pallas_block_geometry(plan, r, tpp)
    run_plan = ext or plan
    xp = tiling.pad_input(x, run_plan)

    ep_ops: tuple = ()
    biases = None
    post = None
    if isinstance(epilogue, registry.ElementwiseOps):
        ep_ops, biases = epilogue.kernel_form()
    elif epilogue is not None:
        post = epilogue  # opaque callable: post-pass on assembled output
    planes = spec.pack_planes(wt, groups)
    if biases is None:
        biases = jnp.zeros((1, planes.shape[-1]), jnp.float32)

    y = _kernel.fused_tile_call(
        xp.astype(jnp.float32), planes, biases,
        spec=spec,
        n_tiles_h=run_plan.n_tiles_h,
        n_tiles_w=run_plan.n_tiles_w,
        r=r,
        tasks_per_program=tpp,
        ep_ops=ep_ops,
        interpret=(b == "pallas_interpret"),
    )
    y = y[:, : plan.h_out, : plan.w_out, :]
    if post is not None:
        y = post(y)
    return y.astype(x.dtype)
