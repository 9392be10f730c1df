"""Pallas kernel: fused depthwise-causal conv1d + bias + SiLU (Mamba short conv).

The Mamba2 conv (K=4, depthwise) is memory-bound: 2K FLOPs per loaded
element against a TPU CMR of ~240.  Winograd gains nothing here (depthwise
convs have no C x C' product to amortise transforms over -- DESIGN.md S5);
what the paper's *fusion* insight buys is (a) the taps + bias stationary in
VMEM via a constant index map and (b) conv + bias + SiLU fused into one
HBM pass instead of three.

Grid: (batch, seq_blocks).  The input block overlaps by K-1 (pl.Element
dims, stride Lb, extent Lb + K - 1) on a front-padded sequence -- the same
overlap-add structure as the 2-D kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _body(x_ref, w_ref, b_ref, o_ref, *, k: int, lb: int, activation: str):
    xblk = x_ref[0].astype(jnp.float32)  # (Lb + K - 1, D)
    w = w_ref[...].astype(jnp.float32)  # (K, D)
    acc = jnp.zeros((lb, xblk.shape[1]), jnp.float32)
    for i in range(k):  # K is tiny; unrolled shifted MACs
        acc = acc + xblk[i : i + lb, :] * w[i]
    acc = acc + b_ref[...].astype(jnp.float32)
    if activation == "silu":
        acc = acc * jax.nn.sigmoid(acc)
    o_ref[0] = acc.astype(o_ref.dtype)


def conv1d_fused_call(
    xp: jnp.ndarray,
    w: jnp.ndarray,
    b: jnp.ndarray,
    *,
    lb: int,
    activation: str = "silu",
    interpret: bool = True,
) -> jnp.ndarray:
    """xp: (B, L + K - 1, D) front-padded input; w: (K, D); b: (D,) -> (B, L, D)."""
    bsz, lpad, d = xp.shape
    k = w.shape[0]
    l = lpad - (k - 1)
    assert l % lb == 0, (l, lb)
    body = functools.partial(_body, k=k, lb=lb, activation=activation)
    # overlapping (element-indexed) input blocks along the sequence
    in_spec = pl.BlockSpec(
        (1, pl.Element(lb + k - 1), d), lambda bi, li: (bi, li * lb, 0)
    )
    return pl.pallas_call(
        body,
        grid=(bsz, l // lb),
        in_specs=[
            in_spec,
            # stationary taps + bias (constant index maps)
            pl.BlockSpec((k, d), lambda bi, li: (0, 0)),
            pl.BlockSpec((d,), lambda bi, li: (0,)),
        ],
        out_specs=pl.BlockSpec((1, lb, d), lambda bi, li: (bi, li, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, l, d), xp.dtype),
        interpret=interpret,
    )(xp, w, b)
