"""The plain reference of a configuration, its weights, and its control.

Nothing here imports the program.  The reference is the net's forward
pass in straightforward `jax.numpy`: XLA's direct convolution with
"same" zero padding, a per-channel bias, ReLU, and 2x2 max-pooling, computed at `highest`
matmul precision so that a float32 convolution on a TPU is a float32
convolution.  The weights are made here, from the seed, and handed to
both the program and the reference.

`precision` selects the arithmetic of the convolutions:

    "highest"  float32 products, float32 sums (the reference)
    "high"     three bfloat16 passes (hi*hi + hi*lo + lo*hi), the split
               that XLA's `high` precision makes on a TPU -- the control
               for a configuration stated at float32 `highest`
    "bf16"     one bfloat16 pass, float32 sums (XLA's default on a TPU)

The lower precisions are spelled out as explicit bfloat16 splits, so a
control reads the same on every backend.  The split rounds by integer
arithmetic on the bits: XLA may drop a float32 -> bfloat16 -> float32
round trip as excess precision, and on a TPU it does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size: JAX keeps only 32 bits of a
    plain integer seed, so the seed is hashed into two words first."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


# standard deviation of the bias vectors
BIAS_STD = 0.1


def weight_shapes(layers: list) -> dict:
    """Shape of every layer's weights, keyed by layer index: HWIO for a
    conv's kernel, (C,) for a bias vector."""
    shapes = {}
    for i, lay in enumerate(layers):
        if lay["kind"] == "conv":
            shapes[i] = (lay.get("k", 3), lay.get("k", 3),
                         lay["c_in"] // lay.get("groups", 1), lay["c_out"])
        elif lay["kind"] == "bias":
            shapes[i] = (lay["c"],)
    return shapes


def make_weights(layers: list, seed: int) -> dict:
    """He-normal HWIO kernels and normal bias vectors (std BIAS_STD)
    from the seed, made on the device in one jitted call, in float32."""
    shapes = weight_shapes(layers)

    @jax.jit
    def init(key):
        out = {}
        for i, shp in shapes.items():
            std = (BIAS_STD if len(shp) == 1
                   else (2.0 / (shp[0] * shp[1] * shp[2])) ** 0.5)
            out[i] = std * jax.random.normal(
                jax.random.fold_in(key, i), shp, jnp.float32)
        return out

    return init(seed_key(seed))


def _round_bf16(a):
    """float32 rounded to the nearest bfloat16 (ties to even), kept in
    float32: the low 16 bits of the word cleared after rounding."""
    u = jax.lax.bitcast_convert_type(a, jnp.uint32)
    u = u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(u & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split(a):
    hi = _round_bf16(a)
    return hi, _round_bf16(a - hi)


def _conv(x, w, lay, precision):
    k = lay.get("k", 3)
    pad = lay.get("pad", k // 2)
    s = lay.get("stride", 1)

    def c(a, b):
        return jax.lax.conv_general_dilated(
            a, b, (s, s), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=lay.get("groups", 1),
            precision=jax.lax.Precision.HIGHEST,
        )

    if precision == "highest":
        return c(x, w)
    xh, xl = _split(x)
    wh, wl = _split(w)
    if precision == "high":
        return c(xh, wh) + (c(xh, wl) + c(xl, wh))
    if precision == "bf16":
        return c(xh, wh)
    raise ValueError(f"unknown precision {precision!r}")


def forward(layers: list, weights: dict, x, precision: str = "highest"):
    """The net on an NHWC batch of equal-sized images."""
    for i, lay in enumerate(layers):
        kind = lay["kind"]
        if kind == "conv":
            x = _conv(x, weights[i], lay, precision)
        elif kind == "bias":
            x = x + weights[i]
        elif kind == "relu":
            x = jnp.maximum(x, 0.0)
        elif kind == "maxpool":
            b, h, w, c = x.shape
            v = lay.get("window", 2)
            x = x.reshape(b, h // v, v, w // v, v, c).max(axis=(2, 4))
        else:
            raise ValueError(f"layer {i}: unknown kind {kind!r}")
    return x


@functools.lru_cache(maxsize=None)
def jitted(precision: str):
    """`forward` jitted, with the layers (frozen) and precision static."""
    return jax.jit(forward, static_argnums=(0, 3))


def run(layers: list, weights: dict, images: list, precision: str = "highest",
        block: int = 8) -> list:
    """The reference's output for each image (a list of HWC arrays, any
    sizes), computed in blocks of `block` equal-sized images; a short
    block is padded with zero images so that each size compiles once."""
    key = freeze(layers)
    fn = jitted(precision)
    out = [None] * len(images)
    by_side = {}
    for j, im in enumerate(images):
        by_side.setdefault(im.shape, []).append(j)
    for shape, idx in sorted(by_side.items()):
        for lo in range(0, len(idx), block):
            part = idx[lo:lo + block]
            x = np.zeros((block,) + shape, np.float32)
            for r, j in enumerate(part):
                x[r] = images[j]
            y = np.asarray(fn(key, weights, jnp.asarray(x), precision))
            for r, j in enumerate(part):
                out[j] = y[r]
    return out


class _Frozen(tuple):
    """Layers as a hashable static argument that still reads as a list
    of dicts."""

    def __new__(cls, layers):
        return super().__new__(cls, tuple(tuple(sorted(d.items()))
                                          for d in layers))

    def __iter__(self):
        return (dict(items) for items in tuple.__iter__(self))


def freeze(layers: list) -> _Frozen:
    return _Frozen(layers)


def rel_err(y: np.ndarray, ref: np.ndarray) -> float:
    """max |y - ref| / max |ref|: the error of one answer."""
    return float(np.abs(y - ref).max() / np.abs(ref).max())
