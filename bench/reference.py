"""What every net's plain reference shares, and its control.

Nothing here imports the program.  A configuration's reference is its
net module's `forward` (`bench/nets/`) in straightforward `jax.numpy`;
each of its products is `conv` here, XLA's direct convolution at
`highest` matmul precision, so that a float32 convolution on a TPU is a
float32 convolution.  The weights are made by the net module from the
seed (`seed_key`), and handed to both the program and the reference.

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
import json

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


def conv(x, w, lay: dict, precision: str):
    """An NHWC by HWIO convolution with zero padding, at `precision`.
    `lay` gives its geometry: k (3), pad (k // 2), stride (1) and
    groups (1)."""
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


@functools.lru_cache(maxsize=None)
def _jitted(net, cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda weights, x: net.forward(cfg, weights, x, precision))


def jitted(net, cfg: dict, precision: str):
    """The net module's `forward` jitted with the configuration and the
    precision closed over: one compiled function per configuration and
    precision, called as `fn(weights, x)`.  A configuration holds
    nested dicts, so it is keyed by its JSON text."""
    return _jitted(net, json.dumps(cfg, sort_keys=True), precision)


def run(net, cfg: dict, weights: dict, images: list,
        precision: str = "highest", block: int = 8) -> list:
    """The reference's output for each image (a list of HWC arrays, any
    sizes), computed in blocks of `block` equal-sized images; a short
    block is padded with zero images so that each size compiles once."""
    fn = jitted(net, cfg, precision)
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
            y = np.asarray(fn(weights, jnp.asarray(x)))
            for r, j in enumerate(part):
                out[j] = y[r]
    return out


def rel_err(y: np.ndarray, ref: np.ndarray) -> float:
    """max |y - ref| / max |ref|: the error of one answer."""
    return float(np.abs(y - ref).max() / np.abs(ref).max())
