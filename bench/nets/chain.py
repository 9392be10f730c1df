"""A chain of layers, listed one by one under the configuration's
`"layers"`: the default net module.

Each layer is a dict with a `kind`:

    conv      c_in, c_out; k (3), stride (1), pad (k // 2), groups (1)
    bias      c: a per-channel bias vector
    relu
    maxpool   window (2)

Weights are keyed by layer index: an HWIO kernel for a conv, (C,) for a
bias.  The reference is XLA's direct convolution with zero padding, the
bias, ReLU and non-overlapping max-pooling.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference


def input_channels(cfg: dict) -> int:
    return cfg["layers"][0]["c_in"]


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


def make_weights(cfg: dict, seed: int) -> dict:
    """He-normal HWIO kernels and normal bias vectors (std
    `reference.BIAS_STD`) from the seed, made on the device in one
    jitted call, in float32."""
    shapes = weight_shapes(cfg["layers"])

    @jax.jit
    def init(key):
        out = {}
        for i, shp in shapes.items():
            std = (reference.BIAS_STD if len(shp) == 1
                   else (2.0 / (shp[0] * shp[1] * shp[2])) ** 0.5)
            out[i] = std * jax.random.normal(
                jax.random.fold_in(key, i), shp, jnp.float32)
        return out

    return init(reference.seed_key(seed))


def forward(cfg: dict, weights: dict, x, precision: str = "highest"):
    """The net on an NHWC batch of equal-sized images."""
    for i, lay in enumerate(cfg["layers"]):
        kind = lay["kind"]
        if kind == "conv":
            x = reference.conv(x, weights[i], lay, precision)
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


def netspec(cfg: dict):
    """The configuration as the program's `NetSpec`."""
    from repro.convserve.graph import NetSpec, bias, conv, maxpool, relu

    layers = []
    for lay in cfg["layers"]:
        if lay["kind"] == "conv":
            k = lay.get("k", 3)
            layers.append(conv(lay["c_in"], lay["c_out"], k=k,
                               pad=lay.get("pad", k // 2),
                               stride=lay.get("stride", 1),
                               groups=lay.get("groups", 1)))
        elif lay["kind"] == "bias":
            layers.append(bias(lay["c"]))
        elif lay["kind"] == "relu":
            layers.append(relu())
        elif lay["kind"] == "maxpool":
            layers.append(maxpool(lay.get("window", 2)))
        else:
            raise ValueError(f"unknown layer kind {lay['kind']!r}")
    return NetSpec(name=cfg["name"], layers=tuple(layers))


def conv_shapes(cfg: dict, side: int) -> list:
    """Each conv layer's geometry at a square input of `side` pixels:
    dicts with h, w (input), ho, wo (output), c_in, c_out, k, groups."""
    out = []
    h = w = side
    for lay in cfg["layers"]:
        kind = lay["kind"]
        if kind == "conv":
            k = lay.get("k", 3)
            pad = lay.get("pad", k // 2)
            s = lay.get("stride", 1)
            ho = (h + 2 * pad - k) // s + 1
            wo = (w + 2 * pad - k) // s + 1
            out.append({
                "h": h, "w": w, "ho": ho, "wo": wo, "c_in": lay["c_in"],
                "c_out": lay["c_out"], "k": k,
                "groups": lay.get("groups", 1),
            })
            h, w = ho, wo
        elif kind == "maxpool":
            win = lay.get("window", 2)
            h, w = h // win, w // win
        elif kind not in ("bias", "relu"):
            raise ValueError(f"unknown layer kind {kind!r}")
    return out
