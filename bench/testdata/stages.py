"""A net module for rehearsals on the CPU: a VGG-style net given as
`stages` of (width, convs), with no list of layers.  Each conv is 3x3,
"same", followed by a bias and ReLU; each stage ends in a 2x2 max-pool.

Weights are keyed by the index of the layer of the program's net that
binds them, as `NetSpec` numbers its layers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference


def _layers(cfg: dict) -> list:
    """(kind, c_in, c_out) of each layer of the program's net."""
    out = []
    c = cfg["input_channels"]
    for width, convs in cfg["stages"]:
        for _ in range(convs):
            out += [("conv", c, width), ("bias", width, width),
                    ("relu", width, width)]
            c = width
        out.append(("maxpool", c, c))
    return out


def input_channels(cfg: dict) -> int:
    return cfg["input_channels"]


def make_weights(cfg: dict, seed: int) -> dict:
    """He-normal kernels and normal biases from the seed, one jitted
    call."""
    shapes = {i: ((3, 3, ci, co) if kind == "conv" else (co,))
              for i, (kind, ci, co) in enumerate(_layers(cfg))
              if kind in ("conv", "bias")}

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(shapes))
        return {i: jax.random.normal(k, shp, jnp.float32)
                * (reference.BIAS_STD if len(shp) == 1
                   else (2.0 / (9 * shp[2])) ** 0.5)
                for k, (i, shp) in zip(keys, sorted(shapes.items()))}

    return init(reference.seed_key(seed))


def forward(cfg: dict, weights: dict, x, precision: str = "highest"):
    for i, (kind, _, _) in enumerate(_layers(cfg)):
        if kind == "conv":
            x = reference.conv(x, weights[i], {"k": 3}, precision)
        elif kind == "bias":
            x = x + weights[i]
        elif kind == "relu":
            x = jnp.maximum(x, 0.0)
        else:
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    return x


def netspec(cfg: dict):
    from repro.convserve.graph import NetSpec, bias, conv, maxpool, relu

    make = {"conv": lambda ci, co: conv(ci, co),
            "bias": lambda ci, co: bias(co),
            "relu": lambda ci, co: relu(),
            "maxpool": lambda ci, co: maxpool(2)}
    return NetSpec(name=cfg["name"], layers=tuple(
        make[kind](ci, co) for kind, ci, co in _layers(cfg)))


def conv_shapes(cfg: dict, side: int) -> list:
    out = []
    for kind, ci, co in _layers(cfg):
        if kind == "conv":
            out.append({"h": side, "w": side, "ho": side, "wo": side,
                        "c_in": ci, "c_out": co, "k": 3, "groups": 1})
        elif kind == "maxpool":
            side //= 2
    return out
