"""The work a net's conv layers must do, from shapes alone, and the
chip's peaks to judge it against.

Every algorithm is judged against the same work: the operations of the
direct convolution, 2 * H_out * W_out * C_in * C_out * k^2 / groups per
image, and the least bytes any algorithm must move through HBM -- the
layer's input and output activations plus its weights, once each, in
the configuration's dtype.  A transformed convolution does fewer
multiplies than that, so its share of this roofline says how close the
layer came to the direct convolution's least time, not how busy the
MXU was.
"""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def load_peaks(device_kind: str) -> dict:
    """The peaks of one device kind, as JAX names it.  A device missing
    from the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device_kind {device_kind!r} in {PEAKS_FILE.name} "
            f"(known: {sorted(table['devices'])})"
        ) from None


def conv_shapes(layers: list, side: int) -> list:
    """Each conv layer's geometry at a square input of `side` pixels:
    dicts with h, w (input), ho, wo (output), c_in, c_out, k, groups."""
    out = []
    h = w = side
    for lay in layers:
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


def layer_work(g: dict, batch: int, dtype_bytes: int) -> tuple:
    """(operations, least bytes) of one conv layer over `batch` images."""
    flops = (2 * g["ho"] * g["wo"] * g["c_in"] * g["c_out"] * g["k"] ** 2
             // g["groups"]) * batch
    acts = batch * (g["h"] * g["w"] * g["c_in"] + g["ho"] * g["wo"] * g["c_out"])
    weights = g["k"] ** 2 * g["c_in"] // g["groups"] * g["c_out"]
    return flops, (acts + weights) * dtype_bytes


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of operations over peak FLOP/s and
    bytes over peak HBM bytes/s, and which of the two it was."""
    t_c = flops / peaks["flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "hbm")


def net_work(cfg: dict, side: int, batch: int, peaks: dict) -> list:
    """Per conv layer of a configuration at `side` px and `batch` images:
    dicts with the geometry, flops, bytes, least_s and bound."""
    nb = DTYPE_BYTES[cfg["dtype"]]
    rows = []
    for g in conv_shapes(cfg["layers"], side):
        flops, nbytes = layer_work(g, batch, nb)
        t, bound = least_time(flops, nbytes, peaks)
        rows.append({**g, "flops": flops, "bytes": nbytes, "least_s": t,
                     "bound": bound})
    return rows


def image_flops(cfg: dict, side: int) -> int:
    """Direct-convolution operations of one image of `side` pixels."""
    return sum(r["flops"] for r in net_work(cfg, side, 1, _NO_PEAKS))


_NO_PEAKS = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
