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


def net_work(net, cfg: dict, side: int, batch: int, peaks: dict) -> list:
    """Per conv layer of a configuration at `side` px and `batch` images,
    as its net module (`bench/nets/`) lists them: dicts with the
    geometry, flops, bytes, least_s and bound."""
    nb = DTYPE_BYTES[cfg["dtype"]]
    rows = []
    for g in net.conv_shapes(cfg, side):
        flops, nbytes = layer_work(g, batch, nb)
        t, bound = least_time(flops, nbytes, peaks)
        rows.append({**g, "flops": flops, "bytes": nbytes, "least_s": t,
                     "bound": bound})
    return rows


def image_flops(net, cfg: dict, side: int) -> int:
    """Direct-convolution operations of one image of `side` pixels."""
    return sum(r["flops"] for r in net_work(net, cfg, side, 1, _NO_PEAKS))


_NO_PEAKS = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
