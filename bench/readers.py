"""Arithmetic the metric readers under `bench/metrics/` share.

Each reader is `read(run) -> float | None`, where `run` is a
`harness.Run`.  A reader that finds nothing to read returns None, and
the metric is left out of the result line.
"""

from __future__ import annotations

import numpy as np

from bench import work


def percentile(values, q: float):
    """The exact q-th percentile of a list (linear between ranks), or
    None for an empty list."""
    return float(np.percentile(np.asarray(values, float), q)) if len(values) else None


def due_in_window(run) -> list:
    """The requests due inside the window."""
    win = run.window
    return [r for r in win.requests if win.t0 <= r["due"] <= win.t1]


def latencies_s(run) -> list:
    """Seconds from the instant each request of the window was due to
    its answer on the host.  A request that was refused or never
    answered counts as answered when the run stopped waiting."""
    win = run.window
    end = max((r.get("done", win.t1) for r in win.requests), default=win.t1)
    return [r.get("done", end) - r["due"] for r in due_in_window(run)]


def done_in_window(run) -> list:
    win = run.window
    return [r for r in win.requests
            if "done" in r and win.t0 <= r["done"] <= win.t1]


def waves_in_window(run, win=None) -> list:
    win = win or run.window
    return [w for w in win.waves if win.t0 <= w["dispatch"] <= win.t1]


def device_waves(run) -> list:
    """(host wave, device execution) pairs of the traced segment,
    matched in order; None where the two counts differ by more than one
    (the last wave may straddle the segment's close)."""
    tr, seg = run.trace, run.window.traced
    if tr is None or seg is None or not tr.devices():
        return None
    host = waves_in_window(run, seg)
    dev = tr.waves(tr.devices()[0])
    if not host or not dev or abs(len(host) - len(dev)) > 1:
        return None
    return list(zip(host, dev))


def device_s_per_wave(run) -> dict:
    """Traced device busy seconds per wave, by bucket, and over all
    waves under the key None; {} where the trace has no waves."""
    pairs = device_waves(run)
    if not pairs:
        return {}
    tr = run.trace
    d = tr.devices()[0]
    by: dict = {}
    for w, m in pairs:
        t = tr.op_time(d, m.start, m.end)
        for key in (w["bucket"], None):
            by.setdefault(key, []).append(t)
    return {k: sum(v) / len(v) for k, v in by.items()}


def device_ms_per_wave(run):
    per = device_s_per_wave(run)
    return 1e3 * per[None] if per else None


def conv_roofline(run):
    """Least time of the conv layers at each traced wave's padded shape
    (bucket and batch), over the device time of the conv ops in those
    waves."""
    pairs = device_waves(run)
    if not pairs or run.peaks is None:
        return None
    tr = run.trace
    d = tr.devices()[0]
    least = conv = 0.0
    for w, m in pairs:
        least += sum(lay["least_s"] for lay in work.net_work(
            run.net, run.cfg, w["bucket"], w["batch"], run.peaks))
        conv += tr.op_time(d, m.start, m.end, "conv")
    return 100.0 * least / conv if conv > 0 else None


def device_idle(run):
    """Share of the untraced window in which the chip ran nothing: one
    less the device time of the window's waves over its length, each
    wave taking the traced mean of its bucket's waves (of all waves,
    for a bucket the traced segment did not serve)."""
    per = device_s_per_wave(run)
    if not per:
        return None
    win = run.window
    busy = sum(per.get(w["bucket"], per[None]) for w in waves_in_window(run))
    return 100.0 * (1.0 - busy / (win.t1 - win.t0))


def mfu(run):
    """Direct-convolution operations of the images answered in the
    window, at their true sizes, over the window times the chip's peak."""
    if run.peaks is None:
        return None
    win = run.window
    flops = sum(work.image_flops(run.net, run.cfg, r["side"])
                for r in done_in_window(run))
    if not flops:
        return None
    return 100.0 * flops / ((win.t1 - win.t0) * run.peaks["flops_per_s"])
