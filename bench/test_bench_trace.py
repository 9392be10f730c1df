"""The trace reduction, against a small trace recorded on the chip.

`testdata/batch224.xplane.pb` is the profiler trace of a 0.4 s window of
`vgg13-s3.batch224` on one TPU v5e (host tracer level 1), kept small by
dropping its ~150,000 per-tile host `Transpose` events and the planes
the reduction does not read.  The totals below are checked two ways:
against numbers read from it once and written here, and against a
plain recount that shares no code with the reduction (a boolean
timeline at 1 us).
"""

import pathlib

import numpy as np
import pytest

from bench import trace_reduce

TRACE = pathlib.Path(__file__).resolve().parent / "testdata" / "batch224.xplane.pb"


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(TRACE))


@pytest.fixture(scope="module")
def red(pd):
    return trace_reduce.reduce_profile(pd)


def _recount(pd, lo, hi, want_conv=None):
    """Device-busy microseconds in [lo, hi) from the raw planes."""
    n = (hi - lo) // 1000 + 1
    busy = np.zeros(n, bool)
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                if want_conv is not None:
                    is_conv = ("custom-call" in e.name
                               or "convolution" in e.name)
                    if is_conv != want_conv:
                        continue
                a = max(int(e.start_ns), lo)
                b = min(int(e.start_ns + e.duration_ns), hi)
                if b > a:
                    busy[(a - lo) // 1000:(b - lo + 999) // 1000] = True
    return busy.sum() * 1e-6


def test_window_and_devices(red):
    assert red.devices() == ["/device:TPU:0"]
    assert 0.35 < red.window_s < 0.6


def test_totals_as_read_once(red):
    dev = red.devices()[0]
    assert red.window_s == pytest.approx(0.400452236, abs=1e-9)
    assert red.busy_s() == pytest.approx(0.091948481, abs=1e-9)
    assert red.busy_s(cls="conv") == pytest.approx(0.079846017, abs=1e-9)
    assert red.busy_s(cls="other") == pytest.approx(0.012102464, abs=1e-9)
    waves = red.waves(dev)
    assert len(waves) == 4
    assert all(m.dur == pytest.approx(29.037e6, rel=1e-3) for m in waves)
    # the host was transposing the next wave's input while the chip idled
    assert red.idle_gaps()[0][0].startswith("TransposePlan::ExecuteTyped")


def test_busy_and_idle_match_a_plain_recount(pd, red):
    lo, hi = red.window
    busy = red.busy_s()
    assert busy == pytest.approx(_recount(pd, lo, hi), abs=2e-4)
    idle = sum(b - a for a, b in red.gaps(red.devices()[0])) / 1e9
    assert busy + idle == pytest.approx(red.window_s, rel=1e-9)
    assert 0 < busy < red.window_s


def test_per_class_totals(pd, red):
    lo, hi = red.window
    conv = red.busy_s(cls="conv")
    other = red.busy_s(cls="other")
    assert conv == pytest.approx(_recount(pd, lo, hi, True), abs=2e-4)
    assert other == pytest.approx(_recount(pd, lo, hi, False), abs=2e-4)
    # ops run one at a time on the chip: the classes split busy time
    assert conv + other == pytest.approx(red.busy_s(), rel=1e-3)
    # the tile kernels do most of the device's work
    assert conv > 0.5 * red.busy_s()


def test_waves_are_the_wave_program(red):
    dev = red.devices()[0]
    waves = red.waves(dev)
    assert len(waves) >= 2
    for m in waves:
        assert red.op_time(dev, m.start, m.end) > 0
        assert red.op_time(dev, m.start, m.end, "conv") > 0


def test_breakdown(red):
    ops = red.top_ops()
    assert 0 < len(ops) <= 10
    assert ops[0][0].endswith("[conv]")
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = red.idle_gaps()
    assert 0 < len(gaps) <= 10
    idle = red.window_s - red.busy_s()
    assert sum(g[1] for g in gaps) <= idle + 1e-9


def test_classify_rule():
    ev = trace_reduce.Event
    assert trace_reduce.classify(ev(
        '%_forward.7 = f32[8,224,224,64]{3,2,1,0} custom-call(f32[8]) '
        'custom_call_target="tpu_custom_call"', 0, 1)) == "conv"
    assert trace_reduce.classify(ev("%convolution.3 = f32[1] convolution(",
                                    0, 1)) == "conv"
    assert trace_reduce.classify(ev("%fusion.2 = f32[8] fusion(", 0, 1)) == "other"
    assert trace_reduce.op_label(ev(
        "%_forward.7 = f32[8,224,224,64]{3,2,1,0:T(8,128)} custom-call(x)",
        0, 1)) == "_forward.7: custom-call -> f32[8,224,224,64]"
