"""The readers of the program's own spans (`bench/spans.py`), against a
hand-built trace whose numbers are worked out by hand below.

The trace, in ms, on one device, window [0, 100):

    wave 0  run [-5, 8)  compute [-3, 6)          starts before the window
    wave 1  run [10, 30) assemble [10, 11) put [11, 14) compute [14, 26)
            fetch [26, 28) crop [28, 29.5)        program [15, 25)
    wave 2  run [33, 50) put [34, 36) compute [36, 47)   program [35.5, 46)
            (the program starts before its compute span: the device's
            clock runs a little behind the host's)
            a stray wave-2 compute [60, 61), outside its run
    wave 3  run [100, 110)                        starts after the window

    loop gap      33 - 30 = 3
    glue          program 1: pad 1 + mask fusion 1.5 + X64 split 0.5 = 3
                  program 2: copy [44, 46) with slice [45, 46.5) = 2
                  (3 + 2) / 2 = 2.5
    idle          [0, 15) + [24.5, 37) + [46.5, 100) = 81, of which the
                  spans cover 13 + 9.5 + 4.5 = 27
"""

import pathlib

import pytest

from bench import harness, spans
from bench.trace_reduce import Event, Reduced

MS = 1_000_000
DEV = "/device:TPU:0"


def ev(name, a, b, **stats):
    return Event(name, int(a * MS), int(b * MS), stats)


def _host():
    out = []

    def wave(w, **phases):
        out.extend(ev(f"convserve.replica.{p}", a, b, wave=w, bucket=224,
                      batch=8, rows=8) for p, (a, b) in phases.items())

    wave(0, run=(-5, 8), compute=(-3, 6))
    wave(1, run=(10, 30), assemble=(10, 11), put=(11, 14),
         compute=(14, 26), fetch=(26, 28), crop=(28, 29.5))
    wave(2, run=(33, 50), put=(34, 36), compute=(36, 47))
    wave(2, compute=(60, 61))
    wave(3, run=(100, 110))
    out.append(ev("bench.wait", 0, 100))
    return out


OPS = [
    ev("%pad.17 = f32[8,72,120,3]{3,2,1,0:T(8,128)} pad(f32[8,64,64,3] %x)",
       15, 16),
    ev("%convserve_tile_fft_t16.4 = f32[8,70,112,64]{3,2,1,0:T(8,128)} "
       "custom-call(%pad.17), custom_call_target=\"tpu_custom_call\"", 16, 20),
    ev("%select_fusion.1 = f32[8,64,64,64]{3,2,1,0} fusion(%a), kind=kLoop",
       20, 21.5),
    ev("%convolution.2 = f32[8,64,64,64]{3,2,1,0} convolution(%a, %b)",
       21.5, 24),
    ev("%custom-call.4 = f32[16,9,64,128]{3,2,1,0} custom-call(%w), "
       "custom_call_target=\"X64SplitHigh\"", 24, 24.5),
    ev("%convserve_tile_winograd_t7.1 = f32[8,20,40,256]{3,2,1,0} "
       "custom-call(%p)", 37, 44),
    ev("%copy.3 = f32[8,224,224,3]{3,2,1,0:T(8,128)} copy(%x.1)", 44, 46),
    ev("%slice.1 = f32[8,64,64,64]{3,2,1,0} slice(%y)", 45, 46.5),
]

MODULES = [ev("jit__forward(1)", -2, 5), ev("jit__forward(1)", 15, 25),
           ev("jit__forward(1)", 35.5, 46), ev("jit__forward(1)", 70, 71)]


@pytest.fixture
def tr():
    return Reduced(window=(0, 100 * MS), ops={DEV: OPS},
                   modules={DEV: MODULES}, host=_host())


def _read(name, tr):
    run = harness.Run({}, {}, {}, None, 0.0, None, tr)
    return harness.load_reader(name)(run)


def test_waves_read_are_those_whose_run_starts_in_the_window(tr):
    assert [r.stats["wave"] for r in spans.runs(tr)] == [1, 2]
    pairs = spans.children(tr, spans.COMPUTE)
    # wave 2's stray compute lies outside its run and is not its child
    assert [(r.stats["wave"], c.start // MS) for r, c in pairs] == [
        (1, 14), (2, 36)]
    assert [m.start / MS for m in spans.wave_programs(tr)] == [15, 35.5]


@pytest.mark.parametrize("name,want", [
    ("loop_gap_ms_per_wave.batch", 3.0),
    ("glue_ms_per_wave.batch", 2.5),
    ("glue_ms_per_wave.online", 2.5),
])
def test_reader_by_hand(tr, name, want):
    assert _read(name, tr) == pytest.approx(want, abs=1e-9)


@pytest.mark.parametrize("name", [
    "loop_gap_ms_per_wave.batch", "glue_ms_per_wave.batch",
    "glue_ms_per_wave.online",
])
def test_readers_find_nothing_without_the_programs_spans(tr, name):
    """No trace, or a trace of a program that writes no spans: None,
    and the metric is left out of the line."""
    assert _read(name, None) is None
    bare = Reduced(window=tr.window, ops=tr.ops, modules=tr.modules,
                   host=[e for e in tr.host if e.name == "bench.wait"])
    assert _read(name, bare) is None


def test_glue_is_found_by_name():
    glue = [o.name.split(" = ")[0] for o in OPS if spans.is_glue(o)]
    assert glue == ["%pad.17", "%select_fusion.1", "%custom-call.4",
                    "%copy.3", "%slice.1"]
    # a CPU op carries its bare instruction name
    assert spans.is_glue(ev("fusion.3", 0, 1))
    assert not spans.is_glue(ev("convolution.1", 0, 1))


def test_idle_covered_by_the_programs_spans(tr):
    assert spans.covered_idle_share(tr) == pytest.approx(27 / 81)
    # gaps shorter than the threshold are left out
    assert spans.covered_idle_share(tr, min_ns=13 * MS) == pytest.approx(
        (13 + 4.5) / (15 + 53.5))


# ------------------------------------------------- a trace from the chip

# A 0.4 s traced window of `vgg13-s3.batch224` on one TPU v5e (host
# tracer level 1), with the program's spans, trimmed as
# `batch224.xplane.pb` was: its ~147,000 per-tile host `Transpose`
# events and the planes and lines the reduction does not read dropped.
CHIP = pathlib.Path(__file__).resolve().parent / "testdata" / "batch224-spans.xplane.pb"
# the new metrics, as read from it once
READ_ONCE = {"loop_gap_ms_per_wave.batch": 1.7341766666666667,
             "glue_ms_per_wave.batch": 3.557292}


@pytest.fixture(scope="module")
def chip():
    from bench import trace_reduce

    return trace_reduce.reduce_file(str(CHIP))


def test_chip_trace_reads_every_new_metric(chip):
    got = {name: _read(name, chip) for name in READ_ONCE}
    assert got == pytest.approx(READ_ONCE, abs=1e-9)
    dev = chip.devices()[0]
    progs = spans.wave_programs(chip)
    assert len(progs) == len(spans.runs(chip)) >= 2
    # glue is a part of each wave program's device time
    per_prog = sum(chip.op_time(dev, m.start, m.end) for m in progs) / len(progs)
    assert got["glue_ms_per_wave.batch"] < 1e3 * per_prog


def test_chip_trace_names_its_kernels_and_covers_its_idle_time(chip):
    dev = chip.devices()[0]
    kernels = [e for e in chip.ops[dev] if not spans.is_glue(e)]
    assert kernels and all(
        e.name.lstrip("%").startswith(spans.TILE_KERNEL) for e in kernels)
    # the chip idles inside the program's own spans
    assert spans.covered_idle_share(chip) >= 0.9
