"""Rehearsals of a whole run on the CPU, at a tiny size.

The test steers the harness past its look for a chip and points it at
a tiny configuration and two tiny mixes in `bench/testdata/`.  It
checks the result line, the exact percentiles, that nothing compiles
in the window, and that `correct` comes out false when the timed path
is broken underneath or the control stands in the program's place.
"""

import io
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import control, harness, nets, readers, reference, traffic, work

DATA = pathlib.Path(__file__).resolve().parent / "testdata"
ROOT = pathlib.Path(__file__).resolve().parents[1]
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2 ** 40 + 3  # more than 32 bits, as the driver's seeds are


# the closed-loop rehearsals: the chain, and a net brought by its own
# module (bench/testdata/stages.py)
BATCH = ["tiny.closed", "stages.closed"]


def _bench():
    def m(name, unit, cells, **kw):
        return {"name": name, "unit": unit, "better": "lower",
                "source": "host_clock", "workloads": cells, **kw}

    return {
        "workloads": [
            {"name": "tiny.closed", "config": "tiny",
             "traffic": "tiny-closed", "chips": 1, "why": "rehearsal"},
            {"name": "tiny.open", "config": "tiny",
             "traffic": "tiny-open", "chips": 1, "why": "rehearsal"},
            {"name": "stages.closed", "config": "stages",
             "traffic": "tiny-closed", "chips": 1, "why": "rehearsal"},
        ],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            m("images_per_s", "images/s", ["tiny.closed", "stages.closed"]),
            m("p50_ms", "ms", ["tiny.open"]),
            m("p95_ms", "ms", ["tiny.open"]),
        ],
        "per_layer": [
            m("gen_late_p99_ms.online", "ms", ["tiny.open"]),
            m("queue_wait_p95_ms.online", "ms", ["tiny.open"]),
            m("wave_fill.online", "%", ["tiny.open"]),
            m("device_ms_per_wave.online", "ms", ["tiny.open"]),
            m("device_idle.online", "%", ["tiny.open"]),
            m("mfu.online", "%", ["tiny.open"]),
            m("device_ms_per_wave.batch", "ms", BATCH),
            m("device_idle.batch", "%", BATCH),
            m("mfu.batch", "%", BATCH),
        ],
    }


def _run(cell, seconds=0.6, trace=False, tmp_path=None):
    out, err = io.StringIO(), io.StringIO()
    result = harness.run_cell(
        cell, SEED, seconds, trace, t_start=time.monotonic(), bench=_bench(),
        require_chip=False, peaks=PEAKS, out=out, err=err,
        config_dir=DATA, traffic_dir=DATA,
        net_dir=DATA if cell.startswith("stages") else nets.NET_DIR,
        trace_dir=str(tmp_path / "trace") if trace else None)
    last = out.getvalue().strip().splitlines()[-1]
    assert json.loads(last) == result
    return result, err.getvalue()


def test_closed_loop_line():
    result, err = _run("tiny.closed")
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "check"]
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 8
    assert set(result["metrics"]) == {"setup_s", "images_per_s"}
    assert result["metrics"]["images_per_s"]["value"] > 0
    chk = result["check"]
    assert chk["compiles_in_window"] == {"value": 0, "limit": 0}
    assert chk["missing_answers"] == {"value": 0, "limit": 0}
    assert chk["worst_rel_err"]["value"] < chk["worst_rel_err"]["limit"]
    # the compared numbers end standard error, each beside its limit
    tail = err.strip().splitlines()[-3:]
    assert [t.split()[1] for t in tail] == list(chk)
    assert all(" limit " in t for t in tail)
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}


def test_open_loop_exact_percentiles_and_no_compiles():
    cfg = harness.load_config("tiny", DATA)
    mix = traffic.load("tiny-open", DATA)
    server = harness.prepare(cfg, mix, SEED)
    win = harness.drive(server, 1.0, SEED)
    harness.release(server)
    assert win.compiles == 0
    assert len(win.requests) == round(mix["rate_hz"] * 1.0)
    run = harness.Run({}, cfg, mix, PEAKS, 1.0, win)
    lat = sorted(r["done"] - r["due"] for r in win.requests)
    # numpy's linear-between-ranks percentile, by hand
    for q, name in ((50, "p50_ms"), (95, "p95_ms")):
        pos = q / 100 * (len(lat) - 1)
        lo = int(np.floor(pos))
        want = lat[lo] + (lat[min(lo + 1, len(lat) - 1)] - lat[lo]) * (pos - lo)
        got = harness.load_reader(name)(run)
        assert got == pytest.approx(1e3 * want, rel=1e-12)
    fill = harness.load_reader("wave_fill.online")(run)
    waves = readers.waves_in_window(run)
    assert fill == pytest.approx(100 * sum(w["n"] for w in waves)
                                 / sum(w["batch"] for w in waves))
    # partial waves ride the warmed max_batch program, padded
    assert {w["batch"] for w in win.waves} == {mix["max_batch"]}
    checked = harness.check(server.net, cfg, server.weights, server.images,
                            win)
    assert checked["missing_answers"] == 0
    assert checked["worst_rel_err"] < cfg["rel_err_limit"]


@pytest.mark.parametrize("cell", ["tiny.open", "tiny.closed", "stages.closed"])
def test_traced_line_covers_the_windows_tail(tmp_path, monkeypatch, cell):
    """A traced run serves its window untraced, then a segment of
    TRACE_S seconds under the profiler; `window_s` is that segment."""
    monkeypatch.setattr(harness, "TRACE_S", 0.5)
    result, err = _run(cell, seconds=1.2, trace=True, tmp_path=tmp_path)
    assert result["correct"] is True
    assert list(result)[-1] == "check"
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert 0.4 < result["device"]["window_s"] < 0.7
    assert 0 < result["device"]["busy_s"] < result["device"]["window_s"]
    assert "traced segment: " in err and " 0 refused" in err
    bd = result["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert "setup_s" not in result["metrics"]
    kind = "online" if cell.endswith(".open") else "batch"
    # `.batch` and `.online` are read by one file each, found by name
    assert {f"device_ms_per_wave.{kind}", f"device_idle.{kind}",
            f"mfu.{kind}"} <= set(result["metrics"])
    if kind == "online":
        assert set(result["metrics"]) >= {"gen_late_p99_ms.online",
                                          "queue_wait_p95_ms.online",
                                          "wave_fill.online"}


def test_host_readings_come_from_the_untraced_window(tmp_path):
    """Tracing slows the host, so host stamps, the rate and the idle
    share are read over the untraced window; the trace gives only the
    device time per wave."""
    from bench import trace_reduce

    cfg = harness.load_config("tiny", DATA)
    mix = dict(traffic.load("tiny-open", DATA), trace_rate_scale=0.5)
    server = harness.prepare(cfg, mix, SEED)
    win = harness.drive(server, 1.0, SEED, trace_dir=str(tmp_path))
    harness.release(server)
    seg = win.traced
    assert win.t1 <= seg.t0 and seg.compiles == 0
    assert len(win.requests) == round(mix["rate_hz"] * 1.0)
    assert len(seg.requests) == round(0.5 * mix["rate_hz"] * harness.TRACE_S)
    assert not {r["rid"] for r in win.requests} & {r["rid"] for r in seg.requests}
    tr = trace_reduce.reduce_file(trace_reduce.find_xplane(str(tmp_path)))
    run = harness.Run({}, cfg, mix, PEAKS, 1.0, win, tr, server.net)
    assert readers.due_in_window(run) == win.requests
    waits = [r["dispatch"] - r["admit"] for r in win.requests]
    assert harness.load_reader("queue_wait_p95_ms.online")(run) == \
        pytest.approx(1e3 * np.percentile(waits, 95), rel=1e-12)
    per = readers.device_s_per_wave(run)
    assert per[None] > 0
    busy = sum(per.get(w["bucket"], per[None])
               for w in readers.waves_in_window(run))
    assert harness.load_reader("device_idle.online")(run) == \
        pytest.approx(100 * (1 - busy / (win.t1 - win.t0)))
    flops = sum(work.image_flops(server.net, cfg, r["side"])
                for r in win.requests
                if win.t0 <= r["done"] <= win.t1)
    assert harness.load_reader("mfu.online")(run) == pytest.approx(
        100 * flops / ((win.t1 - win.t0) * PEAKS["flops_per_s"]))


def test_seeds_order_the_same_work():
    mix = traffic.load("tiny-open", DATA)
    a = traffic.open_arrivals(mix, 5.0, 1)
    b = traffic.open_arrivals(mix, 5.0, 2 ** 33 + 1)
    assert sorted(x.side for x in a) == sorted(x.side for x in b)
    assert [x.t for x in a] != [x.t for x in b]
    assert all(0 <= x.t < 5.0 for x in a)
    # the same seed gives the same traffic and the same weights
    assert a == traffic.open_arrivals(mix, 5.0, 1)
    cfg = harness.load_config("tiny", DATA)
    net = nets.load(cfg)
    w1 = net.make_weights(cfg, 2 ** 33 + 1)
    w2 = net.make_weights(cfg, 1)
    assert not np.allclose(np.asarray(w1[0]), np.asarray(w2[0]))


def _break_crop(monkeypatch, how):
    from repro.convserve.runtime import scheduler

    orig = scheduler.Wave.crop
    last = []

    def crop(self, spec, y):
        y = np.array(y)
        if how == "half_batch_left_out":
            y[len(self.requests) // 2:] = 0.0
        elif how == "previous_wave_returned" and last:
            y = last[0]
        last[:] = [y]
        out = orig(self, spec, y)
        rids = [r.rid for r in self.requests]
        if how == "answer_altered":
            out[rids[0]] = out[rids[0]] * (1 + 1e-3)
        elif how == "answers_misrouted" and len(rids) > 1:
            out[rids[0]], out[rids[1]] = out[rids[1]], out[rids[0]]
        return out

    monkeypatch.setattr(scheduler.Wave, "crop", crop)


@pytest.mark.parametrize(
    "how", ["answer_altered", "half_batch_left_out", "answers_misrouted",
            "previous_wave_returned"])
def test_broken_timed_path_is_not_correct(monkeypatch, how):
    _break_crop(monkeypatch, how)
    result, _ = _run("tiny.closed", seconds=0.4)
    assert result["correct"] is False
    assert result["check"]["worst_rel_err"]["value"] > \
        result["check"]["worst_rel_err"]["limit"]


@pytest.mark.parametrize("precision,correct", [("high", False),
                                               ("bf16", False),
                                               ("highest", True)])
def test_reference_in_the_programs_place(monkeypatch, precision, correct):
    """The control -- the reference one precision down -- in place of
    the wave program fails the check; the reference itself passes it."""
    from repro.convserve import executor

    cfg = harness.load_config("tiny", DATA)
    fn = reference.jitted(nets.load(cfg), cfg, precision)

    def call(self, x, sizes=None, *, mesh=None):
        return fn(self.weights, np.asarray(x, np.float32))

    monkeypatch.setattr(executor.NetExecutor, "__call__", call)
    result, _ = _run("tiny.closed", seconds=0.4)
    assert result["correct"] is correct


def test_run_py_without_a_chip_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "vgg13-s3.batch224", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


@pytest.mark.parametrize("precision,correct", [("high", False),
                                               ("highest", True)])
def test_control_served_through_the_check(precision, correct):
    """bench/control.py serves the control's answers as the window's
    and judges them with the harness's own check and limit."""
    cfg = harness.load_config("tiny", DATA)
    mix = traffic.load("tiny-closed", DATA)
    server = harness.prepare(cfg, mix, SEED)
    win = harness.drive(server, 0.4, SEED)
    harness.release(server)
    ctl = control._control(server.net, cfg, server.weights, server.images,
                           win, precision)
    assert set(ctl.results) == set(win.results)
    got = harness.check(server.net, cfg, server.weights, server.images, ctl)
    got["compiles_in_window"] = win.compiles
    line = harness.limits_line(got, cfg["rel_err_limit"])
    assert harness.is_correct(line, got["compared"]) is correct


def test_reference_biases_match_the_programs_semantics():
    """The reference adds a bias where the configuration has one, as the
    program's own all-direct path does; dropping it is caught."""
    import jax
    import jax.numpy as jnp

    from repro.convserve.graph import run_direct

    cfg = harness.load_config("tiny", DATA)
    net = nets.load(cfg)
    assert sum(lay["kind"] == "bias" for lay in cfg["layers"]) == 4
    w = net.make_weights(cfg, SEED)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, 16, 3)),
                    jnp.float32)
    ref = np.asarray(net.forward(cfg, w, x))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(run_direct(net.netspec(cfg), dict(w), x))
    assert reference.rel_err(got, ref) < 1e-5
    no_bias = {i: (a * 0 if a.ndim == 1 else a) for i, a in w.items()}
    dropped = np.asarray(net.forward(cfg, no_bias, x))
    assert reference.rel_err(dropped, ref) > 1e-3
