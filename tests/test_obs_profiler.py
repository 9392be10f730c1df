"""The serving path's spans on the profiler's clock.

Every same-thread span -- under the default `NullTracer` as under a
real `Tracer` -- is also a `jax.profiler.TraceAnnotation`.  One wave
served through `ServeRuntime` under `jax.profiler.trace` (on the CPU)
must leave every host-phase span in the xplane, all carrying the
wave's id, with the replica's phases nested inside its run; a real
`Tracer` records the same names in its ring.  The wave program names
its stages, and `WaveResult.compute_s` still times put through fetch.
"""

import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.convnets import tiny_testnet
from repro.convserve import Engine, init_weights
from repro.convserve.obs import CAT_HOST, NULL_TRACER, Span, Tracer
from repro.convserve.runtime import ReplicaPool, RuntimeConfig, ServeRuntime

SPEC = tiny_testnet(4)
SIDE = 16
REPLICA = ("run", "assemble", "put", "compute", "fetch", "crop")
NAMES = (
    {"convserve.runtime.dispatch", "convserve.runtime.complete",
     "convserve.exec.transforms", "convserve.exec.launch"}
    | {f"convserve.replica.{p}" for p in REPLICA}
)


def _xplane_events(trace_dir, prefix="convserve."):
    """(name, start_ns, end_ns, stats) of every profiler event whose
    name starts with `prefix`."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    start = int(e.start_ns)
                    out.append((e.name, start, start + int(e.duration_ns),
                                dict(e.stats)))
    return out


def _profiled(trace_dir, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        return fn()


def _serve_one_wave(tmp_path, tracer=None):
    """Warm a threaded one-replica runtime, then serve one full wave of
    two images under the profiler.  Returns (runtime, results, spans)."""
    pool = ReplicaPool.build(Engine(), SPEC, init_weights(SPEC, seed=5),
                             n=1, input_hw=(SIDE, SIDE))
    rt = ServeRuntime(pool, RuntimeConfig(max_batch=2, buckets=(SIDE,)),
                      tracer=tracer)
    rt.warmup()
    results = []
    rt.add_wave_observer(results.append)
    rng = np.random.default_rng(3)

    def serve():
        for rid in range(2):
            img = rng.standard_normal((SIDE, SIDE, 4)).astype(np.float32)
            rt.submit(img, rid=rid)
        rt.drain()

    try:
        _profiled(tmp_path, serve)
    finally:
        rt.shutdown()
    return rt, results, _xplane_events(str(tmp_path))


@pytest.mark.parametrize("real", [False, True], ids=["null", "ring"])
def test_one_wave_records_every_span_with_one_wave_id(tmp_path, real):
    tracer = Tracer() if real else None
    rt, results, spans = _serve_one_wave(tmp_path, tracer)
    assert len(results) == 1 and sorted(rt.results) == [0, 1]
    assert {s[0] for s in spans} == NAMES
    assert len(spans) == len(NAMES)  # one wave: each span once
    wave = results[0].wave
    for name, _, _, stats in spans:
        assert stats["wave"] == wave.wave_id == 1, name
        assert (stats["bucket"], stats["batch"], stats["rows"]) == (
            SIDE, 2, 2), name
    by = {s[0]: s for s in spans}
    assert by["convserve.runtime.dispatch"][3]["queue_wait_max_us"] >= 0
    _, lo, hi, _ = by["convserve.replica.run"]
    inside = [f"convserve.replica.{p}" for p in REPLICA[1:]]
    inside += ["convserve.exec.transforms", "convserve.exec.launch"]
    for name in inside:
        assert lo <= by[name][1] <= by[name][2] <= hi, name
    # the replica's phases run in order, one after the other
    phases = [by[f"convserve.replica.{p}"] for p in REPLICA[1:]]
    assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
    # the executor's spans lie inside the compute phase
    _, c_lo, c_hi, _ = by["convserve.replica.compute"]
    for name in ("convserve.exec.transforms", "convserve.exec.launch"):
        assert c_lo <= by[name][1] <= by[name][2] <= c_hi, name
    if real:
        ring = {e.name for e in tracer.events()
                if isinstance(e, Span) and e.cat == CAT_HOST}
        assert ring == NAMES
    else:
        assert rt.tracer is NULL_TRACER and rt.pool.tracer is NULL_TRACER


def test_compute_s_spans_put_through_fetch(tmp_path):
    """The slack model's wave time: from before the put to after the
    fetch -- the put, compute and fetch spans, not assemble or crop."""
    tracer = Tracer()
    _, results, _ = _serve_one_wave(tmp_path, tracer)
    (res,) = results
    ring = {e.name.rsplit(".", 1)[1]: e for e in tracer.events()
            if isinstance(e, Span) and e.name.startswith("convserve.replica.")}
    assert res.compute_s >= ring["fetch"].t1 - ring["put"].t0 > 0
    assert res.compute_s <= ring["crop"].t0 - ring["assemble"].t1


def test_annotation_args_are_scalars_and_nest_per_thread(tmp_path):
    """A span's annotation carries its scalar args over those of the
    spans it nests in on this thread; ring placement keywords and
    non-scalar args stay off the profiler's event."""
    tracer = Tracer()

    def spans():
        with tracer.span("convserve.test.outer", CAT_HOST, wave=7, rows=3,
                         pid=2, flow_out=("f",), shape=(1, 2)):
            with NULL_TRACER.span("convserve.test.inner", rows=1):
                pass
        with NULL_TRACER.span("convserve.test.after"):
            pass

    _profiled(tmp_path, spans)
    got = {name: stats for name, _, _, stats in _xplane_events(str(tmp_path))}
    assert got["convserve.test.outer"] == {"wave": 7, "rows": 3}
    assert got["convserve.test.inner"] == {"wave": 7, "rows": 1}
    assert got["convserve.test.after"] == {}
    (outer,) = [e for e in tracer.events() if isinstance(e, Span)]
    assert outer.pid == 2 and outer.args["shape"] == (1, 2)


def test_lowered_wave_program_names_its_stages():
    net = Engine().compile(SPEC, init_weights(SPEC, seed=5),
                           input_hw=(SIDE, SIDE))
    ex = net.executor
    text = ex.lower(
        np.zeros((2, SIDE, SIDE, 4), np.float32),
        np.full((2, 2), SIDE, np.int32),
    ).as_text(debug_info=True)
    labels = [f"stage{i}.{s.label}"
              for i, s in enumerate(ex.program.stages)]
    assert len(labels) >= 2
    for scope in labels + ["prologue", "mask", "pool"]:
        assert scope in text, scope


def test_wave_ids_count_the_waves_formed():
    pool = ReplicaPool.build(Engine(), SPEC, init_weights(SPEC, seed=5),
                             n=1, workers=0, input_hw=(SIDE, SIDE))
    rt = ServeRuntime(pool, RuntimeConfig(max_batch=2, buckets=(SIDE,)))
    waves = []
    rt.add_wave_observer(lambda res: waves.append(res.wave))
    img = np.zeros((SIDE, SIDE, 4), np.float32)
    for rid in range(5):
        rt.submit(img, rid=rid)
    rt.drain()
    rt.shutdown()
    assert [w.wave_id for w in waves] == [1, 2, 3]
    # the last wave: one image, padded onto the program of two
    assert waves[-1].trace_args == {"wave": 3, "bucket": SIDE, "batch": 2,
                                    "rows": 1}


def _sampled(workers):
    """Serve 16 images in waves of two on a one-replica pool with a
    ring `Tracer` at `sample_rate` 0.25: (the rids of the request spans
    kept, the number of wave spans kept, the wave ids of the replica
    runs kept)."""
    pool = ReplicaPool.build(Engine(), SPEC, init_weights(SPEC, seed=5),
                             n=1, workers=workers, input_hw=(SIDE, SIDE))
    tracer = Tracer(sample_rate=0.25)
    rt = ServeRuntime(pool, RuntimeConfig(max_batch=2, buckets=(SIDE,)),
                      tracer=tracer)
    rt.warmup()
    img = np.zeros((SIDE, SIDE, 4), np.float32)
    for rid in range(16):
        rt.submit(img, rid=rid)
    rt.drain()
    rt.shutdown()
    spans = [e for e in tracer.events() if isinstance(e, Span)]
    rids = sorted(e.args["rid"] for e in spans if e.name.startswith("request:"))
    waves = {e.sid for e in spans if e.name.startswith("wave:")}
    host = [e for e in spans if e.cat == CAT_HOST]
    assert {e.parent for e in host if e.name in (
        "convserve.runtime.dispatch", "convserve.replica.run",
        "convserve.runtime.complete")} == waves
    assert tracer.open_count() == 0
    return rids, len(waves), sorted(
        e.args["wave"] for e in host if e.name == "convserve.replica.run")


def test_sampling_keeps_the_same_trees_on_a_threaded_pool():
    """Only request and wave spans are roots: a wave's host spans, begun
    on the replica threads too, nest under its span and advance no
    sampling count, so the sampled set is the one an inline pool keeps
    and does not hang on thread timing."""
    # roots: warm-up's two executor spans, 16 requests, then 8 waves;
    # the 4th, 8th, ... of them are kept
    want = ([1, 5, 9, 13], 2, [2, 6])
    assert _sampled(workers=0) == want
    assert _sampled(workers=1) == want
    assert _sampled(workers=1) == want
