"""One run of one cell: set the server up, drive it for a window, check
what it served against the reference, and report.

The cell, its configuration, its net, its traffic mix and its metrics
are all found by name: the cell in `BENCHMARK.json`, the configuration
in `bench/configs/<config>.json`, the module of the net it names in
`bench/nets/<net>.py` (`bench/nets/__init__.py`), the mix in
`bench/traffic/<traffic>.json` and each metric's reader in
`bench/metrics/<metric>.py`.

The served path is the one a user calls: `Engine` plans at the largest
bucket, `ReplicaPool.build` binds the weights, `ServeRuntime` admits,
buckets, batches and dispatches, and the benchmark drives it with
`submit` / `run_until` / `drain`, recording every request through a
wave observer.  Latency runs from the instant a request was due.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import itertools
import json
import pathlib
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from bench import nets, reference, traffic, work

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CONFIG_DIR = BENCH_DIR / "configs"
METRIC_DIR = BENCH_DIR / "metrics"
# answers still owed after the window closes are waited for this long
ANSWER_WAIT_S = 60.0
# the error of an answer of the wrong shape, or with a NaN in it
WRONG = 1e9
# a traced run traces a segment this long, served after its window
TRACE_S = 3.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ lookup

def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: "
                   f"{[w['name'] for w in bench['workloads']]})")


def load_config(name: str, directory: pathlib.Path = CONFIG_DIR) -> dict:
    return json.loads((directory / f"{name}.json").read_text())


def metrics_for(bench: dict, cell: str, kind: str) -> List[dict]:
    """The cell's metrics of one kind ("end_to_end" or "per_layer"):
    those that list the cell, and those that list no cells."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str, directory: pathlib.Path = METRIC_DIR):
    """The reader `<name>.py`; where there is none, the reader of the
    name without its last part (`mfu.py` reads `mfu.batch` and
    `mfu.online`)."""
    path = directory / f"{name}.py"
    if not path.exists() and "." in name:
        path = directory / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------ device

def configure(root: pathlib.Path = ROOT) -> None:
    """JAX's persistent compilation cache in `.jax_cache/` at the
    checkout's root (`repro.compile_cache`'s own default), every
    program kept, so that only a cell's first run in a checkout
    compiles; and a plan from the committed code, never from a tuning
    file left by another run.  A `JAX_COMPILATION_CACHE_DIR` set from
    outside is dropped: two checkouts compared share no cache."""
    import os

    import jax
    from repro.compile_cache import ENV_VAR, enable_compile_cache

    os.environ.pop(ENV_VAR, None)
    enable_compile_cache()
    wisdom = root / ".bench_wisdom.json"
    wisdom.unlink(missing_ok=True)
    os.environ["REPRO_WISDOM"] = str(wisdom)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> dict:
    """Platform, kind and count of JAX's devices; a run with no TPU, or
    with fewer chips than the cell asks for, raises `NoChip`."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {d0.platform!r}; the "
                     "benchmark runs only on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX found "
                     f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


# ------------------------------------------------------------------ server

@dataclasses.dataclass
class Server:
    cfg: dict
    net: object  # the configuration's net module
    mix: dict
    weights: dict
    images: dict  # side -> [HWC]
    engine: object
    pool: object
    rt: object
    recorder: object = None  # the window's `Recorder`, while one runs
    phases: dict = dataclasses.field(default_factory=dict)  # set-up, s
    rids: object = dataclasses.field(default_factory=itertools.count)

    def executors(self):
        return self.pool.executors

    def observe(self, res) -> None:
        if self.recorder is not None:
            self.recorder.observe(res)


def prepare(cfg: dict, mix: dict, seed: int,
            net_dir: pathlib.Path = nets.NET_DIR) -> Server:
    """Weights and images from the seed, the server planned, built and
    warmed at the cell's (bucket, max_batch) programs only; the net is
    the module the configuration names, found in `net_dir`."""
    phases = {}
    t = time.monotonic()
    from repro.convserve import (Engine, ReplicaPool, RuntimeConfig,
                                 ServeRuntime)

    def lap(name):
        nonlocal t
        now = time.monotonic()
        phases[name] = now - t
        t = now

    lap("import_program")
    net = nets.load(cfg, net_dir)
    images = traffic.make_images(mix, seed, net.input_channels(cfg))
    lap("images")
    weights = net.make_weights(cfg, seed)
    lap("weights")
    spec = net.netspec(cfg)
    top = max(mix["buckets"])
    engine = Engine()
    pool = ReplicaPool.build(engine, spec, dict(weights), n=mix["replicas"],
                             input_hw=(top, top))
    rt = ServeRuntime(pool, RuntimeConfig(
        max_batch=mix["max_batch"], buckets=tuple(mix["buckets"]),
        slo_s=mix.get("slo_s")))
    lap("plan_and_bind")
    rt.warmup()
    lap("warmup")
    server = Server(cfg, net, mix, weights, images, engine, pool, rt,
                    phases=phases)
    rt.add_wave_observer(server.observe)
    return server


# ------------------------------------------------------------------ window

class Recorder:
    """Per request: when it was due, submitted, admitted, dispatched and
    answered; per wave: bucket, rows, padded batch, when.  Fed by the
    benchmark's loop and by the runtime's wave observer (replica
    thread)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.requests: Dict[int, dict] = {}
        self.waves: List[dict] = []
        self.on_answer = None  # closed loop: the client's next request

    def due(self, rid: int, side: int, image: int, t_due: float) -> None:
        with self.lock:
            self.requests[rid] = {"rid": rid, "side": side, "image": image,
                                  "due": t_due}

    def submitted(self, rid: int, t: float, rejection) -> None:
        with self.lock:
            r = self.requests[rid]
            r["submitted"] = t
            if rejection is not None:
                r["rejected"] = rejection.reason

    def observe(self, res) -> None:
        wave = res.wave
        with self.lock:
            self.waves.append({
                "bucket": wave.bucket, "batch": wave.batch_size,
                "n": len(wave.requests), "reason": wave.reason,
                "dispatch": wave.requests[0].t_dispatch,
                "done": wave.requests[0].t_done,
                "compute_s": res.compute_s, "compiled": res.compiled,
            })
            for r in wave.requests:
                rec = self.requests[r.rid]
                rec.update(bucket=r.bucket, admit=r.t_admit,
                           dispatch=r.t_dispatch, done=r.t_done)
        if self.on_answer is not None:
            for r in wave.requests:
                self.on_answer(r.rid, r.t_done)

    def answered(self) -> int:
        with self.lock:
            return sum("done" in r for r in self.requests.values())


@dataclasses.dataclass
class Window:
    t0: float  # the measured span
    t1: float
    t_first: float  # the first timed request: where set-up ends
    requests: List[dict]
    waves: List[dict]
    compiles: int  # programs traced, lowered or compiled inside the span
    results: Dict[int, np.ndarray]
    # a traced run: the segment served under the profiler after the
    # window closed and its answers came
    traced: Optional["Window"] = None

    def segments(self) -> List["Window"]:
        return [self] + ([self.traced] if self.traced is not None else [])


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@functools.lru_cache(maxsize=None)
def _compile_events() -> list:
    """A list that grows by one per JAX trace, lowering or compile, for
    the life of the process (JAX's listeners cannot be removed)."""
    import jax

    events: list = []

    def on_event(key, *_a, **_k):
        if key.startswith("/jax/core/compile/"):
            events.append(key)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return events


class _Span:
    """The measured span of a segment, annotated `bench.window` in the
    profiler's timeline; in the traced segment the profiler runs over
    exactly this span."""

    def __init__(self, clock, trace_dir: Optional[str]):
        self.clock = clock
        self.trace_dir = trace_dir
        self.t0 = self.t1 = None
        self._ann = None

    def open(self) -> None:
        if self.trace_dir is not None:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._ann = _annotate("bench.window")
        self._ann.__enter__()
        self.t0 = self.clock.now()

    def close(self) -> None:
        self.t1 = self.clock.now()
        self._ann.__exit__(None, None, None)

    def stop_trace(self) -> None:
        if self.trace_dir is not None and self.t0 is not None:
            import jax

            jax.profiler.stop_trace()


def drive(server: Server, seconds: float, seed: int,
          trace_dir: Optional[str] = None) -> Window:
    """Serve the mix for `seconds`, untraced, and wait for every answer
    owed.  With `trace_dir`, then serve it for TRACE_S more seconds
    under the profiler: tracing slows the host, so the host's readings
    come from the untraced window and only the device's from the
    trace.  An open loop offers `trace_rate_scale` (from the mix) of
    its rate there, to stay under the traced host's knee."""
    win = _segment(server, server.mix, seconds, seed, 0, None)
    if trace_dir is not None:
        mix = server.mix
        if mix["loop"] == "open":
            mix = dict(mix, rate_hz=mix["rate_hz"]
                       * mix.get("trace_rate_scale", 1.0))
        win.traced = _segment(server, mix, TRACE_S, seed, 1, trace_dir)
        win.compiles += win.traced.compiles
    return win


def _segment(server: Server, mix: dict, seconds: float, seed: int,
             stream: int, trace_dir: Optional[str]) -> Window:
    """Serve `mix` for `seconds` from the seed's stream `stream`; then
    wait for every answer owed."""
    rt = server.rt
    clock = rt.clock
    rec = Recorder()
    server.recorder = rec
    compiled_before = sum(ex.compile_count for ex in server.executors())
    events = _compile_events()
    n_compiles = len(events)
    span = _Span(clock, trace_dir)
    loop = _closed_loop if mix["loop"] == "closed" else _open_loop
    try:
        t_first = loop(server, rec, mix, seconds, seed, stream, span)
        compiles = len(events) - n_compiles
        with _annotate("bench.drain"):
            rt.drain()
            owed = sum("rejected" not in r for r in rec.requests.values())
            t_give_up = clock.now() + ANSWER_WAIT_S
            while rec.answered() < owed and clock.now() < t_give_up:
                time.sleep(0.005)
    finally:
        span.stop_trace()
    server.recorder = None
    compiles += sum(ex.compile_count for ex in server.executors()) - compiled_before
    with rec.lock:
        requests = sorted(rec.requests.values(), key=lambda r: r["rid"])
        waves = sorted(rec.waves, key=lambda w: w["dispatch"])
    results = {}
    for r in requests:
        y = rt.pop_result(r["rid"])
        if y is not None:
            results[r["rid"]] = y
    return Window(span.t0, span.t1, t_first, requests, waves, compiles,
                  results)


def _closed_loop(server: Server, rec: Recorder, mix: dict, seconds: float,
                 seed: int, stream: int, span: _Span) -> float:
    rt = server.rt
    clock = rt.clock
    rng = np.random.default_rng([int(seed), 2, stream])
    sides = sorted(mix["sides"])
    p = np.array([mix["sides"][s] for s in sides], float)
    t_end = [float("inf")]

    def send(t_due: float) -> None:
        rid = next(server.rids)
        side = int(rng.choice(sides, p=p / p.sum()))
        k = rid % mix["distinct_images"]
        rec.due(rid, side, k, t_due)
        with _annotate("bench.submit"):
            t = clock.now()
            rej = rt.submit(server.images[side][k], rid=rid)
        rec.submitted(rid, t, rej)

    lock = threading.Lock()

    def on_answer(_rid: int, t_done: float) -> None:
        if t_done < t_end[0]:
            with lock:
                send(clock.now())

    span.open()
    t0 = clock.now()
    t_end[0] = t0 + seconds
    rec.on_answer = on_answer
    with lock:
        for _ in range(mix["clients"]):
            send(t0)
    with _annotate("bench.wait"):
        rt.run_until(t_end[0])
    span.close()
    rec.on_answer = None
    return t0


def _open_loop(server: Server, rec: Recorder, mix: dict, seconds: float,
               seed: int, stream: int, span: _Span) -> float:
    rt = server.rt
    clock = rt.clock
    arrivals = traffic.open_arrivals(mix, seconds, seed, stream)
    span.open()
    t0 = clock.now()
    for a in arrivals:
        rid = next(server.rids)
        t_due = t0 + a.t
        if clock.now() < t_due:
            with _annotate("bench.wait"):
                rt.run_until(t_due)
        rec.due(rid, a.side, a.image, t_due)
        with _annotate("bench.submit"):
            t = clock.now()
            rej = rt.submit(server.images[a.side][a.image], rid=rid)
        rec.submitted(rid, t, rej)
    with _annotate("bench.wait"):
        rt.run_until(t0 + seconds)
    span.close()
    return t0


def release(server: Server) -> None:
    """Stop the server and free what it holds on the device."""
    server.rt.shutdown()
    server.engine.cache.invalidate()
    server.rt = server.pool = server.engine = None
    gc.collect()


# ------------------------------------------------------------------- check

def check(net, cfg: dict, weights: dict, images: dict, win: Window,
          precision: str = "highest") -> dict:
    """Every answer of the window (and of a traced segment) against the
    reference's answer (the net module's `forward`) for the same image:
    the worst error, and how many answers never came."""
    requests, results = _served(win)
    keys = sorted({(r["side"], r["image"]) for r in requests
                   if r["rid"] in results})
    refs = reference.run(net, cfg, weights,
                         [images[s][k] for s, k in keys], precision)
    ref = dict(zip(keys, refs))
    worst = 0.0
    for r in requests:
        y = results.get(r["rid"])
        if y is None:
            continue
        want = ref[(r["side"], r["image"])]
        e = (reference.rel_err(y, want) if y.shape == want.shape
             else WRONG)
        worst = max(worst, e if np.isfinite(e) else WRONG)
    missing = sum("rejected" not in r and r["rid"] not in results
                  for r in requests)
    return {"worst_rel_err": worst, "missing_answers": missing,
            "compared": len(results), "distinct_images": len(keys)}


def _served(win: Window) -> tuple:
    """The requests and answers of all the window's segments."""
    requests, results = [], {}
    for seg in win.segments():
        requests += seg.requests
        results.update(seg.results)
    return requests, results


# ------------------------------------------------------------------ report

@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    cell: dict
    cfg: dict
    mix: dict
    peaks: Optional[dict]
    setup_s: float
    window: Window
    trace: object = None  # trace_reduce.Reduced, in a traced run
    net: object = None  # the configuration's net module


def read_metrics(run: Run, specs: List[dict]) -> dict:
    out = {}
    for m in specs:
        v = load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def counts(win: Window) -> tuple:
    requests, results = _served(win)
    failed = sum("rejected" in r or r["rid"] not in results
                 for r in requests)
    return len(requests), failed


def limits_line(checked: dict, limit: float) -> dict:
    return {
        "worst_rel_err": {"value": checked["worst_rel_err"], "limit": limit},
        "missing_answers": {"value": checked["missing_answers"], "limit": 0},
        "compiles_in_window": {"value": checked["compiles_in_window"],
                               "limit": 0},
    }


def is_correct(line: dict, compared: int) -> bool:
    return compared > 0 and all(v["value"] <= v["limit"]
                                for v in line.values())


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: Optional[dict] = None,
             require_chip: bool = True, peaks: Optional[dict] = None,
             trace_dir: Optional[str] = None, out=sys.stdout,
             err=sys.stderr, config_dir: pathlib.Path = CONFIG_DIR,
             traffic_dir: pathlib.Path = traffic.TRAFFIC_DIR,
             net_dir: pathlib.Path = nets.NET_DIR) -> dict:
    """One run of a cell, as `bench/run.py` makes it; returns the
    result line it printed."""
    bench = bench or load_benchmark()
    cell = find_cell(bench, name)
    cfg = load_config(cell["config"], config_dir)
    mix = traffic.load(cell["traffic"], traffic_dir)
    if require_chip:
        device = device_info(cell["chips"])
        peaks = work.load_peaks(device["kind"])
    else:
        import jax

        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(jax.devices())}
    t_prep = time.monotonic()
    server = prepare(cfg, mix, seed, net_dir)
    phases = {"start_to_prepare": t_prep - t_start, **server.phases}
    if trace:
        import shutil

        trace_dir = trace_dir or str(ROOT / ".bench_trace" / name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = drive(server, seconds, seed, trace_dir if trace else None)
    setup_s = win.t_first - t_start
    print("setup phases (s): " + ", ".join(
        f"{k} {v:.3f}" for k, v in phases.items()), file=err)
    device["memory_peak_bytes"] = memory_peak_bytes()
    net, weights, images = server.net, server.weights, server.images
    release(server)

    reduced = None
    if trace:
        from bench import trace_reduce

        t_red = time.monotonic()
        reduced = trace_reduce.reduce_file(trace_reduce.find_xplane(trace_dir))
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s
        print(f"trace read in {time.monotonic() - t_red:.1f} s", file=err)
        seg = win.traced
        waits = [r["dispatch"] - r["admit"] for r in seg.requests
                 if "dispatch" in r]
        print(f"traced segment: {len(seg.requests)} requests, "
              f"{sum('rejected' in r for r in seg.requests)} refused, "
              f"{len(seg.waves)} waves, queue wait p95 "
              f"{1e3 * np.percentile(waits, 95) if waits else float('nan'):.1f}"
              " ms", file=err)
    run = Run(cell, cfg, mix, peaks, setup_s, win, reduced, net)
    kind = "per_layer" if trace else "end_to_end"
    metrics = read_metrics(run, metrics_for(bench, name, kind))

    checked = check(net, cfg, weights, images, win)
    checked["compiles_in_window"] = win.compiles
    line = limits_line(checked, cfg["rel_err_limit"])
    correct = is_correct(line, checked["compared"])
    attempted, failed = counts(win)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced.top_ops(),
                               "idle_gaps": reduced.idle_gaps()}
        top = max(mix["buckets"])
        for lay in work.net_work(net, cfg, top, mix["max_batch"], peaks) if peaks else ():
            print(f"layer {lay['h']}px {lay['c_in']}->{lay['c_out']}: "
                  f"bound {lay['bound']} at bucket {top}, "
                  f"batch {mix['max_batch']}", file=err)
    result["check"] = line
    print(f"answers compared {checked['compared']} over "
          f"{checked['distinct_images']} distinct images", file=err)
    for k, v in line.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result

