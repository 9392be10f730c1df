"""Online ConvNet serving: the runtime end to end on Poisson traffic.

Compiles a planned convnet into a 2-replica pool (one shared
pre-transformed kernel cache), replays a seeded open-loop Poisson trace
with a 50 ms SLO through the deadline-aware wave scheduler, and prints
the telemetry document -- throughput, queue/compute/e2e percentiles,
wave + admission counters, cache reuse.

The flight recorder rides along: every admit/wave/stage lands in a span
ring, incidents (SLO breach, verification error) dump it immediately,
and the whole run is written to ``serve_online.trace.json`` on exit --
open it in Perfetto (https://ui.perfetto.dev) or chrome://tracing.

    PYTHONPATH=src python examples/serve_online.py
"""

import json
import sys

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.convnets import tiny_testnet  # noqa: E402
from repro.convserve import Engine, init_weights  # noqa: E402
from repro.convserve.obs import (  # noqa: E402
    FlightRecorder,
    Tracer,
    roofline_table,
    write_trace,
)
from repro.convserve.runtime import (  # noqa: E402
    INTERACTIVE,
    STANDARD,
    ReplicaPool,
    RuntimeConfig,
    ServeRuntime,
    make_images,
    poisson_trace,
)

TRACE_PATH = "serve_online.trace.json"


def main() -> None:
    enable_compile_cache()
    spec = tiny_testnet(4)
    weights = init_weights(spec, seed=0)
    engine = Engine()

    pool = ReplicaPool.build(engine, spec, weights, n=2, input_hw=(32, 32))
    cfg = RuntimeConfig(
        max_batch=8,
        buckets=(32, 64),
        queue_depth=64,
        # interactive requests flush waves after 60 ms of slack,
        # standard ones after 200 ms
        slo_s={INTERACTIVE: 0.06, STANDARD: 0.20},
        service_est_s=0.005,
    )
    tracer = Tracer()
    recorder = FlightRecorder(tracer, path_prefix="serve_online")
    rt = ServeRuntime(pool, cfg, tracer=tracer, recorder=recorder)

    # compile the max_batch program for every (bucket, replica) and
    # prepare the shared kernel transforms, so the trace measures
    # serving rather than jit compiles
    rt.warmup()

    trace = poisson_trace(
        rate_hz=120.0, n=150, seed=7, sizes=(24, 32, 48, 64),
        priorities=(INTERACTIVE, STANDARD),
    )
    images = make_images(trace, c=4, seed=8)
    results = rt.play(trace, images)
    print(f"served {len([a for a in trace if a.rid in results])}"
          f"/{len(trace)} requests")

    doc = rt.stats(profile_bucket=32)
    e2e = doc["latency"]["e2e"]
    print(f"p50 {e2e['p50_s'] * 1e3:.1f} ms   "
          f"p95 {e2e['p95_s'] * 1e3:.1f} ms   "
          f"p99 {e2e['p99_s'] * 1e3:.1f} ms")
    print(json.dumps(
        {k: doc[k] for k in ("counters", "scheduler", "cache")},
        indent=1, sort_keys=True,
    ))
    rf = doc.get("roofline")
    if rf:
        print(roofline_table(rf["stages"], hw_name=rf["hw"]["name"]))
    rt.shutdown()

    n = write_trace(tracer, TRACE_PATH)
    print(f"wrote {TRACE_PATH} ({n} events) -- open in Perfetto; "
          f"recorder trips: {recorder.stats()['trips'] or 'none'}")


if __name__ == "__main__":
    main()
