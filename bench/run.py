#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json, at the root of the
checkout.  Set-up (the server planned, built and warmed from the seed)
is timed from the start of this process to the first timed request;
then the cell's traffic runs for `--seconds`, and every answer of the
window is compared with the plain reference.  The last line of
standard output is one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics, or with `--trace 1` its
per-layer metrics), `device`, with `--trace 1` a `breakdown`, and last
`check`: each number compared, beside its limit.  The same numbers end
standard error.  With `--trace 1` the window is served untraced, as
without, and then the mix for a few seconds more under the profiler:
host readings come from the window, device times from the trace.

With no TPU, or fewer chips than the cell asks for, it exits non-zero
and prints no result.  JAX's persistent compilation cache is kept in
`.jax_cache/` at the checkout's root.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="where the profiler writes (default "
                         ".bench_trace/<workload> in the checkout)")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    harness.configure()
    try:
        harness.run_cell(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START,
                         trace_dir=args.trace_dir)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
