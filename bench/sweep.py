#!/usr/bin/env python3
"""Find the highest Poisson rate an open-loop cell's server sustains.

    python bench/sweep.py --workload vgg13-s3.online-mixed \\
        --rates 80,120,160 --seconds 8 --seed 7 [--bursts 0|1]

One process sets the cell's server up once, then offers each rate in
turn for `--seconds`, as Poisson arrivals alone (`--bursts 0`, the
default) or with the mix's bursts, and prints one JSON line per rate:
latency percentiles from the instant each request was due, the answers
still owed when the window closed (a backlog that grows with the
window), and the p95 of each half of the window.  A rate is sustained
when the backlog does not grow and p95 stays within the mix's `slo_s`.
The benchmark's cells never search: the rate found here is written
into the mix file once.  Off the chip it exits non-zero.
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated Hz")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--bursts", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import numpy as np

    from bench import harness, traffic

    harness.configure()
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    try:
        harness.device_info(cell["chips"])
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    cfg = harness.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    server = harness.prepare(cfg, mix, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        server.mix = dict(mix, rate_hz=rate,
                          burst=mix.get("burst") if args.bursts else None)
        win = harness.drive(server, args.seconds, args.seed)
        lat = np.array([r["done"] - r["due"] for r in win.requests
                        if "done" in r])
        due = np.array([r["due"] for r in win.requests if "done" in r])
        mid = win.t0 + args.seconds / 2
        owed = sum(r["due"] < win.t1 and r.get("done", np.inf) > win.t1
                   for r in win.requests)
        row = {
            "rate_hz": rate, "requests": len(win.requests),
            "answered": int(lat.size),
            "p50_ms": float(1e3 * np.percentile(lat, 50)),
            "p95_ms": float(1e3 * np.percentile(lat, 95)),
            "p95_first_half_ms": float(1e3 * np.percentile(lat[due < mid], 95)),
            "p95_second_half_ms": float(1e3 * np.percentile(lat[due >= mid], 95)),
            "owed_at_close": int(owed),
            "waves": len(win.waves),
            "wave_fill": sum(w["n"] for w in win.waves)
            / max(sum(w["batch"] for w in win.waves), 1),
            "slo_s": mix.get("slo_s"),
        }
        print(json.dumps(row), flush=True)
    harness.release(server)
    return 0


if __name__ == "__main__":
    sys.exit(main())
