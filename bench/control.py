#!/usr/bin/env python3
"""Readings that set a configuration's error limit, on the chip.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 3

For each seed, in one process: the cell's server is set up from the
seed and driven for a short window at the cell's own load, and every
answer is compared with the reference (`program`).  Then the control
-- the reference itself, computed in the next precision down, `high`
(three bfloat16 passes), and in one bfloat16 pass (`bf16`) -- is put
in the program's place: its answer for each request's image is served
as that request's answer, and the window is judged by the harness's
own check at the configuration's `rel_err_limit`.  One JSON line per
seed, with each reading and whether it came out `correct`: the limit
lies above every `program` reading and below every reading of the
control.  Off the chip it exits non-zero.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _control(net, cfg, weights, images, win, precision):
    """The window as it would read with the reference at `precision` in
    the program's place: each answered request's answer replaced by the
    control's for the same image."""
    from bench import harness, reference

    keys = sorted({(r["side"], r["image"]) for r in win.requests
                   if r["rid"] in win.results})
    outs = reference.run(net, cfg, weights,
                         [images[s][k] for s, k in keys], precision)
    ctl = dict(zip(keys, outs))
    results = {r["rid"]: ctl[(r["side"], r["image"])] for r in win.requests
               if r["rid"] in win.results}
    return harness.Window(win.t0, win.t1, win.t_first, win.requests,
                          win.waves, win.compiles, results)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, traffic

    harness.configure()
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    try:
        harness.device_info(cell["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    cfg = harness.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        server = harness.prepare(cfg, mix, seed)
        win = harness.drive(server, args.seconds, seed)
        net, weights, images = server.net, server.weights, server.images
        harness.release(server)
        row = {"seed": seed, "answers": len(win.results)}
        for name, served in [("program", win)] + [
                (prec, _control(net, cfg, weights, images, win, prec))
                for prec in ("high", "bf16")]:
            got = harness.check(net, cfg, weights, images, served)
            got["compiles_in_window"] = win.compiles
            line = harness.limits_line(got, cfg["rel_err_limit"])
            row[name] = got["worst_rel_err"]
            row[f"{name}_correct"] = harness.is_correct(line, got["compared"])
        row["missing"] = got["missing_answers"]
        row["compiles_in_window"] = win.compiles
        row["seconds"] = time.monotonic() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
