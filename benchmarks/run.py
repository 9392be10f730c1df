"""Benchmark driver: one section per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/*.py).
    PYTHONPATH=src python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller batches")
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny-geometry CI smoke: catches dispatcher regressions that "
        "only bite at execution time (implies --only convserve unless "
        "--only is given)",
    )
    ap.add_argument(
        "--only", default=None,
        help="comma list: fig2,fig3,analysis,r_sweep,lm,roofline,convserve",
    )
    ap.add_argument(
        "--bench-json", default=None, metavar="PATH",
        help="where the convserve section writes its machine-readable "
        "results (default: BENCH_convserve.json in the cwd)",
    )
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    batch = 1 if (args.quick or args.smoke) else 2
    if args.smoke and args.only is None:
        args.only = "convserve"
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    sections = []
    if want("analysis"):
        from benchmarks import analysis_table

        sections.append(("paper S5 analysis table", analysis_table.main, ()))
    if want("fig2"):
        from benchmarks import paper_fig2

        sections.append(
            ("paper Fig2 (VGG/ResNet layers)", paper_fig2.main, (batch,))
        )
    if want("fig3"):
        from benchmarks import paper_fig3

        sections.append(("paper Fig3 (i7 layers)", paper_fig3.main, (batch,)))
    if want("r_sweep"):
        from benchmarks import r_sweep

        sections.append(("R-parameter sweep (S4.1.2)", r_sweep.main, (batch,)))
    if want("lm"):
        from benchmarks import lm_bench

        sections.append(("LM framework benches", lm_bench.main, ()))
    if want("roofline"):
        from benchmarks import roofline_report

        sections.append(
            ("roofline table", roofline_report.main, ([],))
        )
    if want("convserve"):
        import pathlib

        from benchmarks import convserve_bench

        if args.bench_json:
            convserve_bench.BENCH_PATH = pathlib.Path(args.bench_json)
        sections.append(
            (
                "convserve engine (planned nets)",
                convserve_bench.main,
                (batch, 64, args.smoke),
            )
        )

    failures = 0
    for title, fn, fargs in sections:
        print(f"\n## {title}", flush=True)
        try:
            fn(*fargs)
        except Exception:
            failures += 1
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
