"""End-to-end LM training driver: a small model, a few hundred steps, with
checkpointing + resume (scaled to this 1-core container; the same code path
`launch/train.py` runs the full configs on a real cluster).

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse
import sys

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main as train_main


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    train_main([
        "--arch", args.arch, "--reduced",
        "--steps", str(args.steps),
        "--batch", "8", "--seq", "64", "--lr", "3e-3",
        "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "100",
    ])


if __name__ == "__main__":
    main()
