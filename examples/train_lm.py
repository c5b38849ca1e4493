"""End-to-end LM training example: a ~100M-param member of the assigned
xlstm family for a few hundred steps on the synthetic corpus, with async
checkpointing and exact resume.

  PYTHONPATH=src python examples/train_lm.py                # ~100M, 300 steps
  PYTHONPATH=src python examples/train_lm.py --tiny         # CI-speed
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch import train as train_mod
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()

    if args.tiny:
        argv = ["--arch", "xlstm-125m", "--reduced", "--steps", "30",
                "--batch", "4", "--seq", "64", "--ckpt", args.ckpt,
                "--ckpt-every", "10", "--log-every", "5"]
    else:
        # full xlstm-125m (the ~100M-class assigned arch) on CPU
        argv = ["--arch", "xlstm-125m", "--steps", str(args.steps),
                "--batch", "4", "--seq", "256", "--ckpt", args.ckpt,
                "--ckpt-every", "50", "--log-every", "10"]
    history = train_mod.main(argv)
    losses = [h["loss"] for h in history]
    print(f"\nloss: {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"({'decreasing' if losses[-1] < losses[0] else 'check config'})")


if __name__ == "__main__":
    enable_compile_cache()
    main()
