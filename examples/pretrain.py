"""End-to-end pretraining driver: a paper-scale LLaMA (60M/130M) trained for
a few hundred steps with SCALE, with checkpointing + auto-resume.

  PYTHONPATH=src python examples/pretrain.py --arch llama-60m --steps 300
  # kill it at any point, re-run with the same command: it resumes.

This is the production path — on a TPU slice the identical code shards
over the (data, model) mesh.
"""
import argparse

from repro.launch.train import main as train_main

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-60m")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--optimizer", default="scale")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_pretrain")
    args = ap.parse_args()
    train_main([
        "--arch", args.arch, "--steps", str(args.steps),
        "--batch", str(args.batch), "--seq", str(args.seq),
        "--optimizer", args.optimizer, "--lr", "1e-3",
        "--dtype", "float32",
        "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "50",
        "--resume", "auto", "--log-every", "10",
    ])
