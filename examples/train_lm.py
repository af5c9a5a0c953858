"""End-to-end driver: train a granite-family LM for a few hundred steps on
the synthetic pipeline with DBSCAN curation enabled.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

Defaults are CPU-feasible (~5M params); pass --full-100m on real hardware
for the ~124M-param preset (12 layers x d_model 768, vocab 32k).
"""
import argparse

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main as train_main

if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--full-100m", action="store_true")
    args = ap.parse_args()
    argv = [
        "--arch", "granite-20b",
        "--steps", str(args.steps),
        "--batch", "8", "--seq", "128",
        "--curation", "balance",
        "--ckpt-every", "100",
    ]
    argv += ["--preset", "100m"] if args.full_100m else [
        "--smoke", "--d-model-override", "512"]
    train_main(argv)
