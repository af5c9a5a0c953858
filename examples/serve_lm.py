"""Serve a small model with batched requests + request clustering.

    PYTHONPATH=src python examples/serve_lm.py
"""
from repro.compile_cache import enable_compile_cache
from repro.launch.serve import main as serve_main

if __name__ == "__main__":
    enable_compile_cache()
    serve_main(["--arch", "mamba2-780m", "--smoke", "--requests", "12",
                "--batch", "4", "--cluster"])
