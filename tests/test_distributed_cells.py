"""Distributed integration tests: build_cell lower+compile (and run) on an
8-device host mesh.  Runs in a subprocess because the placeholder device
count must be set before jax initialises (the main test process keeps 1
device, as required)."""

import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch.cells import build_cell
from repro.launch.hlo_analysis import analyze_compiled

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
out = {}

def run(arch, shape_kind, execute=False):
    cfg = get_config(arch).smoke()
    if shape_kind == "train":
        shape = ShapeConfig("t", 32, 8, "train")
    elif shape_kind == "prefill":
        shape = ShapeConfig("p", 64, 4, "prefill")
    else:
        shape = ShapeConfig("d", 64, 8, "decode")
    cell = build_cell(arch, shape.name, mesh, cfg=cfg, shape=shape,
                      grad_accum=2 if shape_kind == "train" else None)
    lowered = cell.lower()
    compiled = lowered.compile()
    rec = analyze_compiled(compiled)
    assert rec["flops_per_device"] > 0
    assert rec["hbm_bytes_per_device"] > 0
    if execute:
        # materialise real inputs from the ShapeDtypeStructs and run 1 step
        def make(x, key=[0]):
            if x.dtype == jnp.int32:
                if x.shape == ():
                    return jnp.asarray(0, jnp.int32)
                return jnp.zeros(x.shape, jnp.int32)
            key[0] += 1
            # non-negative so Adam's second-moment stays valid
            return jnp.abs(
                jax.random.normal(jax.random.PRNGKey(key[0]), x.shape, jnp.float32)
            ).astype(x.dtype) * 0.02
        args = jax.tree.map(make, cell.args)
        res = cell.run(*args)
        flat = jax.tree.leaves(res)
        for l in flat:
            assert np.isfinite(np.asarray(l, np.float32)).all()
    return rec

results = {}
results["dense_train"] = run("granite-20b", "train", execute=True)
results["moe_train"] = run("dbrx-132b", "train", execute=True)
results["ssm_train"] = run("mamba2-780m", "train", execute=True)
results["hybrid_train"] = run("hymba-1.5b", "train")
results["audio_train"] = run("whisper-small", "train")
results["vlm_train"] = run("llava-next-mistral-7b", "train")
results["gemma_train"] = run("gemma3-27b", "train")
results["dense_prefill"] = run("phi3-mini-3.8b", "prefill")
results["dense_decode"] = run("qwen1.5-110b", "decode", execute=True)
results["gemma_decode"] = run("gemma3-27b", "decode", execute=True)
results["ssm_decode"] = run("mamba2-780m", "decode", execute=True)
results["moe_decode"] = run("granite-moe-1b-a400m", "decode")
results["hybrid_decode"] = run("hymba-1.5b", "decode")
results["audio_decode"] = run("whisper-small", "decode")
print("RESULTS" + json.dumps({k: v["flops_per_device"] for k, v in results.items()}))
"""


@pytest.mark.slow
def test_cells_compile_and_run_on_host_mesh():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        cwd=Path(__file__).resolve().parents[1],
        capture_output=True, text=True, timeout=3000,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed\nstdout:\n{proc.stdout[-4000:]}\n"
            f"stderr:\n{proc.stderr[-6000:]}"
        )
    assert "RESULTS" in proc.stdout
