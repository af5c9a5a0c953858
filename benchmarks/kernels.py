"""Kernel micro-benchmarks: lsh_hash / bucket-core / pairwise / attention
wall time on whatever platform JAX runs (``repro.kernels.ops`` picks the
Pallas hash kernel on a TPU and the jnp reference elsewhere) + dynamic-
update throughput across the three inner engines (sequential dict, batched
dict, SoA vectorised)."""

from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ClusterConfig, build_index
from repro.data import blobs
from repro.kernels import ops, ref

RESULTS = Path(__file__).resolve().parent.parent / "results"


def _time(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _insert_throughput(cfg, X, backend, batch):
    t0 = time.perf_counter()
    ix = build_index(cfg.replace(backend=backend))
    for s in range(0, len(X), batch):
        ix.insert_batch(X[s:s + batch])
    return time.perf_counter() - t0


def run(smoke: bool = False):
    rows = []
    rng = np.random.default_rng(0)

    # hashing: (n, d) -> (n, t, 2)
    hash_shapes = ([(20_000, 20, 10)] if smoke
                   else [(100_000, 20, 10), (500_000, 20, 10)])
    for n, d, t in hash_shapes:
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        eta = jnp.asarray(rng.uniform(0, 1.5, t), jnp.float32)
        mix = jnp.asarray(rng.integers(1, 2**31 - 1, (2, t, d)), jnp.int32)
        dt = _time(lambda a, b, c: ops.lsh_hash(a, b, c, inv_cell=1 / 1.5),
                   x, eta, mix)
        rows.append({"bench": f"lsh_hash n={n}", "us_per_call": dt * 1e6,
                     "derived": f"{n / dt / 1e6:.1f} Mpoints/s"})

    # bucket occupancy / support-count kernels (the SoA engine's inner pass)
    n, t, nb = (4_000, 8, 512) if smoke else (65_536, 8, 4_096)
    slots = jnp.asarray(rng.integers(0, nb, (n, t)), jnp.int32)
    sizes = jnp.asarray(rng.integers(0, 20, nb), jnp.int32)
    dt = _time(lambda a, b: ops.bucket_core_stats(a, b, k=10), slots, sizes)
    rows.append({"bench": f"bucket_core_stats n={n}", "us_per_call": dt * 1e6,
                 "derived": f"{n / dt / 1e6:.1f} Mpoints/s"})
    dt = _time(lambda a: ops.slot_counts(a, n_slots=nb), slots)
    rows.append({"bench": f"slot_counts n={n}", "us_per_call": dt * 1e6,
                 "derived": f"{n * t / dt / 1e6:.1f} Mupdates/s"})

    # pairwise counts
    for n, d in [(1_000 if smoke else 4_000, 20)]:
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        dt = _time(lambda a: ref.eps_neighbor_counts(a, 0.75), x)
        rows.append({"bench": f"pairwise n={n}", "us_per_call": dt * 1e6,
                     "derived": f"{2 * n * n * d / dt / 1e9:.1f} GFLOP/s"})

    # attention (jnp chunked fallback used by models)
    from repro.models.attention import chunked_attention
    s_att = 256 if smoke else 1024
    q = jnp.asarray(rng.normal(size=(1, 8, s_att, 64)), jnp.bfloat16)
    kv = jnp.asarray(rng.normal(size=(1, 2, s_att, 64)), jnp.bfloat16)
    dt = _time(lambda a, b: chunked_attention(a, b, b, chunk=256), q, kv)
    flops = 4 * 1 * 8 * s_att * s_att * 64 / 2  # causal half
    rows.append({"bench": f"attention b1 h8 s{s_att}", "us_per_call": dt * 1e6,
                 "derived": f"{flops / dt / 1e9:.1f} GFLOP/s"})

    # dynamic-update throughput: sequential dict vs batched dict vs SoA
    n_dyn = 2_000 if smoke else 16_000
    batch = 250 if smoke else 1_000
    X, _ = blobs(n=n_dyn, d=20, n_clusters=10, seed=1)
    cfg = ClusterConfig(d=20, k=10, t=10, eps=0.75, seed=0)
    t0 = time.perf_counter()
    seq = build_index(cfg.replace(backend="dynamic"))
    for p in X:
        seq.insert(p)
    dt_seq = time.perf_counter() - t0
    dt_bat = _insert_throughput(cfg, X, "batched", batch)
    dt_soa = _insert_throughput(cfg, X, "soa", batch)
    rows.append({"bench": f"dyn insert {n_dyn} seq",
                 "us_per_call": dt_seq / n_dyn * 1e6,
                 "derived": f"{n_dyn / dt_seq:.0f} pts/s"})
    rows.append({"bench": f"dyn insert {n_dyn} batched",
                 "us_per_call": dt_bat / n_dyn * 1e6,
                 "derived": f"{n_dyn / dt_bat:.0f} pts/s ({dt_seq / dt_bat:.2f}x seq)"})
    rows.append({"bench": f"dyn insert {n_dyn} soa",
                 "us_per_call": dt_soa / n_dyn * 1e6,
                 "derived": f"{n_dyn / dt_soa:.0f} pts/s ({dt_bat / dt_soa:.2f}x batched)"})

    for r in rows:
        print(f"{r['bench']:36} {r['us_per_call']:12.1f} us  {r['derived']}")
    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "kernels.json").write_text(json.dumps(rows, indent=1))
    return rows


if __name__ == "__main__":
    import sys
    run(smoke="--smoke" in sys.argv)
