"""Benchmark harness: one entry per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV lines (harness contract) and a
human-readable report; JSON artifacts land in results/.

  PYTHONPATH=src python -m benchmarks.run            # CI-scale
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale n
"""

from __future__ import annotations

import argparse


def main(argv=None) -> None:
    from repro.api import available_backends

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny streams for CI: blobs-only table2, small n")
    ap.add_argument("--only", default=None,
                    choices=["table2", "figure2", "scaling", "shards",
                             "serving", "kernels", "ablations",
                             "paper_roofline", "roofline", "quality"])
    ap.add_argument("--workers", type=int, default=0,
                    help="thread-pool fan-out for the sharded backend")
    ap.add_argument("--transport", default="local",
                    choices=("local", "process"),
                    help="sharded-backend transport for the serving bench "
                         "(process = spawned per-shard server processes)")
    ap.add_argument("--backend", default="dynamic",
                    choices=available_backends(),
                    help="repro.api backend for the dynamic engine under test")
    ap.add_argument("--shards", type=int, default=0,
                    help="shard the engine under test across S key ranges "
                         "(backend=sharded; any other backend becomes the "
                         "inner engine)")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    csv_rows = []

    def emit(name, us, derived):
        csv_rows.append(f"{name},{us:.1f},{derived}")

    if args.only in (None, "table2"):
        print("\n===== Table 2: streaming time / ARI / NMI =====")
        from .table2 import run as t2
        rows = t2(scale=1.0 if args.full else (0.02 if args.smoke else 0.05),
                  datasets=["blobs"] if args.smoke else None,
                  algos=tuple(dict.fromkeys(
                      (args.backend, "emz-static", "emz-fixed", "naive"))),
                  shards=args.shards)
        for r in rows:
            emit(f"table2/{r['dataset']}/{r['algo']}",
                 r["time_s"] * 1e6,
                 f"ARI={r['ari']:.3f};NMI={r['nmi']:.3f}")

    if args.only in (None, "figure2"):
        print("\n===== Figure 2: blobs arrival-order study =====")
        from .figure2 import main as f2
        out = f2(["--n", "20000" if args.full else
                  ("2000" if args.smoke else "8000"),
                  "--backend", args.backend, "--shards", str(args.shards)])
        for order, curves in out.items():
            for algo, c in curves.items():
                emit(f"figure2/{order}/{algo}", c["cum_time"][-1] * 1e6,
                     f"ARI={c['ari'][-1]:.3f}")

    if args.only in (None, "scaling"):
        print("\n===== Update-complexity scaling (Thm 1 / Remark 1) =====")
        from .scaling import run as sc
        rows = sc(max_n=64000 if args.full else
                  (4000 if args.smoke else 16000),
                  backend=args.backend, shards=args.shards)
        for r in rows:
            emit(f"scaling/n{r['n']}", r["dyn_per_update_us"],
                 f"emz_recompute={r['emz_recompute_s']:.3f}s")

    if args.only == "shards" or (args.only is None and args.shards > 1):
        print("\n===== Shard-count scaling (update throughput vs S) =====")
        from .scaling import run_shards as ss
        inner = args.backend if args.backend != "sharded" else "batched"
        rows = ss((1, 2, 4, 8) if not args.smoke else (1, args.shards or 2),
                  max_n=16000 if args.full else
                  (2000 if args.smoke else 8000),
                  inner=inner)
        for r in rows:
            emit(f"shards/S{r['shards']}", r["us_per_update"],
                 f"updates_per_s={r['updates_per_s']:.0f};"
                 f"boundary={r['n_boundary_buckets']}")

    if args.only == "serving" or (args.only is None and args.shards > 1):
        print("\n===== Serving mix (interleaved updates + label() hot path) =====")
        from .serving_mix import run as sm
        inner = args.backend if args.backend != "sharded" else "batched"
        rows = sm(shards=(1, args.shards or 2) if args.smoke else (1, 4, 8),
                  workers=(0, args.workers) if args.workers else (0,),
                  n=1200 if args.smoke else 16000,
                  batch=100 if args.smoke else 500,
                  rounds=3 if args.smoke else 4,
                  queries=8 if args.smoke else 16,
                  inner=inner, transport=args.transport)
        for r in rows:
            emit(f"serving_mix/S{r['shards']}_w{r['workers']}_"
                 f"{'inc' if r['incremental'] else 'rebuild'}",
                 r["label_after_update_p50_us"],
                 f"steady_p50={r['label_steady_p50_us']:.1f}us;"
                 f"updates_per_s={r['updates_per_s']:.0f}")

    if args.only in (None, "kernels"):
        print("\n===== Kernel / batched-update benches =====")
        from .kernels import run as kr
        for r in kr(smoke=args.smoke):
            emit(r["bench"].replace(" ", "_"), r["us_per_call"], r["derived"])

    if args.only in (None, "ablations"):
        print("\n===== Ablations (k/t sensitivity, backends, repair) =====")
        from .ablations import run as ab
        kt, orphan, backend, repair = ab()
        for r in backend:
            emit(f"ablation/ett_{r['backend']}", r["us_per_op"], "per link/cut op")
        emit("ablation/kt_spread",
             (max(r["ari"] for r in kt) - min(r["ari"] for r in kt)) * 1e6,
             "ARI spread over 3x3 (k,t) grid")
        emit("ablation/repair_scans_per_del", repair["frac"] * 1e6,
             f"links={repair['repair_links']}")

    if args.only in (None, "paper_roofline"):
        print("\n===== Paper-technique roofline (grid-LSH hashing) =====")
        from .paper_roofline import run as pr
        rows = pr()
        emit("paper_roofline/floor", rows["roofline_time_floor_us"],
             "traffic floor @819GB/s")
        emit("paper_roofline/jnp_ref", rows["roofline_time_ref_us"],
             f"{rows['ref_vs_floor']:.2f}x floor")
        emit("paper_roofline/pallas", rows["roofline_time_floor_us"],
             "1.00x floor (VMEM single pass)")

    if args.only == "quality":
        # explicit-only: the full sweep re-times every engine on the
        # paper-scale stream, so it does not ride the default run
        print("\n===== Quality/speed frontier (sampled-core tier) =====")
        from .quality_speed import main as qs
        out = qs(["--smoke"] if args.smoke else [])
        for r in out["sweep"]:
            rate = r["sample_rate"]
            if r["backend"] == "approx":
                emit(f"quality/approx_r{rate}",
                     1e6 / r["insert_per_s"],
                     f"ARI={r['ari_vs_exact']:.4f};"
                     f"speedup={r['insert_speedup_vs_soa']:.2f}x")
            else:
                emit(f"quality/tiered_r{rate}",
                     1e6 / r["update_per_s"],
                     f"div_ari={r['divergence_ari']:.4f};"
                     f"label_per_s={r['label_per_s']:.0f}")

    if args.only in (None, "roofline"):
        print("\n===== Roofline table (from dry-run artifacts) =====")
        try:
            from repro.launch.roofline import build_table, format_table
            rows = build_table()
            print(format_table(rows))
            for r in rows:
                if r.get("status") == "ok":
                    emit(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
                         r["bound_time_s"] * 1e6,
                         f"dominant={r['dominant']};MFU_ub={r.get('mfu_upper_bound', 0):.3f}")
        except FileNotFoundError:
            print("(no results/dryrun.json yet — run repro.launch.dryrun)")

    print("\n===== CSV =====")
    print("name,us_per_call,derived")
    for line in csv_rows:
        print(line)


if __name__ == "__main__":
    main()
