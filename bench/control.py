"""The control of the comparison that decides ``correct``: the reference,
put in the program's place one precision step below the configuration's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--steps 20]

The configuration states float32 hashing and float64 coordinates.  The
control hashes in bfloat16 and stores coordinates in float32, answers
for the live window the cell holds after ``--steps`` steps (a snapshot,
``labels()``, a change feed and 16 ``label()`` answers per state for a
cell whose mix reads labels), and is compared with the float32 reference
exactly as a run's outputs are.  Each seed prints the counts; the control
must fail at least one of them.  The same answers at the configuration's
own precisions must compare clean, which checks the comparison itself.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import ml_dtypes
import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import reference  # noqa: E402

LOWER = {"hash": ml_dtypes.bfloat16, "coordinates": np.float32}
STATED = {"hash": np.float32, "coordinates": np.float64}


def readings(workload: str, seed: int, steps: int = 20, lower: bool = True,
             live_window=None, root: Path = harness.ROOT) -> dict:
    """The comparison's counts for the reference in the program's place."""
    cell = harness.find_cell(workload, root)
    conf = cell.config
    gen_mod = harness._load_module(root / "bench" / "generators"
                                   / f"{conf['generator']}.py")
    stream = harness.Stream(gen_mod.Generator(conf["data"], seed))
    batch = int(cell.mix["batch"] or conf["batch"])
    window = int(live_window or conf["live_window"])
    lo = steps * batch
    hi = lo + window
    ids = np.arange(hi, dtype=np.int64)  # ids as an index acknowledges them
    fam = reference.LSHFamily(conf["d"], conf["eps"], conf["t"],
                              int(conf["lsh_seed"]))
    points = stream.range(lo, hi)
    ref = reference.cluster(reference.hash_keys(points, fam), conf["k"],
                            ids[lo:hi])
    reads = sum(op.get("count", 0) for op in cell.mix["steps"]
                if op["op"] == "label")
    rng = np.random.default_rng([seed % 2**64, 7])
    queries = (lo + rng.integers(0, window, size=reads)).tolist()
    prec = LOWER if lower else STATED
    out = reference.reference_outputs(
        points, ids[lo:hi], fam, conf["k"], hash_dtype=prec["hash"],
        coord_dtype=prec["coordinates"], lo=lo, queries=queries)
    exp = reference.Expected(ids, lo, hi, {0: (lo, hi)})
    return reference.compare(points, ref, exp, out,
                             state_points=stream.range, k=conf["k"],
                             fam=fam)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args(argv)
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        low = readings(args.workload, seed, args.steps, lower=True)
        same = readings(args.workload, seed, args.steps, lower=False)
        ok = any(v > 0 for v in low.values())
        failed_all &= ok and not any(same.values())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": low, "stated_precision": same,
                          "control_fails": ok,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
