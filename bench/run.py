"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <config>.<mix> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout.  The run needs as many TPU chips as the cell
asks for: where JAX's first device is not a TPU, or there are too few, or
the device is not in ``bench/peaks.json``, it exits non-zero and prints no
result.  With ``--trace 0`` the result carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics from a profiler trace of
the window.  The last line of standard output is one JSON object; the
numbers compared with the reference, each with its limit, are the last
lines of standard error and the result's last key.

JAX's compilation cache is kept in ``.jax_cache/`` at the root of the
checkout, a fixed path, so only the first run there compiles.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CACHE_DIR = BENCH.parent / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu logs under /tmp/tpu_logs unless told otherwise; a run writes
    # only inside its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    sys.path.insert(0, str(BENCH))
    import harness

    cell = harness.find_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"has {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"bench: no peaks for device {kind!r} in bench/peaks.json",
              file=sys.stderr)
        return 1
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from repro.compile_cache import enable_compile_cache

    print(f"bench: {kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}", file=sys.stderr)
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              peaks=peaks[kind])
    print(f"correct: {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
