#!/usr/bin/env python3
"""HashMem serving benchmark: one run of one cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's table from the seed, serves its YCSB traffic through
``ServingEngine`` for ``--seconds``, checks every answer against a host
reference, and prints one JSON object as the last line of standard output.
``--trace 0`` reports the end-to-end metrics, ``--trace 1`` the per-layer
ones, read from a profiler window inside the measured one.  The numbers
compared for ``correct`` end standard error, each with its limit.

Runs only where JAX finds a TPU with the chips the cell asks for; anywhere
else it exits 1 and prints no result.  A traced run in which a per-layer
metric the cell declares reads nothing exits 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perfbench import harness
    cell = harness.load_cell(ROOT, args.workload)
    # the compile cache is the checkout's own, whatever the environment
    # says, set before JAX starts its backend; libtpu's logs would go to a
    # fixed /tmp path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / harness.CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    harness.enable_cache(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"perfbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s). "
              "Nothing was run.", file=sys.stderr)
        return 1
    try:
        result, checks = harness.run(ROOT, args.workload, args.seed,
                                     args.seconds, bool(args.trace), T_START)
    except harness.SilentMetric as e:
        print(f"perfbench: {e}. No result.", file=sys.stderr)
        return 3
    for name, value, limit in checks:
        print(f"check {name}={value} limit={limit}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
