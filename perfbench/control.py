#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's and the control's.

    python3 perfbench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, in one process: the cell's set-up, a window of ``--seconds``
of its own traffic through the engine, the drain, then two comparisons with
the host reference over the same schedule.  One compares the program's
answers (what every benchmark run does); the other compares a control's,
a table that breaks one guarantee the configuration states:

* cells with writes: :func:`reference.stale_answers`, writes visible one
  tick late (against read-your-writes from the next tick on);
* read-only cells: :func:`reference.short_key_answers`, keys matched on
  their low 16 bits (against an exact 32-bit key match).

Prints one JSON line per seed with both counts of wrong answers.  The
benchmark's own runs never run this.  Needs a TPU like ``run.py``.
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


def readings(root: Path, workload: str, seed: int, seconds: float) -> dict:
    from perfbench import harness, reference
    cell = harness.load_cell(root, workload)
    config, traffic = cell.config, cell.traffic
    words = reference.seed_words(seed)
    space = harness.keyspace(config)
    eng, clients, _ = harness.set_up(config, traffic, seed, words)
    loop = harness.ClosedLoop(eng, clients)
    loop.start()
    for _ in range(traffic["warmup_ticks"]):    # as a run warms up, so the
        loop.tick(record=False)                 # window holds as many ops
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        loop.tick(record=True)
    unanswered = loop.drain()
    schedule = eng.schedule
    program = reference.HostReference(space, words).check(schedule)
    writes = any(kind not in ("read", "scan") for _, kind, _, _, _ in schedule)
    if writes:
        name = "stale_reads"
        answers = reference.stale_answers(space, words, schedule)
    else:
        name = "short_keys"
        table = config["table"]
        answers = reference.short_key_answers(
            space, words, schedule, num_buckets=table["num_buckets"],
            salt=table["salt"])
    control = reference.HostReference(space, words).check(answers)
    return {"workload": workload, "seed": seed, "ticks": eng.ticks,
            "ops": program["ops"], "program_wrong": program["wrong"],
            "unanswered_ops": unanswered, "control": name,
            "control_wrong": control["wrong"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from perfbench import harness
    cell = harness.load_cell(ROOT, args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / harness.CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    harness.enable_cache(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s).",
              file=sys.stderr)
        return 1
    for seed in args.seeds:
        print(json.dumps(readings(ROOT, args.workload, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
