"""Serving-engine benchmark: coalesced ticks vs per-request HashMem calls,
plus multi-tick op pipelining and (optionally) mesh-backed shards.

Drives the multi-tenant continuous-batching engine (repro.serving) with the
YCSB-style loadgen over the SAME request stream in several modes:

  * ``coalesced``   — the engine's step-level coalescing: at most one
    vectorized probe/delete/insert call per shard per tick;
  * ``per_request`` — identical schedule, but one HashMem call per op
    (``coalesce=False``), i.e. the synchronous one-op-per-host-call serving
    loop PR 3 replaced;
  * ``pipelined``   — coalesced + pipeline_depth=2 (tick N+1's phases
    issued while tick N's results are in flight; write-claim fence);
  * ``--mesh-shards N`` adds mesh-backed rows — ``mesh`` /
    ``mesh_pipelined`` run the three-call per-phase path
    (``fused_tick=False``, one shard_map per phase per tick, the pre-fused
    baseline) and ``mesh_fused`` / ``mesh_fused_pipelined`` run the fused
    whole-tick megakernel (ONE shard_map for probe+delete+insert, the
    engine default) with two-pass skew-aware routing; fused rows carry
    ``route_cap_*`` telemetry showing the routed ICI capacity tracking the
    measured key skew instead of the Q_local worst case.  When a CPU
    process has fewer than N jax devices, the mesh rows run in a CHILD
    process with --xla_force_host_platform_device_count=N — forcing host
    devices in THIS process would split the CPU for the host-shard rows
    too and poison their trajectory against single-device prior runs.

The PR-3 acceptance bar: at 64 concurrent requests the coalesced engine
sustains >= 5x the ops/sec of the per-request baseline.  The ISSUE-6
launch-count bar: fused mesh rows show calls_per_tick 1 vs 3.

``--json`` APPENDS this run to ``BENCH_serving.json`` (a ``runs`` list), so
the file keeps a per-PR perf trajectory like BENCH_kernels.json
(tools/bench_check.py guards it against regressions).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from bench_util import append_run

from repro.launch.compile_cache import enable_compile_cache
from repro.serving import build_ycsb_engine

# these rows time the host-side engine on CPU; the reference probe keeps
# them comparable with the earlier runs in BENCH_serving.json
BACKEND = "ref"


def _ratio(num: float, den: float) -> float:
    """num/den with a finite 0.0 fallback — ``float("inf")`` here used to
    reach json.dumps, which emits ``Infinity`` (not valid JSON) and
    corrupts the BENCH trajectory file."""
    return num / den if den > 0 else 0.0


def run_mode(*, coalesce, workloads, slots, shards, record_count,
             ops_per_request, requests, seed, pipeline=1, mesh=None,
             fused=None, tag="", repeats=3, trace=None) -> dict:
    kw = dict(slots=slots, shards=shards, record_count=record_count,
              ops_per_request=ops_per_request, coalesce=coalesce,
              pipeline_depth=pipeline, mesh=mesh, fused_tick=fused,
              trace=trace, backend=BACKEND)
    # warmup: an identical engine REPLAYS the same request stream, so every
    # trace the timed runs will see — op-kind combos, pipeline stall/drain
    # shapes, and (fused mesh rows) the exact routed-capacity tuples baked
    # in by two-pass routing — is compiled outside the timed window; the
    # module-level jit cache is shared, so the measured runs are
    # steady-state.  (A shorter different-seed warmup leaves pipelined rows
    # paying first-compile inside the wall clock.)
    warm, wgens = build_ycsb_engine(workloads, seed=seed, **kw)
    per = requests // len(wgens)
    warm.submit_all([r for g in wgens for r in g.requests(per)])
    warm.run()

    # time the serving drain loop only, best of ``repeats`` fresh engines
    # over the identical stream (the min-of-N discipline kernel_bench uses):
    # a drain is a dozen ticks / tens of ms, so a single GC pause or
    # scheduler hiccup mid-run swings a one-shot reading 2-3x.  The eng.run()
    # call on the already-drained winner just takes the forced end-of-run
    # telemetry sample (chain depth / rows activated) + snapshot, OUTSIDE
    # the timed window.
    wall, eng, reqs = float("inf"), None, None
    for _ in range(max(repeats, 1)):
        e, gens = build_ycsb_engine(workloads, seed=seed, **kw)
        rq = [r for g in gens for r in g.requests(per)]
        t0 = time.perf_counter()
        e.submit_all(rq)
        while not e.pool.idle() and e.ticks < 100_000:
            e.tick()
        e.flush()
        w = time.perf_counter() - t0
        if w < wall:
            wall, eng, reqs = w, e, rq
    snap = eng.run()
    name = tag or ("coalesced" if coalesce else "per_request")
    # two-pass routing telemetry (fused mesh rows): how far the measured
    # per-(src,dst) capacity sits below the Q_local worst-case padding
    route = {}
    if eng.route_cap_log:
        caps = [c for rec in eng.route_cap_log for c in rec["cap"]]
        qls = [q for rec in eng.route_cap_log for q in rec["q_local"]]
        route = {
            "route_cap_mean": sum(caps) / len(caps),
            "route_cap_max": max(caps),
            "route_cap_q_local_max": max(qls),
            "route_cap_fill": _ratio(sum(caps), sum(qls)),
        }
    return {
        "name": f"serving_{''.join(workloads)}_{slots}slots_{name}",
        "mode": name,
        "pipeline_depth": pipeline,
        "mesh_shards": eng.num_shards if mesh is not None else 0,
        "stall_events": eng.stall_events,
        "concurrency": slots,
        "shards": shards,
        "requests": len(reqs),
        "total_ops": snap["total_ops"],
        "ticks": snap["ticks"],
        "wall_seconds": wall,
        "ops_per_sec": snap["total_ops"] / wall if wall > 0 else 0.0,
        "hashmem_calls": dict(eng.batch_calls),
        "calls_per_tick": sum(eng.batch_calls.values()) / max(snap["ticks"], 1),
        "request_latency_ticks_p50": snap["request_latency_ticks"]["p50"],
        "request_latency_ticks_p99": snap["request_latency_ticks"]["p99"],
        "request_latency_ms_p50": snap["request_latency_ms"]["p50"],
        "request_latency_ms_p99": snap["request_latency_ms"]["p99"],
        "occupancy_mean": snap["occupancy"]["mean"],
        "probe_hit_rate": snap["probe_hit_rate"],
        "grow_events": eng.grow_events,
        "compact_events": eng.compact_events,
        "chain_depth_p50": snap["chain_depth"]["p50"],
        "chain_depth_p99": snap["chain_depth"]["p99"],
        "rows_activated_p50": snap["rows_activated"]["p50"],
        "rows_activated_p99": snap["rows_activated"]["p99"],
        **route,
    }


def trace_overhead_row(*, workloads, slots, shards, record_count,
                       ops_per_request, requests, seed, repeats=5) -> dict:
    """Traced vs untraced wall time over the IDENTICAL coalesced stream.
    The two sides are A/B INTERLEAVED (untraced, traced, untraced, ...)
    and each takes its min-of-N: a serving drain is tens of ms, so
    measuring the traced side after the untraced side finishes would fold
    allocator/jit-cache/scheduler drift into the ratio and report it as
    tracer cost.  ``trace_overhead`` is the resulting wall ratio
    (lower-better, 1.0 = free), gated <=1.10x by tools/bench_check.py
    ABS_BARS."""
    kw = dict(slots=slots, shards=shards, record_count=record_count,
              ops_per_request=ops_per_request, coalesce=True,
              backend=BACKEND)
    walls = {False: float("inf"), True: float("inf")}
    total_ops = 0
    for rep in range(-1, max(repeats, 1)):      # rep -1 warms both paths
        for traced in (False, True):
            eng, gens = build_ycsb_engine(workloads, seed=seed,
                                          trace=traced, **kw)
            per = requests // len(gens)
            rq = [r for g in gens for r in g.requests(per)]
            t0 = time.perf_counter()
            eng.submit_all(rq)
            while not eng.pool.idle() and eng.ticks < 100_000:
                eng.tick()
            eng.flush()
            wall = time.perf_counter() - t0
            if rep >= 0 and wall < walls[traced]:
                walls[traced] = wall
            total_ops = eng.metrics.total_ops
    overhead = _ratio(walls[True], walls[False])
    return {"name": f"serving_trace_{slots}slots",
            "untraced_ops_per_sec": _ratio(total_ops, walls[False]),
            "traced_ops_per_sec": _ratio(total_ops, walls[True]),
            "trace_overhead": overhead,
            "meets_trace_bar": overhead <= 1.10}


def growth_row(*, seed=7, repeats=3, slots=16) -> dict:
    """p99 under growth: the IDENTICAL zipfian insert-heavy stream through
    two engines differing ONLY in ``cfg.resize``.  The stream inserts ~500
    hot-skewed keys into an 8-bucket table with a 2-page chain bound, so
    the table must resize many times mid-serving:

      * ``rebuild``     — every repair is a stop-the-world ``grow()``
        rehash of the whole (thousands-of-pages) arena: the requests in
        flight during that tick absorb the rebuild wall time;
      * ``extendible``  — the hot GROUP splits alone (and the directory
        doubles by pointer copy, >= 4 doublings on this stream), so no
        request ever waits on a full rehash.

    The A/B is interleaved (same min-of-N discipline as trace_overhead_row)
    and the acceptance gate is ``p99_growth_ratio`` = extendible p99 ms /
    rebuild p99 ms, hard-bounded < 1.0 by tools/bench_check.py ABS_BARS —
    the raw per-mode ``*request_latency*`` fields are wall-clock noise and
    stay unguarded (SKIP).  Request latency in TICKS is schedule-determined
    and must be identical between the modes (reported as a sanity pair).
    """
    import dataclasses

    import numpy as np

    from repro.configs.base import HashMemConfig
    from repro.serving import Request, ServingEngine

    def streams():
        # mirrors tests/model.make_insert_heavy_schedule (tests/ is not on
        # the bench path): insert-dominated, zipf-skewed key choice so the
        # chain overflow concentrates on hot buckets
        rng = np.random.default_rng(seed)
        keyspace = 4096
        w = 1.0 / np.arange(1, keyspace + 1, dtype=np.float64) ** 0.6
        w /= w.sum()
        probs = [0.8, 0.08, 0.08, 0.04]             # insert/update/read/del
        reqs = []
        for _ in range(128):
            ops = []
            for _ in range(5):
                k = int(rng.choice(keyspace, p=w))
                v = int(rng.integers(1, 2 ** 20))
                kind = ["insert", "update", "read", "delete"][
                    int(rng.choice(4, p=probs))]
                ops.append({"insert": ("insert", k, v),
                            "update": ("update", k, v),
                            "read": ("read", k),
                            "delete": ("delete", k)}[kind])
            reqs.append(ops)
        return reqs

    # one small hot table, arena sized with split-leak slack (a split
    # abandons its old overflow pages until compact/grow reclaims them)
    base = HashMemConfig(num_buckets=8, slots_per_page=4,
                         overflow_pages=2040, max_chain=2, backend="ref",
                         auto_grow=True, max_load_factor=1.0)
    best = {m: None for m in ("rebuild", "extendible")}
    for rep in range(-1, max(repeats, 1)):          # rep -1 warms both
        for mode in ("rebuild", "extendible"):
            cfg = dataclasses.replace(base, resize=mode)
            eng = ServingEngine(cfg, max_slots=slots)
            eng.submit_all([Request(ops=ops) for ops in streams()])
            while not eng.pool.idle() and eng.ticks < 100_000:
                eng.tick()
            eng.flush()
            snap = eng.run()
            if rep < 0:
                continue
            p99 = snap["request_latency_ms"]["p99"]
            if best[mode] is None or p99 < best[mode]["p99_ms"]:
                best[mode] = {
                    "p99_ms": p99,
                    "p50_ms": snap["request_latency_ms"]["p50"],
                    "p99_ticks": snap["request_latency_ticks"]["p99"],
                    "grow_events": eng.grow_events,
                    "splits": eng.split_events,
                    "doublings": eng.directory_doublings,
                }
    reb, ext = best["rebuild"], best["extendible"]
    # the stream must actually force growth in BOTH modes, >= 4 directory
    # doublings extendible-side (the ISSUE acceptance shape) and zero
    # stop-the-world rebuilds on the extendible engine
    assert reb["grow_events"] >= 1, reb
    assert ext["doublings"] >= 4 and ext["splits"] >= 4, ext
    assert ext["grow_events"] == 0, ext
    return {
        "name": f"serving_p99_under_growth_{slots}slots",
        "rebuild_grow_events": reb["grow_events"],
        "extendible_splits": ext["splits"],
        "extendible_doublings": ext["doublings"],
        "rebuild_request_latency_ms_p50": reb["p50_ms"],
        "rebuild_request_latency_ms_p99": reb["p99_ms"],
        "extendible_request_latency_ms_p50": ext["p50_ms"],
        "extendible_request_latency_ms_p99": ext["p99_ms"],
        "rebuild_request_latency_ticks_p99": reb["p99_ticks"],
        "extendible_request_latency_ticks_p99": ext["p99_ticks"],
        "p99_growth_ratio": _ratio(ext["p99_ms"], reb["p99_ms"]),
    }


def _mesh_rows(num_shards: int, slots: int, kw: dict) -> list:
    """mesh/mesh_pipelined (per-phase baseline) + mesh_fused rows, plus the
    fused-vs-unfused comparison row.  Needs ``num_shards`` jax devices."""
    from repro.launch.mesh import make_serving_mesh
    mesh = make_serving_mesh(num_shards)
    # per-phase baseline (fused=False: 3 shard_map launches per tick)
    mu = run_mode(coalesce=True, mesh=mesh, fused=False, tag="mesh", **kw)
    mp = run_mode(coalesce=True, mesh=mesh, fused=False, pipeline=2,
                  tag="mesh_pipelined", **kw)
    # fused whole-tick megakernel (engine default: ONE launch per tick)
    mf = run_mode(coalesce=True, mesh=mesh, tag="mesh_fused", **kw)
    mfp = run_mode(coalesce=True, mesh=mesh, pipeline=2,
                   tag="mesh_fused_pipelined", **kw)
    cmp_row = {"name": f"serving_fused_tick_{slots}slots",
               "launches_per_tick_unfused": mu["calls_per_tick"],
               "launches_per_tick_fused": mf["calls_per_tick"],
               "fused_vs_unfused_throughput_ratio":
                   _ratio(mf["ops_per_sec"], mu["ops_per_sec"]),
               "route_cap_fill": mf.get("route_cap_fill", 1.0)}
    return [mu, mp, mf, mfp, cmp_row]


def _mesh_block(args, kw: dict) -> list:
    """Run the mesh rows in this process when it has enough jax devices.
    Otherwise, and only on CPU, re-exec this script in a CHILD process with
    --xla_force_host_platform_device_count (forcing host devices in the
    parent would split the CPU under the host-shard rows too, poisoning
    their trajectory against single-device prior runs).  A parent on an
    accelerator holds it, so a child could not reach it: that fails."""
    import jax
    if jax.device_count() >= args.mesh_shards:
        return _mesh_rows(args.mesh_shards, args.slots, kw)
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"--mesh-shards {args.mesh_shards} needs that many "
            f"{jax.default_backend()} devices, have {jax.device_count()}")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count="
                        f"{args.mesh_shards}").strip()
    cmd = [sys.executable, os.path.abspath(__file__), "--mesh-rows-json",
           "--mesh-shards", str(args.mesh_shards),
           "--requests", str(args.requests), "--slots", str(args.slots),
           "--shards", str(args.shards),
           "--record-count", str(args.record_count),
           "--ops-per-request", str(args.ops_per_request),
           "--workloads", args.workloads, "--seed", str(args.seed)]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"mesh-row child failed:\n{r.stdout}\n{r.stderr}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="append this run to BENCH_serving.json")
    ap.add_argument("--out", default=None,
                    help="JSON output path (implies --json)")
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--slots", type=int, default=64,
                    help="concurrent request slots (acceptance bar: 64)")
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--record-count", type=int, default=2048)
    ap.add_argument("--ops-per-request", type=int, default=4)
    ap.add_argument("--workloads", default="A,B,E")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh-shards", type=int, default=0,
                    help="also bench mesh-backed shards (needs that many "
                         "jax devices; see module docstring)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run for CI (make ci)")
    ap.add_argument("--growth", action="store_true",
                    help="force the p99-under-growth A/B row (always on "
                         "for non-smoke runs)")
    ap.add_argument("--mesh-rows-json", action="store_true",
                    help=argparse.SUPPRESS)  # child mode: emit mesh rows
    args = ap.parse_args()
    enable_compile_cache()
    if args.out is not None:
        args.json = True
    args.out = args.out or "BENCH_serving.json"
    if args.smoke:
        args.requests, args.slots, args.record_count = 16, 8, 256

    wls = [w.strip().upper() for w in args.workloads.split(",") if w.strip()]
    kw = dict(workloads=wls, slots=args.slots, shards=args.shards,
              record_count=args.record_count,
              ops_per_request=args.ops_per_request, requests=args.requests,
              seed=args.seed)
    if args.mesh_rows_json:
        print(json.dumps(_mesh_rows(args.mesh_shards, args.slots, kw)))
        return
    co = run_mode(coalesce=True, **kw)
    pr = run_mode(coalesce=False, **kw)
    pi = run_mode(coalesce=True, pipeline=2, tag="pipelined", **kw)
    # trace_overhead: the SAME coalesced stream with span recording on —
    # the observability layer's cost as a measured ratio, gated <=1.10x by
    # tools/bench_check.py (ABS_BARS), never assumed
    trace_row = trace_overhead_row(**kw)
    rows = [co, pr, pi]
    if args.growth or not args.smoke:
        # latency-bounded growth acceptance: extendible p99 strictly below
        # rebuild p99 on a >=4-doubling insert storm (bench_check ABS bar)
        rows.append(growth_row(seed=args.seed + 7))
    if args.mesh_shards:
        rows += _mesh_block(args, kw)
    speedup = _ratio(co["ops_per_sec"], pr["ops_per_sec"])
    rows.append({"name": f"serving_speedup_{args.slots}slots",
                 "coalesced_ops_per_sec": co["ops_per_sec"],
                 "per_request_ops_per_sec": pr["ops_per_sec"],
                 "pipelined_ops_per_sec": pi["ops_per_sec"],
                 "speedup": speedup,
                 "pipelined_vs_coalesced":
                     _ratio(pi["ops_per_sec"], co["ops_per_sec"]),
                 "meets_5x_bar": speedup >= 5.0})
    rows.append(trace_row)
    for r in rows:
        print(r)
    if args.json:
        n = append_run(args.out, {
            "bench": "serving",
            "concurrency": args.slots,
            "requests": args.requests,
            "workloads": wls,
            "speedup_coalesced_vs_per_request": speedup,
            "rows": rows,
        })
        print(f"appended run #{n} -> {args.out}")


if __name__ == "__main__":
    main()
