"""TPU probe-kernel analysis: VMEM footprints (the paper-§4.3 'area overhead'
analogue on TPU) + interpret-mode correctness throughput on CPU.

On-TPU wall-clock is not available in this container; the structural numbers
(bytes of BlockSpec tiles per grid step, vector ops per probe) come from the
kernel definitions and are the quantities a Mosaic schedule would be built
around (see EXPERIMENTS.md §Perf).

``--json`` APPENDS this run to ``BENCH_kernels.json`` (see ``make
bench-json``) so per-backend probe and insert/grow timings are tracked as a
per-PR trajectory (a ``runs`` list; one entry per ``make bench-json``).
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from bench_util import append_run
from repro.configs.base import HashMemConfig
from repro.core import hashmap
from repro.core.introspect import count_scatters
from repro.launch.compile_cache import enable_compile_cache

VMEM_BYTES = 128 * 1024 * 1024  # v5e VMEM per core


def vmem_footprint(slots: int, key_bits: int = 32):
    """Bytes resident per grid step for each kernel variant.

    perf/area fetch ONE interleaved (slots, 2) row per chain step — the
    unified PageStore activation carrying keys and values together;
    bitserial's BlockSpec selects only the pool's value lane (its keys live
    in the plane row)."""
    row_kv = slots * 2 * 4                # uint32 interleaved key/value row
    val_lane = slots * 4                  # (1, S, 1) value-lane block
    line = 128 * 4
    planes = key_bits * (slots // 32) * 4
    return {
        "perf": row_kv + line,
        "area": row_kv + line,
        "bitserial": planes + val_lane + line,
    }


def _bench(fn, warmup: int = 2, iters: int = 5) -> float:
    """Best-of-iters wall time of a blocking thunk (compile excluded)."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _median(fn, iters: int = 7) -> float:
    """Median wall time of a blocking thunk (first call = warmup/compile)."""
    fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def insert_bench(batches=(4096, 16384), slots: int = 256):
    """Vectorized batch insert vs the seed's sequential lax.scan insert.

    Two comparisons, same calling convention on both sides:
      * eager — how the serving stack (PageTableManager) actually calls the
        mutation path, and the only way the seed ever ran it.  This is the
        headline ``speedup_vs_seed`` (acceptance bar: >=5x at batch >= 4096
        on CPU — the scan dispatches the whole batch serially, the
        vectorized path is one sort + a handful of scatters).
      * jitted — both compiled, isolates the algorithmic win from dispatch
        overhead (smaller ratio: XLA-CPU scatter cost per element is the
        shared floor).

    Each row also reports ``scatters_per_insert``, the pool-scatter count
    traced from the insert jaxpr: the unified PageStore's fused key/value
    row write brings it from the split layout's 5 down to 3.
    """
    import jax

    rows = []
    cfg = HashMemConfig(num_buckets=2048, slots_per_page=slots,
                        overflow_pages=2048, max_chain=8, backend="perf")
    jit_vec = jax.jit(hashmap.insert)
    jit_scan = jax.jit(hashmap.insert_scan)
    rng = np.random.default_rng(0)
    hm = hashmap.create(cfg)
    for B in batches:
        keys = jnp.asarray(
            rng.choice(2**31, B, replace=False).astype(np.uint32))
        vals = keys * jnp.uint32(3)

        def blocked(fn):
            return lambda: jax.block_until_ready(
                fn(hm, keys, vals)[0].store.pool)

        t_vec = _median(blocked(hashmap.insert))
        t_scan = _median(blocked(hashmap.insert_scan))
        tj_vec = _median(blocked(jit_vec))
        tj_scan = _median(blocked(jit_scan))
        rows.append({"name": f"insert_batch{B}",
                     "scatters_per_insert": count_scatters(hashmap.insert,
                                                           hm, keys, vals),
                     "vec_us_per_elem": t_vec / B * 1e6,
                     "scan_us_per_elem": t_scan / B * 1e6,
                     "speedup_vs_seed": t_scan / t_vec,
                     "jit_vec_us_per_elem": tj_vec / B * 1e6,
                     "jit_scan_us_per_elem": tj_scan / B * 1e6,
                     "speedup_jit": tj_scan / tj_vec})
    return rows


def grow_bench(sizes=(1024, 4096), slots: int = 256):
    """Cost of a full grow() rehash (doubling) at ~60% load."""
    import jax

    rows = []
    rng = np.random.default_rng(1)
    for nb in sizes:
        cfg = HashMemConfig(num_buckets=nb, slots_per_page=slots,
                            overflow_pages=nb, max_chain=8, backend="perf")
        n = int(0.6 * nb * slots)
        keys = jnp.asarray(rng.choice(2**31, n, replace=False).astype(np.uint32))
        hm = hashmap.build(cfg, keys, keys)
        g = jax.jit(hashmap.grow)
        t = _bench(lambda: jax.block_until_ready(g(hm)))
        rows.append({"name": f"grow_{nb}x{slots}",
                     "entries": n,
                     "grow_ms": t * 1e3,
                     "ns_per_live_entry": t / n * 1e9})
    return rows


def run(slots: int = 512, Q: int = 256):
    rows = []
    fp = vmem_footprint(slots)
    for v, b in fp.items():
        rows.append({"name": f"kernel_vmem_{v}", "bytes_per_step": b,
                     "frac_of_vmem": b / VMEM_BYTES,
                     "vector_ops_per_probe":
                         {"perf": 2, "area": slots // 128, "bitserial": 32 + 3}[v]})
    # interpret-mode throughput (correctness-path timing only)
    rng = np.random.default_rng(0)
    n = 64 * slots // 2
    keys = rng.choice(2**31, n, replace=False).astype(np.uint32)
    q = jnp.asarray(keys[:Q])
    for backend in ("ref", "perf", "area", "bitserial"):
        hm2 = hashmap.build(
            HashMemConfig(num_buckets=64, slots_per_page=slots,
                          overflow_pages=64, max_chain=2, backend=backend),
            jnp.asarray(keys), jnp.asarray(keys))
        vfn = lambda: hashmap.probe(hm2, q)[0].block_until_ready()
        # min-of-5 (warmup excludes compile): single-shot wall times were
        # the noisiest rows in the BENCH_kernels.json trajectory
        dt = _bench(vfn, warmup=1, iters=5)
        rows.append({"name": f"kernel_interpret_{backend}",
                     "us_per_probe": dt / Q * 1e6})
    return rows


def zipfian_rows_bench(theta: float = 0.99, Q: int = 2048,
                       rounds: int = 6, per_round: int = 2048):
    """YCSB-zipfian ``rows_activated_per_probe``, fingerprints on vs off.

    Builds a displaced+fingerprinted table through insert/delete churn —
    tombstoned slots accumulate mid-chain, so a fingerprint-blind probe
    keeps activating pages whose keys can no longer match — then probes a
    zipfian(theta) query batch over the live keys and reports the traced
    mean row activations both ways (hashmap.rows_activated_per_probe).
    The fp row is the headline: the paper's ~1 row per probe."""
    import jax

    cfg = HashMemConfig(num_buckets=64, slots_per_page=128,
                        overflow_pages=256, max_chain=8, backend="ref",
                        displacement=True, fingerprint_bits=12,
                        stash_slots=256, auto_grow=False)
    rng = np.random.default_rng(7)
    allk = rng.choice(2**31, rounds * per_round, replace=False) \
        .astype(np.uint32)
    hm = hashmap.create(cfg)
    live: list = []
    for r in range(rounds):
        ks = allk[r * per_round:(r + 1) * per_round]
        hm, ok = hashmap.insert(hm, jnp.asarray(ks), jnp.asarray(ks * 3))
        live.extend(int(k) for k in ks[np.asarray(ok)])
        dead = rng.choice(len(live), len(live) // 3, replace=False)
        dk = np.asarray(live, np.uint32)[dead]
        hm, _ = hashmap.delete(hm, jnp.asarray(dk))
        gone = set(int(k) for k in dk)     # keys are unique: one copy each
        live = [k for k in live if k not in gone]
    live_arr = np.asarray(live, np.uint32)
    w = 1.0 / np.arange(1, len(live_arr) + 1, dtype=np.float64) ** theta
    q = jnp.asarray(rng.choice(live_arr, Q, p=w / w.sum()))
    ra_fp = float(hashmap.rows_activated_per_probe(hm, q))
    ra_nofp = float(hashmap.rows_activated_per_probe(
        hm, q, use_fingerprints=False))
    st = hashmap.stats(hm)
    return [{"name": "kernel_zipfian_rows_activated",
             "rows_activated_per_probe_fp": ra_fp,
             "rows_activated_per_probe_nofp": ra_nofp,
             "fp_bits": cfg.fingerprint_bits,
             "stash_slots": cfg.stash_slots,
             "stash_live": int(st["stash_live"]),
             "zipf_theta": theta,
             "live_keys": int(len(live_arr))}]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="also write all rows to BENCH_kernels.json "
                         "(perf trajectory tracked across PRs)")
    ap.add_argument("--out", default=None,
                    help="JSON output path (implies --json); "
                         "default BENCH_kernels.json")
    args = ap.parse_args()
    enable_compile_cache()
    if args.out is not None:
        args.json = True
    args.out = args.out or "BENCH_kernels.json"

    rows = run() + zipfian_rows_bench() + insert_bench() + grow_bench()
    for r in rows:
        print(r)
    if args.json:
        n = append_run(args.out, {"bench": "kernels", "rows": rows})
        print(f"appended run #{n} ({len(rows)} rows) -> {args.out}")


if __name__ == "__main__":
    main()
