#!/usr/bin/env python3
"""Chip smoke: the HashMem serving path end to end on a TPU, in one process.

    python chip_smoke.py [--seed N]              # one chip
    python chip_smoke.py --chips 4 [--seed N]    # the 4-chip mesh path

One chip: a ``ServingEngine`` built by ``serving.build_ycsb_engine`` with the
paper's performance-optimized Pallas probe (``backend="perf"``) holds the
paper's workload, 100M uint32 -> uint32 pairs, loaded as 8 YCSB tenants
(A,A,B,B,C,C,F,F, zipfian 0.99) x 12.5M records into a 2^18-bucket x
512-slot table (a 1.34 GB pool, direct pages 74.5% full).  It serves 512
requests of 4 ops on 64 slots and checks every read, update and rmw answer
against a plain host model: the preload's value arrays, with the run's
writes replayed in the order the engine recorded.

``--chips 4`` runs only the mesh path: the same table and stream through a
4-device mesh (one quarter of the pool per chip, one fused tick program per
tick), compared answer by answer with the one-chip host-shard engine.

The earlier lines are smoke readings, not benchmark numbers.  The last line
is ``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
There is no CPU mode: tests/test_chip_smoke.py drives the store, serve and
check phases at a tiny size.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import HashMemConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.serving import PAD_KEY, build_ycsb_engine, preload_engine  # noqa: E402

WORKLOADS = ("A", "A", "B", "B", "C", "C", "F", "F")
RECORDS = 12_500_000         # per tenant: 8 x 12.5M = the paper's 100M pairs
TABLE = HashMemConfig(num_buckets=2**18, slots_per_page=512,
                      overflow_pages=2**16, max_chain=8)
REQUESTS, OPS, SLOTS = 512, 4, 64


def require(cond, msg: str):
    if not cond:
        raise RuntimeError(msg)


@dataclasses.dataclass
class Store:
    engine: object
    gens: list
    values: list              # per tenant: preload value of raw key k at [k]
    preload_s: float


def build_store(cfg: HashMemConfig, *, records: int, slots: int, seed: int,
                backend: str = "perf", mesh=None,
                workloads=WORKLOADS) -> Store:
    """Store phase: the engine, and the YCSB load of ``records`` pairs per
    tenant (keys 0..records-1, values from ``seed``)."""
    eng, gens = build_ycsb_engine(
        list(workloads), slots=slots, record_count=records,
        ops_per_request=OPS, backend=backend, seed=seed, cfg=cfg, mesh=mesh,
        record_schedule=True, preload=False)
    t0 = time.perf_counter()
    loaded = preload_engine(eng, gens)
    preload_s = time.perf_counter() - t0
    for keys, _ in loaded:
        require(np.array_equal(keys, np.arange(records, dtype=keys.dtype)),
                "the YCSB load phase loads keys 0..records-1")
    return Store(eng, gens, [vals for _, vals in loaded], preload_s)


def serve(store: Store, requests: int) -> list:
    """Serve phase: ``requests`` requests, round-robin over the tenants,
    submitted at once and drained.  Returns them with their results."""
    reqs = [g.request() for _ in range(requests // len(store.gens))
            for g in store.gens]
    store.engine.submit_all(reqs)
    store.engine.run()
    return reqs


class HostReference:
    """Plain host model of the served table.  Raw key k of tenant t holds
    ``values[t][k]`` from the preload; a key the run touched is kept as a
    FIFO list of its values, oldest first (the table keeps duplicates and
    answers with the oldest)."""

    def __init__(self, values: list, key_bits: int):
        self.values = values
        self.key_bits = key_bits
        self.touched: dict = {}

    def entries(self, key: int) -> list:
        if key not in self.touched:
            t, k = key >> self.key_bits, key & ((1 << self.key_bits) - 1)
            base = self.values[t] if t < len(self.values) else ()
            self.touched[key] = [int(base[k])] if k < len(base) else []
        return self.touched[key]

    def replay(self, schedule) -> dict:
        """Check a ``record_schedule`` log: within a tick the engine runs
        probe, then delete, then insert, so reads see the table as of the
        tick start.  Every write must be acknowledged (ok)."""
        by_tick: dict = {}
        for tick, kind, keys, val, res in schedule:
            if kind not in ("read", "update", "rmw", "insert", "delete"):
                raise ValueError(f"no reference for op {kind!r}")
            by_tick.setdefault(tick, []).append((kind, keys[0], val, res))
        out = {"checked": 0, "mismatches": 0, "first": []}

        def expect(ok: bool, what):
            out["checked"] += 1
            if not ok:
                out["mismatches"] += 1
                if len(out["first"]) < 5:
                    out["first"].append(what)

        for tick in sorted(by_tick):
            ops = by_tick[tick]
            for kind, key, _, res in ops:
                if kind in ("read", "rmw"):
                    e = self.entries(key)
                    got = res["value" if kind == "read" else "old"]
                    expect(res["found"] == bool(e) and (not e or got == e[0]),
                           (tick, kind, key, res, e[:1]))
            for kind, key, _, res in ops:
                if kind in ("delete", "update", "rmw"):
                    e = self.entries(key)
                    field = "found" if kind == "delete" else "replaced"
                    expect(res[field] == bool(e), (tick, kind, key, res))
                    if e:
                        e.pop(0)
            for kind, key, val, res in ops:
                if kind in ("insert", "update", "rmw"):
                    expect(res["ok"] is True, (tick, kind, key, res))
                    if res["ok"]:
                        self.entries(key).append(val)
        return out


def check(store: Store) -> dict:
    """Check phase: every answer of the run against the host model."""
    ref = HostReference(store.values, store.engine.tenants.space.key_bits)
    return ref.replay(store.engine.schedule)


def compile_phases(eng):
    """Compile the engine's probe, delete and insert programs ahead, at the
    padded batch it issues.  Returns (lowering seconds, compile seconds,
    the probe's compiled text); only the compile is kept in the persistent
    cache."""
    hm = eng.shards[0]
    n = eng.pad_min
    keys = jnp.full((n,), PAD_KEY, jnp.uint32)
    t0 = time.perf_counter()
    lowered = [eng._jit_probe.lower(hm, keys),
               eng._jit_delete.lower(hm, keys),
               eng._jit_insert.lower(hm, keys, jnp.zeros((n,), jnp.uint32),
                                     jnp.zeros((n,), bool))]
    t1 = time.perf_counter()
    probe = [low.compile() for low in lowered][0]
    return t1 - t0, time.perf_counter() - t1, probe.as_text()


def report_run(name: str, store: Store, reqs: list, res: dict):
    done = sum(r.done() for r in reqs)
    ops = sum(len(r.results) for r in reqs)
    print(f"{name}: records_loaded={sum(len(v) for v in store.values)} "
          f"preload_s={store.preload_s}")
    print(f"{name}: requests_answered={done}/{len(reqs)} "
          f"ops_answered={ops} answers_checked={res['checked']} "
          f"mismatches={res['mismatches']}")
    require(done == len(reqs), f"{name}: {len(reqs) - done} requests open")
    require(ops == OPS * len(reqs), f"{name}: {ops} ops answered")
    require(res["mismatches"] == 0,
            f"{name}: mismatches against the host model: {res['first']}")


def pool_placement(eng, devices) -> dict:
    """Which device holds which part of a mesh engine's pool, and each
    device's bytes in use (None where the backend keeps no memory stats, as
    the CPU does)."""
    pool = eng.backend.hm_stacked.store.pool
    return {"pool_bytes": pool.nbytes,
            "shards": [(str(s.device), s.index[0].start, s.data.nbytes)
                       for s in pool.addressable_shards],
            "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use")
                             for d in devices]}


def one_chip(seed: int):
    store = build_store(TABLE, records=RECORDS, slots=SLOTS, seed=seed)
    lower_s, compile_s, text = compile_phases(store.engine)
    print(f"first_compile_s={compile_s} lower_s={lower_s} "
          "(probe, delete, insert programs)")
    present = "tpu_custom_call" in text
    print(f"probe_program_tpu_custom_call={'present' if present else 'ABSENT'}")
    require(present, "the compiled probe program holds no Pallas kernel")
    reqs = serve(store, REQUESTS)
    report_run("one_chip", store, reqs, check(store))


def four_chips(seed: int, n: int = 4, table: HashMemConfig = TABLE,
               records: int = RECORDS, slots: int = SLOTS,
               requests: int = REQUESTS):
    devices = jax.devices()[:n]
    shard_cfg = dataclasses.replace(
        table, num_buckets=table.num_buckets // n,
        overflow_pages=table.overflow_pages // n)
    mesh_store = build_store(shard_cfg, records=records, slots=slots,
                             seed=seed, mesh=make_serving_mesh(n))
    place = pool_placement(mesh_store.engine, devices)
    print(f"mesh_pool: bytes={place['pool_bytes']} shards={place['shards']}")
    print(f"mesh_bytes_in_use_after_preload={place['bytes_in_use']}")
    held = {dev for dev, _, _ in place["shards"]}
    require(len(place["shards"]) == n and len(held) == n
            and all(b * n == place["pool_bytes"]
                    for _, _, b in place["shards"]),
            "each device must hold its own quarter of the pool")
    use = place["bytes_in_use"]
    require(None in use or max(use) <= 1.25 * min(use),
            "device memory in use differs by more than 25% across the mesh")

    host_store = build_store(table, records=records, slots=slots, seed=seed)
    mesh_reqs = serve(mesh_store, requests)
    host_reqs = serve(host_store, requests)
    report_run("mesh", mesh_store, mesh_reqs, check(mesh_store))
    report_run("host", host_store, host_reqs, check(host_store))
    differ = sum(a.results != b.results for a, b in zip(mesh_reqs, host_reqs))
    print(f"mesh_vs_host: requests_compared={len(mesh_reqs)} "
          f"requests_differing={differ} "
          f"fused_ticks={mesh_store.engine.batch_calls['fused_tick']}")
    require(differ == 0, "mesh answers differ from the one-chip answers")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run the mesh path and its one-chip comparison")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). "
              f"Nothing was run.", file=sys.stderr)
        return 1
    print(f"compile_cache={enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(args.seed)
    else:
        four_chips(args.seed, args.chips)
    print(f"device_kind={devices[0].device_kind} wall_s="
          f"{time.perf_counter() - t0}")
    print("peak_bytes_in_use=" + json.dumps(
        [d.memory_stats()["peak_bytes_in_use"] for d in devices]))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
