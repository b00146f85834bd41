"""YCSB-style load generator for the serving engine.

Builds :class:`repro.serving.engine.Request` streams from the YCSB core
workloads (A update-heavy, B read-mostly, C read-only, D read-latest,
E short-scans, F read-modify-write) with Zipfian / uniform / latest key
choice, on top of the shared generators in ``repro.data.kv_synth``
(``ycsb_mix`` / ``zipfian_weights``).  Each request is a short session of
``ops_per_request`` ops, so continuous batching has multi-tick lifetimes to
schedule around.

The load phase (`preload`) inserts ``record_count`` keys 0..N-1; the run
phase draws op keys from the loaded range, extending it on "insert" ops
(the YCSB insertion-point counter), which is what the "latest" distribution
skews toward.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.data.kv_synth import ycsb_default_dist, ycsb_mix, zipfian_weights
from repro.serving.engine import Request
from repro.serving.tenancy import Tenant

DISTRIBUTIONS = ("zipfian", "uniform", "latest")


def _cdf(p: np.ndarray) -> np.ndarray:
    """``Generator.choice``'s own CDF of the probabilities ``p``."""
    cdf = np.cumsum(p)
    cdf /= cdf[-1]
    return cdf


@dataclass
class WorkloadSpec:
    """One tenant's workload: a YCSB mix (or explicit op probabilities)
    over a bounded key range."""
    workload: str = "A"                 # YCSB core workload id
    record_count: int = 1024            # preloaded keys 0..record_count-1
    ops_per_request: int = 4
    distribution: str = ""              # "" -> the workload's YCSB default
    theta: float = 0.99                 # zipfian skew constant
    scan_len: int = 8                   # max scan length (E)
    mix: dict | None = None             # overrides ycsb_mix(workload)

    def resolved_mix(self) -> dict:
        return dict(self.mix) if self.mix else ycsb_mix(self.workload)

    def resolved_dist(self) -> str:
        d = self.distribution or ycsb_default_dist(self.workload)
        assert d in DISTRIBUTIONS, d
        return d


class LoadGen:
    """Request-stream generator for one (tenant, workload) pair."""

    def __init__(self, spec: WorkloadSpec, tenant: Tenant | None = None,
                 seed: int = 0):
        self.spec = spec
        self.tenant = tenant
        self.rng = np.random.default_rng(seed)
        self.mix = spec.resolved_mix()
        self.dist = spec.resolved_dist()
        self.kinds = list(self.mix)
        self.probs = np.asarray([self.mix[k] for k in self.kinds])
        self.probs = self.probs / self.probs.sum()
        self._kind_cdf = _cdf(self.probs)
        self.insert_point = spec.record_count    # YCSB insertion counter
        self._zipf_n = 0
        self._zipf_cdf = None

    # -- key choice --------------------------------------------------------
    def _draw(self, cdf) -> int:
        """Index drawn from a cached CDF: the same uniform draw and search
        as ``Generator.choice(n, p=...)``, so streams are bit-identical to
        it for a seed, without rebuilding the O(n) CDF on every op."""
        return int(cdf.searchsorted(self.rng.random(), side="right"))

    def _zipf(self, n: int) -> int:
        """Zipfian rank in [0, n).  The O(n) CDF is rebuilt only when the
        key range has grown ~25% past the cached size (inserts bump
        ``insert_point`` on every op in insert-bearing workloads); between
        rebuilds ranks are drawn over the cached prefix — the hot head,
        which is where a zipfian draw lands anyway."""
        if self._zipf_cdf is None or n < self._zipf_n \
                or n > self._zipf_n * 1.25:
            self._zipf_n = n
            self._zipf_cdf = _cdf(zipfian_weights(n, self.spec.theta))
        return min(self._draw(self._zipf_cdf), n - 1)

    def choose_key(self) -> int:
        n = max(self.insert_point, 1)
        if self.dist == "uniform":
            return int(self.rng.integers(0, n))
        if self.dist == "latest":
            # skew toward the most recently inserted keys: zipfian over
            # recency rank (YCSB's LatestGenerator)
            return (n - 1) - self._zipf(n)
        return self._zipf(n)

    def next_insert_key(self) -> int:
        k = self.insert_point
        self.insert_point += 1
        return k

    # -- ops / requests ----------------------------------------------------
    def next_op(self) -> tuple:
        kind = self.kinds[self._draw(self._kind_cdf)]
        val = int(self.rng.integers(1, 2**31))
        if kind == "read":
            return ("read", self.choose_key())
        if kind == "update":
            return ("update", self.choose_key(), val)
        if kind == "insert":
            return ("insert", self.next_insert_key(), val)
        if kind == "scan":
            n = int(self.rng.integers(1, self.spec.scan_len + 1))
            return ("scan", self.choose_key(), n)
        if kind == "rmw":
            return ("rmw", self.choose_key(), val)
        raise ValueError(kind)

    def request(self) -> Request:
        ops = [self.next_op() for _ in range(self.spec.ops_per_request)]
        return Request(ops=ops, tenant=self.tenant)

    def requests(self, n: int) -> list:
        return [self.request() for _ in range(n)]

    # -- load phase --------------------------------------------------------
    def preload_kv(self, seed: int | None = None):
        """(keys, vals) for the YCSB load phase: keys 0..record_count-1."""
        rng = np.random.default_rng(self.rng.integers(2**31)
                                    if seed is None else seed)
        keys = np.arange(self.spec.record_count, dtype=np.uint32)
        vals = rng.integers(1, 2**31, self.spec.record_count,
                            dtype=np.int64).astype(np.uint32)
        return keys, vals


def preload_engine(engine, gens: list) -> list:
    """Run the load phase for every generator into the engine's shards;
    returns the loaded ``(keys, vals)`` per generator."""
    loaded = []
    for g in gens:
        keys, vals = g.preload_kv()
        engine.preload(keys, vals, tenant=g.tenant)
        loaded.append((keys, vals))
    return loaded


def build_ycsb_engine(workloads, *, slots=16, shards=1, record_count=1024,
                      ops_per_request=4, coalesce=True, backend="perf",
                      seed=0, max_pending=0, tenant_slots=0, metrics=None,
                      cfg=None, mesh=None, pipeline_depth=1,
                      fused_tick=None, trace=None, record_schedule=False,
                      preload=True):
    """One preloaded engine + one (tenant, LoadGen) per YCSB workload letter
    — the single assembly path shared by the serve.py kv CLI,
    benchmarks/serving_bench.py and chip_smoke.py, so all exercise
    identically-sized tables.  ``backend`` is the probe backend (it
    overrides ``cfg.backend``).
    ``mesh``: route the shards through the RLU mesh path (one stacked table,
    one shard per device on the 'model' axis; ``shards`` is ignored).
    ``pipeline_depth``: multi-tick op pipelining (engine.py).
    ``fused_tick``: None = engine default (fused whole-tick megakernel on
    mesh+coalesce), False = per-phase shard_map calls.
    ``preload=False`` leaves the load phase to the caller
    (``preload_engine``).
    Returns (engine, [LoadGen, ...])."""
    import dataclasses

    from repro.configs.base import HashMemConfig
    from repro.serving.engine import ServingEngine
    from repro.serving.tenancy import TenantRegistry

    reg = TenantRegistry()
    gens = []
    for i, wl in enumerate(workloads):
        t = reg.register(f"tenant{i}-{wl}", max_slots=tenant_slots)
        gens.append(LoadGen(WorkloadSpec(wl, record_count=record_count,
                                         ops_per_request=ops_per_request),
                            t, seed=seed + i))
    cfg = cfg or HashMemConfig(num_buckets=max(256, record_count // 16),
                               slots_per_page=64,
                               overflow_pages=max(256, record_count // 16),
                               max_chain=8)
    cfg = dataclasses.replace(cfg, backend=backend)
    eng = ServingEngine(cfg, num_shards=shards, max_slots=slots,
                        max_pending=max_pending, tenants=reg,
                        metrics=metrics, coalesce=coalesce, mesh=mesh,
                        pipeline_depth=pipeline_depth, fused_tick=fused_tick,
                        trace=trace, record_schedule=record_schedule)
    if preload:
        preload_engine(eng, gens)
    return eng, gens
