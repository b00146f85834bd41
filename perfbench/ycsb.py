"""YCSB core-workload op streams, the benchmark's own copy.

The program's generator (``serving/loadgen.py`` ``LoadGen``, over
``data/kv_synth.py`` ``ycsb_mix`` and ``zipfian_weights``) is copied here,
draw for draw, so that no change to the program can change the traffic it
is measured on.  For a seed, :class:`OpStream` draws exactly the ops
``LoadGen`` draws (``tests/perfbench/test_perfbench_ycsb.py`` holds this for
workloads A-F).
"""
from __future__ import annotations

import numpy as np

# YCSB core workload op mixes (Cooper et al., SoCC'10; the YCSB repo's
# workloads/workloada..f).  "rmw" is read-modify-write, "scan" a short run
# of consecutive keys.
MIXES = {
    "A": {"read": 0.5, "update": 0.5},
    "B": {"read": 0.95, "update": 0.05},
    "C": {"read": 1.0},
    "D": {"read": 0.95, "insert": 0.05},
    "E": {"scan": 0.95, "insert": 0.05},
    "F": {"read": 0.5, "rmw": 0.5},
}
DEFAULT_DIST = {"A": "zipfian", "B": "zipfian", "C": "zipfian",
                "D": "latest", "E": "zipfian", "F": "zipfian"}
DISTRIBUTIONS = ("zipfian", "uniform", "latest")


def zipfian_weights(n: int, theta: float = 0.99) -> np.ndarray:
    """YCSB zipfian popularity over ranks 1..n, normalized to sum 1."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
    return w / w.sum()


def _cdf(p: np.ndarray) -> np.ndarray:
    """``Generator.choice``'s own CDF of the probabilities ``p``."""
    cdf = np.cumsum(p)
    cdf /= cdf[-1]
    return cdf


class OpStream:
    """One tenant's YCSB op stream: what ``LoadGen(WorkloadSpec(workload,
    record_count, ops_per_request, distribution, theta, scan_len),
    seed=seed)`` draws, as lists of op tuples.  ``cdfs`` may be shared
    between streams: it caches zipfian CDFs by (n, theta)."""

    def __init__(self, workload: str, record_count: int, *,
                 ops_per_request: int = 4, distribution: str = "",
                 theta: float = 0.99, scan_len: int = 8, seed=0,
                 cdfs: dict | None = None):
        wl = workload.upper()
        mix = MIXES[wl]
        self.dist = distribution or DEFAULT_DIST[wl]
        if self.dist not in DISTRIBUTIONS:
            raise ValueError(f"unknown key distribution {self.dist!r}")
        self.rng = np.random.default_rng(seed)
        self.kinds = list(mix)
        probs = np.asarray([mix[k] for k in self.kinds])
        self._kind_cdf = _cdf(probs / probs.sum())
        self.ops_per_request = ops_per_request
        self.theta = theta
        self.scan_len = scan_len
        self.insert_point = record_count     # YCSB insertion counter
        self.cdfs = {} if cdfs is None else cdfs
        self._zipf_n = 0
        self._zipf_cdf = None

    def _draw(self, cdf) -> int:
        return int(cdf.searchsorted(self.rng.random(), side="right"))

    def _zipf(self, n: int) -> int:
        if self._zipf_cdf is None or n < self._zipf_n \
                or n > self._zipf_n * 1.25:
            self._zipf_n = n
            key = (n, self.theta)
            if key not in self.cdfs:
                self.cdfs[key] = _cdf(zipfian_weights(*key))
            self._zipf_cdf = self.cdfs[key]
        return min(self._draw(self._zipf_cdf), n - 1)

    def _key(self) -> int:
        n = max(self.insert_point, 1)
        if self.dist == "uniform":
            return int(self.rng.integers(0, n))
        if self.dist == "latest":
            return (n - 1) - self._zipf(n)
        return self._zipf(n)

    def next_op(self) -> tuple:
        kind = self.kinds[self._draw(self._kind_cdf)]
        val = int(self.rng.integers(1, 2**31))
        if kind == "read":
            return ("read", self._key())
        if kind == "insert":
            self.insert_point += 1
            return ("insert", self.insert_point - 1, val)
        if kind == "scan":
            n = int(self.rng.integers(1, self.scan_len + 1))
            return ("scan", self._key(), n)
        return (kind, self._key(), val)        # update, rmw

    def requests(self, n: int) -> list:
        """The next ``n`` requests, each a list of op tuples."""
        k = self.ops_per_request
        return [[self.next_op() for _ in range(k)] for _ in range(n)]
