"""LoadGen draws from cached CDFs; its streams must stay bit-identical to
the ``Generator.choice(n, p=...)`` draws they replace."""
import numpy as np
import pytest

from repro.data.kv_synth import zipfian_weights
from repro.serving.loadgen import LoadGen, WorkloadSpec


def _choice_stream(spec: WorkloadSpec, seed: int, n_ops: int):
    """The same op stream, drawn with ``rng.choice`` and a CDF rebuilt on
    every op (the generator's draws before the CDFs were cached)."""
    rng = np.random.default_rng(seed)
    mix = spec.resolved_mix()
    kinds = list(mix)
    probs = np.asarray([mix[k] for k in kinds])
    probs = probs / probs.sum()
    dist = spec.resolved_dist()
    insert_point = spec.record_count
    zn, zw = 0, None
    out = []

    def zipf(n):
        nonlocal zn, zw
        if zw is None or n < zn or n > zn * 1.25:
            zn, zw = n, zipfian_weights(n, spec.theta)
        return min(int(rng.choice(zn, p=zw)), n - 1)

    def key():
        n = max(insert_point, 1)
        if dist == "uniform":
            return int(rng.integers(0, n))
        if dist == "latest":
            return (n - 1) - zipf(n)
        return zipf(n)

    for _ in range(n_ops):
        kind = kinds[int(rng.choice(len(kinds), p=probs))]
        val = int(rng.integers(1, 2**31))
        if kind == "insert":
            out.append(("insert", insert_point, val))
            insert_point += 1
        elif kind == "read":
            out.append(("read", key()))
        elif kind == "scan":
            n = int(rng.integers(1, spec.scan_len + 1))
            out.append(("scan", key(), n))
        else:
            out.append((kind, key(), val))
    return out


@pytest.mark.parametrize("workload", ["A", "B", "C", "D", "E", "F"])
def test_cached_cdf_draws_match_generator_choice(workload):
    spec = WorkloadSpec(workload, record_count=257, ops_per_request=1)
    gen = LoadGen(spec, seed=11)
    got = [gen.next_op() for _ in range(600)]
    assert got == _choice_stream(spec, 11, 600)
