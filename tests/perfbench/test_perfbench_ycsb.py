"""The benchmark's generator copy draws what the program's LoadGen draws."""
import numpy as np
import pytest

from perfbench.ycsb import OpStream
from repro.serving.loadgen import LoadGen, WorkloadSpec


@pytest.mark.parametrize("workload,dist,records", [
    ("A", "", 1000), ("B", "", 12_500_000), ("C", "uniform", 100_000_000),
    ("D", "", 100), ("E", "", 300), ("F", "", 5000), ("C", "latest", 777),
    ("E", "uniform", 1)])
def test_streams_equal_loadgen(workload, dist, records):
    seed = 3_000_000_019          # above 32 signed bits: seeds may be large
    gen = LoadGen(WorkloadSpec(workload, record_count=records,
                               distribution=dist), seed=seed)
    copy = OpStream(workload, records, distribution=dist, seed=seed)
    want = [tuple(r.ops) for r in gen.requests(1500)]
    got = copy.requests(500) + copy.requests(1000)   # across draw batches
    assert [tuple(r) for r in got] == want


def test_seed_sequences_are_accepted_and_differ():
    a = OpStream("A", 1000, seed=np.random.SeedSequence([7, 0])).requests(50)
    b = OpStream("A", 1000, seed=np.random.SeedSequence([7, 1])).requests(50)
    again = OpStream("A", 1000,
                     seed=np.random.SeedSequence([7, 0])).requests(50)
    assert a == again and a != b
