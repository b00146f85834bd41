"""The trace reduction on a synthetic event set."""
import numpy as np
import pytest

from perfbench import devtrace, roofline
from perfbench.devtrace import Event

DEV = "/device:TPU:0"


def _op(name, start, dur):
    """An op event named by its HLO text, as the TPU trace names them."""
    return Event(DEV, devtrace.OPS_LINE,
                 f"%{name} = u32[512]{{0}} fusion(u32[512]{{0}} %p.1)",
                 start, dur)


EVENTS = [
    _op("hashmem_probe_perf.1", 100, 50),
    _op("fusion.1", 140, 30),                 # overlaps the kernel
    _op("fusion.2", 300, 100),
    _op("copy.3", 900, 200),                  # runs past the window
    Event(DEV, devtrace.MODULES_LINE, "jit_probe(11)", 90, 100),
    Event(DEV, devtrace.MODULES_LINE, "jit_delete(12)", 290, 120),
    Event(DEV, devtrace.MODULES_LINE, "jit_insert(13)", 890, 220),
    Event("/host:CPU", "python", "perfbench_window", 0, 1000),
    Event("/host:CPU", "python", "tick", 50, 500),
    Event("/host:CPU", "python", "submit", 600, 100),
]


def test_busy_union_and_idle_share():
    # busy: [100,170) + [300,400) + [900,1000) inside [0, 1000)
    assert devtrace.busy_ns(EVENTS, 0, 1000) == 270
    assert devtrace.gaps(EVENTS, 0, 1000) == [(0, 100), (170, 300),
                                              (400, 900)]


def test_kernel_and_module_time_by_name():
    ops = devtrace.ops(EVENTS)
    assert devtrace.matching_ns(ops, "hashmem_probe_perf", 0, 1000) == 50
    assert devtrace.matching_ns(ops, "fusion", 0, 1000) == 130
    assert devtrace.time_by_name(EVENTS, 0, 1000) == {
        "jit_probe/hashmem_probe_perf.1": 50, "jit_probe/fusion.1": 30,
        "jit_delete/fusion.2": 100, "jit_insert/copy.3": 100}
    mods = devtrace.modules(EVENTS)
    assert devtrace.matching_ns(mods, "jit_delete", 0, 1000) == 120
    assert devtrace.matching_ns(mods, "jit_insert", 0, 1000) == 110


def test_gap_attribution_takes_the_innermost_span():
    spans = [("harness:tick", 50, 550), ("engine:gather", 160, 320),
             ("harness:submit", 600, 700)]
    assert devtrace.name_gap((170, 300), spans, "loop") == "engine:gather"
    assert devtrace.name_gap((400, 500), spans, "loop") == "harness:tick"
    assert devtrace.name_gap((600, 700), spans, "loop") == "harness:submit"
    assert devtrace.name_gap((800, 900), spans, "loop") == "loop"


def test_no_device_plane_reads_nothing():
    host = [e for e in EVENTS if not e.plane.startswith("/device")]
    assert devtrace.busy_ns(host, 0, 1000) == 0
    assert devtrace.gaps(host, 0, 1000) == []


TABLE = {"num_buckets": 64, "slots_per_page": 512, "salt": 0x9E3779B9}


def test_least_bytes_count_a_shared_page_once():
    keys = list(range(200))
    once = roofline.probe_least_bytes([keys], TABLE)
    b = roofline.bucket_of(np.asarray(keys), 64, TABLE["salt"])
    assert once == len(set(b.tolist())) * 512 * 8
    # the same queries again, and more queries on pages already counted,
    # in one call: no more bytes
    again = [k for k in range(200, 5000) if roofline.bucket_of(
        np.asarray([k]), 64, TABLE["salt"])[0] in set(b)]
    assert roofline.probe_least_bytes([keys + keys + again], TABLE) == once
    # split over two calls, a page counts in each call that reads it
    assert roofline.probe_least_bytes([keys, keys], TABLE) == 2 * once


def test_bucket_copy_matches_the_program_hash():
    import jax.numpy as jnp

    from repro.core.hashing import hash_to_bucket
    keys = np.arange(0, 1 << 20, 977, dtype=np.uint32)
    want = np.asarray(hash_to_bucket(jnp.asarray(keys), 262144))
    assert (roofline.bucket_of(keys, 262144, 0x9E3779B9) == want).all()


def test_unknown_device_kind_is_an_error():
    assert roofline.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        roofline.peak("TPU v9 imaginary", "hbm_bytes_per_s")
