"""Compile rehearsal for TPU v5e, without a chip: the three Pallas probe
kernels at the paper's row width S = 512, and the fused mesh tick on a
described 4-chip v5e mesh, must lower through Mosaic (a ``tpu_custom_call``
in the compiled program, even under JAX_PLATFORMS=cpu), and the
``(P, 512, 2)`` uint32 pool must cost exactly P*512*2*4 bytes of HBM with
no relayout copy.

The topology is described inside fixtures, never at import: only one
process may load the TPU compiler library at a time, and every test
worker imports this file."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs.base import HashMemConfig
from repro.core import hashmap, rlu
from repro.kernels import ops

S, C = 512, 8
POOL_PAGES = 2**18 + 2**16          # the one-chip serving table (chip_smoke)
Q = 8192                            # the largest pow2 probe batch that fits
                                    # the scalar-prefetched schedules in SMEM


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("model",))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _probe_args(kernel, sharding, pages=POOL_PAGES, q=128):
    pool = _sds((pages, S, 2), jnp.uint32, sharding)
    qs = _sds((q,), jnp.uint32, sharding)
    pg = _sds((q, C), jnp.int32, sharding)
    if kernel == "bitserial":
        planes = _sds((pages, 32, S // 32), jnp.uint32, sharding)
        return (lambda pl_, p, q_, g: ops.probe_bitserial(pl_, p, q_, g,
                                                          key_bits=32),
                (planes, pool, qs, pg))
    return getattr(ops, f"probe_{kernel}"), (pool, qs, pg)


@pytest.mark.parametrize("kernel", ["perf", "area", "bitserial"])
def test_probe_kernel_lowers_through_mosaic(kernel, one_chip):
    fn, args = _probe_args(kernel, one_chip, pages=4096, q=Q)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["perf", "area"])
def test_pool_hbm_bytes_unpadded(kernel, one_chip):
    """The (P, 512, 2) pool is read in place: its bytes are exactly
    P*S*2*4 (no 128-lane padding of the key/value pair) and the program
    makes no copy of it."""
    fn, args = _probe_args(kernel, one_chip)
    mem = jax.jit(fn).lower(*args).compile().memory_analysis()
    pool_bytes = POOL_PAGES * S * 2 * 4
    other = sum(int(np.prod(a.shape)) * 4 for a in args[1:])
    assert mem.argument_size_in_bytes == pool_bytes + other
    assert mem.temp_size_in_bytes < 1 << 20


def test_fused_mesh_tick_carries_the_kernel(mesh4):
    """rlu.tick_mesh with backend="perf" over a 4-chip v5e mesh: the
    compiled shard_map program holds the Pallas probe."""
    cfg = HashMemConfig(num_buckets=1024, slots_per_page=S,
                        overflow_pages=256, max_chain=C, backend="perf")
    one = jax.eval_shape(lambda: hashmap.create(cfg))
    shard = NamedSharding(mesh4, P("model"))
    hm = jax.tree.map(lambda x: _sds((4, *x.shape), x.dtype, shard), one)
    q = _sds((128,), jnp.uint32, shard)
    caps = (8, 8, 8)

    def tick(hm, pq, dq, ik, iv):
        return rlu.tick_mesh(mesh4, hm, pq, dq, ik, iv, cfg, "model",
                             caps=caps, shard_by="highbits")
    compiled = jax.jit(tick).lower(hm, q, q, q, q).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text


def test_engine_delete_writes_the_pool_in_place(one_chip):
    """The serving engine's delete program at the one-chip table's size
    (2^18 buckets + 2^16 overflow pages of 512 slots, a 512-key batch):
    the donated pool is the output pool, and the program holds no
    pool-sized buffer of its own: no copy, no relayout, no key plane."""
    from repro.serving import engine
    cfg = HashMemConfig(num_buckets=2**18, slots_per_page=S,
                        overflow_pages=2**16, max_chain=C, backend="perf")
    hm = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                      jax.eval_shape(lambda: hashmap.create(cfg)))
    keys = _sds((512,), jnp.uint32, one_chip)
    compiled = engine.DeleteInPlace().lower(hm, keys).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == POOL_PAGES * S * 2 * 4
    assert mem.temp_size_in_bytes < 1 << 26
