"""Tombstone deletes in place.

The engine's delete program takes the table's page pool donated and writes
the tombstones into it (``engine.DeleteInPlace``); ``hashmap.delete`` reads
only the chain rows it probes (``PageStore.key_rows``), never the whole
key plane.  Pinned here on the CPU: the compiled program aliases the pool
and copies none of it, the jaxpr makes no key plane, a table kept from
before a delete still reads its chains, and the engine's answers and its
``stats()["delete_in_place"]`` counter.
"""
import re

import numpy as np
import jax.numpy as jnp
import pytest

from repro.configs.base import HashMemConfig
from repro.core import hashmap
from repro.core.introspect import primitive_shapes
from repro.serving import Request, ServingEngine
from repro.serving import engine as engine_mod

CONFIGS = {
    "chained": dict(num_buckets=16, slots_per_page=16, overflow_pages=16,
                    max_chain=4, backend="ref"),
    "bitplanes": dict(num_buckets=16, slots_per_page=64, overflow_pages=16,
                      max_chain=4, backend="bitserial"),
    "displaced": dict(num_buckets=8, slots_per_page=32, overflow_pages=24,
                      max_chain=4, backend="perf", displacement=True,
                      fingerprint_bits=8, stash_slots=16),
}


def _table(name: str, n: int = 96):
    cfg = HashMemConfig(auto_grow=False, **CONFIGS[name])
    keys = jnp.arange(1, n + 1, dtype=jnp.uint32) * 7
    hm, ok = hashmap.insert(hashmap.create(cfg), keys, keys + 1)
    assert bool(np.asarray(ok).all())
    return hm, keys


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_delete_program_aliases_the_pool_and_copies_none_of_it(name):
    hm, keys = _table(name)
    compiled = engine_mod.DeleteInPlace().lower(hm, keys[:8]).compile()
    pool = hm.store.pool
    assert compiled.memory_analysis().alias_size_in_bytes >= pool.nbytes
    text = compiled.as_text()
    assert re.search(r"input_output_alias=\{ \{0\}: \(0, \{\}",
                     text.splitlines()[0])
    pool_copy = re.compile(r"= u32\[{},{},2\]\{{[^}}]*\}} copy\(".format(
        *pool.shape[:2]))
    assert not [ln for ln in text.splitlines() if pool_copy.search(ln)]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_delete_jaxpr_makes_no_key_plane(name):
    hm, keys = _table(name)
    plane = (hm.config.num_pages, hm.config.slots_per_page)
    shapes = primitive_shapes(hashmap.delete, "", hm, keys[:8])
    assert shapes and plane not in shapes


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_table_kept_from_before_a_delete_reads_its_chains(name):
    """What the benchmark does with the tables it keeps at the window's
    close: the engine deletes on, and ``hashmap.chain_lengths`` of the kept
    table still reads (only its pool went to the delete)."""
    hm, keys = _table(name)
    prog = engine_mod.DeleteInPlace()
    kept = hm
    chains = np.asarray(hashmap.chain_lengths(hm))
    hm, found = prog(hm, keys[:8])
    assert bool(np.asarray(found).all())
    assert kept.store.pool.is_deleted()
    np.testing.assert_array_equal(
        np.asarray(hashmap.chain_lengths(kept)), chains)
    vals, hit = hashmap.probe(hm, keys)
    np.testing.assert_array_equal(np.asarray(hit),
                                  np.arange(len(keys)) >= 8)
    np.testing.assert_array_equal(np.asarray(vals)[8:],
                                  np.asarray(keys + 1)[8:])
    assert prog.not_aliased == 0


@pytest.mark.parametrize("depth", [1, 2])
def test_probe_and_delete_of_one_key_in_one_tick(depth):
    """The probe phase runs before the delete that consumes the pool it
    read: a read and a delete of one key in one tick see the old value,
    the next tick's read sees the tombstone."""
    eng = ServingEngine(HashMemConfig(**CONFIGS["chained"]), max_slots=4,
                        pipeline_depth=depth)
    eng.preload(np.asarray([5, 6], np.uint32),
                np.asarray([50, 60], np.uint32))
    read = Request(ops=[("read", 5), ("read", 5)])
    delete = Request(ops=[("delete", 5)])
    eng.submit_all([read, delete])
    eng.run()
    assert read.results[0] == {"op": "read", "key": 5, "value": 50,
                               "found": True}
    assert delete.results[0]["found"] is True
    assert read.results[1]["found"] is False
    assert eng.stats()["delete_in_place"] == {"calls": 1, "not_aliased": 0}


@pytest.mark.parametrize("name", ["chained", "displaced"])
def test_tables_passed_to_the_engine_stay_readable(name):
    """The engine copies the pool of each table it is given once, before
    its first delete there, so the caller's table reads as before however
    much the engine deletes."""
    table, keys = _table(name)
    eng = ServingEngine(tables=[table], max_slots=8, trace=True)
    eng.submit(Request(ops=[("read", int(keys[0]))]))
    eng.run()
    assert eng.shards[0].store.pool is table.store.pool
    raw = [int(k) for k in np.asarray(keys)[:16]]
    eng.submit_all([Request(ops=[("delete", k), ("delete", k + 1)])
                    for k in raw])
    eng.run()
    vals, hit = hashmap.probe(table, keys)
    assert bool(np.asarray(hit).all())
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(keys + 1))
    _, hit = hashmap.probe(eng.shards[0], keys)
    np.testing.assert_array_equal(np.asarray(hit), np.arange(len(keys)) >= 16)
    st = eng.stats()["delete_in_place"]
    assert st == {"calls": eng.batch_calls["delete"], "not_aliased": 0}
    assert st["calls"] >= 2
    counted = [e["args"] for e in eng.tracer.to_events()
               if e.get("ph") == "C" and e["name"] == "delete_in_place.calls"]
    assert counted[-1] == {"value": float(st["calls"])}
