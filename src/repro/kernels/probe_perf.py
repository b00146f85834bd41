"""Performance-optimized HashMem probe kernel (paper §2.2) — TPU-native form.

Paper mechanism: many comparison units pitch-matched under the row buffer
compare *all* keys of the activated row simultaneously (CAM semantics).

TPU adaptation (DESIGN.md §2): one grid step == one row activation.  The
BlockSpec index_map uses the scalar-prefetched page list (the RLU command
stream) to "activate" the page row into VMEM; the 8x128 VPU lanes are the
pitch-matched comparators — the whole row is compared in O(1) vector ops.
The row is the INTERLEAVED (slots, 2) key/value segment of the unified
PageStore, so ONE BlockSpec fetch per chain step exposes both the keys to
compare and the value to latch — exactly the paper's single row activation
serving the whole probe (§2.2, §2.4).  Because TPU lanes are 32-bit, the
compare is element-parallel AND bit-parallel (in DRAM the sense amps force
bit-serial; see probe_bitserial for the faithful bit-serial variant).

Grid: (Q, C) — C (chain position) iterates fastest and accumulates
first-match results into a 128-lane output "cache line" per query, matching
the paper's RLU returning the value padded to a cache line (§2.5).  The
grid, the (P, 2, S) row view of the pool, the cache line and the choice
between Mosaic and the interpreter are shared with the other two kernels
(probe_common.py).
"""
from __future__ import annotations

from jax.experimental import pallas as pl

from repro.kernels.probe_common import (as_i32, first_match, latch,
                                        probe_call, row_view, step_page)


def _kernel(pages_ref, fetch_ref, queries_ref, pool_ref, out_ref, hit_ref):
    del fetch_ref   # consumed by the BlockSpec index maps only
    page = step_page(pages_ref)
    kv = as_i32(pool_ref[...])                    # (2, S): ONE activated row
    # element-parallel compare of every key of the row; the value comes
    # from the same activated row
    slot, val = first_match(kv[0:1, :], kv[1:2, :], queries_ref[pl.program_id(0)],
                            page >= 0)
    latch(out_ref, hit_ref, slot, val, page)


def probe_pages_perf(pool, queries, pages, *, interpret=None):
    """(values (Q,) u32, found (Q,) bool).  ``pool`` is the interleaved
    (P, S, 2) page pool; see module docstring.  ``interpret=None``: the
    lowering platform decides (probe_common)."""
    return probe_call(_kernel, "hashmem_probe_perf", queries, pages,
                      (row_view(pool),), interpret)
