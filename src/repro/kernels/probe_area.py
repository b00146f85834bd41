"""Area-optimized HashMem probe kernel (paper §2.1).

Paper mechanism: ONE comparison unit per subarray walks the activated row
buffer *element-serial, bit-parallel* — one key/value pair per step, matched
keys latched into the output register.

TPU adaptation (DESIGN.md §2): a TPU has no efficient scalar element walk
over VMEM; the closest faithful analogue is *strip-serial*: a fori_loop
steps through the row one 128-lane strip at a time, performing a single
compare per step and latching the first match — serial at strip granularity
(the "one comparator" is one VPU issue slot per step), versus probe_perf
which consumes the whole row at once.  The activated row is the interleaved
(slots, 2) key/value segment of the unified PageStore — ONE BlockSpec fetch
per chain step; each strip compares the key lane and latches the matching
value lane of the SAME row.  This preserves the paper's area/perf contrast:
same single-activation I/O, serialized compare schedule.

Same grid/O contract as probe_perf (probe_common.py); each strip is a
``pl.ds`` slice of the resident row, read straight from VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.probe_common import (NO_SLOT, as_i32, first_match, latch,
                                        probe_call, row_view, step_page)

STRIP = 128


def _make_kernel(strip: int, n_strips: int):
    def _kernel(pages_ref, fetch_ref, queries_ref, pool_ref, out_ref,
                hit_ref):
        del fetch_ref   # consumed by the BlockSpec index maps only
        page = step_page(pages_ref)
        query = queries_ref[pl.program_id(0)]
        valid = page >= 0

        def body(i, carry):
            slot, val = carry
            start = pl.multiple_of(i * strip, strip)
            strip_kv = as_i32(pool_ref[:, pl.ds(start, strip)])   # (2, strip)
            s_local, v_local = first_match(strip_kv[0:1, :],
                                           strip_kv[1:2, :], query, valid)
            # element-serial latch: only the first matching strip counts
            take = (slot == NO_SLOT) & (s_local != NO_SLOT)
            return (jnp.where(take, start + s_local, slot),
                    jnp.where(take, v_local, val))

        slot, val = jax.lax.fori_loop(0, n_strips, body,
                                      (jnp.int32(NO_SLOT), jnp.int32(0)))
        latch(out_ref, hit_ref, slot, val, page)

    return _kernel


def probe_pages_area(pool, queries, pages, *, interpret=None):
    S = pool.shape[1]
    # full lane strips on real shapes; small test pages fall back to one strip
    strip = min(STRIP, S)
    assert S % strip == 0, "slots must be a multiple of the strip width"
    return probe_call(_make_kernel(strip, S // strip), "hashmem_probe_area",
                      queries, pages, (row_view(pool),), interpret)
