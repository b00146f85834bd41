"""Bit-serial element-parallel HashMem probe kernel — the faithful §2.2 form.

Paper mechanism (performance-optimized version): keys are stored
column-oriented so "each row contains a single-bit slice from thousands of
values"; comparison proceeds one bit-plane per step — b steps for b-bit keys
— with ALL keys compared in parallel at every step.

TPU adaptation (DESIGN.md §2): bit-planes are packed 32-slots-per-uint32-word
(layout.pack_bitplanes); the per-bit step is a single vector XOR+OR over the
word lanes, so one grid step performs `key_bits` vector ops regardless of the
number of slots — exactly the paper's b-cycle CAM scan.  The value readout
comes from the unified PageStore's page row, row 1 of the (P, 2, S) view
(probe_common).  Mosaic tiles the second-minor axis by 8 or takes it whole,
so the BlockSpec fetches the whole (2, S) page and the key row rides along
unread.  On TPU this wins over probe_perf only for sub-32-bit keys
(b = 4/8/16, the paper's column widths); at b=32 the bit-parallel compare
of probe_perf is strictly better.  The benchmark harness quantifies that crossover (EXPERIMENTS.md
§Perf).

I/O: planes (P, b, W=S//32) u32 bit-planes, pool (P, S, 2) u32 interleaved
pages, queries (Q,) u32, pages (Q, C) i32.  Output cache line as probe_perf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.probe_common import (I32, INT_MIN, NO_SLOT, as_i32,
                                        latch, probe_call, row_view,
                                        step_page)


def _make_kernel(key_bits: int):
    def _kernel(pages_ref, fetch_ref, queries_ref, planes_ref, page_ref,
                out_ref, hit_ref):
        del fetch_ref   # consumed by the BlockSpec index maps only
        page = step_page(pages_ref)
        query = queries_ref[pl.program_id(0)]
        planes = as_i32(planes_ref[...])                     # (b, W)
        W = planes.shape[1]

        # --- the bit-serial scan: key_bits steps, all slots in parallel ---
        mismatch = jnp.zeros((1, W), I32)
        for j in range(key_bits):                            # static unroll: b steps
            qword = -((query >> j) & 1)                      # 0 or all ones
            mismatch = mismatch | (planes[j:j + 1, :] ^ qword)
        match_words = ~mismatch                              # (1, W)

        # --- one-time extraction (the RLU readout, not part of the b-scan) ---
        # bit i of word w is slot 32*w + i: (32, W) tiles, no relayout
        bit_i = jax.lax.broadcasted_iota(I32, (32, W), 0)
        word_i = jax.lax.broadcasted_iota(I32, (32, W), 1)
        bits = (jax.lax.shift_right_logical(
            jnp.broadcast_to(match_words, (32, W)), bit_i) & 1) == 1
        slot = jnp.min(jnp.where(bits & (page >= 0), word_i * 32 + bit_i,
                                 NO_SLOT))
        vals = as_i32(page_ref[...])[1:2, :]                 # value row
        slot_iota = jax.lax.broadcasted_iota(I32, vals.shape, 1)
        val = jnp.max(jnp.where(slot_iota == slot, vals, INT_MIN))
        latch(out_ref, hit_ref, slot, val, page)

    return _kernel


def probe_pages_bitserial(planes, pool, queries, pages, key_bits: int,
                          *, interpret=None):
    P, b, W = planes.shape
    assert b == key_bits
    S = pool.shape[1]
    assert S == W * 32
    return probe_call(_make_kernel(key_bits), "hashmem_probe_bitserial",
                      queries, pages, (planes, row_view(pool)), interpret)
