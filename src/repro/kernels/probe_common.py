"""Plumbing shared by the three Pallas probe kernels.

  * the pool view: the unified PageStore pool is ``(P, S, 2)`` uint32, which
    the TPU keeps in a ``{1,2,0}`` layout (slots on the lanes, the key/value
    pair on the sublanes).  ``row_view`` relabels it as ``(P, 2, S)`` — the
    same bytes, no copy — so one page is a lane-dense ``(2, S)`` block:
    row 0 the keys, row 1 the values.  A ``(1, S, 2)`` block would make
    Mosaic want the key/value pair on the lanes, i.e. a 64x padded relayout
    of the whole pool in HBM.
  * the grid: ``(Q, C)`` over the RLU page schedule, which is
    scalar-prefetched into SMEM together with its forward-filled fetch
    index and the queries (bit-cast to int32: Mosaic reduces and compares
    signed lanes only).  The schedules go in flat, ``(Q*C,)``: SMEM pads a
    2-D array's rows to 128 words, which would cap Q near 500 in its 1 MiB.
  * the output cache line: one ``(1, LINE)`` int32 row per query,
    ``[value, found, page, slot, 0...]`` (the paper's RLU returns the value
    padded to a cache line, §2.5), written with one vector store by the
    first matching chain step; a one-word SMEM flag latches "found".
  * the lowering: ``interpret=None`` lets the platform the call is lowered
    for decide — the Pallas interpreter on CPU, Mosaic on TPU — through
    ``jax.lax.platform_dependent``, so a compile for a TPU always carries
    the kernel (a ``tpu_custom_call``).  Lowering for any other platform
    raises.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import fill_fetch_pages

I32 = jnp.int32
U32 = jnp.uint32
LINE = 128                       # output cache line width (lanes)
NO_SLOT = jnp.iinfo(jnp.int32).max
INT_MIN = jnp.iinfo(jnp.int32).min


def row_view(pool):
    """(P, S, 2) pool -> (P, 2, S): keys in row 0, values in row 1."""
    return jnp.swapaxes(pool, 1, 2)


def as_i32(x):
    return jax.lax.bitcast_convert_type(x, I32)


def first_match(keys, vals, query, valid):
    """First matching slot of one page row and its value.

    ``keys``/``vals`` are (1, n) int32 rows.  Returns (slot, value) scalars;
    slot is NO_SLOT when nothing matches."""
    iota = jax.lax.broadcasted_iota(I32, keys.shape, 1)
    slot = jnp.min(jnp.where((keys == query) & valid, iota, NO_SLOT))
    val = jnp.max(jnp.where(iota == slot, vals, INT_MIN))
    return slot, val


def latch(out_ref, hit_ref, slot, val, page):
    """First-match latch into the query's cache line.  Chain step 0 clears
    the line; a step with a match writes it unless an earlier step did."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        hit_ref[0] = 0

    @pl.when((slot != NO_SLOT) & (hit_ref[0] == 0))
    def _write():
        lane = jax.lax.broadcasted_iota(I32, out_ref.shape, 1)
        out_ref[...] = jnp.where(
            lane == 0, val, jnp.where(
                lane == 1, 1, jnp.where(
                    lane == 2, page, jnp.where(lane == 3, slot, 0))))
        hit_ref[0] = 1


def step_page(pages_ref):
    """Page id of the current grid step (q, c); -1 is a hole."""
    q, c = pl.program_id(0), pl.program_id(1)
    return pages_ref[q * pl.num_programs(1) + c]


def probe_call(kernel, name: str, queries, pages, operands, interpret=None):
    """Run a probe kernel over the (Q, C) schedule.

    Every operand is indexed by page on its leading axis; grid step (q, c)
    fetches the whole trailing block of the page the fetch schedule names
    (one row activation).  Mosaic tiles the second-minor axis by 8 or takes
    it whole, so a page's value row cannot be fetched without its key row.
    ``kernel(pages_ref, fetch_ref, queries_ref, *operand_refs, out_ref,
    hit_ref)``.  Returns (values (Q,) uint32, found (Q,) bool)."""
    qn, C = pages.shape
    pages = pages.astype(I32)
    # forward-filled fetch schedule: a filtered (-1) step repeats the last
    # block index, so Pallas keeps the row resident instead of re-fetching
    # (zero extra row activations; see ref.fill_fetch_pages)
    fetch = fill_fetch_pages(pages)
    args = (pages.reshape(-1), fetch.reshape(-1),
            as_i32(queries.astype(U32)), *operands)

    def page_spec(op):
        tail = (0,) * (op.ndim - 1)
        return pl.BlockSpec((None, *op.shape[1:]),
                            lambda q, c, pages, fetch, queries:
                            (fetch[q * C + c], *tail))

    def run(interp, *args):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,       # pages, fetch, queries
            grid=(qn, C),
            in_specs=[page_spec(op) for op in operands],
            out_specs=pl.BlockSpec((None, 1, LINE),
                                   lambda q, c, pages, fetch, queries:
                                   (q, 0, 0)),
            scratch_shapes=[pltpu.SMEM((1,), I32)],
        )
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((qn, 1, LINE), I32),
            interpret=interp, name=name)(*args)

    if interpret is None:
        out = jax.lax.platform_dependent(*args, cpu=partial(run, True),
                                         tpu=partial(run, False))
    else:
        out = run(interpret, *args)
    line = out[:, 0, :]
    return jax.lax.bitcast_convert_type(line[:, 0], U32), line[:, 1] > 0
