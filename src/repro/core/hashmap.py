"""Functional HashMem structure (paper §2.4-2.5, §3).

Semantics mirror the paper exactly:

  * bucket i owns page i (bucket-per-row mapping); overflow pages are chained
    through ``page_next`` — the paper's "bookkeeping structure ... attaches and
    links new page to old page in a Linked List fashion".
  * ``free_top`` is the ``pim_malloc`` bump allocator over the overflow arena.
  * deletion writes TOMBSTONE_KEY "at the cost of wasted space" (paper §2.5):
    tombstoned slots are NOT reused; inserts append at the chain tail.
  * probing resolves the page chain (the RLU command stream) and hands the
    page list to a backend (ref / area / perf / bitserial — see probe.py and
    kernels/).

Storage layout
--------------
The structure is a thin shell around a :class:`repro.core.layout.PageStore`:
one interleaved ``(num_pages, slots, 2)`` uint32 pool (lane 0 = key,
lane 1 = value) plus the chain links, fill marks, bit-planes and the
pim_malloc pointer.  One page == one DRAM row holding keys AND values, so

  * every probe backend reads key and value from the SAME activated row —
    one page fetch per chain step (the paper's row-buffer semantics), and
  * every mutation writes key+value with ONE fused pool scatter
    (``store.write_slots``) instead of the split layout's two.

``hm.key_pages`` / ``hm.val_pages`` / ``hm.planes`` / ``hm.page_next`` /
``hm.page_fill`` / ``hm.free_top`` remain available as thin views so
external callers and the differential harness see the same split API.

Everything is a JAX pytree and jit/vmap/pjit-compatible; the structure is
immutable — every mutation returns a new HashMem.

Mutation & resizing semantics
-----------------------------
The online mutation engine extends the paper's populate-once model:

  * ``insert`` is VECTORIZED: the whole batch is resolved with the same
    sort/rank/segment machinery as ``build_with_buckets`` and appended to the
    existing chain tails in one shot — three pool-shaped scatters total
    (fused key/value write, fill high-water, chain link).  Within a batch it
    is equivalent to repeated single inserts in batch order (stable sort
    keeps intra-bucket batch order; duplicates are all stored, probe returns
    the oldest).  The original sequential version is kept as ``insert_scan``
    (reference semantics + benchmark baseline).
  * ``ok=False`` now means the element was NOT stored because pim_malloc
    failed — either the overflow arena is exhausted or appending would push
    the bucket's chain past ``config.max_chain`` (the RLU command-depth
    bound).  The scan version silently exceeded the chain bound, making keys
    unfindable; the vectorized engine refuses instead so callers can grow.
  * ``grow(hm)`` rebuilds into a larger arena (``growth_factor`` x buckets
    and overflow pages), re-bucketing every live entry, rebuilding chains and
    (for the bitserial backend) the bit-planes from scratch.  ``compact(hm)``
    is the same rebuild at the current size: it reclaims all tombstoned slots
    and overflow pages (the paper's "wasted space", §2.5).  Both preserve
    relative chain order of same-key duplicates, so probe/delete semantics
    are unchanged across resizes.  Both are jit-compatible for a fixed
    (old config, new config) pair — shapes are static per config.
  * ``insert_auto`` is the HOST-level policy loop (not jit-compatible:
    growth changes array shapes): it grows proactively when the batch would
    push the load factor past ``config.max_load_factor`` and reactively while
    any element reports ok=False, up to ``max_grows`` doublings.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import HashMemConfig
from repro.core import layout
from repro.core.hashing import (EMPTY_KEY, TOMBSTONE_KEY, bits_used,
                                fingerprint, hash_to_bucket, hash_to_bucket2)

I32 = jnp.int32
U32 = jnp.uint32

# bucket_fn(keys (N,) u32, cfg) -> (N,) i32 bucket ids (see grow/_rebuild);
# None means the default hash_to_bucket(cfg) assignment.
BucketFn = Callable[[jax.Array, HashMemConfig], jax.Array]


@partial(jax.tree_util.register_dataclass,
         data_fields=["store", "bucket_head"],
         meta_fields=["config"])
@dataclass
class HashMem:
    store: layout.PageStore       # interleaved pool + page bookkeeping
    bucket_head: jax.Array        # (num_buckets,) int32
    config: HashMemConfig

    # -- thin split views (external callers / differential harness) --------
    @property
    def key_pages(self) -> jax.Array:      # (num_pages, slots) uint32
        return self.store.key_pages

    @property
    def val_pages(self) -> jax.Array:      # (num_pages, slots) uint32
        return self.store.val_pages

    @property
    def planes(self) -> Optional[jax.Array]:
        return self.store.planes

    @property
    def page_next(self) -> jax.Array:      # (num_pages,) int32, -1 terminal
        return self.store.page_next

    @property
    def page_fill(self) -> jax.Array:      # (num_pages,) int32 high-water
        return self.store.page_fill

    @property
    def free_top(self) -> jax.Array:       # () int32 pim_malloc bump pointer
        return self.store.free_top


def _keep_planes(cfg: HashMemConfig) -> bool:
    return cfg.backend == "bitserial"


def _check_resize(cfg: HashMemConfig) -> Optional[int]:
    """Validate the resize knob; returns the global depth for extendible
    tables (None for rebuild).  Extendible resize needs a power-of-two
    directory (the bucket id IS the low-bits hash prefix) and excludes the
    displacement/stash paths (a displaced entry lives at H1 OR H2, so a
    single group's entries are not re-bucketable in isolation)."""
    if cfg.resize not in ("rebuild", "extendible"):
        raise ValueError(f"unknown resize mode {cfg.resize!r} "
                         f"(want 'rebuild' or 'extendible')")
    if cfg.resize != "extendible":
        return None
    if cfg.displacement or cfg.stash_slots:
        raise ValueError("resize='extendible' excludes displacement/stash "
                         "(split re-buckets one group in isolation; a "
                         "displaced entry's home is H1 OR H2)")
    return bits_used(cfg.num_buckets)


def create(cfg: HashMemConfig) -> HashMem:
    """Empty HashMem: every bucket pre-owns its direct page (paper §2.4)."""
    gd = _check_resize(cfg)
    store = layout.empty_store(cfg.num_pages, cfg.slots_per_page,
                               cfg.key_bits, with_planes=_keep_planes(cfg),
                               fp_bits=cfg.fingerprint_bits,
                               stash_slots=cfg.stash_slots,
                               local_depth=gd)
    store = dataclasses.replace(
        store, free_top=jnp.asarray(cfg.num_buckets, dtype=I32))
    return HashMem(
        store=store,
        bucket_head=jnp.arange(cfg.num_buckets, dtype=I32),
        config=cfg,
    )


# ---------------------------------------------------------------------------
# Bulk build (vectorized; the paper populates the dataset before probing)
# ---------------------------------------------------------------------------

def build(cfg: HashMemConfig, keys: jax.Array, vals: jax.Array) -> HashMem:
    """Vectorized bulk load of N key/value pairs.

    Buckets receive ceil(count/slots) pages; overflow pages are allocated
    contiguously from the arena in bucket order.  Duplicate keys are all
    stored; probe returns the first match in chain order.
    """
    b = hash_to_bucket(keys.astype(U32), cfg.num_buckets, cfg.hash_fn, cfg.salt)
    return build_with_buckets(cfg, keys, vals, b)


def build_with_buckets(cfg: HashMemConfig, keys: jax.Array, vals: jax.Array,
                       b: jax.Array) -> HashMem:
    """Bulk load with caller-supplied bucket ids (used by the RLU channel
    layer, which derives (owner shard, local bucket) from one global hash).

    Under ``cfg.displacement`` the load is replayed through the displaced
    insert path (EMPTY_KEY pads are dropped, not stored, unlike the
    chained bulk loader which stores whatever it is given)."""
    if cfg.displacement:
        k = keys.astype(U32)
        hm, _ = _insert_displaced(create(cfg), k, vals, b,
                                  valid=k != EMPTY_KEY)
        return hm
    return _scatter_build(cfg, keys, vals, b, valid=None)


def _scatter_build(cfg: HashMemConfig, keys: jax.Array, vals: jax.Array,
                   b: jax.Array, valid: Optional[jax.Array]) -> HashMem:
    """Shared sort/rank/segment bulk loader.  Entries with ``valid=False``
    (or bucket id >= num_buckets) are dropped; relative order of surviving
    entries within a bucket follows their input order (stable sort)."""
    cfg_slots = cfg.slots_per_page
    n = keys.shape[0]
    keys = keys.astype(U32)
    vals = vals.astype(U32)
    b = b.astype(I32)
    if valid is not None:
        b = jnp.where(valid, b, cfg.num_buckets)               # sorts to the end
    order = jnp.argsort(b)
    bs, ks, vs = b[order], keys[order], vals[order]
    dropped = bs >= cfg.num_buckets

    start = jnp.searchsorted(bs, bs, side="left")
    rank = jnp.arange(n, dtype=I32) - start.astype(I32)                    # rank in bucket
    depth = rank // cfg_slots
    slot = rank % cfg_slots

    counts = jnp.zeros((cfg.num_buckets,), I32).at[bs].add(1, mode="drop")
    n_over = jnp.maximum((counts + cfg_slots - 1) // cfg_slots - 1, 0)     # overflow pages/bucket
    over_off = jnp.cumsum(n_over) - n_over                                 # exclusive prefix

    ob = jnp.minimum(bs, cfg.num_buckets - 1)                              # safe gather
    page = jnp.where(depth == 0, bs,
                     cfg.num_buckets + over_off[ob] + depth - 1)
    page = jnp.where(dropped, cfg.num_pages, page).astype(I32)             # OOB -> dropped

    pool = layout.empty_pool(cfg.num_pages, cfg_slots)
    pool = pool.at[page, slot].set(jnp.stack([ks, vs], axis=-1), mode="drop")
    page_fill = jnp.zeros((cfg.num_pages,), I32).at[page].max(slot + 1,
                                                              mode="drop")

    # chain links: first element landing on a depth>=1 page links prev -> page
    is_link = (depth >= 1) & (slot == 0) & ~dropped
    prev_page = jnp.where(depth == 1, bs,
                          cfg.num_buckets + over_off[ob] + depth - 2).astype(I32)
    link_idx = jnp.where(is_link, prev_page, cfg.num_pages)                # OOB -> dropped
    page_next = jnp.full((cfg.num_pages,), -1, I32).at[link_idx].set(page, mode="drop")

    free_top = cfg.num_buckets + jnp.sum(n_over)
    planes = layout.pack_bitplanes(pool[..., layout.KEY_LANE], cfg.key_bits) \
        if _keep_planes(cfg) else None
    fprints = None
    if cfg.fingerprint_bits > 0:
        fprints = layout.pack_bitplanes(
            fingerprint(pool[..., layout.KEY_LANE], cfg.fingerprint_bits),
            cfg.fingerprint_bits)
    stash = stash_fill = None
    if cfg.stash_slots > 0:
        stash = jnp.broadcast_to(jnp.array([EMPTY_KEY, 0], dtype=U32),
                                 (cfg.stash_slots, 2))
        stash_fill = jnp.asarray(0, dtype=I32)
    # extendible tables leave a (re)build with a flat directory: every group
    # back at the global depth, all leaked split pages reclaimed
    gd = _check_resize(cfg)
    depths = None if gd is None else jnp.full((cfg.num_pages,), gd, I32)

    store = layout.PageStore(pool=pool, planes=planes, page_next=page_next,
                             page_fill=page_fill,
                             free_top=free_top.astype(I32),
                             key_bits=cfg.key_bits,
                             fprints=fprints, stash=stash,
                             stash_fill=stash_fill,
                             local_depth=depths,
                             fp_bits=cfg.fingerprint_bits)
    return HashMem(store=store,
                   bucket_head=jnp.arange(cfg.num_buckets, dtype=I32),
                   config=cfg)


def _fit_report(counts, cfg: HashMemConfig) -> dict:
    """Shared fit check: would per-bucket `counts` fit the chain/arena bounds?"""
    import numpy as np
    pages = np.maximum((counts + cfg.slots_per_page - 1) // cfg.slots_per_page, 0)
    return {
        "max_chain_needed": int(pages.max(initial=0)),
        "overflow_pages_needed": int(np.maximum(pages - 1, 0).sum()),
        "fits": bool(pages.max(initial=0) <= cfg.max_chain
                     and np.maximum(pages - 1, 0).sum() <= cfg.overflow_pages),
    }


def build_check(cfg: HashMemConfig, keys) -> dict:
    """Pre-flight (non-jit) checks that the arena/chain bounds suffice."""
    import numpy as np
    b = np.asarray(hash_to_bucket(jnp.asarray(keys, U32), cfg.num_buckets,
                                  cfg.hash_fn, cfg.salt))
    counts = np.bincount(b, minlength=cfg.num_buckets)
    rep = _fit_report(counts, cfg)
    rep["load_factor"] = float(counts.sum() / (cfg.num_pages * cfg.slots_per_page))
    rep["bucket_counts"] = counts
    return rep


# ---------------------------------------------------------------------------
# RLU command-stream resolution (paper §2.3: RLU locates subarray rows)
# ---------------------------------------------------------------------------

def resolve_pages(hm: HashMem, queries: jax.Array) -> jax.Array:
    """queries (Q,) uint32 -> (Q, max_chain) int32 page ids, -1 padded.

    This is the RLU step: translate each probe key into the ordered list of
    subarray rows (pages) to activate.  Bounded by config.max_chain.
    """
    cfg = hm.config
    b = hash_to_bucket(queries.astype(U32), cfg.num_buckets, cfg.hash_fn, cfg.salt)
    return resolve_pages_by_bucket(hm, b)


def resolve_pages_by_bucket(hm: HashMem, b: jax.Array) -> jax.Array:
    cfg = hm.config
    page = hm.bucket_head[b]                                              # (Q,)
    cols = [page]
    for _ in range(cfg.max_chain - 1):
        nxt = jnp.where(page >= 0, hm.page_next[jnp.maximum(page, 0)], -1)
        cols.append(nxt)
        page = nxt
    return jnp.stack(cols, axis=1).astype(I32)


def chain_lengths(hm: HashMem) -> jax.Array:
    """(num_buckets,) int32 chain lengths via a bounded vectorized walk.

    Walks one step past ``config.max_chain`` so an over-long chain (an
    invariant violation) is visible as a length of max_chain + 1.
    """
    cfg = hm.config
    p = hm.bucket_head
    clen = (p >= 0).astype(I32)
    for _ in range(cfg.max_chain):
        p = jnp.where(p >= 0, hm.page_next[jnp.maximum(p, 0)], -1)
        clen = clen + (p >= 0).astype(I32)
    return clen


def max_chain_len(hm: HashMem) -> int:
    """Longest bucket chain, in pages (the per-probe RLU command depth)."""
    return int(jnp.max(chain_lengths(hm)))


def compact_due(hm: HashMem, tombstones: int, *, fraction: bool = True,
                chain: bool = True) -> bool:
    """THE compaction trigger policy (single definition for every serving
    layer — PageTableManager and ServingEngine): with tombstones present,
    compact when they exceed ``compact_tombstone_frac`` of capacity
    (``fraction``) or, with ``compact_chain_len`` > 0, when any bucket
    chain exceeds that many pages (``chain`` — a device walk + host sync;
    callers that need to throttle it pass chain=False on cheap checks)."""
    cfg = hm.config
    if tombstones <= 0:
        return False
    if fraction and \
            tombstones > cfg.compact_tombstone_frac * cfg.num_pages * \
            cfg.slots_per_page:
        return True
    return chain and cfg.compact_chain_len > 0 and \
        max_chain_len(hm) > cfg.compact_chain_len


# ---------------------------------------------------------------------------
# Probe / insert / delete
# ---------------------------------------------------------------------------

def resolve_pages_displaced(hm: HashMem, queries: jax.Array,
                            b1: Optional[jax.Array] = None) -> jax.Array:
    """Displaced page schedule: [H1 direct page] + [H2 chain], -1 padded.

    Search order matches the displaced insert's placement order (H1 direct
    first, then the H2 chain, then the stash — handled by the caller), so
    the first match is still the oldest duplicate.  When b1 == b2 the H2
    chain's head duplicates the direct page; it is blanked to -1 (only
    position 0 can collide: overflow pages sit above num_buckets)."""
    cfg = hm.config
    q = queries.astype(U32)
    if b1 is None:
        b1 = hash_to_bucket(q, cfg.num_buckets, cfg.hash_fn, cfg.salt)
    b2 = hash_to_bucket2(q, cfg.num_buckets, cfg.hash_fn, cfg.salt)
    direct = hm.bucket_head[b1.astype(I32)][:, None]                  # (Q, 1)
    chain = resolve_pages_by_bucket(hm, b2)                           # (Q, C)
    head = jnp.where(chain[:, :1] == direct, -1, chain[:, :1])
    return jnp.concatenate([direct, head, chain[:, 1:]], axis=1).astype(I32)


def _fp_filter(store: layout.PageStore, queries: jax.Array,
               pages: jax.Array) -> jax.Array:
    """Fingerprint pre-pass: blank (to -1) every page of the schedule whose
    fingerprint lane holds no slot matching the query's fingerprint.

    This is the Dash trick on the paper's bit-plane layout: fp_bits narrow
    plane words are scanned INSTEAD of activating the full (slots, 2) row;
    only fp-matching rows survive to the wide fetch.  True matches are never
    filtered (the lane is exact per slot); false positives (~S/2^fp_bits
    slots per page) cost one extra row activation and are rejected by the
    full key compare."""
    fb = store.fp_bits
    qfp = fingerprint(queries.astype(U32), fb)                        # (Q,)
    rows = store.fprints[jnp.maximum(pages, 0)]                       # (Q,C,fb,W)
    j = jnp.arange(fb, dtype=U32)
    qbits = (qfp[:, None] >> j[None, :]) & U32(1)                     # (Q, fb)
    qwords = jnp.where(qbits == U32(1), U32(0xFFFFFFFF), U32(0))
    mism = rows ^ qwords[:, None, :, None]                            # (Q,C,fb,W)
    agg = mism[:, :, 0, :]
    for i in range(1, fb):       # OR over planes: bit set => some bit differs
        agg = agg | mism[:, :, i, :]
    hit = jnp.any(~agg != U32(0), axis=-1)                            # (Q, C)
    return jnp.where(hit & (pages >= 0), pages, -1)


def stash_probe(store: layout.PageStore, queries: jax.Array):
    """(values, found) against the stash only — whole-stash compare, zero
    row activations (the stash is register-resident by design)."""
    q = queries.astype(U32)
    m = store.stash[None, :, 0] == q[:, None]                         # (Q, T)
    sf = jnp.any(m, axis=1)
    sv = store.stash[jnp.argmax(m, axis=1), 1]    # argmax = oldest match
    return jnp.where(sf, sv, U32(0)), sf


def probe(hm: HashMem, queries: jax.Array, backend: Optional[str] = None):
    """Batched probe.  Returns (values (Q,) uint32, found (Q,) bool)."""
    cfg = hm.config
    b = hash_to_bucket(queries.astype(U32), cfg.num_buckets, cfg.hash_fn,
                       cfg.salt)
    return probe_with_buckets(hm, queries, b, backend)


def probe_with_buckets(hm: HashMem, queries: jax.Array, b: jax.Array,
                       backend: Optional[str] = None):
    """``probe`` with caller-supplied H1 bucket ids (RLU channel layer).

    Pipeline: resolve the page schedule (displaced or chained), fingerprint-
    filter it when the lane is present, hand the surviving pages to the
    backend, then fold in the stash (pool matches win: stash entries are by
    construction the NEWEST duplicates of their key)."""
    from repro.core.probe import probe_pages   # local import to avoid cycle
    cfg = hm.config
    q = queries.astype(U32)
    if cfg.displacement:
        pages = resolve_pages_displaced(hm, q, b)
    else:
        pages = resolve_pages_by_bucket(hm, b.astype(I32))
    if hm.store.fprints is not None:
        pages = _fp_filter(hm.store, q, pages)
    vals, found = probe_pages(hm, q, pages, backend=backend or cfg.backend)
    if hm.store.stash is not None:
        sv, sf = stash_probe(hm.store, q)
        vals = jnp.where(found, vals, sv)
        found = found | sf
    return vals, found


def rows_activated_per_probe(hm: HashMem, queries: jax.Array,
                             use_fingerprints: bool = True,
                             b: Optional[jax.Array] = None) -> jax.Array:
    """Traced mean DRAM-row activations one probe of this batch costs —
    the paper's unit of probe work, derived the same way kernel_bench's
    ``scatters_per_insert`` is (from the op structure, not a timer).

    A hit activates every unfiltered page up to and including the first
    true match; a miss activates every unfiltered page of its schedule.
    The stash is register-resident and counts zero."""
    cfg = hm.config
    q = queries.astype(U32)
    if b is None:
        b = hash_to_bucket(q, cfg.num_buckets, cfg.hash_fn, cfg.salt)
    if cfg.displacement:
        pages = resolve_pages_displaced(hm, q, b)
    else:
        pages = resolve_pages_by_bucket(hm, b.astype(I32))
    if use_fingerprints and hm.store.fprints is not None:
        pages = _fp_filter(hm.store, q, pages)
    valid = pages >= 0
    rows = hm.store.key_rows(pages)                                   # (Q,C,S)
    pmatch = jnp.any(rows == q[:, None, None], axis=-1) & valid
    anym = jnp.any(pmatch, axis=1)
    first = jnp.argmax(pmatch, axis=1)
    upto = jnp.arange(pages.shape[1], dtype=I32)[None, :] <= first[:, None]
    acts = jnp.where(anym, jnp.sum((valid & upto).astype(I32), axis=1),
                     jnp.sum(valid.astype(I32), axis=1))
    return jnp.mean(acts.astype(jnp.float32))


def _write_key_bits(planes, page, slot, key, key_bits: int):
    """Incremental bit-plane maintenance for a single (page, slot) write."""
    word = slot // 32
    bit = (slot % 32).astype(U32)
    j = jnp.arange(key_bits, dtype=U32)
    kbits = ((key.astype(U32) >> j) & U32(1))                              # (b,)
    old = planes[page, :, word]                                           # (b,)
    mask = ~(U32(1) << bit)
    new = (old & mask) | (kbits << bit)
    return planes.at[page, :, word].set(new)


def _chain_tails(hm: HashMem, b: jax.Array):
    """Per-key chain tail page, tail fill and chain length (bounded walk)."""
    cfg = hm.config
    tail = hm.bucket_head[b]                                              # (B,)
    clen = jnp.ones_like(tail)
    for _ in range(cfg.max_chain - 1):
        nxt = hm.page_next[tail]
        has = nxt >= 0
        tail = jnp.where(has, nxt, tail)
        clen = clen + has.astype(I32)
    return tail, hm.page_fill[tail], clen


def insert(hm: HashMem, keys: jax.Array, vals: jax.Array,
           valid: Optional[jax.Array] = None):
    """Vectorized batched insert: appends the whole batch at the existing
    chain tails in one shot (sort/rank/segment, same machinery as
    ``build_with_buckets``).  Equivalent to repeated single inserts in batch
    order.  Returns (new_hm, ok (B,) bool); see the module docstring for the
    ok=False (PR_ERROR) semantics.

    ``valid`` (optional (B,) bool) marks padding: invalid elements write
    nothing, claim no arena pages and report ok=False — the serving engine
    pads insert batches to power-of-two shapes to bound the set of compiled
    shapes (engine.py).
    """
    cfg = hm.config
    b = hash_to_bucket(keys.astype(U32), cfg.num_buckets, cfg.hash_fn, cfg.salt)
    return insert_with_buckets(hm, keys, vals, b, valid)


def insert_with_buckets(hm: HashMem, keys: jax.Array, vals: jax.Array,
                        b: jax.Array, valid: Optional[jax.Array] = None):
    """``insert`` with caller-supplied bucket ids (RLU channel layer).

    Dispatches to the displaced path (H1 direct -> H2 chain -> stash) when
    ``config.displacement`` is set, else to the chained append."""
    if hm.config.displacement:
        return _insert_displaced(hm, keys, vals, b, valid)
    return _insert_chained(hm, keys, vals, b, valid)


def _insert_chained(hm: HashMem, keys: jax.Array, vals: jax.Array,
                    b: jax.Array, valid: Optional[jax.Array] = None):
    """Chain-append insert at the buckets' existing tails.

    Three pool-shaped scatters total: the fused key/value row write
    (store.write_slots), the fill high-water max, and the chain-link set;
    the per-element ok mask is un-permuted with a gather, not a scatter.
    """
    cfg = hm.config
    slots = cfg.slots_per_page
    n = keys.shape[0]
    keys = keys.astype(U32)
    vals = vals.astype(U32)
    b = b.astype(I32)
    if valid is not None:
        b = jnp.where(valid, b, cfg.num_buckets)   # pads sort to the end
    if cfg.resize == "extendible" and hm.store.local_depth is not None:
        # canonicalize to the group id (low local_depth bits): directory
        # aliases of one group must form ONE sort segment below, or two
        # aliased buckets would both append at the same tail fill and
        # collide on slots.  Probe/delete need no such fold — the aliased
        # bucket_head gather already lands on the shared chain.
        heads = hm.bucket_head[jnp.minimum(b, cfg.num_buckets - 1)]
        ld = hm.store.local_depth[heads]
        mask = (jnp.int32(1) << ld) - 1
        b = jnp.where(b < cfg.num_buckets, b & mask, b)

    # clamped gather: dropped entries read bucket 0's tail, never used
    tail, fill, clen = _chain_tails(hm, jnp.minimum(b, cfg.num_buckets - 1))

    # stable sort by bucket keeps intra-bucket batch order (duplicate keys
    # land in insertion order, matching sequential semantics)
    order = jnp.argsort(b)
    bs, ks, vs = b[order], keys[order], vals[order]
    tails, fills, clens = tail[order], fill[order], clen[order]
    dropped = bs >= cfg.num_buckets

    start = jnp.searchsorted(bs, bs, side="left")
    rank = jnp.arange(n, dtype=I32) - start.astype(I32)
    pos = fills + rank                          # position past the tail start
    depth = pos // slots                        # 0 = existing tail page
    slot = pos % slots

    # pim_malloc: every chain-admissible page start claims the next arena
    # page, in sorted (bucket) order — one cumsum, no per-bucket arrays.
    # Pages of one bucket stay contiguous (no other bucket's start can fall
    # between two starts of the same bucket segment).
    ok_chain = (clens + depth <= cfg.max_chain) & ~dropped  # RLU depth bound
    is_new_page = ok_chain & (depth >= 1) & (slot == 0)
    page_idx = jnp.cumsum(is_new_page.astype(I32)) - 1     # shared along page
    new_id = hm.free_top + page_idx
    n_fit = jnp.clip(cfg.num_pages - hm.free_top, 0,
                     jnp.sum(is_new_page.astype(I32)))
    ok = jnp.where(depth == 0, ~dropped, ok_chain & (new_id < cfg.num_pages))
    page = jnp.where(depth == 0, tails, new_id).astype(I32)
    wp = jnp.where(ok, page, cfg.num_pages)                # OOB drop if !ok

    store = hm.store.write_slots(wp, slot, ks, vs)         # fused k+v scatter
    page_fill = store.page_fill.at[wp].max(slot + 1, mode="drop")

    # chain links: first element on each newly allocated page links prev -> page
    is_link = ok & (depth >= 1) & (slot == 0)
    prev = jnp.where(depth == 1, tails, page - 1)
    link_idx = jnp.where(is_link, prev, cfg.num_pages)
    page_next = store.page_next.at[link_idx].set(page, mode="drop")

    store = dataclasses.replace(
        store, page_fill=page_fill, page_next=page_next,
        free_top=(hm.free_top + n_fit).astype(I32))

    ok_orig = ok[jnp.argsort(order)]            # inverse permutation (gather)
    return HashMem(store=store, bucket_head=hm.bucket_head,
                   config=cfg), ok_orig


def _insert_displaced(hm: HashMem, keys: jax.Array, vals: jax.Array,
                      b1: jax.Array, valid: Optional[jax.Array] = None):
    """IcebergHT-style displaced insert: three rounds.

      1. H1 direct page only (no chaining): fill-ranked append into the
         bucket's own row while it has room.
      2. Residue chains at H2 (``hash_to_bucket2``) via the normal chained
         append — this is the only round that allocates overflow pages, so
         chains grow at the SECOND hash's (near-uniform) bucket, not at the
         skewed H1 hot spot.
      3. Whatever both buckets reject falls into the stash (bump-allocated;
         slots are not reused until a rebuild).

    A key's round class is non-decreasing over its duplicates' lifetimes
    (direct fill and chain capacity are monotone), and probes search
    direct -> H2 chain -> stash, so the first match remains the OLDEST
    duplicate — the same FIFO contract as the chained path.
    """
    cfg = hm.config
    S = cfg.slots_per_page
    n = keys.shape[0]
    keys = keys.astype(U32)
    vals = vals.astype(U32)
    b1 = b1.astype(I32)
    valid_all = jnp.ones((n,), bool) if valid is None else valid

    # -- round 1: H1 direct page, fill-only (never allocates, never links) --
    b = jnp.where(valid_all, b1, cfg.num_buckets)          # pads sort to end
    order = jnp.argsort(b)
    bs, ks, vs = b[order], keys[order], vals[order]
    dropped = bs >= cfg.num_buckets
    head = hm.bucket_head[jnp.minimum(bs, cfg.num_buckets - 1)]
    fill = hm.page_fill[head]
    start = jnp.searchsorted(bs, bs, side="left")
    rank = jnp.arange(n, dtype=I32) - start.astype(I32)
    pos = fill + rank
    ok1s = (pos < S) & ~dropped
    wp = jnp.where(ok1s, head, cfg.num_pages)              # OOB drop if !ok
    slot = jnp.minimum(pos, S - 1).astype(I32)
    store = hm.store.write_slots(wp, slot, ks, vs)
    page_fill = store.page_fill.at[wp].max(slot + 1, mode="drop")
    store = dataclasses.replace(store, page_fill=page_fill)
    hm1 = HashMem(store=store, bucket_head=hm.bucket_head, config=cfg)
    ok1 = ok1s[jnp.argsort(order)]

    # -- round 2: chain the residue at H2 ----------------------------------
    b2 = hash_to_bucket2(keys, cfg.num_buckets, cfg.hash_fn, cfg.salt)
    hm2, ok2 = _insert_chained(hm1, keys, vals, b2, valid_all & ~ok1)

    # -- round 3: stash the rest (batch order == age order) ----------------
    st = hm2.store
    if st.stash is None:
        return hm2, ok1 | ok2
    T = st.stash.shape[0]
    valid3 = valid_all & ~ok1 & ~ok2
    rank3 = jnp.cumsum(valid3.astype(I32)) - valid3.astype(I32)
    pos3 = st.stash_fill + rank3
    ok3 = valid3 & (pos3 < T)
    sp = jnp.where(ok3, pos3, T)                           # OOB drop if !ok
    stash = st.stash.at[sp].set(jnp.stack([keys, vals], axis=-1),
                                mode="drop")
    stash_fill = (st.stash_fill + jnp.sum(ok3.astype(I32))).astype(I32)
    store = dataclasses.replace(st, stash=stash, stash_fill=stash_fill)
    return HashMem(store=store, bucket_head=hm2.bucket_head,
                   config=cfg), ok1 | ok2 | ok3


def insert_scan(hm: HashMem, keys: jax.Array, vals: jax.Array):
    """Sequential per-element insert (paper §3.1 Listing 1) via ``lax.scan``.

    Kept as the reference semantics for the vectorized ``insert`` (see the
    differential tests) and as the benchmark baseline.  NOTE: unlike
    ``insert``, this version does not enforce the max_chain bound.
    """
    cfg = hm.config
    slots = cfg.slots_per_page

    def step(state, kv):
        pool, planes, fprints, page_next, page_fill, free_top = state
        k, v = kv
        b = hash_to_bucket(k[None], cfg.num_buckets, cfg.hash_fn, cfg.salt)[0]
        # walk to chain tail (bounded)
        last = hm.bucket_head[b]
        for _ in range(cfg.max_chain - 1):
            nxt = page_next[jnp.maximum(last, 0)]
            last = jnp.where(nxt >= 0, nxt, last)
        fill = page_fill[last]
        need_new = fill >= slots
        new_page = free_top
        ok = jnp.where(need_new, new_page < cfg.num_pages, True)
        tp = jnp.where(need_new, new_page, last).astype(I32)
        ts = jnp.where(need_new, 0, fill).astype(I32)
        wp = jnp.where(ok, tp, cfg.num_pages)                              # OOB drop if !ok
        pool = pool.at[wp, ts].set(jnp.stack([k, v]), mode="drop")  # fused k+v
        if planes is not None:
            planes = jnp.where(ok, _write_key_bits(planes, tp, ts, k, cfg.key_bits), planes)
        if fprints is not None:
            fprints = jnp.where(
                ok, _write_key_bits(fprints, tp, ts,
                                    fingerprint(k, cfg.fingerprint_bits),
                                    cfg.fingerprint_bits), fprints)
        page_fill = page_fill.at[wp].set(ts + 1, mode="drop")
        do_link = need_new & ok
        page_next = page_next.at[jnp.where(do_link, last, cfg.num_pages)].set(
            new_page, mode="drop")
        free_top = free_top + do_link.astype(I32)
        return (pool, planes, fprints, page_next, page_fill, free_top), ok

    init = (hm.store.pool, hm.planes, hm.store.fprints, hm.page_next,
            hm.page_fill, hm.free_top)
    (pool, pl, fp, pn, pf, ft), oks = jax.lax.scan(
        step, init, (keys.astype(U32), vals.astype(U32)))
    store = dataclasses.replace(hm.store, pool=pool, planes=pl, fprints=fp,
                                page_next=pn, page_fill=pf, free_top=ft)
    return HashMem(store=store, bucket_head=hm.bucket_head, config=cfg), oks


def delete(hm: HashMem, keys: jax.Array):
    """Batched tombstone delete (paper §2.5).  Returns (new_hm, found).
    Each query tombstones the FIRST chain-order match of its key; duplicate
    queries in one batch resolve to the same slot (one removal).  Only the
    key lane of the row is rewritten — the value is the paper's "wasted
    space" until compact()."""
    cfg = hm.config
    b = hash_to_bucket(keys.astype(U32), cfg.num_buckets, cfg.hash_fn,
                       cfg.salt)
    return delete_with_buckets(hm, keys, b)


def delete_with_buckets(hm: HashMem, keys: jax.Array, b: jax.Array):
    """``delete`` with caller-supplied bucket ids (the RLU channel layer
    derives the local bucket from one global hash — see rlu.py)."""
    if hm.config.displacement:
        return _delete_displaced(hm, keys, b)
    cfg = hm.config
    slots = cfg.slots_per_page
    q = keys.astype(U32)
    pages = resolve_pages_by_bucket(hm, b.astype(I32))                     # (Q, C)
    rows = hm.store.key_rows(pages)                                        # (Q, C, S)
    match = (rows == q[:, None, None]) & (pages >= 0)[:, :, None]
    qn, C = pages.shape
    flat = match.reshape(qn, C * slots)
    found = jnp.any(flat, axis=1)
    idx = jnp.argmax(flat, axis=1)
    c, s = idx // slots, (idx % slots).astype(I32)
    pg = pages[jnp.arange(qn), c]
    wp = jnp.where(found, pg, cfg.num_pages)                               # OOB drop
    plane_pages = _dedup_plane_pages(hm, found, pg, s)
    store = hm.store.write_keys(wp, s, jnp.full((qn,), TOMBSTONE_KEY, U32),
                                plane_pages=plane_pages)
    return HashMem(store=store, bucket_head=hm.bucket_head,
                   config=cfg), found


def _dedup_plane_pages(hm: HashMem, found, pg, s):
    """Dedup identical (page, slot) tombstone targets (duplicate queries) so
    the batched bit-plane/fingerprint scatters add each bit exactly once;
    None when neither packed lane exists (no dedup needed)."""
    cfg = hm.config
    qn = found.shape[0]
    if (hm.planes is None and hm.store.fprints is None) or qn == 0:
        return None
    flatidx = jnp.where(found, pg * cfg.slots_per_page + s, -1)
    o = jnp.argsort(flatidx)
    fs = flatidx[o]
    first = jnp.concatenate([jnp.ones((1,), bool), fs[1:] != fs[:-1]])
    uniq = jnp.zeros((qn,), bool).at[o].set(first)
    return jnp.where(found & uniq, pg, cfg.num_pages)


def _delete_displaced(hm: HashMem, keys: jax.Array, b1: jax.Array):
    """Tombstone delete over the displaced search order: H1 direct page,
    H2 chain, then the stash.  Stash hits rewrite the stash key lane to
    TOMBSTONE (the slot is reclaimed at the next rebuild, like any
    tombstone); duplicate queries resolve to the same slot."""
    cfg = hm.config
    S = cfg.slots_per_page
    q = keys.astype(U32)
    pages = resolve_pages_displaced(hm, q, b1.astype(I32))                 # (Q, C)
    st = hm.store
    rows = st.key_rows(pages)
    match = (rows == q[:, None, None]) & (pages >= 0)[:, :, None]
    qn, C = pages.shape
    flat = match.reshape(qn, C * S)
    if st.stash is not None:
        flat = jnp.concatenate([flat, st.stash[None, :, 0] == q[:, None]],
                               axis=1)
    found = jnp.any(flat, axis=1)
    idx = jnp.argmax(flat, axis=1)
    in_pool = idx < C * S
    pidx = jnp.minimum(idx, C * S - 1)
    c, s = pidx // S, (pidx % S).astype(I32)
    pg = pages[jnp.arange(qn), c]
    pool_hit = found & in_pool
    wp = jnp.where(pool_hit, pg, cfg.num_pages)                            # OOB drop
    plane_pages = _dedup_plane_pages(hm, pool_hit, pg, s)
    store = st.write_keys(wp, s, jnp.full((qn,), TOMBSTONE_KEY, U32),
                          plane_pages=plane_pages)
    if st.stash is not None:
        sp = jnp.where(found & ~in_pool, idx - C * S, st.stash.shape[0])
        stash = store.stash.at[sp, 0].set(TOMBSTONE_KEY, mode="drop")
        store = dataclasses.replace(store, stash=stash)
    return HashMem(store=store, bucket_head=hm.bucket_head,
                   config=cfg), found


# ---------------------------------------------------------------------------
# Dynamic resizing (grow / compact / auto-grow policy)
# ---------------------------------------------------------------------------

def live_count(hm: HashMem) -> jax.Array:
    """() int32 number of live (non-empty, non-tombstone) entries,
    stash included."""
    kp = hm.key_pages
    n = jnp.sum((kp != EMPTY_KEY) & (kp != TOMBSTONE_KEY)).astype(I32)
    if hm.store.stash is not None:
        sk = hm.store.stash[:, 0]
        n = n + jnp.sum((sk != EMPTY_KEY) & (sk != TOMBSTONE_KEY)).astype(I32)
    return n


def load_factor(hm: HashMem) -> jax.Array:
    """Live entries / total slot capacity, as a traced float32 scalar."""
    cap = hm.config.num_pages * hm.config.slots_per_page
    return live_count(hm).astype(jnp.float32) / jnp.float32(cap)


def _rebuild(hm: HashMem, new_cfg: HashMemConfig,
             bucket_fn: Optional[BucketFn]) -> HashMem:
    """Re-bucket every live entry into a fresh arena under ``new_cfg``.

    Flat (page-major) slot order IS chain order per bucket (page ids increase
    along every chain), so same-key duplicates keep their relative order —
    probe/delete semantics survive the rebuild.  The interleaved pool makes
    this one reshape: rows flatten to (P*S, 2) key/value pairs directly.
    """
    if hm.config.displacement:
        return _rebuild_displaced(hm, new_cfg, bucket_fn)
    flat = hm.store.pool.reshape(-1, 2)
    keys = flat[:, layout.KEY_LANE]
    vals = flat[:, layout.VAL_LANE]
    live = (keys != EMPTY_KEY) & (keys != TOMBSTONE_KEY)
    if bucket_fn is None:
        b = hash_to_bucket(keys, new_cfg.num_buckets, new_cfg.hash_fn,
                           new_cfg.salt)
    else:
        b = bucket_fn(keys, new_cfg)
    return _scatter_build(new_cfg, keys, vals, b, valid=live)


def _rebuild_displaced(hm: HashMem, new_cfg: HashMemConfig,
                       bucket_fn: Optional[BucketFn]) -> HashMem:
    """Displaced rebuild: replay every live entry through the displaced
    insert path, oldest placement class first.

    Flat order alone is NOT age order here (a key's H2 chain entries can sit
    at a lower page id than another key's H1 direct entries), but WITHIN a
    key all duplicates share (b1, b2), so classifying each slot as
    was-H1-direct (its page IS its H1 bucket's own row) vs was-chained and
    replaying class 0, then class 1, then the stash preserves per-key age
    order — the only order probe/delete semantics depend on.  A compact
    never drops entries: the replay faces at least the capacity the entries
    already fit in, and any cascade ends in the (non-decreasing) stash."""
    cfg = hm.config
    S = cfg.slots_per_page
    flat = hm.store.pool.reshape(-1, 2)
    keys = flat[:, layout.KEY_LANE]
    vals = flat[:, layout.VAL_LANE]
    live = (keys != EMPTY_KEY) & (keys != TOMBSTONE_KEY)
    n = keys.shape[0]
    if bucket_fn is None:
        b_old = hash_to_bucket(keys, cfg.num_buckets, cfg.hash_fn, cfg.salt)
    else:
        b_old = bucket_fn(keys, cfg)
    page_of = jnp.arange(n, dtype=I32) // S
    cls = jnp.where(page_of == b_old, 0, 1)
    sortkey = jnp.where(live, cls * n + jnp.arange(n), 2 * n + jnp.arange(n))
    order = jnp.argsort(sortkey)
    ks, vs, lv = keys[order], vals[order], live[order]
    if hm.store.stash is not None:
        sk, sv = hm.store.stash[:, 0], hm.store.stash[:, 1]
        ks = jnp.concatenate([ks, sk])
        vs = jnp.concatenate([vs, sv])
        lv = jnp.concatenate([lv, (sk != EMPTY_KEY) & (sk != TOMBSTONE_KEY)])
    if bucket_fn is None:
        b1 = hash_to_bucket(ks, new_cfg.num_buckets, new_cfg.hash_fn,
                            new_cfg.salt)
    else:
        b1 = bucket_fn(ks, new_cfg)
    hm2, _ = _insert_displaced(create(new_cfg), ks, vs, b1, valid=lv)
    return hm2


def grow(hm: HashMem, factor: Optional[int] = None,
         bucket_fn: Optional[BucketFn] = None) -> HashMem:
    """Rehash into a ``factor``x larger arena (default config.growth_factor):
    num_buckets and overflow_pages both scale, all live entries are
    re-bucketed, chains and bit-planes are rebuilt.  Tombstones are dropped
    (grow subsumes compact)."""
    cfg = hm.config
    f = factor or cfg.growth_factor
    new_cfg = dataclasses.replace(cfg, num_buckets=cfg.num_buckets * f,
                                  overflow_pages=cfg.overflow_pages * f)
    return _rebuild(hm, new_cfg, bucket_fn)


def compact(hm: HashMem, bucket_fn: Optional[BucketFn] = None) -> HashMem:
    """Reclaim tombstoned slots and overflow pages by rebuilding in place
    (same config).  After compact: stats()['tombstones'] == 0 and every
    chain is the minimum length for its live population."""
    return _rebuild(hm, hm.config, bucket_fn)


def rebuild_check(hm: HashMem, new_cfg: HashMemConfig,
                  bucket_fn: Optional[BucketFn] = None) -> dict:
    """Host-side pre-flight: would the live entries fit under new_cfg?"""
    import numpy as np
    keys = np.asarray(hm.key_pages).reshape(-1)
    live = (keys != np.uint32(0xFFFFFFFF)) & (keys != np.uint32(0xFFFFFFFE))
    lk = jnp.asarray(keys[live])
    if bucket_fn is None:
        b = hash_to_bucket(lk, new_cfg.num_buckets, new_cfg.hash_fn,
                           new_cfg.salt)
    else:
        b = bucket_fn(lk, new_cfg)
    counts = np.bincount(np.asarray(b), minlength=new_cfg.num_buckets)
    return _fit_report(counts, new_cfg)


# ---------------------------------------------------------------------------
# Extendible resize (directory-based; Dash) — resize="extendible"
# ---------------------------------------------------------------------------
#
# The existing structure already IS a directory: with num_buckets = 2^gd the
# bucket id (hash % num_buckets) is the low-gd-bits hash prefix, and the
# bucket_head gather every probe/delete/insert performs is the directory
# indirection.  Extendible mode adds per-GROUP local depths (a page lane on
# the store, meaningful at group-head pages): directory entries sharing the
# low local_depth bits alias ONE page-chain group.
#
#   * split_group: an overflowing group (local depth ld < global depth gd)
#     splits ALONE — its live entries are re-bucketed on hash bit ld into
#     the old head and ONE newly allocated page region; the directory
#     aliases are repointed (pointer writes); every other group's pages,
#     chains and directory entries are untouched and probe-able throughout.
#   * double_directory: when ld == gd the directory doubles by POINTER COPY
#     (bucket_head -> concat of itself) with ZERO data movement.  The page
#     arena is deliberately kept the same size (num_buckets doubles,
#     overflow_pages shrinks by the same amount) so every array shape in the
#     store is invariant — only the directory itself reallocates.
#   * grow()/compact() stay available as the fallback/reclaim path: a
#     rebuild under an extendible config resets the directory flat (every
#     group back at depth gd) and reclaims pages leaked by splits (a split
#     abandons its old overflow pages to keep pim_malloc a bump pointer).

def split_group(hm: HashMem, bucket: int,
                bucket_fn: Optional[BucketFn] = None):
    """Split the group owning ``bucket`` one level deeper (HOST-level,
    shape-preserving).  Returns (hm, status):

      * "ok"          — split done; group entries re-bucketed on bit ld.
      * "need_double" — local depth == global depth: double_directory first.
      * "full"        — the arena cannot supply the new head/overflow pages.
      * "stuck"       — a child would exceed max_chain (entries share hash
                        bits past this depth); only a full grow() helps.

    The mutation is ordered like any insert-phase write: it touches only
    this group's pages plus the directory aliases of this group, so every
    concurrent probe of OTHER groups resolves identically before/after."""
    import numpy as np
    cfg = hm.config
    gd = bits_used(cfg.num_buckets)
    S = cfg.slots_per_page
    head0 = int(hm.bucket_head[int(bucket) % cfg.num_buckets])
    ld = int(hm.store.local_depth[head0])
    if ld >= gd:
        return hm, "need_double"
    c = int(bucket) & ((1 << ld) - 1)              # canonical group id

    # walk the chain on the host (bounded) and pull the live entries in
    # chain order — flat page-major slot order IS per-key age order
    pages = []
    page_next = np.asarray(hm.page_next)
    p = head0
    while p >= 0 and len(pages) <= cfg.max_chain:
        pages.append(p)
        p = int(page_next[p])
    flat = np.asarray(hm.store.pool[jnp.asarray(pages, I32)]).reshape(-1, 2)
    k, v = flat[:, 0], flat[:, 1]
    live = (k != np.uint32(0xFFFFFFFF)) & (k != np.uint32(0xFFFFFFFE))
    lk, lv = k[live], v[live]

    # pre-flight: both children must fit their chain/arena bounds BEFORE any
    # mutation (a half-performed split would lose entries)
    if lk.size:
        if bucket_fn is None:
            hb = np.asarray(hash_to_bucket(jnp.asarray(lk), cfg.num_buckets,
                                           cfg.hash_fn, cfg.salt))
        else:
            hb = np.asarray(bucket_fn(jnp.asarray(lk), cfg))
        goes_hi = ((hb >> ld) & 1) == 1
        n_lo, n_hi = int((~goes_hi).sum()), int(goes_hi.sum())
    else:
        n_lo = n_hi = 0
    pg_lo = max(-(-n_lo // S), 1)
    pg_hi = max(-(-n_hi // S), 1)
    if pg_lo > cfg.max_chain or pg_hi > cfg.max_chain:
        return hm, "stuck"
    need = 1 + (pg_lo - 1) + (pg_hi - 1)           # new head + overflow
    free_top = int(hm.free_top)
    if free_top + need > cfg.num_pages:
        return hm, "full"

    # clear the old chain through write_slots (keeps bit-planes and the
    # fingerprint lane consistent), reset its fills/links; overflow pages of
    # the old chain are LEAKED (bump allocator) until compact()/grow()
    new_head = free_top
    L = len(pages)
    store = hm.store.write_slots(
        jnp.asarray(np.repeat(pages, S), I32),
        jnp.asarray(np.tile(np.arange(S), L), I32),
        jnp.full((L * S,), EMPTY_KEY, U32), jnp.zeros((L * S,), U32))
    pg_arr = jnp.asarray(pages, I32)
    both = jnp.asarray([head0, new_head], I32)
    store = dataclasses.replace(
        store,
        page_fill=store.page_fill.at[pg_arr].set(0),
        page_next=store.page_next.at[pg_arr].set(-1),
        local_depth=store.local_depth.at[both].set(ld + 1),
        free_top=jnp.asarray(new_head + 1, I32))

    # directory: the group's aliases are c + m*2^ld; bit ld of the alias
    # (odd m) selects the new head — pointer writes only
    m = jnp.arange(cfg.num_buckets >> ld, dtype=I32)
    idxs = c + (m << ld)
    heads = jnp.where((m & 1) == 1, new_head, head0).astype(I32)
    hm2 = HashMem(store=store,
                  bucket_head=hm.bucket_head.at[idxs].set(heads),
                  config=cfg)

    # re-insert the extracted entries: the insert path's canonicalization
    # routes each to its (depth ld+1) child, preserving chain order
    if lk.size:
        if bucket_fn is None:
            b = hash_to_bucket(jnp.asarray(lk), cfg.num_buckets, cfg.hash_fn,
                               cfg.salt)
        else:
            b = bucket_fn(jnp.asarray(lk), cfg)
        hm2, ok = insert_with_buckets(hm2, jnp.asarray(lk), jnp.asarray(lv), b)
        assert bool(np.asarray(ok).all()), "split re-insert overflowed"
    return hm2, "ok"


def double_directory(hm: HashMem) -> Optional[HashMem]:
    """Double the bucket directory by pointer copy — NO data movement.

    num_buckets doubles while overflow_pages shrinks by the old directory
    size, so ``num_pages`` (and with it every store array shape) is
    INVARIANT: the new directory entries are aliases of their low-half
    groups at unchanged local depths.  Returns None when the overflow
    arena cannot cede num_buckets pages of accounting (the caller falls
    back to a genuine grow() rebuild)."""
    cfg = hm.config
    bits_used(cfg.num_buckets)                     # validate pow2
    if cfg.overflow_pages < cfg.num_buckets:
        return None
    cfg2 = dataclasses.replace(
        cfg, num_buckets=cfg.num_buckets * 2,
        overflow_pages=cfg.overflow_pages - cfg.num_buckets)
    return HashMem(store=hm.store,
                   bucket_head=jnp.concatenate([hm.bucket_head,
                                                hm.bucket_head]),
                   config=cfg2)


def grow_extendible(hm: HashMem, bucket: int,
                    bucket_fn: Optional[BucketFn] = None):
    """Make room in the group owning ``bucket``: split it, doubling the
    directory first when its local depth has reached the global depth.
    Falls back to a full grow() rebuild only when the arena or the chain
    bound cannot admit a split.  Returns (hm, how) with how in
    {"split", "double", "rebuild"} — "double" implies a split happened
    after the doubling."""
    hm2, status = split_group(hm, bucket, bucket_fn=bucket_fn)
    if status == "ok":
        return hm2, "split"
    if status == "need_double":
        doubled = double_directory(hm)
        if doubled is not None:
            hm2, status = split_group(doubled, bucket, bucket_fn=bucket_fn)
            if status == "ok":
                return hm2, "double"
            hm = doubled                           # keep the wider directory
    return grow(hm, bucket_fn=bucket_fn), "rebuild"


def insert_extendible(hm: HashMem, keys: jax.Array, vals: jax.Array,
                      bucket_fn: Optional[BucketFn] = None,
                      max_splits: int = 256, max_grows: int = 8,
                      events: Optional[dict] = None):
    """Host-level insert loop for resize="extendible": refused elements
    trigger per-GROUP splits (plus directory doublings) instead of a
    stop-the-world rehash; a full grow() rebuild remains the bounded
    fallback.  Returns (new_hm, ok (B,) bool).  ``events`` (optional dict)
    accumulates "splits"/"doublings"/"rebuilds" counts for telemetry."""
    import numpy as np
    keys = jnp.asarray(keys).astype(U32)
    vals = jnp.asarray(vals).astype(U32)
    n = keys.shape[0]
    ok = np.zeros((n,), bool)
    remaining = np.arange(n)
    splits = grows = 0
    while remaining.size:
        kr, vr = keys[remaining], vals[remaining]
        if bucket_fn is None:
            br = hash_to_bucket(kr, hm.config.num_buckets, hm.config.hash_fn,
                                hm.config.salt)
        else:
            br = bucket_fn(kr, hm.config)
        hm, ok_r = insert_with_buckets(hm, kr, vr, br)
        ok_np = np.asarray(ok_r)
        ok[remaining[ok_np]] = True
        remaining = remaining[~ok_np]
        if remaining.size == 0:
            break
        if splits >= max_splits or grows > max_grows:
            break
        # split every refused group once, then retry the residue; each
        # successful split strictly deepens a group, so the loop terminates
        for b0 in np.unique(np.asarray(br)[~ok_np]):
            if splits >= max_splits or grows > max_grows:
                break
            hm, how = grow_extendible(hm, int(b0), bucket_fn=bucket_fn)
            splits += 1
            if how == "rebuild":
                grows += 1
            if events is not None:
                key = {"split": "splits", "double": "doublings",
                       "rebuild": "rebuilds"}[how]
                events[key] = events.get(key, 0) + 1
                if how == "double":
                    events["splits"] = events.get("splits", 0) + 1
    return hm, jnp.asarray(ok)


def insert_auto(hm: HashMem, keys: jax.Array, vals: jax.Array,
                bucket_fn: Optional[BucketFn] = None, max_grows: int = 8,
                events: Optional[dict] = None):
    """Host-level insert with auto-grow (NOT jit-compatible: growth changes
    array shapes).  Grows proactively when the batch would exceed
    config.max_load_factor and reactively while any element fails — the two
    loops draw on SEPARATE ``max_grows`` budgets (a proactive doubling must
    never starve the reactive repair of an ok=False batch into a spurious
    refusal).  Under resize="extendible" the reactive path splits the
    refused groups (insert_extendible) instead of rebuilding.  Returns
    (new_hm, ok (B,) bool) — ok is all-True unless growth was
    exhausted/disabled."""
    import numpy as np
    keys = jnp.asarray(keys).astype(U32)
    vals = jnp.asarray(vals).astype(U32)
    n = keys.shape[0]
    cfg = hm.config
    if cfg.auto_grow:
        proactive = 0
        cap = cfg.num_pages * cfg.slots_per_page
        live = int(live_count(hm))
        while (live + n) > cfg.max_load_factor * cap \
                and proactive < max_grows:
            hm = grow(hm, bucket_fn=bucket_fn)
            cfg = hm.config
            cap = cfg.num_pages * cfg.slots_per_page
            proactive += 1
            if events is not None:
                events["rebuilds"] = events.get("rebuilds", 0) + 1

    if cfg.resize == "extendible" and cfg.auto_grow:
        return insert_extendible(hm, keys, vals, bucket_fn=bucket_fn,
                                 max_grows=max_grows, events=events)

    ok = np.zeros((n,), bool)
    remaining = np.arange(n)
    reactive = 0
    while remaining.size:
        kr, vr = keys[remaining], vals[remaining]
        if bucket_fn is None:
            br = hash_to_bucket(kr, hm.config.num_buckets, hm.config.hash_fn,
                                hm.config.salt)
        else:
            br = bucket_fn(kr, hm.config)
        hm, ok_r = insert_with_buckets(hm, kr, vr, br)
        ok_np = np.asarray(ok_r)
        ok[remaining[ok_np]] = True
        remaining = remaining[~ok_np]
        if remaining.size == 0:
            break
        if not hm.config.auto_grow or reactive >= max_grows:
            break
        hm = grow(hm, bucket_fn=bucket_fn)
        reactive += 1
        if events is not None:
            events["rebuilds"] = events.get("rebuilds", 0) + 1
    return hm, jnp.asarray(ok)


# ---------------------------------------------------------------------------
# Introspection (fig. 4 reproduction + invariants for property tests)
# ---------------------------------------------------------------------------

def stats(hm: HashMem) -> dict:
    import numpy as np
    cfg = hm.config
    kp = np.asarray(hm.key_pages)
    fill = np.asarray(hm.page_fill)
    live = (kp != np.uint32(0xFFFFFFFF)) & (kp != np.uint32(0xFFFFFFFE))
    chain_len = np.asarray(chain_lengths(hm))
    cap = cfg.num_pages * cfg.slots_per_page
    stash_live = stash_tomb = stash_fill = 0
    if hm.store.stash is not None:
        sk = np.asarray(hm.store.stash[:, 0])
        stash_live = int(((sk != np.uint32(0xFFFFFFFF))
                          & (sk != np.uint32(0xFFFFFFFE))).sum())
        stash_tomb = int((sk == np.uint32(0xFFFFFFFE)).sum())
        stash_fill = int(np.asarray(hm.store.stash_fill))
    return {
        "live_entries": int(live.sum()) + stash_live,
        "tombstones": int((kp == np.uint32(0xFFFFFFFE)).sum()) + stash_tomb,
        "pages_used": int(np.sum(fill > 0)),
        "free_pages": int(cfg.num_pages - np.asarray(hm.free_top)),
        "chain_lengths": chain_len,
        "max_chain": int(chain_len.max(initial=0)),
        "capacity": cap,
        "load_factor": float((live.sum() + stash_live) / cap),
        "num_buckets": cfg.num_buckets,
        "stash_live": stash_live,
        "stash_tombstones": stash_tomb,
        "stash_fill": stash_fill,
    } | ({
        # extendible-resize telemetry: directory size == num_buckets;
        # local depths read at the group-head pages the directory points to
        "global_depth": bits_used(cfg.num_buckets),
        "min_local_depth": int(np.asarray(
            hm.store.local_depth[hm.bucket_head]).min()),
        "max_local_depth": int(np.asarray(
            hm.store.local_depth[hm.bucket_head]).max()),
    } if hm.store.local_depth is not None else {})
