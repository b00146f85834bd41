"""Unified PageStore: interleaved bucket-row layout, bit-plane packing,
fingerprint lane and stash (paper §2, §2.2, §2.4; Dash/IcebergHT).

The HashMem pool mirrors the paper's DRAM organization, where ONE row
activation exposes an entire bucket segment — keys *and* values — to the
subarray compare units:

  * page  == one subarray row: ``slots`` columns of interleaved key/value
    pairs, stored as a single ``(num_pages, slots, 2)`` uint32 array
    (lane 0 = key, lane 1 = value).  Opening a page (loading its row into
    VMEM) exposes the whole bucket segment in ONE fetch, exactly like a
    DRAM row activation — probes read the key AND its value from the same
    activated row, and mutations write both with a single fused scatter
    (``PageStore.write_slots``).  IcebergHT/Dash make the same argument for
    PM: co-locating a bucket's keys and payloads in one access unit is what
    makes probes single-access.
  * ``PageStore`` owns the pool plus all per-page bookkeeping: the optional
    column-oriented bit-planes, the overflow chain links (``page_next``),
    the per-page fill high-water marks and the ``pim_malloc`` bump pointer
    (``free_top``).  ``key_pages``/``val_pages`` remain available as thin
    lane views for callers that want the split layout.
  * The performance-optimized version stores keys **column-oriented as bit
    slices** (paper: "each row contains a single-bit slice from thousands of
    values").  ``pack_bitplanes`` produces that layout: plane j, word w holds
    bit j of keys at slots [32w, 32w+32).  A b-bit probe is then b bitwise
    vector ops over int32 lane words — element-parallel, bit-serial.
  * **Fingerprint lane** (``fp_bits > 0``, Dash §4): ``fprints`` holds the
    low ``fp_bits`` of an independent hash of each slot's key, packed with
    the SAME bit-plane machinery as ``planes`` — ``(num_pages, fp_bits,
    slots//32)``.  A probe scans this narrow lane first (fp_bits bitwise
    ops instead of a full row fetch) and activates the wide ``(slots, 2)``
    row only for pages holding a fingerprint match, dropping rows activated
    per probe toward 1 under skew.  ``write_slots``/``write_keys`` keep it
    in sync automatically; the invariant is
    ``unpack_bitplanes(fprints, fp_bits) == fingerprint(key_pages, fp_bits)``
    (EMPTY and TOMBSTONE sentinels are fingerprinted like any key — a probe
    for a user key simply never matches their fingerprints except as a
    bounded false positive, rejected by the full row compare).
  * **Stash** (``stash_slots > 0``, IcebergHT §3): a tiny ``(stash_slots,
    2)`` register-file of key/value pairs absorbing inserts that neither
    bucket choice could place.  It is deliberately NOT page-backed: probes
    compare it whole, in-register, with zero row activations.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.hashing import EMPTY_KEY, fingerprint

U32 = jnp.uint32
I32 = jnp.int32

KEY_LANE = 0
VAL_LANE = 1


# ---------------------------------------------------------------------------
# PageStore: the one owner of the interleaved pool + page bookkeeping
# ---------------------------------------------------------------------------

@partial(jax.tree_util.register_dataclass,
         data_fields=["pool", "planes", "page_next", "page_fill", "free_top",
                      "fprints", "stash", "stash_fill", "local_depth"],
         meta_fields=["key_bits", "fp_bits"])
@dataclass
class PageStore:
    """Interleaved page pool + per-page bookkeeping (one pytree).

    ``pool[p, s, KEY_LANE]`` is the key at slot s of page p and
    ``pool[p, s, VAL_LANE]`` its value — one row activation serves both.
    All mutations flow through ``write_slots`` (fused key+value scatter,
    keeping the bit-planes AND the fingerprint lane in sync) or the
    dedicated tombstone/link helpers.
    """

    pool: jax.Array               # (num_pages, slots, 2) uint32
    planes: Optional[jax.Array]   # (num_pages, key_bits, slots//32) | None
    page_next: jax.Array          # (num_pages,) int32, -1 terminal
    page_fill: jax.Array          # (num_pages,) int32 fill high-water mark
    free_top: jax.Array           # () int32 pim_malloc bump pointer
    key_bits: int                 # static: width of the bit-plane scan
    fprints: Optional[jax.Array] = None   # (num_pages, fp_bits, slots//32)
    stash: Optional[jax.Array] = None     # (stash_slots, 2) uint32 | None
    stash_fill: Optional[jax.Array] = None  # () int32 bump pointer | None
    local_depth: Optional[jax.Array] = None  # (num_pages,) int32 extendible
                                  # local depth, meaningful at group HEAD
                                  # pages (hashmap.py "extendible resize");
                                  # None when resize="rebuild"
    fp_bits: int = 0              # static: fingerprint width (0 = lane off)

    # -- thin split views (external callers / differential harness) --------
    @property
    def key_pages(self) -> jax.Array:
        return self.pool[..., KEY_LANE]

    @property
    def val_pages(self) -> jax.Array:
        return self.pool[..., VAL_LANE]

    def key_rows(self, pages: jax.Array) -> jax.Array:
        """Key lane of just the rows ``pages`` names (any shape, -1 read as
        page 0): one gather on the pool, never a pass over the whole key
        plane that ``key_pages`` would materialise."""
        return self.pool[jnp.maximum(pages, 0), :, KEY_LANE]

    @property
    def num_pages(self) -> int:
        return self.pool.shape[0]

    @property
    def slots(self) -> int:
        return self.pool.shape[1]

    # -- the fused write path ----------------------------------------------
    def write_slots(self, pages, slots_idx, keys, vals) -> "PageStore":
        """ONE pool scatter writes key and value into the same activated
        rows (out-of-range page => dropped, ``mode="drop"``); the bit-planes
        are maintained incrementally when present.  In-range (page, slot)
        pairs must be unique within the batch (bit-plane merge is additive).
        """
        kv = jnp.stack([keys.astype(U32), vals.astype(U32)], axis=-1)
        pool = self.pool.at[pages, slots_idx].set(kv, mode="drop")
        planes = self.planes
        if planes is not None:
            planes = update_bitplanes_batch(planes, pages, slots_idx,
                                            keys.astype(U32), self.key_bits)
        fprints = self.fprints
        if fprints is not None:
            fprints = update_bitplanes_batch(
                fprints, pages, slots_idx,
                fingerprint(keys.astype(U32), self.fp_bits), self.fp_bits)
        return dataclasses.replace(self, pool=pool, planes=planes,
                                   fprints=fprints)

    def write_keys(self, pages, slots_idx, keys,
                   plane_pages=None) -> "PageStore":
        """Key-lane-only write (tombstone writes): the value lane of the
        row is left as it was.  Each slot's value is read back and the
        slot is written whole, so the scatter keeps the pool's own layout
        (a scatter into the key lane alone has XLA's TPU backend relayout
        the whole pool, and back).  ``plane_pages`` optionally overrides
        the page ids used for the bit-plane update (delete dedups
        duplicate targets there)."""
        vals = self.pool[pages, slots_idx, VAL_LANE]  # out of range: unused
        kv = jnp.stack([keys.astype(U32), vals], axis=-1)
        pool = self.pool.at[pages, slots_idx].set(kv, mode="drop")
        pp = pages if plane_pages is None else plane_pages
        planes = self.planes
        if planes is not None:
            planes = update_bitplanes_batch(planes, pp, slots_idx,
                                            keys.astype(U32), self.key_bits)
        fprints = self.fprints
        if fprints is not None:
            fprints = update_bitplanes_batch(
                fprints, pp, slots_idx,
                fingerprint(keys.astype(U32), self.fp_bits), self.fp_bits)
        return dataclasses.replace(self, pool=pool, planes=planes,
                                   fprints=fprints)

def empty_store(num_pages: int, slots: int, key_bits: int = 32,
                with_planes: bool = False, fp_bits: int = 0,
                stash_slots: int = 0,
                local_depth: Optional[int] = None) -> PageStore:
    """Fresh PageStore: every key EMPTY, every value 0, no chains.

    ``fp_bits > 0`` allocates the fingerprint lane (initialized to the
    fingerprint of EMPTY_KEY in every slot, matching the pool);
    ``stash_slots > 0`` allocates the stash (keys EMPTY, fill 0);
    ``local_depth`` (an int) allocates the extendible-hashing depth lane
    filled with that initial depth (= the table's global depth)."""
    pool = empty_pool(num_pages, slots)
    planes = pack_bitplanes(pool[..., KEY_LANE], key_bits) if with_planes \
        else None
    fprints = None
    if fp_bits > 0:
        fprints = pack_bitplanes(
            fingerprint(pool[..., KEY_LANE], fp_bits), fp_bits)
    stash = stash_fill = None
    if stash_slots > 0:
        stash = jnp.broadcast_to(jnp.array([EMPTY_KEY, 0], dtype=U32),
                                 (stash_slots, 2))
        stash_fill = jnp.asarray(0, dtype=I32)
    depths = None
    if local_depth is not None:
        depths = jnp.full((num_pages,), local_depth, dtype=I32)
    return PageStore(
        pool=pool,
        planes=planes,
        page_next=jnp.full((num_pages,), -1, dtype=I32),
        page_fill=jnp.zeros((num_pages,), dtype=I32),
        free_top=jnp.asarray(0, dtype=I32),
        key_bits=key_bits,
        fprints=fprints,
        stash=stash,
        stash_fill=stash_fill,
        local_depth=depths,
        fp_bits=fp_bits,
    )


def empty_pool(num_pages: int, slots: int) -> jax.Array:
    """(num_pages, slots, 2) interleaved pool: keys EMPTY, values 0.

    Built by broadcast (not a strided lane scatter) so bulk builds spend
    their scatter budget only on real writes."""
    row = jnp.array([EMPTY_KEY, 0], dtype=U32)
    return jnp.broadcast_to(row, (num_pages, slots, 2))


def interleave(key_pages, val_pages) -> jax.Array:
    """Zip split (P, S) key/value arrays into the (P, S, 2) pool layout."""
    return jnp.stack([key_pages.astype(U32), val_pages.astype(U32)], axis=-1)


# ---------------------------------------------------------------------------
# Bit-plane packing (the paper's column-oriented key layout)
# ---------------------------------------------------------------------------

def pack_bitplanes(key_pages, key_bits: int):
    """(P, S) uint32 keys -> (P, key_bits, S//32) uint32 bit-planes.

    Word layout: plane[p, j, w] bit i (LSB-first) = bit j of key_pages[p, 32w+i].
    """
    P, S = key_pages.shape
    assert S % 32 == 0, "slots must be a multiple of 32 for bit-plane packing"
    # (P, S, key_bits) bit j of each key
    j = jnp.arange(key_bits, dtype=U32)
    bits = (key_pages[:, :, None] >> j[None, None, :]) & U32(1)  # (P, S, b)
    bits = bits.transpose(0, 2, 1).reshape(P, key_bits, S // 32, 32)
    weights = (U32(1) << jnp.arange(32, dtype=U32))
    planes = jnp.sum(bits * weights[None, None, None, :], axis=-1, dtype=U32)
    return planes


def update_bitplanes_batch(planes, pages, slots_idx, new_keys, key_bits: int):
    """Batched incremental bit-plane maintenance for a set of slot writes.

    ``pages``/``slots_idx`` (B,) int32 name the written slots (out-of-range
    page => the update is dropped, matching ``.at[...].set(mode="drop")`` on
    the key lane); ``new_keys`` (B,) uint32 are the values written there.
    Each in-range (page, slot) pair must be unique within the batch: bits are
    merged with scatter-adds, which only act as OR when every added bit is
    distinct.
    """
    P, kb, W = planes.shape
    assert kb == key_bits
    word = (slots_idx // 32).astype(jnp.int32)
    bit = (slots_idx % 32).astype(U32)
    # per-(page, word) mask of rewritten lanes, then per-plane replacement bits
    clear = jnp.zeros((P, W), U32).at[pages, word].add(U32(1) << bit,
                                                       mode="drop")
    j = jnp.arange(key_bits, dtype=U32)
    kbits = (((new_keys.astype(U32)[:, None] >> j[None, :]) & U32(1))
             << bit[:, None])                                       # (B, kb)
    setb = jnp.zeros((P, kb, W), U32).at[pages, :, word].add(kbits, mode="drop")
    return (planes & ~clear[:, None, :]) | setb


def unpack_bitplanes(planes, key_bits: int):
    """Inverse of pack_bitplanes (for tests): (P, b, W) -> (P, 32W) uint32."""
    P, b, W = planes.shape
    assert b == key_bits
    i = jnp.arange(32, dtype=U32)
    bits = (planes[:, :, :, None] >> i[None, None, None, :]) & U32(1)  # (P,b,W,32)
    bits = bits.reshape(P, b, W * 32).transpose(0, 2, 1)               # (P,S,b)
    j = jnp.arange(key_bits, dtype=U32)
    return jnp.sum(bits * (U32(1) << j)[None, None, :], axis=-1, dtype=U32)
