"""Paged KV cache managed by a HashMem page table (DESIGN.md §3.1).

This is the paper's virtualization layer (§2.4-2.5) applied to serving:

  * a KV "page" holds ``page_tokens`` tokens of one sequence — the
    bucket-per-page mapping (logical bucket = (seq, block index)).
  * the page table is a real ``repro.core.hashmap.HashMem``: key =
    seq_id * MAX_BLOCKS + block, value = physical page id.  Allocation is
    ``pim_malloc`` from per-channel free lists; freeing a sequence writes
    tombstones (paper deletion semantics) and recycles the physical pages.
  * physical pages are spread across the mesh — the paper's §2.5
    optimization of spreading overflow pages "across different channels ...
    to enable the parallel probing of pages".  Decode attention is split-KV
    across channels with a log-sum-exp combine (flash-decoding semantics
    falling out of the paper's channel parallelism).

Pool layout (grouped): the flat page-pool dim is sharded jointly over ALL
mesh axes.  Device (batch-group g, channel m) owns physical pages
[flat*pps, (flat+1)*pps), flat = g*Dm + m.  Sequence b belongs to batch
group g(b) (its batch shard); logical page j of b lives on channel j mod Dm.
With no batch sharding (long-context B=1) every axis is a channel.

Inside jit, the resolved block table (the RLU command stream) is a dense
(B, n_pages) int32 array; the HashMem manager lives at the serving layer.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Pools
# ---------------------------------------------------------------------------

def init_pool(num_pages: int, page_tokens: int, kv_heads: int, head_dim: int,
              dtype=jnp.bfloat16):
    shape = (num_pages, page_tokens, kv_heads, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _flat_index(axes: Sequence[str]) -> jax.Array:
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _axes_size(axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


# ---------------------------------------------------------------------------
# Local (single-device) paths
# ---------------------------------------------------------------------------

def append(k_pool, v_pool, block_table, pos, k_new, v_new):
    """Write one new token per sequence into its tail page (local pool)."""
    pt = k_pool.shape[1]
    j = pos // pt
    off = pos % pt
    page = jnp.take_along_axis(block_table, j[:, None], axis=1)[:, 0]
    k_pool = k_pool.at[page, off].set(k_new[:, 0])
    v_pool = v_pool.at[page, off].set(v_new[:, 0])
    return k_pool, v_pool


def _partial_decode(q, k, v, positions, pos, window):
    """Partial (per-channel) attention.  q (B,K,G,hd); k/v (B,T,K,hd);
    positions (B,T) absolute token positions (-1 = invalid).
    Returns (m, l, acc) for LSE combine."""
    hd = q.shape[-1]
    s = jnp.einsum("bkgh,btkh->bkgt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (hd ** -0.5)
    valid = (positions >= 0) & (positions <= pos[:, None])
    if window:
        valid &= positions > (pos[:, None] - window)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(valid[:, None, None], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgt,btkh->bkgh", p, v.astype(jnp.float32))
    return m, l, acc


def paged_decode_attention(q, k_pool, v_pool, block_table, pos, cfg):
    """Single-device decode attention (gather path)."""
    B, _, H, hd = q.shape
    K = k_pool.shape[2]
    G = H // K
    pt = k_pool.shape[1]
    qg = q.reshape(B, K, G, hd)
    n_pages = block_table.shape[1]
    k = k_pool[block_table].reshape(B, n_pages * pt, K, hd)
    v = v_pool[block_table].reshape(B, n_pages * pt, K, hd)
    positions = jnp.broadcast_to(jnp.arange(n_pages * pt), (B, n_pages * pt))
    m, l, acc = _partial_decode(qg, k, v, positions, pos, cfg.sliding_window)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Channel-parallel (inside shard_map over the WHOLE mesh)
# ---------------------------------------------------------------------------

def decode_attention_sharded(q, k_pool, v_pool, block_table, pos, cfg,
                             batch_axes: Sequence[str],
                             channel_axes: Sequence[str],
                             pages_per_shard: int):
    """q (B_loc,1,H,hd) local batch; pools are the LOCAL page slice;
    block_table (B_loc, n_pages) holds GLOBAL physical page ids."""
    B, _, H, hd = q.shape
    K = k_pool.shape[2]
    G = H // K
    pt = k_pool.shape[1]
    qg = q.reshape(B, K, G, hd)
    n_pages = block_table.shape[1]

    Dm = _axes_size(channel_axes)
    me_m = _flat_index(channel_axes)
    me_flat = _flat_index(tuple(batch_axes) + tuple(channel_axes))
    nl = max(n_pages // Dm, 1)

    # logical pages j ≡ me_m (mod Dm)
    bt_r = block_table[:, :nl * Dm].reshape(B, nl, Dm)
    local_bt = jnp.take_along_axis(
        bt_r, jnp.full((B, nl, 1), me_m, jnp.int32), axis=2)[..., 0]
    mine = (local_bt // pages_per_shard) == me_flat        # allocator guarantee
    slot = jnp.where(mine, local_bt % pages_per_shard, 0)
    k = k_pool[slot].reshape(B, nl * pt, K, hd)
    v = v_pool[slot].reshape(B, nl * pt, K, hd)
    j_log = jnp.arange(nl) * Dm + me_m
    positions = (j_log[:, None] * pt + jnp.arange(pt)[None, :])  # (nl, pt)
    positions = jnp.where(mine[:, :, None], positions[None], -1) \
        .reshape(B, nl * pt)
    m, l, acc = _partial_decode(qg, k, v, positions, pos, cfg.sliding_window)
    # LSE combine across channels only (batch axes hold distinct sequences)
    if channel_axes:
        M = m
        for a in channel_axes:
            M = jax.lax.pmax(M, a)
        r = jnp.exp(m - M)
        num = jax.lax.psum(acc * r[..., None], tuple(channel_axes))
        den = jax.lax.psum(l * r, tuple(channel_axes))
    else:
        num, den = acc, l
    out = num / jnp.maximum(den, 1e-30)[..., None]
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def append_sharded(k_pool, v_pool, block_table, pos, k_new, v_new,
                   batch_axes: Sequence[str], channel_axes: Sequence[str],
                   pages_per_shard: int):
    """Owner-channel append.  All args local-batch views."""
    pt = k_pool.shape[1]
    me_flat = _flat_index(tuple(batch_axes) + tuple(channel_axes))
    j = pos // pt
    off = pos % pt
    page = jnp.take_along_axis(block_table, j[:, None], axis=1)[:, 0]
    mine = (page // pages_per_shard) == me_flat
    slot = jnp.where(mine, page % pages_per_shard, k_pool.shape[0])
    k_pool = k_pool.at[slot, off].set(k_new[:, 0], mode="drop")
    v_pool = v_pool.at[slot, off].set(v_new[:, 0], mode="drop")
    return k_pool, v_pool


def prefill_pages(k_pool, v_pool, block_table, k, v):
    """Scatter prefill KV (B,S,K,hd) into pages (local pool).  S must be a
    multiple of page_tokens; block_table (B, >=S/pt)."""
    B, S, K, hd = k.shape
    pt = k_pool.shape[1]
    n = S // pt
    kp = k.reshape(B, n, pt, K, hd)
    vp = v.reshape(B, n, pt, K, hd)
    bt = block_table[:, :n]
    k_pool = k_pool.at[bt].set(kp)
    v_pool = v_pool.at[bt].set(vp)
    return k_pool, v_pool


# ---------------------------------------------------------------------------
# Serving layer: the HashMem page-table manager (outside jit)
# ---------------------------------------------------------------------------

class PageTableManager:
    """Page-table = HashMem; pim_malloc = per-owner free-list arenas.

    Keys are seq_id * max_blocks + block_idx (uint32); values are physical
    page ids.  ``block_table`` resolves the dense in-jit table by PROBING
    the hashmap (through any backend, including the Pallas kernels).

    ``num_channels`` arenas follow the grouped layout: arena c owns physical
    ids [c*pps, (c+1)*pps).  ``alloc_seq(..., group=g)`` places logical page
    j in arena g*Dm + (j % Dm) — batch group g, channel j mod Dm.
    """

    MAX_BLOCKS = 1 << 12
    CHAIN_CHECK_EVERY = 4   # frees between compact_chain_len device walks

    def __init__(self, total_pages: int, num_channels: int = 1,
                 num_groups: int = 1, hashmem_cfg=None, backend: str = "ref",
                 compact_chain_len: int | None = None):
        import dataclasses

        from repro.configs.base import HashMemConfig
        from repro.core import hashmap

        arenas = num_channels * num_groups
        assert total_pages % arenas == 0
        self.Dm = num_channels
        self.groups = num_groups
        self.pps = total_pages // arenas
        self.total_pages = total_pages
        cfg = hashmem_cfg or HashMemConfig(
            num_buckets=max(64, total_pages // 4), slots_per_page=128,
            overflow_pages=max(64, total_pages // 8), max_chain=8,
            backend=backend)
        if compact_chain_len is not None:
            cfg = dataclasses.replace(cfg, compact_chain_len=compact_chain_len)
        self.cfg = cfg
        self.hm = hashmap.create(cfg)
        self.free = [list(range(c * self.pps, (c + 1) * self.pps))[::-1]
                     for c in range(arenas)]
        self.owned: dict[int, list[int]] = {}
        self.grow_events = 0
        self.compact_events = 0
        self._tombstones = 0        # host-side count; avoids device syncs
        self._frees_since_chain_check = 0   # throttles the device chain walk

    def _key(self, seq_id: int, block: int) -> int:
        assert block < self.MAX_BLOCKS
        return seq_id * self.MAX_BLOCKS + block

    def _return_pages(self, pages):
        for p in pages:
            self.free[p // self.pps].append(p)

    def alloc_seq(self, seq_id: int, n_blocks: int, group: int = 0) -> np.ndarray:
        return self.alloc_seqs([(seq_id, n_blocks, group)])[seq_id]

    def alloc_seqs(self, reqs) -> dict:
        """Coalesced allocation: ``reqs`` is [(seq_id, n_blocks, group), ...]
        — pages for ALL sequences are claimed from the arenas and their table
        entries land in ONE batched HashMem insert (the serving engine calls
        this once per tick, so page-table round trips stay O(1) in the number
        of admitted requests).  Returns {seq_id: (n_blocks,) int32 phys}."""
        from repro.core import hashmap
        from repro.core.hashing import validate_user_keys
        # decode-path key-domain guard (same shared check as the serving
        # engine's submit/preload): a seq-derived key reaching the reserved
        # pad/sentinel range would silently become routing padding/EMPTY —
        # checked BEFORE any page is claimed so a rejected request leaks
        # nothing.  Each request's largest key is at its last block.
        if reqs:
            validate_user_keys(
                np.asarray([self._key(s, max(n - 1, 0))
                            for s, n, _ in reqs], np.int64),
                where="page-table alloc")
        phys, keys, spans = [], [], []
        for seq_id, n_blocks, group in reqs:
            start = len(phys)
            for j in range(n_blocks):
                arena = self.free[group * self.Dm + j % self.Dm]
                if not arena:
                    self._return_pages(phys)        # no partial-alloc leak
                    raise MemoryError("pim_malloc: PR_ERROR (arena exhausted)")
                p = arena.pop()
                phys.append(p)
                keys.append(self._key(seq_id, j))
            spans.append((seq_id, start, len(phys)))
        if not phys:
            # nothing to insert, but zero-block sequences still get their
            # (empty) entries — alloc_seq(s, 0) keeps returning an empty
            # table rather than raising
            out = {}
            for seq_id, _, _ in spans:
                self.owned.setdefault(seq_id, [])
                out[seq_id] = np.empty((0,), np.int32)
            return out
        if self.cfg.auto_grow:
            # arena exhaustion / chain overflow in the page table triggers a
            # resize instead of a dropped allocation (hashmap.py docstring)
            before = self.hm.config.num_pages
            self.hm, ok = hashmap.insert_auto(
                self.hm, jnp.asarray(keys, jnp.uint32),
                jnp.asarray(phys, jnp.uint32))
            if self.hm.config.num_pages != before:   # arena REBUILT (an
                # extendible directory doubling keeps num_pages — and every
                # tombstone — in place, so it must not reset the count)
                self.grow_events += 1
                self.cfg = self.hm.config
                self._tombstones = 0                # grow rebuild dropped them
        else:
            self.hm, ok = hashmap.insert(
                self.hm, jnp.asarray(keys, jnp.uint32),
                jnp.asarray(phys, jnp.uint32))
        if not bool(jnp.all(ok)):
            self._return_pages(phys)
            raise MemoryError("page-table insert failed (PR_ERROR)")
        out = {}
        for seq_id, a, b in spans:
            self.owned.setdefault(seq_id, []).extend(phys[a:b])
            out[seq_id] = np.asarray(phys[a:b], np.int32)
        return out

    def block_table(self, seq_ids, n_blocks: int) -> np.ndarray:
        """Resolve (B, n_blocks) dense table by probing the HashMem."""
        from repro.core import hashmap
        B = len(seq_ids)
        keys = np.asarray([[self._key(s, j) for j in range(n_blocks)]
                           for s in seq_ids], np.uint32).reshape(-1)
        vals, found = hashmap.probe(self.hm, jnp.asarray(keys))
        vals = np.asarray(vals).astype(np.int32)
        found = np.asarray(found)
        vals[~found] = 0  # unallocated blocks -> page 0 (masked by pos in-attn)
        return vals.reshape(B, n_blocks)

    def free_seq(self, seq_id: int):
        """Tombstone the table entries (paper §2.5) and recycle pages."""
        self.free_seqs([seq_id])

    def free_seqs(self, seq_ids):
        """Coalesced free: every finished sequence's table entries are
        tombstoned in ONE batched HashMem delete (one call per engine tick,
        however many requests completed in it)."""
        from repro.core import hashmap
        keys, pages = [], []
        for seq_id in seq_ids:
            own = self.owned.pop(seq_id, [])
            keys.extend(self._key(seq_id, j) for j in range(len(own)))
            pages.extend(own)
        if not pages:
            return
        self.hm, _ = hashmap.delete(self.hm, jnp.asarray(keys, jnp.uint32))
        # every owned key was inserted, so every delete tombstones one slot;
        # counting host-side avoids a device reduction+sync per free
        self._tombstones += len(keys)
        self._return_pages(pages)
        self.maybe_compact()

    def maybe_compact(self):
        """Reclaim tombstoned page-table slots (the paper's §2.5 'wasted
        space') on either of two triggers:

          * GLOBAL: tombstones exceed ``compact_tombstone_frac`` of capacity
            (long-lived serving would otherwise grow chains without bound);
          * CHAIN (``compact_chain_len`` > 0): any bucket chain exceeds that
            many pages while tombstones exist.  Skewed delete streams pile
            tombstoned pages onto a few hot chains — per-probe RLU command
            depth degrades long before the global fraction trips.  The chain
            walk is a device computation + host sync, so it is throttled to
            every ``CHAIN_CHECK_EVERY`` checks (tombstone counting stays
            pure host-side, see __init__).

        Called from every free AND from the serving engine's tick clock
        (:meth:`tick`) — a long-running skewed tenant that stops freeing
        still gets its accumulated tombstones reclaimed.
        """
        from repro.core import hashmap
        cfg = self.hm.config
        trigger = hashmap.compact_due(self.hm, self._tombstones, chain=False)
        if (not trigger and cfg.compact_chain_len > 0
                and self._tombstones > 0):
            self._frees_since_chain_check += 1
            if self._frees_since_chain_check >= self.CHAIN_CHECK_EVERY:
                self._frees_since_chain_check = 0
                trigger = hashmap.compact_due(self.hm, self._tombstones,
                                              fraction=False)
        if trigger:
            self.hm = hashmap.compact(self.hm)
            self.compact_events += 1
            self._tombstones = 0
            self._frees_since_chain_check = 0

    def tick(self):
        """Engine-tick maintenance hook: re-run the compaction triggers on
        the tick clock rather than only on frees.  Before this hook existed,
        ``maybe_compact`` ran only inside :meth:`free_seq` — a tenant whose
        frees stopped (but whose earlier deletes left tombstones on hot
        chains) never compacted.  The decode loop in launch/serve.py calls
        this once per step."""
        self.maybe_compact()

    def live_pages(self) -> int:
        return sum(len(v) for v in self.owned.values())
