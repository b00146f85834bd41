"""RLU: orchestration between probe/mutation requests and HashMem shards.

Single-device: the RLU resolves each probe key to its page chain (the
"command stream", hashmap.resolve_pages) and issues it to a compare backend.

Multi-device ("channel-level parallelism", paper §6 — future work there,
IMPLEMENTED here): buckets are partitioned across a mesh axis the way the
paper spreads pages "across different channels and ranks ... to enable the
parallel probing of pages".  One global hash h(key) defines the routing;
two routers are supported (``shard_by``):

    "mod"       owner = h mod D,                  local bucket = (h div D) mod B
    "highbits"  owner = ((h >> 16) * D) >> 16,    local bucket = h mod B

"mod" is the original channel split; "highbits" is the fastrange split over
the hash's top 16 bits (any D, not just powers of two; pure uint32
arithmetic — the container's jax runs without x64) whose local bucket is
the plain ``hash_to_bucket`` assignment over the LOW bits — so a
"highbits" shard is just an ordinary HashMem whose keys happen to route to
it, and the default ``hashmap.grow`` rebucketing works per shard
unchanged.  The serving engine uses "highbits" for its mesh-backed shards.

Requests are routed to owners with ``all_to_all``, executed locally
(probe with the configured kernel backend; delete/insert with the
vectorized mutation engine), and routed back — the TPU ICI plays the role
of the paper's memory-channel fan-out.  ``probe_sharded`` /
``delete_sharded`` / ``insert_mesh`` are each ONE cached-jitted shard_map
call per invocation: a serving tick's whole coalesced phase crosses the
host<->mesh boundary once, no matter how many shards participate.

Every shard is a full HashMem over the unified PageStore (one interleaved
(P, S, 2) pool pytree per shard), so stacking shards for the mesh, the
synchronized-growth insert path and the local kernel probes all move ONE
pool leaf per shard instead of split key/value pairs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import HashMemConfig
from repro.core import hashmap
from repro.core.hashing import EMPTY_KEY, HASH_FNS

U32 = jnp.uint32
I32 = jnp.int32

# Routing pad: below every sentinel, above every workload/tenant-folded key
# (kv_synth keeps raw keys < 0xFFFFFFF0 and tenancy.py reserves the top
# tenant id), so a padded routing slot probes/deletes nothing and an insert
# treats it as invalid — shared with the serving engine's batch pad.
ROUTE_PAD = np.uint32(0xFFFFFFF0)

SHARD_ROUTERS = ("mod", "highbits")


def _global_hash(keys, cfg: HashMemConfig):
    return HASH_FNS[cfg.hash_fn](keys.astype(U32), cfg.salt)


def _owner_from_hash(h, num_shards: int, shard_by: str):
    """THE owner formula (jnp) — single definition shared by owner_of and
    owner_and_local_bucket so a router change can't split routing between
    the build path and the per-phase calls."""
    if shard_by == "highbits":
        return (((h >> U32(16)) * U32(num_shards)) >> U32(16)).astype(I32)
    assert shard_by == "mod", shard_by
    return (h % U32(num_shards)).astype(I32)


def owner_of(keys, cfg: HashMemConfig, num_shards: int,
             shard_by: str = "mod"):
    """(N,) keys -> (N,) int32 owner shard ids under the chosen router."""
    return _owner_from_hash(_global_hash(keys, cfg), num_shards, shard_by)


def owner_of_np(keys, cfg: HashMemConfig, num_shards: int,
                shard_by: str = "mod") -> np.ndarray:
    """Host-side (numpy) mirror of ``owner_of`` — one vectorized call per
    serving phase partitions a whole coalesced batch without touching the
    device (see tests/test_hashing.py for the jnp<->np equivalence check)."""
    k = np.asarray(keys, np.uint32)
    if cfg.hash_fn == "murmur3_fmix":
        h = k ^ np.uint32(cfg.salt)
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(0x85EBCA6B)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(0xC2B2AE35)
        h = h ^ (h >> np.uint32(16))
    elif cfg.hash_fn == "mult_shift":
        h = (k * np.uint32(2654435761)) ^ np.uint32(cfg.salt)
    else:                                   # identity
        h = k
    if shard_by == "highbits":
        return (((h >> np.uint32(16)) * np.uint32(num_shards))
                >> np.uint32(16)).astype(np.int32)
    assert shard_by == "mod", shard_by
    return (h % np.uint32(num_shards)).astype(np.int32)


def owner_and_local_bucket(keys, cfg: HashMemConfig, num_shards: int,
                           shard_by: str = "mod"):
    h = _global_hash(keys, cfg)
    owner = _owner_from_hash(h, num_shards, shard_by)
    if shard_by == "highbits":
        local = (h % U32(cfg.num_buckets)).astype(I32)
    else:
        local = ((h // U32(num_shards)) % U32(cfg.num_buckets)).astype(I32)
    return owner, local


def build_sharded(cfg: HashMemConfig, keys, vals, num_shards: int,
                  shard_by: str = "mod"):
    """Build per-shard HashMems; returns a stacked pytree with leading axis
    num_shards (shard i's arrays at index i), ready to shard over 'model'.

    cfg.num_buckets is the PER-SHARD bucket count.
    """
    owner, local = owner_and_local_bucket(keys, cfg, num_shards, shard_by)
    shards = []
    for d in range(num_shards):
        m = owner == d
        # density: route shard-d keys to front; pad with EMPTY (never probed)
        idx = jnp.argsort(~m)  # shard-d keys first
        k = jnp.where(m[idx], keys[idx].astype(U32), EMPTY_KEY)
        v = jnp.where(m[idx], vals[idx].astype(U32), U32(0))
        b = jnp.where(m[idx], local[idx], 0)
        # EMPTY keys land in bucket 0 but as EMPTY they never match a probe;
        # they do consume slots, so size the scaled config accordingly.
        shards.append(hashmap.build_with_buckets(cfg, k, v, b))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *shards)


def _local_bucket_fn(num_shards: int, shard_by: str = "mod"):
    """bucket_fn for hashmap.grow/insert on one shard: re-derive the local
    bucket from the global hash under the (possibly grown) shard config."""
    def fn(keys, cfg: HashMemConfig):
        h = HASH_FNS[cfg.hash_fn](keys.astype(U32), cfg.salt)
        if shard_by == "highbits":
            return (h % U32(cfg.num_buckets)).astype(I32)
        return ((h // U32(num_shards)) % U32(cfg.num_buckets)).astype(I32)
    return fn


def _padded_len(n: int) -> int:
    """``n`` rounded up to a sixteenth of its power-of-two octave (at most
    6.25% padding), so batches of nearly equal size share one shape."""
    step = 1 << max(n.bit_length() - 4, 0)
    return -(-n // step) * step


def insert_sharded(hm_stacked, keys, vals, cfg: HashMemConfig,
                   num_shards: int, max_grows: int = 4,
                   shard_by: str = "mod", max_splits: int = 256,
                   events: Optional[dict] = None):
    """``insert_shards`` over a stacked shard pytree: returns
    (hm_stacked', ok (N,) bool, cfg')."""
    shards = [jax.tree.map(lambda x, d=d: x[d], hm_stacked)
              for d in range(num_shards)]
    shards, ok, cfg2 = insert_shards(shards, keys, vals, cfg, num_shards,
                                     max_grows=max_grows, shard_by=shard_by,
                                     max_splits=max_splits, events=events)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *shards), ok, cfg2


def insert_shards(shards: list, keys, vals, cfg: HashMemConfig,
                  num_shards: int, max_grows: int = 4,
                  shard_by: str = "mod", max_splits: int = 256,
                  events: Optional[dict] = None):
    """Host-level routed insert into a list of per-shard HashMems (each
    mutated where it lives: on a mesh, shard d stays on device d).

    Keys are routed to their owner shard (same global-hash split as
    build_sharded) and batch-inserted with the vectorized engine.  When a
    shard reports PR_ERROR and cfg.auto_grow is set, the repair depends on
    ``cfg.resize``:

      * "rebuild" — ALL shards grow by the same factor (the stacked pytree
        must stay shape-homogeneous to remain shardable over the mesh axis)
        and the failed elements retry.
      * "extendible" — the failed GROUPS on the failed shards split
        (hashmap.split_group): a split is shape-preserving, so it is a
        purely LOCAL per-shard mutation — the other shards' pytree leaves
        are untouched and stacking stays homogeneous.  Only a directory
        doubling (bucket_head reallocates, cfg.num_buckets changes) must be
        synchronized across all shards, and it moves no slot data on any of
        them.  A split the arena/chain bound refuses falls back to a
        synchronized grow() rebuild.

    Returns (shards', ok (N,) bool, cfg').  cfg' differs from cfg after
    growth/doubling; pass it to subsequent probe_sharded/insert_sharded
    calls.  ``events`` (optional dict) accumulates "splits"/"doublings"/
    "rebuilds" counts.
    """
    keys = jnp.asarray(keys).astype(U32)
    vals = jnp.asarray(vals).astype(U32)
    n = keys.shape[0]
    owner = owner_of(keys, cfg, num_shards, shard_by)         # owner is
    owner_np = np.asarray(owner)                              # grow-invariant
    bfn = _local_bucket_fn(num_shards, shard_by)
    shards = list(shards)
    extendible = cfg.resize == "extendible"

    def _bump(k):
        if events is not None:
            events[k] = events.get(k, 0) + 1

    ok = np.zeros((n,), bool)
    remaining = {d: np.nonzero(owner_np == d)[0] for d in range(num_shards)}
    grows = splits = 0
    while True:
        any_fail = False
        failed_buckets: dict = {}
        for d in range(num_shards):
            idx = remaining[d]
            if idx.size == 0:
                continue
            # pad to a shared length: the shards' batches differ by a few
            # keys, and every eager op of the insert compiles per shape
            m = _padded_len(idx.size)
            pidx = np.concatenate([idx, np.zeros(m - idx.size, idx.dtype)])
            kd, vd = keys[pidx], vals[pidx]
            bd = bfn(kd, shards[d].config)
            hm_d, ok_d = hashmap.insert_with_buckets(
                shards[d], kd, vd, bd, jnp.asarray(np.arange(m) < idx.size))
            shards[d] = hm_d
            ok_np = np.asarray(ok_d)[:idx.size]
            ok[idx[ok_np]] = True
            remaining[d] = idx[~ok_np]
            if remaining[d].size:
                any_fail = True
                failed_buckets[d] = np.unique(
                    np.asarray(bd)[:idx.size][~ok_np])
        if not any_fail or not cfg.auto_grow:
            break
        rebuild = not extendible
        if extendible and splits < max_splits:
            # split the refused groups in place — local, shape-preserving
            need_double = False
            progressed = False
            for d, bks in failed_buckets.items():
                for b0 in bks:
                    hm2, status = hashmap.split_group(shards[d], int(b0),
                                                      bucket_fn=bfn)
                    if status == "ok":
                        shards[d] = hm2
                        splits += 1
                        progressed = True
                        _bump("splits")
                    elif status == "need_double":
                        need_double = True
                    else:                         # "full" | "stuck"
                        rebuild = True
            if need_double and not rebuild:
                doubled = [hashmap.double_directory(s) for s in shards]
                if all(x is not None for x in doubled):
                    shards = doubled            # synchronized pointer copy
                    progressed = True
                    _bump("doublings")
                else:                           # arena can't cede pages
                    rebuild = True
            if not progressed and not rebuild:
                rebuild = True                  # nothing moved: escalate
        elif extendible:
            rebuild = True                      # split budget exhausted
        if rebuild:
            if grows >= max_grows:
                break
            # synchronized growth keeps every shard the same shape
            shards = [hashmap.grow(s, bucket_fn=bfn) for s in shards]
            grows += 1
            _bump("rebuilds")

    return shards, jnp.asarray(ok), shards[0].config


def place_shards(mesh, shards: list, axis: str = "model"):
    """Stack per-shard HashMem pytrees onto the mesh, one shard per device
    along ``axis``: shard d goes to device d as it is (no copy when it is
    already there) and the stacked arrays are assembled from those pieces,
    so the stack never materializes on a single device.  Done at table
    build/growth time so the per-tick RLU calls start from device-resident
    shards instead of resharding every call."""
    devices = list(mesh.devices.reshape(-1))
    assert len(shards) == len(devices), (len(shards), len(devices))
    sharding = NamedSharding(mesh, P(axis))

    def stack(*xs):
        parts = [jax.device_put(x, d)[None] for x, d in zip(xs, devices)]
        return jax.make_array_from_single_device_arrays(
            (len(xs), *xs[0].shape), sharding, parts)
    return jax.tree.map(stack, *shards)


def local_shards(hm_stacked) -> list:
    """Per-shard pytrees of a stacked shard pytree (leading dim =
    num_shards).  Each shard of a placed pytree is read from the device
    that holds it; an unplaced one is sliced."""
    n = jax.tree.leaves(hm_stacked)[0].shape[0]

    def piece(x, d):
        shards = getattr(x, "addressable_shards", ())
        if len(shards) == n:
            for s in shards:
                if s.index[0].start == d:
                    return s.data[0]
        return x[d]
    return [jax.tree.map(lambda x, d=d: piece(x, d), hm_stacked)
            for d in range(n)]


def _local_probe(hm_local, queries, cfg: HashMemConfig, num_shards: int,
                 shard_by: str = "mod"):
    _, local_bucket = owner_and_local_bucket(queries, cfg, num_shards,
                                             shard_by)
    # full probe pipeline per shard: displaced resolve + fingerprint filter
    # + backend + stash, so the fused tick_mesh megakernel (which runs this
    # inside its single shard_map) probes fingerprints and the stash
    # in-kernel too
    return hashmap.probe_with_buckets(hm_local, queries, local_bucket)


class _Route:
    """Owner-routing bookkeeping for one shard's local queries: the send
    buffer layout (stable argsort keeps intra-owner batch order, which is
    what preserves duplicate-key FIFO semantics end to end) plus the gather
    indices that un-route results.

    ``drop_invalid=True`` (the fused-tick path) excludes entries equal to
    ``pad`` from routing entirely: they get an out-of-range owner, are
    dropped from the send scatter, and never consume per-(src,dst)
    capacity — which is what lets the two-pass scheme set ``c`` to the
    measured max VALID count instead of Q_local.  Their gathered-back
    results are masked to 0/False."""

    def __init__(self, q_local, owner, num_shards: int, c: int, pad,
                 drop_invalid: bool = False):
        qn = q_local.shape[0]
        self.c = c
        self.num_shards = num_shards
        self.drop_invalid = drop_invalid
        q_local = q_local.astype(U32)
        if drop_invalid:
            self.valid = q_local != U32(pad)
            owner = jnp.where(self.valid, owner, I32(num_shards))
        self.order = jnp.argsort(owner)          # stable
        self.o_sorted = owner[self.order]
        q_sorted = q_local[self.order]
        # position within each owner group
        start = jnp.searchsorted(self.o_sorted, self.o_sorted, side="left")
        self.pos = jnp.arange(qn, dtype=I32) - start.astype(I32)
        self.overflow = self.pos >= c
        send = jnp.full((num_shards, c), pad, dtype=U32)
        if drop_invalid:
            # out-of-range rows (invalid) and pos >= c (overflow) both drop
            self.send = send.at[self.o_sorted, self.pos].set(
                q_sorted, mode="drop")
        else:
            self.send = send.at[self.o_sorted,
                                jnp.minimum(self.pos, c - 1)].set(
                jnp.where(self.overflow, pad, q_sorted))
        self.inv = jnp.argsort(self.order)

    def counts(self):
        """(num_shards,) int32: valid local queries per destination shard —
        the payload of the two-pass count exchange (drop_invalid only)."""
        assert self.drop_invalid
        return jnp.bincount(self.o_sorted, length=self.num_shards + 1)[
            :self.num_shards].astype(I32)

    def send_aux(self, x_local, num_shards: int, fill):
        """Route a second per-query array (e.g. insert values) the same way."""
        xs = x_local[self.order].astype(U32)
        send = jnp.full((num_shards, self.c), fill, dtype=U32)
        if self.drop_invalid:
            return send.at[self.o_sorted, self.pos].set(xs, mode="drop")
        return send.at[self.o_sorted, jnp.minimum(self.pos, self.c - 1)].set(
            jnp.where(self.overflow, fill, xs))

    def gather_back(self, back, mask_overflow: bool = False):
        """(num_shards, c) routed-back results -> original query order."""
        out = back[jnp.minimum(self.o_sorted, self.num_shards - 1),
                   jnp.minimum(self.pos, self.c - 1)]
        if mask_overflow:
            out = out & ~self.overflow
        if self.drop_invalid:
            out = jnp.where(self.valid[self.order], out,
                            jnp.zeros((), out.dtype))
        return out[self.inv]


# jitted shard_map'd phase calls, cached per (kind, mesh, axis, shard_by,
# cfg, cap) so a serving engine's hot loop reuses ONE compiled executable
# per phase per batch shape instead of re-tracing the shard_map every tick.
_sharded_call_cache: dict = {}


def _sharded_call(kind: str, mesh, cfg: HashMemConfig, axis: str,
                  shard_by: str, cap):
    """The jitted shard_map program of ``kind``, named ``<kind>_mesh``, so
    it traces as ``jit_<kind>_mesh``: ``jit_tick_mesh`` for the fused
    tick, ``jit_probe_mesh``, ``jit_delete_mesh`` and ``jit_insert_mesh``
    for the per-phase calls."""
    key = (kind, mesh, cfg, axis, shard_by, cap)
    fn = _sharded_call_cache.get(key)
    if fn is None:
        num_shards = mesh.shape[axis]
        builder = {"probe": _probe_shard_fn, "delete": _delete_shard_fn,
                   "insert": _insert_shard_fn, "tick": _tick_shard_fn}[kind]
        shard_fn, n_in, n_out = builder(cfg, num_shards, axis, shard_by, cap)
        shard_fn.__name__ = shard_fn.__qualname__ = f"{kind}_mesh"
        fn = jax.jit(jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(axis),) * n_in,
            out_specs=(P(axis),) * n_out,
            check_vma=False,
        ))
        _sharded_call_cache[key] = fn
    return fn


def _probe_shard_fn(cfg, num_shards, axis, shard_by, cap):
    def shard_fn(hm_stacked_local, q_local):
        hm_local = jax.tree.map(lambda x: x[0], hm_stacked_local)
        c = cap or q_local.shape[0]
        owner, _ = owner_and_local_bucket(q_local, cfg, num_shards, shard_by)
        rt = _Route(q_local, owner, num_shards, c, EMPTY_KEY)
        # route to owners: recv[s] = what shard s sent to me
        recv = jax.lax.all_to_all(rt.send, axis, 0, 0, tiled=False)
        rv, rf = _local_probe(hm_local, recv.reshape(-1), cfg, num_shards,
                              shard_by)
        back_v = jax.lax.all_to_all(rv.reshape(num_shards, c), axis, 0, 0,
                                    tiled=False)
        back_f = jax.lax.all_to_all(rf.reshape(num_shards, c), axis, 0, 0,
                                    tiled=False)
        return rt.gather_back(back_v), rt.gather_back(back_f,
                                                      mask_overflow=True)
    return shard_fn, 2, 2


def probe_sharded(mesh, hm_stacked, queries, cfg: HashMemConfig,
                  axis: str = "model", cap: Optional[int] = None,
                  shard_by: str = "mod"):
    """Channel-parallel probe: queries (Q,) sharded over `axis`.

    cap = per-(src,dst) routing capacity; None -> Q_local (always sufficient).
    Returns (values (Q,), found (Q,)) with the same sharding as queries.
    """
    fn = _sharded_call("probe", mesh, cfg, axis, shard_by, cap)
    return fn(hm_stacked, queries)


def _delete_shard_fn(cfg, num_shards, axis, shard_by, cap):
    def shard_fn(hm_stacked_local, q_local):
        hm_local = jax.tree.map(lambda x: x[0], hm_stacked_local)
        c = cap or q_local.shape[0]
        owner = owner_of(q_local, cfg, num_shards, shard_by)
        rt = _Route(q_local, owner, num_shards, c, jnp.uint32(ROUTE_PAD))
        recv = jax.lax.all_to_all(rt.send, axis, 0, 0, tiled=False)
        flat = recv.reshape(-1)
        _, lb = owner_and_local_bucket(flat, cfg, num_shards, shard_by)
        # ROUTE_PAD never matches a stored row -> found=False, no write
        hm2, found = hashmap.delete_with_buckets(hm_local, flat, lb)
        back_f = jax.lax.all_to_all(found.reshape(num_shards, c), axis, 0, 0,
                                    tiled=False)
        hm_out = jax.tree.map(lambda x: x[None], hm2)
        return hm_out, rt.gather_back(back_f, mask_overflow=True)
    return shard_fn, 2, 2


def delete_sharded(mesh, hm_stacked, keys, cfg: HashMemConfig,
                   axis: str = "model", cap: Optional[int] = None,
                   shard_by: str = "mod"):
    """Channel-parallel batched tombstone delete: ONE shard_map call routes
    every key to its owner shard, deletes locally, and routes the found
    mask back.  Returns (hm_stacked', found (Q,)).  Mirrors
    ``hashmap.delete`` semantics per owner shard (duplicate queries resolve
    to one removal)."""
    fn = _sharded_call("delete", mesh, cfg, axis, shard_by, cap)
    return fn(hm_stacked, keys)


def _insert_shard_fn(cfg, num_shards, axis, shard_by, cap):
    def shard_fn(hm_stacked_local, q_local, v_local):
        hm_local = jax.tree.map(lambda x: x[0], hm_stacked_local)
        c = cap or q_local.shape[0]
        owner, _ = owner_and_local_bucket(q_local, cfg, num_shards, shard_by)
        rt = _Route(q_local, owner, num_shards, c, jnp.uint32(ROUTE_PAD))
        recv_k = jax.lax.all_to_all(rt.send, axis, 0, 0, tiled=False)
        recv_v = jax.lax.all_to_all(
            rt.send_aux(v_local, num_shards, jnp.uint32(0)), axis, 0, 0,
            tiled=False)
        flat_k = recv_k.reshape(-1)
        valid = flat_k != jnp.uint32(ROUTE_PAD)
        _, lb = owner_and_local_bucket(flat_k, cfg, num_shards, shard_by)
        hm2, ok = hashmap.insert_with_buckets(hm_local, flat_k,
                                              recv_v.reshape(-1), lb,
                                              valid=valid)
        back_ok = jax.lax.all_to_all(ok.reshape(num_shards, c), axis, 0, 0,
                                     tiled=False)
        hm_out = jax.tree.map(lambda x: x[None], hm2)
        return hm_out, rt.gather_back(back_ok, mask_overflow=True)
    return shard_fn, 3, 2


def insert_mesh(mesh, hm_stacked, keys, vals, cfg: HashMemConfig,
                axis: str = "model", cap: Optional[int] = None,
                shard_by: str = "mod"):
    """Channel-parallel FIXED-ARENA batched insert: one shard_map call
    routes keys/values to owner shards and appends with the vectorized
    mutation engine.  Returns (hm_stacked', ok (Q,)).

    ok=False elements were refused (PR_ERROR: arena/chain bound) — shapes
    cannot change inside shard_map, so growth is the caller's host-level
    fallback (``insert_sharded``, which keeps all shards shape-homogeneous).
    Keys equal to ROUTE_PAD are padding: never stored, always ok=False.
    Duplicate keys keep global batch order (flat order == (source shard,
    local position) lexicographic == recv concatenation order).
    """
    fn = _sharded_call("insert", mesh, cfg, axis, shard_by, cap)
    return fn(hm_stacked, keys, vals)


# ---------------------------------------------------------------------------
# Fused whole-tick megakernel: probe -> delete -> insert in ONE shard_map
# ---------------------------------------------------------------------------

def route_max(keys, cfg: HashMemConfig, num_shards: int,
              shard_by: str = "mod") -> int:
    """Pass 1 of the two-pass count+route scheme, host mirror: the max
    per-(src,dst) VALID-key count for a (Q,) batch laid out contiguously
    across ``num_shards`` devices (entries equal to ROUTE_PAD don't count —
    the fused route drops them).  One hash of each key on the host."""
    k = np.asarray(keys, np.uint32)
    q = k.shape[0]
    assert q % num_shards == 0, (q, num_shards)
    valid = k != ROUTE_PAD
    if not valid.any():
        return 0
    owner = owner_of_np(k, cfg, num_shards, shard_by)
    src = np.arange(q) // (q // num_shards)
    pair = (src * num_shards + owner)[valid]
    return int(np.bincount(pair, minlength=num_shards * num_shards).max())


def ladder_cap(mx: int, q_local: int) -> int:
    """The routing capacity for a measured per-(src,dst) max ``mx``: the
    smallest power of two at or above it, floored at min(8, Q_local) and
    capped at Q_local.  The floor applies first and the ceiling LAST, so a
    tiny batch (Q_local < 8) caps at Q_local — a cap above Q_local would
    trace an all_to_all buffer larger than the (num_shards, Q_local) source
    slice.  Rounding is up, so the capacity never truncates; the values
    for one Q_local are :func:`cap_ladder`'s, a set the caller can compile
    before it routes a single batch."""
    floor = min(8, q_local)
    cap = max(floor, 1 << max(mx - 1, 0).bit_length())
    return min(cap, q_local)


def cap_ladder(q_local: int) -> tuple:
    """Every capacity :func:`ladder_cap` gives for ``q_local``, ascending:
    the powers of two from min(8, Q_local) below Q_local, then Q_local."""
    caps, c = [], min(8, q_local)
    while c < q_local:
        caps.append(c)
        c *= 2
    return tuple(caps) + (q_local,)


def routing_cap(keys, cfg: HashMemConfig, num_shards: int,
                shard_by: str = "mod", *, quantum: Optional[int] = None) \
        -> int:
    """The routing capacity of a (Q,) batch from its measured
    per-(src,dst) max (:func:`route_max`): by default the ladder step
    (:func:`ladder_cap`), so the capacities a stream can produce are known
    before it starts.  ``quantum=q`` instead rounds the max up to a
    multiple of q, clamped to [min(q, Q_local), Q_local]; ``quantum=1`` is
    the exact max.  On a skewed tick either tracks the measured max instead
    of the worst-case Q_local the unfused path pads to."""
    q_local = len(keys) // num_shards
    mx = route_max(keys, cfg, num_shards, shard_by)
    if quantum is None:
        return ladder_cap(mx, q_local)
    cap = max(quantum, -(-mx // quantum) * quantum)
    return min(cap, q_local)                # ceiling wins over the floor


def _tick_shard_fn(cfg, num_shards, axis, shard_by, caps):
    cap_p, cap_d, cap_i = caps

    def shard_fn(hm_stacked_local, pq, dq, ik, iv):
        hm1 = jax.tree.map(lambda x: x[0], hm_stacked_local)
        pad = jnp.uint32(ROUTE_PAD)
        cp = cap_p or pq.shape[0]
        cd = cap_d or dq.shape[0]
        ci = cap_i or ik.shape[0]
        po, _ = owner_and_local_bucket(pq, cfg, num_shards, shard_by)
        do = owner_of(dq, cfg, num_shards, shard_by)
        io, _ = owner_and_local_bucket(ik, cfg, num_shards, shard_by)
        rt_p = _Route(pq, po, num_shards, cp, pad, drop_invalid=True)
        rt_d = _Route(dq, do, num_shards, cd, pad, drop_invalid=True)
        rt_i = _Route(ik, io, num_shards, ci, pad, drop_invalid=True)
        # pass 1 on-device: ONE small all_to_all of per-(src,dst) valid
        # counts for all three phases — row s of the result is what shard s
        # sent me, so counts_in[s, ph] bounds the dense prefix of recv row s
        counts = jnp.stack([rt_p.counts(), rt_d.counts(), rt_i.counts()],
                           axis=-1)                       # (D, 3)
        counts_in = jax.lax.all_to_all(counts, axis, 0, 0, tiled=False)
        # pass 2: routed payloads at the measured capacities
        # -- probe (pre-tick table) ----------------------------------------
        recv_p = jax.lax.all_to_all(rt_p.send, axis, 0, 0, tiled=False)
        rv, rf = _local_probe(hm1, recv_p.reshape(-1), cfg, num_shards,
                              shard_by)
        back_v = jax.lax.all_to_all(rv.reshape(num_shards, cp), axis, 0, 0,
                                    tiled=False)
        back_f = jax.lax.all_to_all(rf.reshape(num_shards, cp), axis, 0, 0,
                                    tiled=False)
        # -- delete ---------------------------------------------------------
        recv_d = jax.lax.all_to_all(rt_d.send, axis, 0, 0, tiled=False)
        flat_d = recv_d.reshape(-1)
        _, lb_d = owner_and_local_bucket(flat_d, cfg, num_shards, shard_by)
        hm2, dfound = hashmap.delete_with_buckets(hm1, flat_d, lb_d)
        back_df = jax.lax.all_to_all(dfound.reshape(num_shards, cd), axis,
                                     0, 0, tiled=False)
        # -- insert (post-delete table) -------------------------------------
        recv_k = jax.lax.all_to_all(rt_i.send, axis, 0, 0, tiled=False)
        recv_v = jax.lax.all_to_all(
            rt_i.send_aux(iv, num_shards, jnp.uint32(0)), axis, 0, 0,
            tiled=False)
        flat_k = recv_k.reshape(-1)
        # validity from the count exchange: slot j of recv row s is a real
        # key iff j < counts_in[s, 2] (the routed prefix is dense)
        valid = (jnp.arange(ci, dtype=I32)[None, :]
                 < counts_in[:, 2:3]).reshape(-1)
        _, lb_i = owner_and_local_bucket(flat_k, cfg, num_shards, shard_by)
        hm3, iok = hashmap.insert_with_buckets(hm2, flat_k,
                                               recv_v.reshape(-1), lb_i,
                                               valid=valid)
        back_ok = jax.lax.all_to_all(iok.reshape(num_shards, ci), axis, 0, 0,
                                     tiled=False)
        hm_out = jax.tree.map(lambda x: x[None], hm3)
        return (hm_out,
                rt_p.gather_back(back_v),
                rt_p.gather_back(back_f, mask_overflow=True),
                rt_d.gather_back(back_df, mask_overflow=True),
                rt_i.gather_back(back_ok, mask_overflow=True))
    return shard_fn, 5, 5


def tick_mesh(mesh, hm_stacked, probe_q, del_q, ins_k, ins_v,
              cfg: HashMemConfig, axis: str = "model",
              caps=None, shard_by: str = "mod"):
    """A whole coalesced serving tick in ONE shard_map call: the sharded
    PageStore pytree is carried functionally through probe -> delete ->
    insert on-device, so a tick costs one host<->mesh launch instead of
    three (the paper's one-activation-per-chain-step economics applied to
    the launch path).

    ``caps``: per-phase (probe, delete, insert) per-(src,dst) routing
    capacities from the two-pass scheme — compute each with
    ``routing_cap`` on the same batches; ``None`` (or a 0 entry) falls
    back to the worst-case Q_local padding.  Entries equal to ROUTE_PAD
    are padding in every phase: dropped from routing (they consume no
    capacity), never stored, results 0/False.

    Returns (hm_stacked', probe_vals, probe_found, del_found, ins_ok) with
    phase semantics identical to ``probe_sharded`` (against the pre-tick
    table) -> ``delete_sharded`` -> ``insert_mesh`` (against the
    post-delete table) issued back to back.
    """
    fn = tick_mesh_program(mesh, cfg, axis, caps, shard_by)
    return fn(hm_stacked, probe_q, del_q, ins_k, ins_v)


def tick_mesh_program(mesh, cfg: HashMemConfig, axis: str = "model",
                      caps=None, shard_by: str = "mod"):
    """The jitted program behind :func:`tick_mesh` at these ``caps``, for a
    caller that compiles it ahead of time (``.lower(...).compile()``)."""
    caps = tuple(caps) if caps is not None else (None, None, None)
    assert len(caps) == 3, caps
    return _sharded_call("tick", mesh, cfg, axis, shard_by, caps)


def probe_replicated(mesh, hm, queries, cfg: HashMemConfig, axis: str = "data"):
    """Throughput mode: HashMem replicated, queries sharded over `axis`
    (pure DP — the paper's multi-rank replication counterpoint)."""
    def shard_fn(hm_local, q_local):
        return hashmap.probe(hm_local, q_local, backend=cfg.backend)

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )
    return fn(hm, queries)
