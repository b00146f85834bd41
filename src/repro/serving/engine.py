"""Continuous-batching request engine over HashMem shards.

The paper's throughput win is many bucket traversals in flight per device
call; this layer makes a *request stream* exploit that.  Requests (short
sessions of read/update/insert/delete/scan/rmw ops, typically YCSB-shaped —
see loadgen.py) occupy slots of a fixed pool; every engine **tick**

  1. admits pending requests into free slots (admission control: global
     pool occupancy, global queue bound, per-tenant slot/pending quotas);
  2. takes the next op from every active slot and **coalesces** the whole
     tick into fixed op phases — probe, then delete, then insert — executed
     by a pluggable shard backend:

       * host shards (default, the PR-3 path): ``num_shards`` independent
         HashMems; one vectorized jitted call per phase per *touched*
         shard, keys partitioned on the host with ONE vectorized
         ``rlu.owner_of_np`` call per phase;
       * mesh shards (``mesh=``): one stacked HashMem pytree laid out over
         a real ``jax.sharding.Mesh`` axis, ONE ``rlu.probe_sharded`` /
         ``rlu.delete_sharded`` / ``rlu.insert_mesh`` shard_map call per
         phase per tick no matter how many shards participate — the
         paper's channel-level parallelism on the serving hot path.
         Shard routing (hash-high-bits fastrange, rlu.py) happens INSIDE
         the RLU call, not in host dicts;

  3. scatters results back to the issuing requests, completes exhausted
     requests, frees their slots, and refills from the queue.

Phase order within a tick is fixed (probe -> delete -> insert), so

  * ``read`` observes the table as of the tick start;
  * ``update`` = tombstone-oldest + append (read-your-writes from the next
    tick on, exact semantics of the differential DictModel);
  * ``rmw`` reads the pre-tick value and writes in the same tick;
  * same-tick WRITE contention on one key is serialized by deferral: the
    first delete-phase op (delete/update/rmw) on a key claims it for the
    tick, later writers wait a tick — so a tick's delete batch never holds
    duplicate keys and the schedule is sequential-equivalent.

Multi-tick op pipelining (``pipeline_depth`` > 1)
-------------------------------------------------
With depth d, tick N+1's phases are ISSUED while up to d-1 earlier ticks'
results are still in flight: device calls chain functionally through the
shard pytree (tick N+1's probe consumes the table returned by tick N's
insert), and the host materializes results — writebacks, hit counts,
tombstone accounting, and the insert-failure check — only when a tick is
*drained*.  The op->tick schedule is IDENTICAL to the unpipelined engine
(the gather rules don't change), so chained purity alone makes every
result equal to the unpipelined run — with ONE exception: an in-flight
insert may turn out to have been refused (PR_ERROR: arena/chain bound),
which is only discovered, and repaired through the grow path, when its
tick drains.  The **write-claim fence** guards exactly that window: every
in-flight tick holds a claim on its insert-phase keys (insert/update/rmw),
and a tick about to issue an op touching a claimed key first STALLS the
pipeline — drains all in-flight ticks, resolving any deferred growth —
then issues.  Ops on unclaimed keys never stall: a refused insert writes
nothing, so no other key's probe/delete can observe the difference, and
the drain-time re-insert commutes with everything issued in between.
Results and per-tick op counts are therefore exactly those of the
unpipelined schedule.  Growth and tick-clock compaction flush the
pipeline; ``stall_events`` counts fence stalls (hot-key write contention
degrades pipelining gracefully, it never breaks equivalence).

``coalesce=False`` runs the identical schedule but issues one HashMem call
per op — the per-request baseline that benchmarks/serving_bench.py measures
the engine against.  Tenant ids are folded into the key space (tenancy.py),
so isolation is structural: no batched call can mix one tenant's writes
into another's keys.  Tenant stats are attributed exactly once per op at
gather time and once per probed key at writeback — ops routed to any shard
(including scans spanning shards) are never double-counted
(tests/test_tenancy.py pins this).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import HashMemConfig
from repro.core import hashmap, rlu
from repro.serving.metrics import MetricsCollector
from repro.serving.tenancy import Tenant, TenantRegistry
from repro.serving.tracing import COMPILES, NULL_TRACER, Tracer

# never generated by workloads (kv_synth keeps keys < 0xFFFFFFF0) and never
# inserted: probes/deletes padded with it report found=False and write
# nothing, so batches can be padded to power-of-two shapes to bound the set
# of traced shapes without perturbing the table.  Identical to the RLU
# routing pad, so a padded mesh batch routes as a no-op too.
PAD_KEY = rlu.ROUTE_PAD

OP_KINDS = ("read", "update", "insert", "delete", "scan", "rmw")

# ring bound for the two-pass routing telemetry log (exact totals live in
# ``route_cap_totals``; the log keeps the most recent launches for stats()
# and the bench's fill-ratio telemetry without growing with run length)
ROUTE_CAP_LOG_MAX = 1024
# the fused tick's phases, in launch order
ROUTE_PHASES = ("probe", "delete", "insert")

# slow-tick record (``stats()["slow_ticks"]``): a tick longer than
# SLOW_TICK_FACTOR x the running median tick is kept, with its phases, in a
# ring of SLOW_TICK_LOG_MAX; the median is re-read from the collector's
# tick histogram every SLOW_TICK_REFRESH ticks
SLOW_TICK_FACTOR = 4.0
SLOW_TICK_REFRESH = 64
SLOW_TICK_LOG_MAX = 16


@dataclass
class Request:
    """One client request: a short FIFO of ops executed one per tick.

    Ops are tuples: ("read", key) | ("update", key, val) |
    ("insert", key, val) | ("delete", key) | ("scan", start_key, n) |
    ("rmw", key, new_val).  Keys are RAW (tenant-relative); the engine
    folds the tenant id at issue time.
    """
    ops: list
    tenant: Optional[Tenant] = None
    rid: int = -1
    results: list = field(default_factory=list)
    submit_time: float = 0.0
    admit_tick: int = -1
    admit_time: float = 0.0
    cursor: int = 0
    killed: bool = False

    def done(self) -> bool:
        return self.cursor >= len(self.ops)


class SlotPool:
    """Slot lifecycle + pending queue (mechanics only; quota policy is a
    pluggable ``admit_ok`` predicate).  Shared by the KV engine and the
    decode loop in launch/serve.py."""

    def __init__(self, n_slots: int, max_pending: int = 0,
                 admit_ok: Optional[Callable] = None,
                 on_admit: Optional[Callable] = None):
        self.slots: list = [None] * n_slots
        self.pending: deque = deque()
        self.max_pending = max_pending
        self.admit_ok = admit_ok or (lambda item: True)
        # fires the moment an item lands in a slot — BEFORE the next
        # admit_ok check, so quota state stays exact within one refill batch
        self.on_admit = on_admit or (lambda slot, item: None)

    # -- queries -----------------------------------------------------------
    def occupancy(self) -> int:
        return sum(s is not None for s in self.slots)

    def active(self):
        return [(i, s) for i, s in enumerate(self.slots) if s is not None]

    def idle(self) -> bool:
        return self.occupancy() == 0 and not self.pending

    # -- lifecycle ---------------------------------------------------------
    def submit(self, item) -> str:
        """Returns "admitted" | "queued" | "rejected"."""
        if self._place(item) >= 0:
            return "admitted"
        if self.max_pending and len(self.pending) >= self.max_pending:
            return "rejected"
        self.pending.append(item)
        return "queued"

    def _place(self, item) -> int:
        """Admit into a free slot if the quota allows; returns the slot
        index or -1.  The single admission path for submit AND refill."""
        if not self.admit_ok(item):
            return -1
        for i, s in enumerate(self.slots):
            if s is None:
                self.slots[i] = item
                self.on_admit(i, item)
                return i
        return -1

    def refill(self) -> list:
        """Admit from the queue into free slots (FIFO, skipping items whose
        quota check fails so one saturated tenant can't head-of-line-block
        the rest).  Returns the newly admitted [(slot, item)]."""
        admitted = []
        if not self.pending:
            return admitted
        blocked = []
        while self.pending and any(s is None for s in self.slots):
            item = self.pending.popleft()
            i = self._place(item)
            if i >= 0:
                admitted.append((i, item))
            else:
                blocked.append(item)     # quota-refused; a free slot existed
        self.pending.extendleft(reversed(blocked))   # keep FIFO order
        return admitted

    def release(self, slot: int):
        item = self.slots[slot]
        self.slots[slot] = None
        return item


def _pad_pow2(arr: np.ndarray, fill, min_n: int = 1) -> np.ndarray:
    """Pad to the next power of two, at least ``min_n``: bounds the set of
    traced batch shapes (ideally to ONE per op kind) so jitted calls stay
    cache hits tick after tick."""
    n = len(arr)
    if n == 0:
        return arr
    m = max(min_n, 1)
    while m < n:
        m <<= 1
    if m == n:
        return arr
    return np.concatenate([arr, np.full(m - n, fill, arr.dtype)])


# jit caches are MODULE-level so every engine (and both bench modes) share
# compiled traces: jax keys them by (config metadata, batch shape), and
# pad_pow2 keeps the shape set logarithmic.
_jit_cache: dict = {}


def _jitted(name: str):
    if not _jit_cache:
        _jit_cache["probe"] = jax.jit(hashmap.probe)
        _jit_cache["delete"] = DeleteInPlace()
        _jit_cache["insert"] = jax.jit(hashmap.insert)
    return _jit_cache[name]


def _with_pool(hm, pool):
    """``hm`` with its page pool replaced by ``pool`` (None: the rest of
    the table, which the delete program takes apart from the pool)."""
    return dataclasses.replace(
        hm, store=dataclasses.replace(hm.store, pool=pool))


def _own_pool(hm):
    """``hm`` over a fresh copy of its pool, for the delete program to
    consume in place of the pool a caller still holds."""
    return _with_pool(hm, jnp.copy(hm.store.pool))


class DeleteInPlace:
    """``hashmap.delete`` as ``(hm, keys) -> (hm', found)``, compiled with
    the table's pool donated: the tombstones are written into the pool it
    is given, with no pass over the whole pool.  Only the pool is donated,
    so a table kept from before a call still reads its bucket heads and
    chain links (``hashmap.chain_lengths``); its pool is gone.

    ``not_aliased`` counts the compiled programs (one per shape) whose
    output pool does not reuse the donated one, where XLA copies after all.
    """

    def __init__(self):
        def delete(pool, rest, keys):  # so named: ``jit_delete`` in traces
            hm, found = hashmap.delete(_with_pool(rest, pool), keys)
            return hm.store.pool, _with_pool(hm, None), found
        self._jit = jax.jit(delete, donate_argnums=0)
        self._shapes: set = set()
        self.not_aliased = 0

    def __call__(self, hm, keys):
        pool, rest, found = self._jit(hm.store.pool, _with_pool(hm, None),
                                      keys)
        shape = (hm.config, keys.shape, keys.dtype)
        if shape not in self._shapes:      # compiled by the call above
            self._shapes.add(shape)
            mem = self._jit.lower(pool, rest, keys).compile() \
                .memory_analysis()
            self.not_aliased += mem.alias_size_in_bytes < pool.nbytes
        return _with_pool(rest, pool), found

    def lower(self, hm, keys):
        return self._jit.lower(hm.store.pool, _with_pool(hm, None), keys)


# ---------------------------------------------------------------------------
# Shard backends: where a coalesced phase actually executes
# ---------------------------------------------------------------------------

class _HostShards:
    """PR-3 shard backend: independent HashMems, one jitted call per phase
    per touched shard.  Keys are partitioned with one vectorized
    ``rlu.owner_of_np`` call per phase (no per-op host hashing)."""

    is_mesh = False

    def __init__(self, eng: "ServingEngine", shards: list,
                 borrowed: bool = False):
        self.eng = eng
        self.shards = shards
        self.num_shards = len(shards)
        # tables a caller handed in (``borrowed``): the delete program
        # consumes the pool it is given, so the first delete on each shard
        # takes a copy of the pool first and the caller's table stays whole
        self._borrowed = [borrowed] * self.num_shards

    @property
    def auto_grow(self) -> bool:
        return self.shards[0].config.auto_grow

    def owners(self, keys: np.ndarray) -> np.ndarray:
        return rlu.owner_of_np(keys, self.shards[0].config, self.num_shards,
                               self.eng.shard_by)

    # -- phases (return a zero-arg finalize; device work is issued NOW) ----
    def probe(self, keys: np.ndarray, pad: bool = True):
        owners = self.owners(keys)
        parts = []
        for s in np.unique(owners):
            idx = np.nonzero(owners == s)[0]
            q = self.eng._padded(keys[idx], PAD_KEY, pad)
            v, f = self.eng._jit_probe(self.shards[s], jnp.asarray(q))
            self.eng._record_call("probe")
            parts.append((idx, v, f))

        def finalize():
            vals = np.zeros(len(keys), np.uint32)
            found = np.zeros(len(keys), bool)
            for idx, v, f in parts:
                vals[idx] = np.asarray(v)[:len(idx)]
                found[idx] = np.asarray(f)[:len(idx)]
            return vals, found
        return finalize

    def delete(self, keys: np.ndarray, pad: bool = True):
        """One jitted delete per touched shard; it consumes the shard's
        pool (``DeleteInPlace``)."""
        owners = self.owners(keys)
        parts = []
        for s in np.unique(owners):
            idx = np.nonzero(owners == s)[0]
            q = self.eng._padded(keys[idx], PAD_KEY, pad)
            if self._borrowed[s]:
                self.shards[s] = _own_pool(self.shards[s])
                self._borrowed[s] = False
            self.shards[s], f = self.eng._jit_delete(self.shards[s],
                                                     jnp.asarray(q))
            self.eng._record_call("delete")
            parts.append((int(s), idx, f))

        def finalize():
            found = np.zeros(len(keys), bool)
            tombs: dict = {}
            for s, idx, f in parts:
                fs = np.asarray(f)[:len(idx)]
                found[idx] = fs
                tombs[s] = tombs.get(s, 0) + int(fs.sum())
            return found, tombs
        return finalize

    def insert(self, keys: np.ndarray, vals: np.ndarray, pad: bool = True):
        """Fixed-arena attempt: ONE jitted insert per touched shard, padded
        with a valid mask (pads write nothing, claim no pages).  PR_ERROR
        handling (growth) is deferred to the engine's drain."""
        owners = self.owners(keys)
        parts = []
        for s in np.unique(owners):
            idx = np.nonzero(owners == s)[0]
            n = len(idx)
            q = self.eng._padded(keys[idx], PAD_KEY, pad)
            v = self.eng._padded(vals[idx], np.uint32(0), pad)
            valid = np.zeros(len(q), bool)
            valid[:n] = True
            self.shards[s], ok = self.eng._jit_insert(
                self.shards[s], jnp.asarray(q), jnp.asarray(v),
                jnp.asarray(valid))
            self.eng._record_call("insert")
            parts.append((idx, ok))

        def finalize():
            ok_all = np.zeros(len(keys), bool)
            for idx, ok in parts:
                ok_all[idx] = np.asarray(ok)[:len(idx)]
            return ok_all
        return finalize

    @property
    def resize_mode(self) -> str:
        return self.shards[0].config.resize

    # -- slow paths --------------------------------------------------------
    def grow_insert(self, keys: np.ndarray, vals: np.ndarray):
        """Drain-time PR_ERROR fallback: host-level auto-grow insert of the
        refused elements.  Each host shard grows independently; returns
        (ok, [shard ids whose arena was REBUILT], events).  Rebuild
        detection is by num_pages — an extendible directory doubling
        changes num_buckets while every page (and tombstone) stays put, so
        it must NOT reset tombstone accounting or bump the rebuild epoch."""
        owners = self.owners(keys)
        ok_all = np.zeros(len(keys), bool)
        grown = []
        events: dict = {}
        for s in np.unique(owners):
            idx = np.nonzero(owners == s)[0]
            before = self.shards[s].config.num_pages
            self.shards[s], ok = hashmap.insert_auto(
                self.shards[s], jnp.asarray(keys[idx]),
                jnp.asarray(vals[idx]), events=events)
            ok_all[idx] = np.asarray(ok)
            if self.shards[s].config.num_pages != before:
                grown.append(int(s))
        return ok_all, grown, events

    def preload(self, keys: np.ndarray, vals: np.ndarray):
        """Bulk load; returns the shard ids whose arena was rebuilt so the
        engine can run the same grow bookkeeping as the drain path."""
        owners = self.owners(keys)
        grown = []
        for s in range(self.num_shards):
            m = owners == s
            if m.any():
                before = self.shards[s].config.num_pages
                self.shards[s], ok = hashmap.insert_auto(
                    self.shards[s], jnp.asarray(keys[m]), jnp.asarray(vals[m]))
                assert bool(np.asarray(ok).all()), "preload overflowed"
                if self.shards[s].config.num_pages != before:
                    grown.append(s)
        return grown

    def compact_shards(self, tombstones: list) -> list:
        out = []
        for s, hm in enumerate(self.shards):
            if hashmap.compact_due(hm, tombstones[s]):
                self.shards[s] = hashmap.compact(hm)
                out.append(s)
        return out

    def shard_list(self) -> list:
        return self.shards


class _MeshShards:
    """Mesh shard backend: ONE stacked HashMem pytree sharded over a mesh
    axis (one shard per device), every coalesced phase ONE rlu shard_map
    call.  Routing lives in the RLU layer (hash-high-bits fastrange); the
    host never partitions keys on the request path."""

    is_mesh = True

    def __init__(self, eng: "ServingEngine", mesh, axis: str,
                 cfg: HashMemConfig, max_slots: int,
                 tables: Optional[list] = None):
        # shard d is built, grown and compacted on device d; the stacked
        # pytree is assembled from the per-device pieces
        self._place = lambda shards: rlu.place_shards(mesh, shards, axis)
        self.eng = eng
        self.mesh = mesh
        self.axis = axis
        self.num_shards = mesh.shape[axis]
        self.cfg = cfg
        # the fused tick's phase length: a phase without scans holds at most
        # one key per slot, so every such tick fits one power of two, split
        # evenly over the shards
        self.tick_len = self._split(1 << max(max_slots - 1, 0).bit_length())
        self._batch = NamedSharding(mesh, PartitionSpec(axis))
        # fused tick programs by (table avals, phase length, caps), compiled
        # ahead of time and called directly; the ladder set is compiled by
        # compile_ticks before the first tick
        self._ticks: dict = {}
        if tables is None:
            tables = []
            for d in mesh.devices.reshape(-1):
                with jax.default_device(d):
                    tables.append(hashmap.create(cfg))
        assert len(tables) == self.num_shards
        self.hm_stacked = self._place(list(tables))

    @property
    def auto_grow(self) -> bool:
        return self.cfg.auto_grow

    def owners(self, keys: np.ndarray) -> np.ndarray:
        """Host mirror of the device-side router — used only for per-shard
        ACCOUNTING (tombstone attribution), never for request routing."""
        return rlu.owner_of_np(keys, self.cfg, self.num_shards,
                               self.eng.shard_by)

    # -- phases ------------------------------------------------------------
    def probe(self, keys: np.ndarray, pad: bool = True):
        q = self.eng._padded(keys, PAD_KEY, pad)
        v, f = rlu.probe_sharded(self.mesh, self.hm_stacked, jnp.asarray(q),
                                 self.cfg, self.axis,
                                 shard_by=self.eng.shard_by)
        self.eng._record_call("probe")

        def finalize():
            return np.asarray(v)[:len(keys)], np.asarray(f)[:len(keys)]
        return finalize

    def _split(self, n: int) -> int:
        """``n`` rounded up to a multiple of the shard count."""
        return -(-n // self.num_shards) * self.num_shards

    def _tick_program(self, n: int, caps: tuple):
        """The fused tick compiled for phases of ``n`` keys at ``caps``, and
        the XLA compiles it took (none where the process holds it)."""
        key = (self.cfg, n, caps)
        prog = self._ticks.get(key)
        if prog is not None:
            return prog, 0
        compiles0 = COMPILES.counts["compiles"]
        sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa: E731
                                             sharding=x.sharding)
        q = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=self._batch)
        prog = rlu.tick_mesh_program(
            self.mesh, self.cfg, self.axis, caps, self.eng.shard_by).lower(
                jax.tree.map(sds, self.hm_stacked), q, q, q, q).compile()
        self._ticks[key] = prog
        return prog, COMPILES.counts["compiles"] - compiles0

    def compile_ticks(self) -> int:
        """Compile the fused tick for every capacity the ladder gives at
        ``tick_len``: (probe cap, write cap) over ``rlu.cap_ladder`` of
        tick_len / num_shards each, so no tick of up to ``tick_len`` keys a
        phase compiles once serving starts.  Returns the programs held."""
        ladder = rlu.cap_ladder(self.tick_len // self.num_shards)
        for cp in ladder:
            for cw in ladder:
                self._tick_program(self.tick_len, (cp, cw, cw))
        return len(ladder) ** 2

    def tick_fused(self, pk, dk, ik, iv):
        """The whole tick's three phases in ONE rlu.tick_mesh shard_map
        launch, routed at two-pass skew-aware capacities.  Every phase is
        padded to one length, ``tick_len`` unless scans make a phase longer
        (then the power of two above it); empty phases ride along all-pad.
        The probe phase's capacity is the ladder step
        (``rlu.ladder_cap``) of its measured per-(src,dst) max, and the
        delete and insert phases share the step of the larger of theirs, so
        a tick at ``tick_len`` runs one of the programs ``compile_ticks``
        compiled.  Returns one finalize per phase with the same contracts
        as probe/delete/insert."""
        m = max(len(pk), len(dk), len(ik))
        n = self.tick_len if m <= self.tick_len else \
            self._split(1 << (m - 1).bit_length())

        def prep(arr, fill):
            q = np.full(n, fill, np.uint32)
            q[:len(arr)] = arr
            return q
        q_p, q_d, q_k = prep(pk, PAD_KEY), prep(dk, PAD_KEY), prep(ik, PAD_KEY)
        q_v = prep(iv, 0)
        ql = n // self.num_shards
        # pass 1 (host mirror of the on-device count exchange): measured
        # per-(src,dst) maxima set the routing capacities for pass 2
        with self.eng._phase_span("route", self.eng._cur_lane):
            meas = [rlu.route_max(q, self.cfg, self.num_shards,
                                  self.eng.shard_by) for q in (q_p, q_d, q_k)]
        cw = rlu.ladder_cap(max(meas[1], meas[2]), ql)
        caps = (rlu.ladder_cap(meas[0], ql), cw, cw)
        prog, compiled = self._tick_program(n, caps)
        self.hm_stacked, v, f, df, iok = prog(
            self.hm_stacked, *jax.device_put((q_p, q_d, q_k, q_v),
                                             self._batch))
        self.eng._record_call("fused_tick")
        self.eng._record_route_caps([ql] * 3, caps, meas,
                                    (len(pk), len(dk), len(ik)))
        self.eng.route_cap_totals["tick_programs_after"] += compiled
        owners_d = self.owners(dk) if len(dk) else np.zeros(0, np.int32)

        def fin_probe():
            return np.asarray(v)[:len(pk)], np.asarray(f)[:len(pk)]

        def fin_delete():
            found = np.asarray(df)[:len(dk)]
            tombs: dict = {}
            for s in np.unique(owners_d[found]):
                tombs[int(s)] = int((owners_d[found] == s).sum())
            return found, tombs

        def fin_insert():
            return np.asarray(iok)[:len(ik)]
        return fin_probe, fin_delete, fin_insert

    def delete(self, keys: np.ndarray, pad: bool = True):
        q = self.eng._padded(keys, PAD_KEY, pad)
        self.hm_stacked, f = rlu.delete_sharded(
            self.mesh, self.hm_stacked, jnp.asarray(q), self.cfg, self.axis,
            shard_by=self.eng.shard_by)
        self.eng._record_call("delete")
        owners = self.owners(keys)

        def finalize():
            found = np.asarray(f)[:len(keys)]
            tombs: dict = {}
            for s in np.unique(owners[found]):
                tombs[int(s)] = int((owners[found] == s).sum())
            return found, tombs
        return finalize

    def insert(self, keys: np.ndarray, vals: np.ndarray, pad: bool = True):
        q = self.eng._padded(keys, PAD_KEY, pad)
        v = self.eng._padded(vals, np.uint32(0), pad)
        self.hm_stacked, ok = rlu.insert_mesh(
            self.mesh, self.hm_stacked, jnp.asarray(q), jnp.asarray(v),
            self.cfg, self.axis, shard_by=self.eng.shard_by)
        self.eng._record_call("insert")

        def finalize():
            return np.asarray(ok)[:len(keys)]
        return finalize

    @property
    def resize_mode(self) -> str:
        return self.cfg.resize

    # -- slow paths --------------------------------------------------------
    def grow_insert(self, keys: np.ndarray, vals: np.ndarray):
        """Drain-time PR_ERROR fallback: the repair runs in rlu.insert_shards
        — extendible splits are per-shard local (shape-preserving) and
        directory doublings are synchronized pointer copies; only a full
        grow() REBUILD (detected by num_pages, which doubling preserves)
        rebuilds all shards and resets their tombstone epochs.  Returns
        (ok, [rebuilt shard ids] — all or none here, events)."""
        before = self.cfg.num_pages
        events: dict = {}
        shards, ok, cfg2 = rlu.insert_shards(
            self.shard_list(), jnp.asarray(keys), jnp.asarray(vals), self.cfg,
            self.num_shards, shard_by=self.eng.shard_by, events=events)
        grew = cfg2.num_pages != before
        self.cfg = cfg2
        self.hm_stacked = self._place(shards)
        return (np.asarray(ok),
                list(range(self.num_shards)) if grew else [], events)

    def preload(self, keys: np.ndarray, vals: np.ndarray):
        ok, grown, _ = self.grow_insert(keys, vals)
        assert bool(ok.all()), "preload overflowed"
        return grown

    def compact_shards(self, tombstones: list) -> list:
        shards = self.shard_list()
        bfn = rlu._local_bucket_fn(self.num_shards, self.eng.shard_by)
        out = []
        for s, hm in enumerate(shards):
            if hashmap.compact_due(hm, tombstones[s]):
                shards[s] = hashmap.compact(hm, bucket_fn=bfn)
                out.append(s)
        if out:
            self.hm_stacked = self._place(shards)
        return out

    def shard_list(self) -> list:
        return rlu.local_shards(self.hm_stacked)


# ---------------------------------------------------------------------------
# In-flight tick bookkeeping (pipelining)
# ---------------------------------------------------------------------------

@dataclass
class _PhasePending:
    kind: str                 # "probe" | "delete" | "insert"
    entries: list
    finalize: Callable        # () -> phase result arrays, input order
    keys: np.ndarray
    vals: Optional[np.ndarray] = None


@dataclass
class _TickRecord:
    tick: int
    epochs: list              # per-shard grow/compact epochs at issue time
    phases: list              # [_PhasePending, ...]
    claims: set               # insert-phase keys (fence until drained)
    lane: int = 0             # trace track (tick % pipeline_depth)
    span: object = None       # open "tick" span token, closed at drain


class _PhaseTimer:
    """Context manager pairing one tracer span with one MetricsCollector
    ``record_phase`` sample (engine._phase_span), and adding the duration
    to the tick's phase totals for the slow-tick record."""

    __slots__ = ("eng", "name", "lane", "args", "tok", "t0")

    def __init__(self, eng, name, lane, args):
        self.eng, self.name, self.lane, self.args = eng, name, lane, args

    def __enter__(self):
        tr = self.eng.tracer
        self.tok = tr.begin(self.name, self.lane, **self.args) \
            if tr.enabled else None
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        eng = self.eng
        eng.metrics.record_phase(self.name, dt)
        phases = eng._tick_phases
        phases[self.name] = phases.get(self.name, 0.0) + dt
        eng.tracer.end(self.tok)
        return False


class ServingEngine:
    """Multi-tenant continuous-batching engine over HashMem shards.

    ``mesh=None`` (default): ``num_shards`` host-routed independent tables.
    ``mesh=Mesh(...)``: one stacked table sharded over ``mesh_axis`` — every
    coalesced phase is ONE rlu shard_map call across all devices, and
    ``num_shards`` is the mesh axis size.  Tables auto-grow (host shards
    independently; mesh shards synchronized) and are compacted by an
    engine-tick policy (tombstone fraction OR chain-length trigger, checked
    every ``compact_every`` ticks).

    ``pipeline_depth`` > 1 enables multi-tick op pipelining (module
    docstring): requires ``coalesce=True``.
    """

    def __init__(self, cfg: Optional[HashMemConfig] = None, *,
                 tables: Optional[list] = None, num_shards: int = 1,
                 max_slots: int = 16, max_pending: int = 0,
                 tenants: Optional[TenantRegistry] = None,
                 metrics: Optional[MetricsCollector] = None,
                 coalesce: bool = True, pad_pow2: bool = True,
                 compact_every: int = 64,
                 mesh=None, mesh_axis: str = "model",
                 shard_by: str = "highbits", pipeline_depth: int = 1,
                 record_schedule: bool = False,
                 fused_tick: Optional[bool] = None,
                 trace=None):
        assert shard_by in rlu.SHARD_ROUTERS, shard_by
        assert pipeline_depth >= 1
        assert pipeline_depth == 1 or coalesce, \
            "pipelining needs coalesced phases (coalesce=True)"
        # fused whole-tick megakernel: the DEFAULT on a coalesced mesh (one
        # rlu.tick_mesh shard_map launch per tick, two-pass skew-aware
        # routing); fused_tick=False keeps the three-call reference path
        if fused_tick is None:
            fused_tick = mesh is not None and coalesce
        assert not fused_tick or (mesh is not None and coalesce), \
            "fused_tick needs a mesh backend and coalesce=True"
        self.fused_tick = fused_tick
        # observability: ``trace=True`` (or a Tracer instance) turns on
        # tick-level span recording (tracing.py); the default NULL_TRACER
        # keeps every call site a single enabled-flag check
        if isinstance(trace, Tracer):
            self.tracer = trace
        else:
            self.tracer = Tracer() if trace else NULL_TRACER
        COMPILES.watch(self.tracer)
        self._compiles0 = COMPILES.snapshot()
        self._profile_window = None    # (start_tick, stop_tick, logdir)
        self._profiling = False
        # a profiler start/stop that raised, as its repr: the run goes on
        # untraced and the caller reads the failure here or in stats()
        self.profiler_error: Optional[str] = None
        self.shard_by = shard_by
        self.coalesce = coalesce
        self.pad_pow2 = pad_pow2
        self.pipeline_depth = pipeline_depth
        # floor for padded batch shapes: 2*slots covers the common tick
        # (reads+updates for every slot) so most ticks hit ONE shape
        self.pad_min = 1 << max(2 * max_slots - 1, 1).bit_length()
        self.compact_every = compact_every
        self.tenants = tenants or TenantRegistry()
        self.metrics = metrics or MetricsCollector()
        if cfg is None and tables is None:
            cfg = HashMemConfig(num_buckets=256, slots_per_page=64,
                                overflow_pages=256, max_chain=8)
        # shared jit-compiled call paths (module-level cache).  The insert
        # fast path is the fixed-arena hashmap.insert — PR_ERROR falls back
        # to the host-level grow loop at drain time.
        self._jit_probe = _jitted("probe")
        self._jit_delete = _jitted("delete")
        self._jit_insert = _jitted("insert")
        if mesh is not None:
            base = tables[0].config if tables else cfg
            self.backend = _MeshShards(self, mesh, mesh_axis, base,
                                       max_slots, tables)
        elif tables is not None:
            self.backend = _HostShards(self, list(tables), borrowed=True)
        else:
            self.backend = _HostShards(
                self, [hashmap.create(cfg) for _ in range(num_shards)])
        self.num_shards = self.backend.num_shards
        # mesh phases must split evenly across devices
        self._pad_multiple = self.num_shards if mesh is not None else 1
        self.pool = SlotPool(max_slots, max_pending, admit_ok=self._quota_ok,
                             on_admit=self._on_admit)
        self.ticks = 0
        self._rid = 0
        self._active_by_tenant: dict[int, int] = {}
        self._pending_by_tenant: dict[int, int] = {}
        # engine-level HashMem API calls, cumulative and per-tick (the
        # coalescing tests assert calls_last_tick[kind] <= num_shards on
        # host shards and == 1 on a mesh; "fused_tick" counts whole-tick
        # megakernel launches — a fused tick is ONE launch for all phases)
        self.batch_calls = {"probe": 0, "delete": 0, "insert": 0,
                            "fused_tick": 0}
        self.calls_last_tick = dict(self.batch_calls)
        # two-pass routing telemetry: one record per fused launch —
        # per-phase (q_local, cap, measured max) so benches can show ICI
        # capacity tracking skew instead of the Q_local worst case.  The
        # log is a bounded ring; exact lifetime sums live in the totals.
        self.route_cap_log: deque = deque(maxlen=ROUTE_CAP_LOG_MAX)
        # per phase, the routed buffer rows (all chips' send buffers) and
        # the real keys in them; and the fused tick programs compiled
        # before the first tick and after it
        self.route_cap_totals = {"launches": 0, "cap_sum": 0,
                                 "q_local_sum": 0, "measured_sum": 0,
                                 "tick_programs_before": 0,
                                 "tick_programs_after": 0}
        for ph in ROUTE_PHASES:
            self.route_cap_totals[ph + "_rows"] = 0
            self.route_cap_totals[ph + "_keys"] = 0
        self._tombstones = [0] * self.num_shards
        self.grow_events = 0
        # extendible-resize telemetry: group splits and directory doublings
        # repaired inline at drain time (no pipeline flush; see grow-smoke)
        self.split_events = 0
        self.directory_doublings = 0
        self.compact_events = 0
        self.killed_requests = 0
        self.stall_events = 0
        # per-shard rebuild epoch: bumped by grow/compact of THAT shard so
        # drain-time tombstone accounting from before the rebuild is dropped
        # for it alone (other shards' counters keep accumulating)
        self._shard_epochs = [0] * self.num_shards
        self._inflight: list[_TickRecord] = []
        self._cur_lane = 0             # trace track of the tick being built
        # probe keys of the most recent tick that had any — reused by the
        # throttled rows-activated telemetry sample (metrics.py)
        self._last_probe_keys: Optional[np.ndarray] = None
        # optional executed-op log for the differential harness: entries
        # (tick, kind, folded_keys tuple, val|None, res dict) in gather
        # order — with the fixed phase order this is enough to replay the
        # exact schedule against the DictModel (tests/model.py)
        self.record_schedule = record_schedule
        self.schedule: list = []
        # always-on slow-tick record: seconds per phase of the current tick
        # (_PhaseTimer adds to it), the running median tick, and the ring
        self._tick_phases: dict = {}
        self._tick_p50 = 0.0
        self.slow_ticks: deque = deque(maxlen=SLOW_TICK_LOG_MAX)
        if self.fused_tick:
            # every program a tick of up to max_slots ops can need, now: a
            # compile inside serving stalls every request in flight
            self.route_cap_totals["tick_programs_before"] = \
                self.backend.compile_ticks()

    # -- back-compat views -------------------------------------------------
    @property
    def shards(self) -> list:
        """Per-shard HashMems (host: the live list; mesh: device slices of
        the stacked pytree)."""
        return self.backend.shard_list()

    # -- admission ---------------------------------------------------------
    def _quota_ok(self, req: Request) -> bool:
        t = req.tenant
        if t is None or not t.max_slots:
            return True
        return self._active_by_tenant.get(t.tid, 0) < t.max_slots

    def _validate_ops(self, req: Request):
        """Reject keys that collide with the reserved pad/sentinel range
        BEFORE admission (a real exception, not an assert — it must survive
        ``python -O``).  A stored key equal to ROUTE_PAD (0xFFFFFFF0) would
        silently become routing padding: never stored, probes always miss —
        so the engine/tenancy boundary is where the key domain is closed.
        Raw (untenanted) keys must stay below PAD_KEY; tenant keys must fit
        the tenant key space (folding then keeps them below PAD_KEY because
        the all-ones tenant id is unregistrable)."""
        bound = int(self.tenants.space.key_space) if req.tenant is not None \
            else int(PAD_KEY)
        for op in req.ops:
            kind = op[0]
            if kind not in OP_KINDS:
                raise ValueError(f"unknown op kind {kind!r}")
            lo = int(op[1])
            hi = lo + int(op[2]) - 1 if kind == "scan" else lo
            if lo < 0 or hi >= bound:
                raise ValueError(
                    f"op {kind!r} key {hi:#x} outside the usable key domain "
                    f"[0, {bound:#x}) — keys at or above "
                    f"{int(PAD_KEY):#x} are reserved for routing padding "
                    f"and the EMPTY/TOMBSTONE sentinels")

    def submit(self, req: Request) -> str:
        """Admission control: place in a free slot, else queue, else reject.
        Rejection happens when the global queue is full OR the tenant's own
        pending quota is exhausted.  Raises ValueError on keys colliding
        with the reserved pad/sentinel range."""
        tok = self.tracer.begin("submit")
        try:
            return self._submit(req)
        finally:
            self.tracer.end(tok)

    def _submit(self, req: Request) -> str:
        self._validate_ops(req)
        req.rid, self._rid = self._rid, self._rid + 1
        req.submit_time = time.perf_counter()
        tr = self.tracer
        if tr.enabled:
            # request lifecycle slices: "request" wraps "queue" (submit ->
            # admit) then "service" (admit -> complete/kill), keyed by rid
            tr.async_begin("request", req.rid, ops=len(req.ops))
            tr.async_begin("queue", req.rid)
        t = req.tenant
        if t is not None:
            t.stats["submitted"] += 1
            if t.max_pending and \
                    self._pending_by_tenant.get(t.tid, 0) >= t.max_pending \
                    and not (self._quota_ok(req)
                             and any(s is None for s in self.pool.slots)):
                t.stats["rejected"] += 1
                self._trace_close_request(req, "rejected")
                return "rejected"
        status = self.pool.submit(req)
        if status == "queued":
            req._queued = True
            if t is not None:
                t.stats["queued"] += 1
                self._pending_by_tenant[t.tid] = \
                    self._pending_by_tenant.get(t.tid, 0) + 1
        elif status == "rejected":
            if t is not None:
                t.stats["rejected"] += 1
            self._trace_close_request(req, "rejected")
        return status

    def _trace_close_request(self, req: Request, status: str):
        """Balance the request's open async slices at a terminal event
        (reject or kill) so the exported trace never holds a dangling
        begin.  Completion closes them inline in ``tick()``."""
        tr = self.tracer
        if not tr.enabled:
            return
        if req.admit_tick >= 0:
            tr.async_end("service", req.rid, status=status)
        else:
            tr.async_end("queue", req.rid, status=status)
        tr.async_end("request", req.rid, status=status)

    def submit_all(self, reqs: Iterable[Request]) -> dict:
        out = {"admitted": 0, "queued": 0, "rejected": 0}
        for r in reqs:
            out[self.submit(r)] += 1
        return out

    def _on_admit(self, slot: int, req: Request):
        """SlotPool admission callback — runs the moment a slot is taken, so
        quota state is exact even within one refill batch."""
        req.admit_tick = self.ticks
        req.admit_time = time.perf_counter()
        if self.tracer.enabled:
            self.tracer.async_end("queue", req.rid)
            self.tracer.async_begin("service", req.rid, slot=slot,
                                    tick=self.ticks)
        t = req.tenant
        if getattr(req, "_queued", False):
            req._queued = False
            if t is not None:
                self._pending_by_tenant[t.tid] = \
                    max(self._pending_by_tenant.get(t.tid, 0) - 1, 0)
        if t is not None:
            t.stats["admitted"] += 1
            self._active_by_tenant[t.tid] = \
                self._active_by_tenant.get(t.tid, 0) + 1

    def _refill(self):
        self.pool.refill()

    def kill(self, req: Request) -> bool:
        """Abort a request mid-flight (client disconnect / failure
        injection): remaining ops are dropped, the slot (or queue entry) is
        reclaimed immediately.  Ops already issued in still-in-flight ticks
        complete on-device — their write claims stay fenced until drained —
        and their results land in ``req.results`` harmlessly."""
        t = req.tenant
        for slot, r in self.pool.active():
            if r is req:
                req.killed = True
                self.pool.release(slot)
                if t is not None:
                    t.stats["killed"] += 1
                    self._active_by_tenant[t.tid] = \
                        max(self._active_by_tenant.get(t.tid, 0) - 1, 0)
                self.killed_requests += 1
                self.tracer.instant("kill", tid=self._cur_lane, rid=req.rid)
                self._trace_close_request(req, "killed")
                return True
        for i, r in enumerate(self.pool.pending):
            if r is req:              # identity — Request.__eq__ is by value
                req.killed = True
                del self.pool.pending[i]
                if t is not None:
                    t.stats["killed"] += 1
                    self._pending_by_tenant[t.tid] = \
                        max(self._pending_by_tenant.get(t.tid, 0) - 1, 0)
                self.killed_requests += 1
                self.tracer.instant("kill", tid=self._cur_lane, rid=req.rid)
                self._trace_close_request(req, "killed")
                return True
        return False                  # already completed/released: no-op

    # -- key handling ------------------------------------------------------
    def _fold(self, req: Request, key: int) -> int:
        """Scalar tenant fold for the per-op gather hot loop (the vectorized
        TenantSpace.fold would build a throwaway array per key).  Tenant ids
        are range-checked at registration; the key range is checked here."""
        if req.tenant is None:
            key = int(key)
            # PAD_KEY/ROUTE_PAD and the sentinels live above this floor; a
            # stored key up there would break the padding/routing no-op
            # invariant (kv_synth keeps workload keys below it too)
            assert 0 <= key < int(PAD_KEY), \
                f"key {key:#x} collides with the pad/sentinel range"
            return key
        sp = self.tenants.space
        key = int(key)
        assert 0 <= key < sp.key_space, \
            f"tenant key {key} must fit {sp.key_bits} bits"
        return (req.tenant.tid << sp.key_bits) | key

    def _padded(self, arr: np.ndarray, fill, pow2: bool = True) -> np.ndarray:
        """Batch padding: pow2 floor (bounds traced shapes) and, on a mesh,
        round up to a multiple of num_shards (shard_map divisibility)."""
        q = _pad_pow2(arr, fill, self.pad_min) \
            if (pow2 and self.pad_pow2) else arr
        m = self._pad_multiple
        if m > 1 and len(q) % m:
            q = np.concatenate([q, np.full(m - len(q) % m, fill, arr.dtype)])
        return q

    def _record_call(self, kind: str):
        self.batch_calls[kind] += 1
        self.calls_last_tick[kind] += 1

    def _phase_span(self, name: str, lane: int, **args):
        """Span + per-phase metrics in one context manager: the tracer gets
        a duration span on ``lane`` and MetricsCollector a ``phase_ms``
        sample, so the snapshot's per-phase latency blocks exist even when
        tracing is off."""
        return _PhaseTimer(self, name, lane, args)

    def _record_route_caps(self, q_locals, caps, measured, keys=(0, 0, 0)):
        """Log one fused launch: per phase (probe, delete, insert) its
        Q_local, routed capacity, measured per-(src,dst) max and real
        keys."""
        self.route_cap_log.append({
            "tick": self.ticks,
            "q_local": list(q_locals),     # worst-case (unfused) capacity
            "cap": list(caps),             # two-pass routed capacity
            "max": list(measured),         # exact measured per-(src,dst) max
        })
        tot = self.route_cap_totals
        tot["launches"] += 1
        tot["cap_sum"] += int(sum(caps))
        tot["q_local_sum"] += int(sum(q_locals))
        tot["measured_sum"] += int(sum(measured))
        # each phase's exchange carries D*cap rows per device across D
        # devices
        rows = [self.num_shards ** 2 * int(c) for c in caps]
        for ph, r, k in zip(ROUTE_PHASES, rows, keys):
            tot[ph + "_rows"] += r
            tot[ph + "_keys"] += int(k)
        tr = self.tracer
        if tr.enabled:
            ql = sum(q_locals)
            tr.counter("route_cap_fill", sum(caps) / ql if ql else 1.0)
            # routed all_to_all element volume this launch, and the real
            # keys in it
            tr.counter("routed_elems", sum(rows))
            tr.counter("routed_keys", int(sum(keys)))

    # -- the tick ----------------------------------------------------------
    def tick(self) -> int:
        """One engine step.  Returns the number of ops issued.

        Trace shape: the tick's lane (``tick % pipeline_depth``) is its
        trace track, so with depth>=2 consecutive tick spans land on
        DIFFERENT tracks and render overlapped — the issue->drain span of
        tick N is still open on lane N%d while tick N+1 runs on the next
        lane, and a write-claim stall shows as a ``pipeline_stall`` span
        nested in the stalling tick.  The span is *begun* here and *ended*
        by ``_drain_one`` (depth=1 drains within this call)."""
        t0 = time.perf_counter()
        tick = self.ticks
        compiles0, traces0 = COMPILES.counts["compiles"], \
            COMPILES.counts["traces"]
        self._tick_phases.clear()
        tr = self.tracer
        lane = self.ticks % self.pipeline_depth
        self._cur_lane = lane
        pw = self._profile_window
        if pw is not None and not self._profiling and self.ticks >= pw[0]:
            self._start_profiler()
        tick_tok = tr.begin("tick", lane, tick=self.ticks)
        self._refill()
        self.calls_last_tick = {"probe": 0, "delete": 0, "insert": 0,
                                "fused_tick": 0}

        with self._phase_span("gather", lane):
            probes, deletes, inserts, claims, conflict, n_ops = \
                self._gather()
        if conflict and self._inflight:
            # write-claim fence: an op touches a key whose in-flight insert
            # could still be refused — stall (drain, resolving any deferred
            # growth) before issuing, so the op observes the repaired table
            self.stall_events += 1
            tr.instant("write_fence", tid=lane,
                       inflight=len(self._inflight))
            with self._phase_span("pipeline_stall", lane):
                self.flush()
        if probes:
            self._last_probe_keys = np.asarray([k for k, _ in probes],
                                               np.uint32)
        rec = self._issue(probes, deletes, inserts, claims)
        rec.lane = lane
        rec.span = tick_tok
        self._inflight.append(rec)
        # drain down to the pipeline window: depth=1 materializes THIS tick
        # before completion (the unpipelined PR-3 behavior); depth=d keeps
        # d-1 ticks in flight
        while len(self._inflight) > self.pipeline_depth - 1:
            self._drain_one()

        # completion + slot recycling (cursor-driven: a request can complete
        # while its last results are still in flight); the completed
        # requests' latencies are recorded in one block after the sweep
        with self._phase_span("admit", lane):
            done = []
            for slot, req in self.pool.active():
                if req.done():
                    self.pool.release(slot)
                    t = req.tenant
                    now = time.perf_counter()
                    queue_s = max(req.admit_time - req.submit_time, 0.0)
                    service_s = max(now - req.admit_time, 0.0)
                    if t is not None:
                        t.stats["completed"] += 1
                        t.stats["queue_secs"] += queue_s
                        t.stats["service_secs"] += service_s
                        self._active_by_tenant[t.tid] = \
                            max(self._active_by_tenant.get(t.tid, 0) - 1, 0)
                    if tr.enabled:
                        tr.async_end("service", req.rid)
                        tr.async_end("request", req.rid, status="ok")
                    done.append((self.ticks - req.admit_tick + 1,
                                 now - req.submit_time, queue_s, service_s))
            if done:
                with self._phase_span("telemetry", lane):
                    for ticks, secs, queue_s, service_s in done:
                        self.metrics.record_request(
                            ticks, secs, queue_secs=queue_s,
                            service_secs=service_s)
            self._refill()

        self.ticks += 1
        with self._phase_span("telemetry", lane):
            occupancy = self.pool.occupancy()
            self.metrics.record_tick(n_ops, occupancy,
                                     time.perf_counter() - t0)
            if tr.enabled:
                tr.counter("occupancy", occupancy)
                tr.counter("tick_ops", n_ops)
                for k, v in self._delete_in_place().items():
                    tr.counter("delete_in_place." + k, v)
        # throttled telemetry: a span only on the ticks that sample
        if self.metrics.chain_sample_due():
            with tr.span("sample", lane):
                self.metrics.force_chain_sample(self.backend.shard_list)
                if self._last_probe_keys is not None:
                    rows = self._rows_activated(self._last_probe_keys)
                    self.metrics.record_rows_activated(rows)
                    tr.counter("rows_activated", rows)
        if self.ticks % self.compact_every == 0:
            self.maybe_compact()
        if self._profiling and self.ticks >= self._profile_window[1]:
            self._stop_profiler()
        self._note_slow_tick(tick, time.perf_counter() - t0,
                             COMPILES.counts["compiles"] - compiles0,
                             COMPILES.counts["traces"] - traces0)
        return n_ops

    def _note_slow_tick(self, tick: int, secs: float, compiles: int,
                        traces: int):
        """Keep a tick that took over SLOW_TICK_FACTOR x the running median
        in ``slow_ticks``, with its phases and the compiles and jaxpr
        traces it ran; refresh the median every SLOW_TICK_REFRESH ticks."""
        if secs > SLOW_TICK_FACTOR * self._tick_p50 > 0.0:
            self.slow_ticks.append({
                "tick": tick, "ms": secs * 1e3,
                "phases_ms": {k: v * 1e3
                              for k, v in self._tick_phases.items()},
                "compiles": compiles, "traces": traces})
        if self.ticks % SLOW_TICK_REFRESH == 0:
            self._tick_p50 = self.metrics.tick_secs_h.percentile(50)

    def _gather(self):
        """One op per active slot, expanded into three global phase buffers
        (probe/delete/insert) — UNROUTED: shard partitioning happens inside
        the backend.  Entry shapes: probe/delete (key, writeback); insert
        (key, val, writeback).

        The gather rules are IDENTICAL with pipelining on or off (that's
        what makes pipelined results bit-equal): a batched delete
        tombstones only the FIRST chain match per key, so the first
        delete-phase op (delete/update/rmw) on a folded key claims it for
        the tick and later writers wait a tick.  The only pipelining
        byproduct is the ``conflict`` flag: True when any op touches an
        insert-phase key of a still-in-flight tick (the caller stalls the
        pipeline before issuing — module docstring).
        """
        probes: list = []
        deletes: list = []
        inserts: list = []
        fence: set = set()
        for r in self._inflight:
            fence |= r.claims
        conflict = False
        claimed: set = set()
        claims: set = set()
        hot: list = []
        n_ops = 0
        for _, req in self.pool.active():
            if req.done() or req.killed:
                continue
            op = req.ops[req.cursor]
            kind = op[0]
            if kind == "scan":
                touched = [self._fold(req, int(op[1]) + i)
                           for i in range(int(op[2]))]
            else:
                touched = [self._fold(req, op[1])]
            if fence and any(k in fence for k in touched):
                conflict = True
            if kind in ("delete", "update", "rmw"):
                if touched[0] in claimed:
                    # same-tick write contention: deferred to the next tick
                    self.tracer.instant("deferred_write",
                                        tid=self._cur_lane,
                                        key=touched[0], kind=kind)
                    continue
                claimed.add(touched[0])
            req.cursor += 1
            n_ops += 1
            hot.extend(touched)
            res = {"op": kind, "key": int(op[1])}
            req.results.append(res)
            if req.tenant is not None:
                req.tenant.stats["ops"][kind] += 1
            if self.record_schedule:
                self.schedule.append(
                    (self.ticks, kind, tuple(touched),
                     int(op[2]) if kind in ("insert", "update", "rmw")
                     else None, res))

            def wb(field, res=res):
                def set_(v):
                    res[field] = v
                return set_

            k = touched[0]
            if kind == "read":
                probes.append((k, self._wb_read(res, req)))
            elif kind == "scan":
                n = int(op[2])
                res.update(n=n, values=[0] * n, found=[False] * n)
                for i, ki in enumerate(touched):
                    probes.append((ki, self._wb_scan(res, req, i)))
            elif kind == "delete":
                deletes.append((k, wb("found")))
            elif kind == "insert":
                inserts.append((k, int(op[2]), wb("ok")))
                claims.add(k)
            elif kind == "update":
                deletes.append((k, wb("replaced")))
                inserts.append((k, int(op[2]), wb("ok")))
                claims.add(k)
            elif kind == "rmw":
                probes.append((k, self._wb_read(res, req, field="old")))
                deletes.append((k, wb("replaced")))
                inserts.append((k, int(op[2]), wb("ok")))
                claims.add(k)
            else:
                raise ValueError(f"unknown op kind {kind!r}")
        if hot:
            with self._phase_span("telemetry", self._cur_lane):
                self.metrics.record_hot_keys(hot)
        return probes, deletes, inserts, claims, conflict, n_ops

    def _issue(self, probes, deletes, inserts, claims) -> _TickRecord:
        """Issue the tick's phases in fixed order (probe -> delete ->
        insert).  Device calls are dispatched now; host materialization
        waits for drain.  On a fused-tick mesh engine all three phases go
        out in ONE rlu.tick_mesh launch."""
        phases = []
        if self.fused_tick and (probes or deletes or inserts):
            pk = np.asarray([k for k, _ in probes], np.uint32)
            dk = np.asarray([k for k, _ in deletes], np.uint32)
            ik = np.asarray([k for k, _, _ in inserts], np.uint32)
            iv = np.asarray([v for _, v, _ in inserts], np.uint32)
            with self._phase_span("fused_tick", self._cur_lane,
                                  probes=len(pk), deletes=len(dk),
                                  inserts=len(ik)):
                fp, fd, fi = self.backend.tick_fused(pk, dk, ik, iv)
            if probes:
                phases.append(_PhasePending("probe", probes, fp, pk))
            if deletes:
                phases.append(_PhasePending("delete", deletes, fd, dk))
            if inserts:
                phases.append(_PhasePending("insert", inserts, fi, ik, iv))
            return _TickRecord(self.ticks, list(self._shard_epochs), phases,
                               claims)
        if probes:
            phases.append(self._issue_phase("probe", probes))
        if deletes:
            phases.append(self._issue_phase("delete", deletes))
        if inserts:
            phases.append(self._issue_phase("insert", inserts))
        return _TickRecord(self.ticks, list(self._shard_epochs), phases,
                           claims)

    def _issue_phase(self, kind: str, entries: list) -> _PhasePending:
        with self._phase_span(kind, self._cur_lane, n=len(entries)):
            return self._issue_phase_inner(kind, entries)

    def _issue_phase_inner(self, kind: str, entries: list) -> _PhasePending:
        if kind == "insert":
            keys = np.asarray([k for k, _, _ in entries], np.uint32)
            vals = np.asarray([v for _, v, _ in entries], np.uint32)
        else:
            keys = np.asarray([k for k, _ in entries], np.uint32)
            vals = None
        if self.coalesce:
            if kind == "insert":
                fin = self.backend.insert(keys, vals)
            else:
                fin = getattr(self.backend, kind)(keys)
        else:
            # per-request baseline: one (unpadded) call per op
            fins = []
            for i in range(len(keys)):
                if kind == "insert":
                    fins.append(self.backend.insert(keys[i:i + 1],
                                                    vals[i:i + 1], pad=False))
                else:
                    fins.append(getattr(self.backend, kind)(keys[i:i + 1],
                                                            pad=False))
            fin = self._merge_finalizes(kind, fins)
        return _PhasePending(kind, entries, fin, keys, vals)

    @staticmethod
    def _merge_finalizes(kind: str, fins: list):
        def finalize():
            outs = [f() for f in fins]
            if kind == "probe":
                return (np.concatenate([o[0] for o in outs]),
                        np.concatenate([o[1] for o in outs]))
            if kind == "delete":
                found = np.concatenate([o[0] for o in outs])
                tombs: dict = {}
                for _, t in outs:
                    for s, c in t.items():
                        tombs[s] = tombs.get(s, 0) + c
                return found, tombs
            return np.concatenate(outs)
        return finalize

    def _drain_one(self):
        """Materialize the oldest in-flight tick: run writebacks, account
        hits/tombstones, and resolve deferred insert PR_ERRORs through the
        grow path.  Releases the tick's write claims."""
        rec = self._inflight.pop(0)
        with self._phase_span("writeback", rec.lane, tick=rec.tick):
            self._drain_phases(rec)
        self.tracer.end(rec.span)       # the tick's issue->drain span

    def _drain_phases(self, rec: _TickRecord):
        for ph in rec.phases:
            # the host blocks on the phase's device results here
            with self._phase_span("wait", rec.lane, phase=ph.kind):
                out = ph.finalize()
            if ph.kind == "probe":
                vals, found = out
                hits = 0
                for i, (_, fwb) in enumerate(ph.entries):
                    fwb((vals[i], found[i]))
                    hits += int(found[i])
                self.metrics.record_ops("read", len(ph.entries), hits)
            elif ph.kind == "delete":
                found, tombs = out
                for i, (_, fwb) in enumerate(ph.entries):
                    fwb(bool(found[i]))
                for s, c in tombs.items():
                    # a rebuild of THAT shard since issue reclaimed these
                    if rec.epochs[s] == self._shard_epochs[s]:
                        self._tombstones[s] += c
                self.metrics.record_ops("delete", len(ph.entries))
            else:
                ok = np.array(out)               # writable copy (grow repair)
                failed = np.nonzero(~ok)[0]
                if failed.size and self.backend.auto_grow:
                    # extendible repair is a per-shard LOCAL mutation ordered
                    # like this tick's own insert-phase writes (the fence
                    # already claims every key it can move), so it runs
                    # inline WITHOUT flushing the pipeline — hence its own
                    # span name, which grow-smoke forbids being "grow"
                    span = "split" if self.backend.resize_mode == \
                        "extendible" else "grow"
                    with self._phase_span(span, rec.lane,
                                          refused=int(failed.size)):
                        ok2, grown, events = self.backend.grow_insert(
                            ph.keys[failed], ph.vals[failed])
                    ok[failed] = ok2
                    self.split_events += events.get("splits", 0)
                    self.directory_doublings += events.get("doublings", 0)
                    if grown:
                        self.grow_events += 1
                        for s in grown:          # rebuilds drop tombstones
                            self._tombstones[s] = 0
                            self._shard_epochs[s] += 1
                for i, (_, _, fwb) in enumerate(ph.entries):
                    fwb(bool(ok[i]))
                self.metrics.record_ops("insert", len(ph.entries))

    def flush(self):
        """Drain every in-flight tick (pipeline barrier)."""
        while self._inflight:
            self._drain_one()

    def run(self, max_ticks: int = 100_000) -> dict:
        """Tick until all submitted work drains; returns a metrics snapshot."""
        while not self.pool.idle() and self.ticks < max_ticks:
            self.tick()
        self.flush()
        # short runs can finish inside one sample window: force a final
        # chain/rows-activated sample so the snapshot is never empty
        if not self.metrics.chain_samples:
            self.metrics.force_chain_sample(self.backend.shard_list)
        if self.metrics.rows_h.count == 0 \
                and self._last_probe_keys is not None:
            self.metrics.record_rows_activated(
                self._rows_activated(self._last_probe_keys))
        return self.metrics.snapshot()

    def _rows_activated(self, keys) -> float:
        """Mean DRAM-row activations per probe for ``keys`` against the
        live table(s): ``hashmap.rows_activated_per_probe`` per owning
        shard with the SAME bucket routing as the serving probe path (host
        shards hash the full key locally; the mesh path probes the rlu
        local bucket), weighted by per-shard key count."""
        keys = np.asarray(keys, np.uint32)
        if not keys.size:
            return 0.0
        shards = self.backend.shard_list()
        cfg = shards[0].config
        owner = rlu.owner_of_np(keys, cfg, self.num_shards, self.shard_by)
        total, n = 0.0, 0
        for s in range(self.num_shards):
            mine = jnp.asarray(keys[owner == s])
            if not mine.size:
                continue
            b = None
            if self.backend.is_mesh:
                _, b = rlu.owner_and_local_bucket(
                    mine, cfg, self.num_shards, self.shard_by)
            total += float(hashmap.rows_activated_per_probe(
                shards[s], mine, b=b)) * int(mine.size)
            n += int(mine.size)
        return total / n if n else 0.0

    # -- writeback closures ------------------------------------------------
    def _wb_read(self, res: dict, req: Request, field: str = "value"):
        def set_(vf):
            v, f = vf
            res[field] = int(v)
            res["found"] = bool(f)
            if req.tenant is not None:
                req.tenant.stats["hits" if f else "misses"] += 1
        return set_

    def _wb_scan(self, res: dict, req: Request, i: int):
        def set_(vf):
            v, f = vf
            res["values"][i] = int(v)
            res["found"][i] = bool(f)
            if req.tenant is not None:
                req.tenant.stats["hits" if f else "misses"] += 1
        return set_

    # -- maintenance -------------------------------------------------------
    def maybe_compact(self):
        """Engine-tick compaction: tombstone-fraction OR chain-length
        trigger per shard.  Runs on the tick clock, so long-running skewed
        tenants get compacted even when no request ever frees/deletes again
        after piling up tombstones.  Flushes the pipeline first (a rebuild
        invalidates in-flight tombstone accounting)."""
        self.flush()
        with self._phase_span("compact", self._cur_lane):
            compacted = self.backend.compact_shards(self._tombstones)
        for s in compacted:
            self._tombstones[s] = 0
            self._shard_epochs[s] += 1
            self.compact_events += 1

    # -- profiler window ---------------------------------------------------
    def profile_ticks(self, start_tick: int, stop_tick: int, logdir: str):
        """Arm a ``jax.profiler`` window: ``start_trace(logdir)`` fires when
        the engine reaches ``start_tick``, ``stop_trace`` once ``stop_tick``
        ticks have completed.  The host-side Tracer marks both edges with
        ``profiler_start``/``profiler_stop`` instants so the device profile
        can be aligned against the engine timeline."""
        assert stop_tick > start_tick >= 0
        self._profile_window = (start_tick, stop_tick, logdir)

    def _start_profiler(self):
        self._profiling = True
        logdir = self._profile_window[2]
        self.tracer.instant("profiler_start", tid=self._cur_lane,
                            tick=self.ticks, logdir=logdir)
        try:
            jax.profiler.start_trace(logdir)
        except Exception as e:             # profiler backend unavailable
            self.profiler_error = repr(e)
            self._profiling = False
            self._profile_window = None

    def _stop_profiler(self):
        self._profiling = False
        self._profile_window = None
        self.tracer.instant("profiler_stop", tid=self._cur_lane,
                            tick=self.ticks)
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            self.profiler_error = repr(e)

    # -- introspection -----------------------------------------------------
    def export_trace(self, path: str, **metadata) -> int:
        """Write the tracer's ring as Chrome trace-event JSON (annotated
        with the engine shape); returns the event count."""
        return self.tracer.export(path, ticks=self.ticks,
                                  pipeline_depth=self.pipeline_depth,
                                  num_shards=self.num_shards,
                                  fused_tick=self.fused_tick,
                                  stalls=self.stall_events, **metadata)

    def preload(self, keys, vals, tenant: Optional[Tenant] = None):
        """Bulk-load the table(s) outside the request path (YCSB load
        phase).  Growth during the load runs the same bookkeeping as the
        drain path (tombstone reset + rebuild epoch + grow_events)."""
        keys = np.asarray(keys, np.uint32)
        vals = np.asarray(vals, np.uint32)
        if tenant is not None:
            keys = self.tenants.fold(tenant.tid, keys)
        elif keys.size and int(keys.max()) >= int(PAD_KEY):
            raise ValueError(
                f"preload key {int(keys.max()):#x} collides with the "
                f"reserved pad/sentinel range [{int(PAD_KEY):#x}, "
                f"0xffffffff]")
        self.flush()
        with self._phase_span("preload", self._cur_lane, n=len(keys)):
            grown = self.backend.preload(keys, vals)
        if grown:
            self.grow_events += 1
            for s in grown:
                self._tombstones[s] = 0
                self._shard_epochs[s] += 1

    def _delete_in_place(self) -> dict:
        """Delete calls given a donated pool (every host-shard delete; the
        mesh path donates nothing), and the process's compiled delete
        programs that copied the pool all the same."""
        return {"calls": 0 if self.backend.is_mesh
                else self.batch_calls["delete"],
                "not_aliased": self._jit_delete.not_aliased}

    def stats(self) -> dict:
        return {
            "ticks": self.ticks,
            "batch_calls": dict(self.batch_calls),
            "grow_events": self.grow_events,
            "split_events": self.split_events,
            "directory_doublings": self.directory_doublings,
            "resize": self.backend.resize_mode,
            "compact_events": self.compact_events,
            "killed_requests": self.killed_requests,
            "profiler_error": self.profiler_error,
            "occupancy": self.pool.occupancy(),
            "pending": len(self.pool.pending),
            "pipeline": {"depth": self.pipeline_depth,
                         "inflight": len(self._inflight),
                         "stalls": self.stall_events},
            "mesh_backed": self.backend.is_mesh,
            "fused_tick": self.fused_tick,
            "route_caps": list(self.route_cap_log)[-8:],
            "route_cap_totals": dict(self.route_cap_totals),
            "trace": {"enabled": self.tracer.enabled,
                      "recorded": self.tracer._recorded,
                      "dropped": self.tracer.dropped},
            "compiles": {k: v - self._compiles0[k]
                         for k, v in COMPILES.counts.items()},
            "delete_in_place": self._delete_in_place(),
            "slow_ticks": list(self.slow_ticks),
            "chain_depth": {
                "p50": self.metrics.chain_samples[-1]["chain_p50"]
                if self.metrics.chain_samples else 0.0,
                "p99": self.metrics.chain_samples[-1]["chain_p99"]
                if self.metrics.chain_samples else 0.0,
            },
            "rows_activated": {
                "p50": self.metrics.rows_h.percentile(50),
                "p99": self.metrics.rows_h.percentile(99),
            },
            "tenants": self.tenants.stats(),
            "shards": [
                {k: v for k, v in hashmap.stats(hm).items()
                 if k != "chain_lengths"}
                for hm in self.backend.shard_list()],
        }
