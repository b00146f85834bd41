"""The plain reference that decides ``correct``, and the data it starts from.

The table a run serves holds, for every record, a value that is a pure
function of the folded key and the run's seed (:func:`values_of`).  The
benchmark builds the table from it on the device and the reference
recomputes it on the host; the reference imports nothing of the program.

:class:`HostReference` is the model of ``chip_smoke.py``'s host check,
copied here: a key holds a FIFO list of its values, oldest first (the table
keeps duplicates and answers with the oldest); within a tick every probe
sees the table as of the tick start, then deletes run, then inserts.  It
replays the engine's executed-op log (``record_schedule``) and compares
every answer.

Two controls stand in for a program that breaks one stated guarantee, and
must come out as not correct: :func:`stale_answers` makes writes visible
one tick late, :func:`short_key_answers` matches keys on their low 16
bits.
"""
from __future__ import annotations

import numpy as np

READS = ("read", "rmw", "scan")


def seed_words(seed: int) -> tuple:
    """Two 32-bit words from a seed of any size."""
    w = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return int(w[0]), int(w[1])


def values_of(keys, words: tuple, xp=np):
    """The preloaded value of each folded key: a murmur3 finalizer of the
    key under the seed's words.  ``xp`` is ``numpy`` or ``jax.numpy``; both
    compute the same uint32s."""
    u = xp.uint32
    h = keys.astype(u) ^ u(words[0])
    h = h ^ (h >> u(16))
    h = h * u(0x85EBCA6B)
    h = h ^ (h >> u(13))
    h = h * u(0xC2B2AE35)
    h = h ^ (h >> u(16))
    return h ^ u(words[1])


class Keyspace:
    """Which folded keys the table was built with: ``tenants`` blocks of
    ``records`` keys, tenant t's raw key k folded to ``t << key_bits | k``
    (``key_bits`` 32: one block of raw keys)."""

    def __init__(self, tenants: int, records: int, key_bits: int):
        self.tenants, self.records, self.key_bits = tenants, records, key_bits

    @property
    def size(self) -> int:
        return self.tenants * self.records

    def preloaded(self, keys: np.ndarray) -> np.ndarray:
        keys = keys.astype(np.uint64)
        t = keys >> np.uint64(self.key_bits)
        k = keys & np.uint64((1 << self.key_bits) - 1)
        return (t < self.tenants) & (k < self.records)

    def build_order(self, keys: np.ndarray) -> np.ndarray:
        """Position of each preloaded key in the build's input: the table's
        chain order among keys of one bucket."""
        keys = keys.astype(np.int64)
        t, k = keys >> self.key_bits, keys & ((1 << self.key_bits) - 1)
        return t * self.records + k


def ops_by_tick(schedule) -> dict:
    """{tick: [(kind, keys, val, res), ...]} in gather order."""
    out: dict = {}
    for tick, kind, keys, val, res in schedule:
        if kind not in ("read", "update", "rmw", "insert", "delete", "scan"):
            raise ValueError(f"no reference for op {kind!r}")
        out.setdefault(tick, []).append((kind, keys, val, res))
    return out


class HostReference:
    """Expected answers for a schedule, and their comparison with the
    answers given."""

    def __init__(self, space: Keyspace, words: tuple):
        self.space = space
        self.words = words
        self.entries: dict = {}

    def _load(self, by_tick: dict):
        keys = {k for ops in by_tick.values() for _, ks, _, _ in ops
                for k in ks}
        arr = np.fromiter(keys, np.uint32, len(keys))
        vals = values_of(arr, self.words)
        live = self.space.preloaded(arr)
        self.entries = {int(k): ([int(v)] if p else [])
                        for k, v, p in zip(arr, vals, live)}

    def check(self, schedule, window_end_tick: int = -1) -> dict:
        """Replay ``schedule`` and compare every answer.  Returns the ops
        checked, the ops with a wrong or missing answer, the first few of
        them, and the live pair count after ``window_end_tick``."""
        by_tick = ops_by_tick(schedule)
        self._load(by_tick)
        out = {"ops": 0, "wrong": 0, "first": [], "live_at_end": None}
        live = self.space.size
        for tick in sorted(by_tick):
            if out["live_at_end"] is None and tick > window_end_tick >= 0:
                out["live_at_end"] = live
            bad, delta = self._tick(by_tick[tick])
            live += delta
            out["ops"] += len(by_tick[tick])
            out["wrong"] += len(bad)
            out["first"].extend((tick,) + b
                                for b in bad[:max(0, 5 - len(out["first"]))])
        if out["live_at_end"] is None:
            out["live_at_end"] = live
        return out

    def _tick(self, ops: list) -> tuple:
        """One tick in the engine's phase order; returns the wrong ops and
        the change in live pairs."""
        bad = set()
        e = self.entries
        for i, (kind, keys, _, res) in enumerate(ops):
            if kind in READS:
                field = "old" if kind == "rmw" else "value"
                for j, k in enumerate(keys):
                    got = (res.get("found"), res.get(field)) if kind != "scan" \
                        else (res["found"][j], res["values"][j])
                    if not self._read_ok(k, got):
                        bad.add(i)
        delta = 0
        for i, (kind, keys, _, res) in enumerate(ops):
            if kind in ("delete", "update", "rmw"):
                field = "found" if kind == "delete" else "replaced"
                have = e[keys[0]]
                if res.get(field) is not bool(have):
                    bad.add(i)
                if have:
                    have.pop(0)
                    delta -= 1
        for i, (kind, keys, val, res) in enumerate(ops):
            if kind in ("insert", "update", "rmw"):
                if res.get("ok") is not True:
                    bad.add(i)
                else:
                    e[keys[0]].append(val)
                    delta += 1
        return [(ops[i][0], ops[i][1], ops[i][3]) for i in sorted(bad)], delta

    def _read_ok(self, key: int, got: tuple) -> bool:
        have = self.entries[key]
        return got[0] is True and got[1] == have[0] if have \
            else got[0] is False


def stale_answers(space: Keyspace, words: tuple, schedule) -> list:
    """Control: the answers of a table whose writes become visible one tick
    late, against the guarantee that an acknowledged write is read back
    from the next tick on.  Returns a copy of ``schedule`` with them."""
    ref = HostReference(space, words)
    by_tick = ops_by_tick(schedule)
    ref._load(by_tick)
    e = ref.entries
    out, late = [], []
    for tick in sorted(by_tick):
        ops = by_tick[tick]
        answers = []
        for kind, keys, val, _ in ops:
            res = {"op": kind}
            if kind == "scan":
                res["values"] = [e[k][0] if e[k] else 0 for k in keys]
                res["found"] = [bool(e[k]) for k in keys]
            elif kind in ("read", "rmw"):
                have = e[keys[0]]
                res["old" if kind == "rmw" else "value"] = have[0] if have else 0
                res["found"] = bool(have)
            if kind in ("delete", "update", "rmw"):
                res["found" if kind == "delete" else "replaced"] = \
                    bool(e[keys[0]])
            if kind in ("insert", "update", "rmw"):
                res["ok"] = True
            answers.append(res)
        for k, op in late:                    # last tick's writes land now
            if op == "pop":
                if e[k]:
                    e[k].pop(0)
            else:
                e[k].append(op)
        late = []
        for kind, keys, val, _ in ops:
            if kind in ("delete", "update", "rmw"):
                late.append((keys[0], "pop"))
            if kind in ("insert", "update", "rmw"):
                late.append((keys[0], val))
        out.extend((tick, kind, keys, val, res)
                   for (kind, keys, val, _), res in zip(ops, answers))
    return out


def short_key_answers(space: Keyspace, words: tuple, schedule, *,
                      num_buckets: int, salt: int, bits: int = 16) -> list:
    """Control: the answers of a table that compares keys on their low
    ``bits`` bits, against the guarantee of an exact 32-bit key match.  A
    read then answers with the first entry of its bucket's chain whose key
    agrees in those bits.  For read-only schedules over the preloaded
    table; returns a copy of ``schedule`` with the answers."""
    from perfbench.roofline import bucket_of
    if any(kind not in ("read", "scan") for _, kind, _, _, _ in schedule):
        raise ValueError("the short-key control covers read-only schedules")
    reads = sorted({k for _, _, keys, _, _ in schedule for k in keys})
    step = 1 << bits
    answer: dict = {}
    m = np.arange(1, (reads[-1] if reads else 0) // step + 2, dtype=np.int64)
    for lo in range(0, len(reads), 4096):
        q = np.asarray(reads[lo:lo + 4096], np.int64)
        cand = q[:, None] - step * m[None, :]            # same low bits
        ok = cand >= 0
        cu = np.where(ok, cand, 0).astype(np.uint32)
        ok &= space.preloaded(cu)
        ok &= bucket_of(cu, num_buckets, salt) == \
            bucket_of(q.astype(np.uint32), num_buckets, salt)[:, None]
        order = np.where(ok, space.build_order(cu), np.iinfo(np.int64).max)
        first = np.argmin(order, axis=1)
        hit = ok[np.arange(len(q)), first]
        src = np.where(hit, cand[np.arange(len(q)), first], q)
        for k, s in zip(q.tolist(), src.tolist()):
            answer[k] = s
    srcs = np.asarray(list(answer.values()), np.uint32)
    vals = dict(zip(answer, values_of(srcs, words).tolist()))
    live = dict(zip(answer, space.preloaded(srcs).tolist()))
    out = []
    for tick, kind, keys, val, _ in schedule:
        res = {"op": kind}
        if kind == "scan":
            res["values"] = [vals[k] if live[k] else 0 for k in keys]
            res["found"] = [bool(live[k]) for k in keys]
        else:
            res["value"] = vals[keys[0]] if live[keys[0]] else 0
            res["found"] = bool(live[keys[0]])
        out.append((tick, kind, keys, val, res))
    return out
