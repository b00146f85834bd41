"""Routing capacities from a ladder: the fused mesh tick's per-(src,dst)
capacity is the power of two at or above the measured max (floored at
min(8, Q_local), capped at Q_local), so the mesh engine can compile every
program a stream can need before it serves, and no tick compiles after.

The ladder itself is checked here on the host; the engine runs in a
subprocess on four forced CPU devices (the pattern of
tests/test_serving_sharded.py)."""
import numpy as np
import pytest

from repro.configs.base import HashMemConfig
from repro.core import rlu
from test_serving_sharded import run_sub

CFG = HashMemConfig(num_buckets=128, slots_per_page=64, overflow_pages=256,
                    max_chain=8, backend="ref")


@pytest.mark.parametrize("q_local", [1, 3, 4, 7, 8, 12, 64, 256, 512])
def test_ladder_cap_covers_the_max_with_a_power_of_two(q_local):
    ladder = rlu.cap_ladder(q_local)
    assert list(ladder) == sorted(set(ladder)) and ladder[-1] == q_local
    for mx in range(q_local + 1):
        cap = rlu.ladder_cap(mx, q_local)
        assert mx <= cap <= q_local
        assert cap == q_local or cap & (cap - 1) == 0
        assert cap >= min(8, q_local)
        assert cap in ladder
    # every step of the ladder is reached by some max
    assert {rlu.ladder_cap(mx, q_local) for mx in range(q_local + 1)} \
        == set(ladder)


def _zipf_batch(rng, n, q, records=1 << 20, theta=0.99):
    """``n`` keys drawn zipfian over ``records`` ids (hot ranks scattered
    over the id space), padded with ROUTE_PAD to ``q``."""
    ranks = np.minimum(rng.zipf(1 + theta, n), records) - 1
    keys = ((ranks * 2654435761) % records).astype(np.uint32)
    out = np.full(q, rlu.ROUTE_PAD, np.uint32)
    out[:n] = keys
    return out


@pytest.mark.parametrize("shards", [2, 4])
def test_routing_cap_is_the_ladder_step_of_the_exact_max(shards):
    """On zipfian batches of every fill: the default capacity is the
    ladder step of the exact max that quantum=1 reports, never below it,
    and always one of the ladder's values for the batch's Q_local."""
    rng = np.random.default_rng(16)
    q = 1024
    ladder = rlu.cap_ladder(q // shards)
    seen = set()
    for n in list(range(0, q + 1, 37)) + [q]:
        batch = _zipf_batch(rng, n, q)
        exact = rlu.routing_cap(batch, CFG, shards, "highbits", quantum=1)
        mx = rlu.route_max(batch, CFG, shards, "highbits")
        assert exact == max(mx, 1)
        cap = rlu.routing_cap(batch, CFG, shards, "highbits")
        assert cap == rlu.ladder_cap(mx, q // shards) >= mx
        assert cap in ladder
        seen.add(cap)
    assert len(seen) >= 3, seen


def test_engine_compiles_the_ladder_before_serving_and_never_after():
    """A seeded zipfian YCSB B stream (95% reads, 5% updates) through the
    fused tick of a 4-shard mesh engine: the engine compiles every
    (probe cap, write cap) pair of the ladder when it is built, 200+ ticks
    after a 64-tick warm-up compile nothing, every tick's capacities are
    among the programs compiled before serving, and every answer equals
    the DictModel's."""
    run_sub("""
        import numpy as np
        from model import DictModel, replay_schedule_against_model
        from test_route_ladder import CFG, _zipf_batch
        from repro.core import rlu
        from repro.launch.mesh import make_serving_mesh
        from repro.serving import Request, ServingEngine
        from repro.serving.metrics import MetricsCollector
        from repro.serving.tracing import COMPILES
        D, slots, records = 4, 64, 16384
        mesh = make_serving_mesh(D)
        eng = ServingEngine(
            CFG, mesh=mesh, max_slots=slots, record_schedule=True,
            metrics=MetricsCollector(chain_sample_every=1 << 62))
        ladder = rlu.cap_ladder(eng.backend.tick_len // D)
        assert eng.backend.tick_len == slots and ladder == (8, 16)
        programs = set(eng.backend._ticks)
        assert {caps for _, _, caps in programs} == \\
            {(p, w, w) for p in ladder for w in ladder}
        tot = eng.stats()["route_cap_totals"]
        assert tot["tick_programs_before"] == len(ladder) ** 2 == 4
        keys = np.arange(records, dtype=np.uint32)
        vals = keys * 3 + 1
        eng.preload(keys, vals)
        rng = np.random.default_rng(7)
        reqs = []
        for _ in range(slots * 60):
            ks = _zipf_batch(rng, 4, 4, records)
            ops = [("update", int(k), int(rng.integers(1, 1 << 30)))
                   if rng.random() < 0.05 else ("read", int(k)) for k in ks]
            reqs.append(Request(ops=ops))
        eng.submit_all(reqs)
        for _ in range(64):
            eng.tick()
        c0, t0 = COMPILES.counts["compiles"], eng.ticks
        while not eng.pool.idle():
            eng.tick()
        eng.flush()
        assert eng.ticks - t0 >= 200, eng.ticks - t0
        assert COMPILES.counts["compiles"] == c0, "a tick compiled"
        assert set(eng.backend._ticks) == programs
        tot = eng.stats()["route_cap_totals"]
        assert tot["tick_programs_after"] == 0
        assert eng.grow_events == eng.compact_events == 0
        caps = {tuple(r["cap"]) for r in eng.route_cap_log}
        assert len(caps) >= 2 and caps <= {c for _, _, c in programs}, caps
        for r in eng.route_cap_log:
            for mx, cap in zip(r["max"], r["cap"]):
                assert mx <= cap
        # rows: every chip's send buffer, D x cap a phase; keys: real ones
        n = {k: 0 for k in ("read", "update")}
        for _, kind, _, _, _ in eng.schedule:
            n[kind] += 1
        assert tot["probe_keys"] == n["read"]
        assert tot["delete_keys"] == tot["insert_keys"] == n["update"]
        assert tot["probe_rows"] == D * D * sum(
            r["cap"][0] for r in eng.route_cap_log)
        model = DictModel()
        model.insert(keys, vals, np.ones(records, bool))
        replay_schedule_against_model(eng.schedule, model)
        assert all(r.done() for r in reqs)
        print("OK", eng.ticks, sorted(caps))
        """, devices=4)
