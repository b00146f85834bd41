"""Trace-correctness tests (ISSUE 9): the exported Chrome trace-event JSON
is structurally valid (B/E pairs balance, timestamps monotonic per track),
spans nest, pipelined traces show OVERLAPPING tick spans on distinct lane
tracks while the op->tick schedule stays identical to the unpipelined
engine, a killed request emits its abort exactly once, and the ring bound
keeps tracer memory O(1).
"""
import json
import sys
from collections import defaultdict
from pathlib import Path

import numpy as np
import pytest

from repro.serving import Request, ServingEngine, Tracer
from repro.serving.tracing import NULL_TRACER, SPAN_NAMES

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from tools import trace_report  # noqa: E402


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def validate_events(events):
    """B/E balance + per-track ts monotonicity; returns completed spans as
    (name, tid, ts, dur) and asserts validity."""
    spans, _, problems = trace_report.validate(events)
    assert not problems, problems
    return spans


def run_engine(depth, trace=True, n_reqs=24, seed=3):
    rng = np.random.default_rng(seed)
    eng = ServingEngine(num_shards=2, max_slots=8, pipeline_depth=depth,
                        trace=trace, record_schedule=True)
    eng.preload(np.arange(64, dtype=np.uint32),
                np.arange(64, dtype=np.uint32))
    reqs = []
    for _ in range(n_reqs):
        k = int(rng.integers(0, 64))
        reqs.append(Request(ops=[("read", k), ("update", k, k + 1),
                                 ("read", k)]))
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng, reqs


# ---------------------------------------------------------------------------
# Tracer unit behavior
# ---------------------------------------------------------------------------

def test_export_is_valid_chrome_trace(tmp_path):
    tr = Tracer()
    with tr.span("tick", tid=0, tick=0):
        with tr.span("gather", tid=0):
            pass
        with tr.span("writeback", tid=0):
            pass
    tr.counter("occupancy", 3)
    tr.instant("kill", rid=7)
    tr.async_begin("request", 1)
    tr.async_end("request", 1)
    path = tmp_path / "t.json"
    n = tr.export(str(path), note="unit")
    doc = json.loads(path.read_text())
    assert n == len(doc["traceEvents"])
    assert doc["otherData"]["note"] == "unit"
    assert doc["otherData"]["dropped"] == 0
    evs = doc["traceEvents"]
    validate_events(evs)
    phases = {e["ph"] for e in evs}
    assert {"B", "E", "C", "i", "b", "e", "M"} <= phases
    # global ts ordering (stable sort by ts)
    ts = [e["ts"] for e in evs if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_spans_nest_and_children_stay_inside_parent():
    tr = Tracer()
    outer = tr.begin("tick", 0)
    with tr.span("gather", 0):
        pass
    tr.end(outer)
    evs = [e for e in tr.to_events() if e["ph"] in "BE"]
    # nesting order on the single track: B tick, B gather, E gather, E tick
    assert [(e["ph"], e["name"]) for e in evs] == \
        [("B", "tick"), ("B", "gather"), ("E", "gather"), ("E", "tick")]


def test_ring_bound_and_dropped_counter():
    tr = Tracer(capacity=16)
    for i in range(100):
        tr.counter("tick_ops", i)
    assert len(tr) == 16
    assert tr.dropped == 84
    evs = tr.to_events()
    vals = [e["args"]["value"] for e in evs if e["ph"] == "C"]
    assert vals == [float(v) for v in range(84, 100)]  # newest survive


def test_ring_drops_never_unbalance_export():
    # spans are recorded as COMPLETED tuples, so dropping the oldest ring
    # entries can never orphan a B without its E
    tr = Tracer(capacity=8)
    for i in range(50):
        with tr.span("tick", tid=i % 3, tick=i):
            with tr.span("gather", tid=i % 3):
                pass
    validate_events(tr.to_events())


def test_unmatched_async_half_is_not_exported():
    tr = Tracer()
    tr.async_begin("request", 1)       # never ends (request still queued)
    tr.async_begin("request", 2)
    tr.async_end("request", 2)
    evs = tr.to_events()
    asy = [e for e in evs if e["ph"] in ("b", "e")]
    assert len(asy) == 2
    assert all(e["id"] == 2 for e in asy)


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("tick"):
        tr.counter("occupancy", 1)
        tr.instant("kill")
        tr.async_begin("request", 1)
        tr.async_end("request", 1)
    assert len(tr) == 0 and tr.dropped == 0
    assert NULL_TRACER.to_events() == []


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def test_engine_trace_valid_and_has_span_vocabulary(tmp_path):
    eng, _ = run_engine(depth=1)
    path = tmp_path / "eng.json"
    eng.export_trace(str(path))
    doc = json.loads(path.read_text())
    spans = validate_events(doc["traceEvents"])
    seen = {s[0] for s in spans}
    # the core per-tick vocabulary must appear on a host-shard run
    assert {"tick", "gather", "probe", "writeback", "admit",
            "preload"} <= seen
    assert seen <= set(SPAN_NAMES)
    assert doc["otherData"]["pipeline_depth"] == 1


def test_phase_spans_nest_inside_their_tick():
    eng, _ = run_engine(depth=1)
    spans = validate_events(eng.tracer.to_events())
    ticks = [(s[2], s[2] + s[3]) for s in spans if s[0] == "tick"]
    for name, tid, ts, dur, *_ in spans:
        if name in ("gather", "probe", "delete", "insert"):
            assert any(lo <= ts and ts + dur <= hi + 1e-3
                       for lo, hi in ticks), name


def test_pipelined_ticks_overlap_and_schedule_matches_unpipelined():
    eng1, _ = run_engine(depth=1)
    eng2, _ = run_engine(depth=2)
    eng3, _ = run_engine(depth=3)
    # identical op->tick schedules (pipelining must not change behavior)
    strip = [(t, k, keys, v) for t, k, keys, v, _ in eng1.schedule]
    for e in (eng2, eng3):
        assert [(t, k, keys, v) for t, k, keys, v, _ in e.schedule] == strip
    for eng in (eng2, eng3):
        spans = validate_events(eng.tracer.to_events())
        ticks = [s for s in spans if s[0] == "tick"]
        lanes = {s[1] for s in ticks}
        assert len(lanes) == eng.pipeline_depth      # one track per lane
        # at least one pair of tick spans overlaps in wall time (tick N+1
        # issued while tick N is still in flight on another lane)
        ivs = sorted((s[2], s[2] + s[3], s[1]) for s in ticks)
        overlaps = sum(1 for a, b in zip(ivs, ivs[1:])
                       if b[0] < a[1] and a[2] != b[2])
        assert overlaps >= 1, "no overlapping tick spans at depth>=2"


def test_stall_visible_in_pipelined_trace():
    # read-your-writes on a single hot key forces the write-claim fence
    eng = ServingEngine(num_shards=2, max_slots=4, pipeline_depth=2,
                        trace=True)
    for _ in range(6):
        eng.submit(Request(ops=[("update", 1, 9), ("read", 1),
                                ("update", 1, 10)]))
    eng.run()
    assert eng.stall_events >= 1
    spans = validate_events(eng.tracer.to_events())
    stalls = [s for s in spans if s[0] == "pipeline_stall"]
    assert len(stalls) == eng.stall_events


def test_killed_request_emits_abort_exactly_once():
    eng = ServingEngine(num_shards=1, max_slots=2, trace=True)
    live = Request(ops=[("read", 1)] * 6)
    victim = Request(ops=[("read", 2)] * 6)
    eng.submit(live)
    eng.submit(victim)
    eng.tick()
    assert eng.kill(victim)
    assert not eng.kill(victim)        # second kill is a no-op
    eng.run()
    evs = eng.tracer.to_events()
    kills = [e for e in evs if e["ph"] == "i" and e["name"] == "kill"]
    assert len(kills) == 1
    assert kills[0]["args"]["rid"] == victim.rid
    # the killed request's async lifecycle closed exactly once, with the
    # terminal status
    ends = [e for e in evs if e["ph"] == "e" and e["name"] == "request"
            and e["id"] == victim.rid]
    assert len(ends) == 1
    assert ends[0]["args"]["status"] == "killed"


def test_request_lifecycle_slices_balance():
    eng, reqs = run_engine(depth=2)
    evs = eng.tracer.to_events()
    per = defaultdict(lambda: defaultdict(int))
    for e in evs:
        if e["ph"] in ("b", "e"):
            per[(e["name"], e["id"])][e["ph"]] += 1
    for key, c in per.items():
        assert c["b"] == 1 and c["e"] == 1, (key, dict(c))
    # every completed request exported its request+queue+service slices
    names = defaultdict(set)
    for (name, rid), _ in per.items():
        names[rid].add(name)
    done = [r.rid for r in reqs if r.done()]
    assert done and all(names[rid] == {"request", "queue", "service"}
                        for rid in done)


def test_counter_tracks_emitted_per_tick():
    eng, _ = run_engine(depth=1)
    evs = eng.tracer.to_events()
    occ = [e for e in evs if e["ph"] == "C" and e["name"] == "occupancy"]
    ops = [e for e in evs if e["ph"] == "C" and e["name"] == "tick_ops"]
    assert len(occ) == eng.ticks and len(ops) == eng.ticks


def test_untraced_engine_matches_traced_results():
    eng_t, reqs_t = run_engine(depth=2, trace=True)
    eng_u, reqs_u = run_engine(depth=2, trace=False)
    assert [r.results for r in reqs_t] == [r.results for r in reqs_u]
    assert len(eng_u.tracer) == 0      # NULL_TRACER recorded nothing
    assert eng_u.tracer is NULL_TRACER


def test_tracer_instance_can_be_shared():
    tr = Tracer()
    eng = ServingEngine(num_shards=1, max_slots=2, trace=tr)
    assert eng.tracer is tr
    eng.submit(Request(ops=[("insert", 5, 6), ("read", 5)]))
    eng.run()
    assert len(tr) > 0


# ---------------------------------------------------------------------------
# trace_report CLI
# ---------------------------------------------------------------------------

def test_trace_report_cli_ok(tmp_path, capsys):
    eng, _ = run_engine(depth=2)
    path = tmp_path / "r.json"
    eng.export_trace(str(path))
    rc = trace_report.main([str(path), "--assert-spans",
                            "tick,gather,writeback,admit"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "per-phase breakdown" in out
    assert "slowest" in out
    assert "trace OK" in out


def test_trace_report_cli_fails_on_missing_span_or_stalls(tmp_path, capsys):
    tr = Tracer()
    with tr.span("tick", tick=0):
        pass
    path = tmp_path / "bare.json"
    tr.export(str(path))
    assert trace_report.main([str(path), "--assert-spans", "fused_tick"]) == 1
    assert trace_report.main([str(path), "--assert-stalls", "1"]) == 1
    assert trace_report.main([str(path)]) == 0
    capsys.readouterr()


def test_trace_report_flags_malformed_trace(tmp_path, capsys):
    bad = {"traceEvents": [
        {"name": "tick", "ph": "B", "pid": 1, "tid": 0, "ts": 10.0},
        {"name": "gather", "ph": "E", "pid": 1, "tid": 0, "ts": 12.0},
        {"name": "x", "ph": "B", "pid": 1, "tid": 1, "ts": 5.0},
    ]}
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(bad))
    assert trace_report.main([str(path)]) == 1
    out = capsys.readouterr().out
    assert "interleaved B/E" in out
    assert "unclosed B" in out


# ---------------------------------------------------------------------------
# profiler window hooks
# ---------------------------------------------------------------------------

def test_profiler_window_brackets_ticks(tmp_path, monkeypatch):
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    eng, _ = None, None
    eng = ServingEngine(num_shards=1, max_slots=4, trace=True)
    eng.profile_ticks(1, 3, str(tmp_path))
    for _ in range(8):
        eng.submit(Request(ops=[("insert", 3, 4), ("read", 3)]))
    eng.run()
    assert calls == [("start", str(tmp_path)), ("stop", None)]
    evs = eng.tracer.to_events()
    marks = [e["name"] for e in evs if e["ph"] == "i"
             and e["name"].startswith("profiler_")]
    assert marks == ["profiler_start", "profiler_stop"]


def test_profiler_backend_failure_is_survivable(tmp_path, monkeypatch):
    import jax

    def boom(_):
        raise RuntimeError("no profiler backend")
    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    eng = ServingEngine(num_shards=1, max_slots=4, trace=True)
    eng.profile_ticks(0, 1, str(tmp_path))
    eng.submit(Request(ops=[("insert", 3, 4), ("read", 3)]))
    eng.run()                           # must not raise
    assert eng._profiling is False
    # ... but the failure is not swallowed: the caller sees it
    assert "no profiler backend" in eng.profiler_error
    assert eng.stats()["profiler_error"] == eng.profiler_error


# ---------------------------------------------------------------------------
# bounded engine telemetry (satellite: route_cap_log ring)
# ---------------------------------------------------------------------------

def test_route_cap_log_is_bounded():
    from repro.serving.engine import ROUTE_CAP_LOG_MAX
    eng = ServingEngine(num_shards=1, max_slots=2)
    for i in range(ROUTE_CAP_LOG_MAX + 50):
        eng._record_route_caps([1], [1], [1])
    assert len(eng.route_cap_log) == ROUTE_CAP_LOG_MAX
    assert eng.route_cap_totals["launches"] == ROUTE_CAP_LOG_MAX + 50
    assert len(eng.stats()["route_caps"]) == 8


def test_tenant_queue_service_split_accumulates():
    from repro.serving import TenantRegistry
    reg = TenantRegistry()
    t = reg.register("a")
    eng = ServingEngine(num_shards=1, max_slots=2, tenants=reg)
    for _ in range(3):
        eng.submit(Request(ops=[("insert", 1, 2), ("read", 1)], tenant=t))
    eng.run()
    assert t.stats["completed"] == 3
    assert t.stats["queue_secs"] >= 0.0
    assert t.stats["service_secs"] > 0.0
    snap = eng.metrics.snapshot()
    assert snap["service_ms"]["p50"] > 0.0


# ---------------------------------------------------------------------------
# profiler annotations, compile counter, slow-tick record
# ---------------------------------------------------------------------------

def _count_annotations(monkeypatch, session=True):
    """Stand in for ``jax.profiler.TraceAnnotation``, with a profiler
    session running or not: the log counts what the tracer opens and
    closes, by name."""
    from repro.serving import tracing
    log: list = []

    class CountingAnnotation:
        def __init__(self, name):
            self.name = name
            log.append(("new", name))

        @staticmethod
        def is_enabled():
            return session

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(tracing, "TraceAnnotation", CountingAnnotation)
    return log


def test_disabled_tracer_makes_no_annotation(monkeypatch):
    log = _count_annotations(monkeypatch)
    run_engine(depth=2, trace=False)
    tr = Tracer(enabled=False)
    with tr.span("tick"):
        tr.end(tr.begin("gather"))
    assert log == []


def test_enabled_tracer_outside_a_profiler_session_makes_no_annotation(
        monkeypatch):
    log = _count_annotations(monkeypatch, session=False)
    eng, _ = run_engine(depth=2)
    assert any(s[0] == "submit" for s in
               validate_events(eng.tracer.to_events()))
    assert log == []


def test_enabled_tracer_annotates_each_span_once(monkeypatch):
    log = _count_annotations(monkeypatch)
    eng, _ = run_engine(depth=2)
    ring = defaultdict(int)
    for name, *_ in validate_events(eng.tracer.to_events()):
        if name != "compile":          # recorded after the fact: ring only
            ring["hashmem/" + name] += 1
    for what in ("new", "enter", "exit"):
        got = defaultdict(int)
        for w, name in log:
            if w == what:
                got[name] += 1
        assert got == ring, what
    assert {"hashmem/submit", "hashmem/wait",
            "hashmem/telemetry"} <= set(ring)


def test_sample_span_only_on_sampled_ticks(monkeypatch):
    from repro.serving.metrics import MetricsCollector
    log = _count_annotations(monkeypatch)
    eng = ServingEngine(num_shards=1, max_slots=4, trace=True,
                        metrics=MetricsCollector(chain_sample_every=3))
    eng.preload(np.arange(8, dtype=np.uint32), np.arange(8, dtype=np.uint32))
    eng.submit_all([Request(ops=[("read", k % 8)] * 4) for k in range(8)])
    while eng.ticks < 9:
        eng.tick()
    spans = validate_events(eng.tracer.to_events())
    assert sum(s[0] == "sample" for s in spans) == 3
    assert log.count(("enter", "hashmem/sample")) == \
        log.count(("exit", "hashmem/sample")) == 3


def test_compile_counter_rises_on_new_shape_not_on_cache_hit():
    from repro.serving import tracing
    from repro.serving.metrics import MetricsCollector
    eng = ServingEngine(num_shards=1, max_slots=4, trace=True,
                        metrics=MetricsCollector(chain_sample_every=1 << 30))
    eng.preload(np.arange(64, dtype=np.uint32),
                np.arange(64, dtype=np.uint32))

    def serve(ops):
        eng.submit_all([Request(ops=list(ops)) for _ in range(4)])
        eng.run()
        return eng.stats()["compiles"]

    serve([("read", 1), ("update", 2, 3)])
    warm = serve([("read", 1), ("update", 2, 3)])
    assert serve([("read", 5), ("update", 6, 7)]) == warm   # cache hits
    n_compile = sum(s[0] == "compile" for s in
                    validate_events(eng.tracer.to_events()))
    # a 40-key scan makes a probe batch of 64, a shape not yet compiled
    grown = serve([("scan", 0, 40)])
    assert grown["compiles"] > warm["compiles"]
    assert grown["compile_s"] > warm["compile_s"]
    spans = validate_events(eng.tracer.to_events())
    assert sum(s[0] == "compile" for s in spans) > n_compile
    assert all(s[1] == tracing.COMPILE_TID for s in spans
               if s[0] == "compile")


def test_slow_tick_lands_in_the_record_with_its_phases(monkeypatch):
    import time
    from repro.serving import engine as engine_mod
    eng = ServingEngine(num_shards=1, max_slots=4)
    eng.preload(np.arange(8, dtype=np.uint32), np.arange(8, dtype=np.uint32))
    eng.submit_all([Request(ops=[("read", k % 8)] * 200) for k in range(4)])
    while eng.ticks < engine_mod.SLOW_TICK_REFRESH:
        eng.tick()
    median_s = eng.metrics.tick_secs_h.percentile(50)
    gather = eng._gather

    def slow_gather():
        time.sleep(max(0.05, 8 * median_s))
        return gather()
    monkeypatch.setattr(eng, "_gather", slow_gather)
    slowed = eng.ticks
    eng.tick()
    monkeypatch.setattr(eng, "_gather", gather)
    eng.tick()
    rec = [r for r in eng.stats()["slow_ticks"] if r["tick"] == slowed]
    assert len(rec) == 1
    phases = rec[0]["phases_ms"]
    assert max(phases, key=phases.get) == "gather"
    assert {"gather", "probe", "wait", "writeback", "admit",
            "telemetry"} <= set(phases)
    assert rec[0]["ms"] >= phases["gather"] >= 50.0
    assert rec[0]["compiles"] == 0
    assert len(eng.slow_ticks) <= engine_mod.SLOW_TICK_LOG_MAX


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
