"""The engine's spans on the profiler's clock: a traced engine run under
``jax.profiler`` on the CPU holds one ``hashmem/<name>`` host event for each
span its Tracer's ring holds, and the readers of ``submit_ms_per_tick`` and
``telemetry_ms_per_tick`` sum those events over a synthetic window."""
import collections
import types

import numpy as np
import pytest

from perfbench import devtrace, harness
from perfbench_tiny import REPO

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _requests(n, seed):
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    return [Request(ops=[("read", int(k)), ("update", int(k), int(k) + 1)])
            for k in rng.integers(0, 64, n)]


def test_each_ring_span_has_one_profiler_event(tmp_path):
    import jax
    from repro.serving import ServingEngine, Tracer
    tr = Tracer(enabled=False)
    eng = ServingEngine(num_shards=1, max_slots=8, trace=tr)
    eng.preload(np.arange(64, dtype=np.uint32),
                np.arange(64, dtype=np.uint32))
    eng.submit_all(_requests(16, 0))
    eng.run()                              # compiles outside the window
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    tr.enabled = True
    try:
        eng.submit_all(_requests(16, 1))
        eng.run()
    finally:
        tr.enabled = False
        jax.profiler.stop_trace()
    events = devtrace.load(str(tmp_path))
    ring = collections.Counter(n for n, _, _ in harness.engine_spans(tr))
    for name in ("submit", "gather", "wait", "telemetry", "tick"):
        host = devtrace.host_spans(events, "hashmem/" + name)
        assert ring[name] > 0 and len(host) == ring[name], name


def _view(events, ticks=2):
    return harness.TraceView(events, 100.0, 1000.0, ticks, 0, [])


def _run(events, ticks=2):
    # no ``cell``: a reader reads the trace and touches nothing else
    return types.SimpleNamespace(trace=_view(events, ticks))


EVENTS = [
    devtrace.Event(DEV, devtrace.OPS_LINE, "%fusion.1 = u32[8]", 120, 10),
    devtrace.Event(HOST, "python", "hashmem/submit", 50, 100),    # 50 in
    devtrace.Event(HOST, "python", "hashmem/submit", 200, 100),   # 100 in
    devtrace.Event(HOST, "python", "hashmem/submit", 950, 100),   # 50 in
    devtrace.Event(HOST, "python", "hashmem/submit", 1100, 100),  # outside
    devtrace.Event(HOST, "python", "hashmem/telemetry", 400, 30),
    devtrace.Event(HOST, "python", "submit", 300, 500),     # the harness's
    devtrace.Event(DEV, "python", "hashmem/telemetry", 500, 300),
]


@pytest.mark.parametrize("metric,ns", [("submit_ms_per_tick", 200),
                                       ("telemetry_ms_per_tick", 30)])
def test_reader_clips_to_the_window_and_divides_by_its_ticks(metric, ns):
    read = harness.metric_reader(REPO, metric)
    assert read(_run(EVENTS, ticks=2)) == pytest.approx(ns * 1e-6 / 2)
    assert read(_run(EVENTS, ticks=4)) == pytest.approx(ns * 1e-6 / 4)


@pytest.mark.parametrize("metric,span", [("submit_ms_per_tick", "submit"),
                                         ("telemetry_ms_per_tick",
                                          "telemetry")])
def test_reader_reads_nothing_without_its_events(metric, span):
    read = harness.metric_reader(REPO, metric)
    others = [e for e in EVENTS if e.name != "hashmem/" + span]
    assert read(_run(others)) is None
    outside = others + [devtrace.Event(HOST, "python", "hashmem/" + span,
                                       1200, 50)]
    assert read(_run(outside)) is None
    assert read(_run(EVENTS, ticks=0)) is None


@pytest.mark.parametrize("metric", ["submit_ms_per_tick",
                                    "telemetry_ms_per_tick"])
def test_reader_reads_nothing_from_renamed_annotations(metric):
    """The name is the benchmark's, not read from the program: annotations
    under another prefix leave the metric reading nothing, which the
    harness reports as a silent metric on the chip."""
    read = harness.metric_reader(REPO, metric)
    renamed = [devtrace.Event(e.plane, e.line, "engine/" + e.name[8:],
                              e.start_ns, e.dur_ns)
               if e.name.startswith("hashmem/") else e for e in EVENTS]
    assert read(_run(renamed)) is None
