"""Host time of one of the engine's own profiler annotations per tick.

While its Tracer is on, the engine opens a ``jax.profiler.TraceAnnotation``
named ``hashmem/<span>`` around each of its spans, so the profiler's host
planes hold them on the device trace's clock.  A reader sums one name's
events, clipped to the traced window, over the window's ticks.  The name is
the benchmark's own: a program that drops or renames the annotation leaves
the metric reading nothing.
"""
from perfbench import devtrace

PREFIX = "hashmem/"


def ms_per_tick(run, span: str):
    """Milliseconds per traced tick inside ``hashmem/<span>`` events; None
    when no such event lies in the traced window."""
    t = run.trace
    if t is None or not t.ticks:
        return None
    ns = sum(max(0.0, min(e.end_ns, t.hi_ns) - max(e.start_ns, t.lo_ns))
             for e in devtrace.host_spans(t.events, PREFIX + span))
    return ns * 1e-6 / t.ticks if ns else None
