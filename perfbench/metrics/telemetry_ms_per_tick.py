"""engine: host time of the engine's always-on telemetry (the hot-key
sketch, request latency and tick records of ``MetricsCollector``), from the
engine's ``hashmem/telemetry`` profiler annotations in the traced window,
per tick."""
from perfbench import program_spans


def read(run):
    return program_spans.ms_per_tick(run, "telemetry")
