"""engine: host time of ``ServingEngine.submit`` (op validation, tenant
quota, slot placement and admission), from the engine's ``hashmem/submit``
profiler annotations in the traced window, per tick."""
from perfbench import program_spans


def read(run):
    return program_spans.ms_per_tick(run, "submit")
