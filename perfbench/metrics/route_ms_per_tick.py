"""router: host time of the fused mesh tick's pass 1 (each phase's keys
hashed on the host to their owners, the per-(src,dst) maxima and the
routing capacities from them), from the engine's ``hashmem/route``
profiler annotations in the traced window, per tick.  A table on one chip
routes nothing and reads nothing."""
from perfbench import program_spans


def read(run):
    return program_spans.ms_per_tick(run, "route")
