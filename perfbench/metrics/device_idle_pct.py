"""device: share of the traced window in which no operation ran on the
cell's chips: 1 - (the union of each chip's op intervals, summed over its
chips) / (chips x the window).  A chip that ran nothing counts idle."""
from perfbench import devtrace


def read(run):
    t = run.trace
    if t is None or t.hi_ns <= t.lo_ns:
        return None
    busy = devtrace.busy_ns(t.events, t.lo_ns, t.hi_ns, t.planes)
    if not busy:
        return None
    return 100.0 * (1.0 - busy / (t.hi_ns - t.lo_ns))
