"""device: share of the traced window in which no operation ran on the
device (1 - the union of the device's op intervals over the window)."""
from perfbench import devtrace


def read(run):
    t = run.trace
    if t is None or t.hi_ns <= t.lo_ns:
        return None
    busy = devtrace.busy_ns(t.events, t.lo_ns, t.hi_ns)
    if not busy:
        return None
    return 100.0 * (1.0 - busy / (t.hi_ns - t.lo_ns))
