"""table ops: device time of the jitted delete and insert programs
(``jit_delete``, ``jit_insert`` in the trace's XLA Modules line) in the
traced window, per tick."""
from perfbench import devtrace


def read(run):
    t = run.trace
    if t is None or not t.ticks:
        return None
    mods = devtrace.modules(t.events)
    ns = sum(devtrace.matching_ns(mods, name, t.lo_ns, t.hi_ns)
             for name in ("jit_delete", "jit_insert"))
    return ns * 1e-6 / t.ticks if ns else None
