"""table ops (mesh): device time of the fused tick program
``jit_tick_mesh`` (routing, the all_to_all exchanges, probe, delete and
insert on every shard) in the traced window, summed over the cell's chips,
per chip and per tick.  A program whose mesh calls all traced as
``jit_shard_fn``, before each was named, is read under that name: on the
fused path the tick is the only one of them."""
from perfbench import devtrace


def read(run):
    t = run.trace
    if t is None or not t.ticks:
        return None
    mods = devtrace.modules(t.events)
    ns = devtrace.matching_ns(mods, "jit_tick_mesh", t.lo_ns, t.hi_ns) or \
        devtrace.matching_ns(mods, "jit_shard_fn", t.lo_ns, t.hi_ns)
    if not ns:
        return None
    chips = len(t.planes or devtrace.device_planes(t.events))
    return ns * 1e-6 / chips / t.ticks
