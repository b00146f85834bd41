"""collectives: time inside all-to-all operations that no other operation
of the same chip overlaps, in the traced window: per chip, the union of
its all-to-all intervals less the part covered by its other operations,
summed over the cell's chips (a chip that ran none counts), per chip and
per tick."""
from perfbench import devtrace

NAMES = ("all-to-all", "all_to_all")


def exposed_ns(ops, lo: float, hi: float) -> float | None:
    """ns of one chip's all-to-all intervals in [lo, hi] that none of its
    other ``ops`` overlaps; None when it ran no all-to-all there."""
    a2a, other = [], []
    for o in ops:
        hit = devtrace.short_name(o.name).startswith(NAMES)
        (a2a if hit else other).append((o.start_ns, o.end_ns))
    spans = devtrace.merged(a2a, lo, hi)
    if not spans:
        return None
    busy = devtrace.merged(other, lo, hi)
    total = 0.0
    for s, e in spans:
        total += e - s - sum(max(0.0, min(e, be) - max(s, bs))
                             for bs, be in busy)
    return total


def read(run):
    t = run.trace
    if t is None or not t.ticks:
        return None
    planes = t.planes or devtrace.device_planes(t.events)
    per_chip = [exposed_ns(devtrace.ops(t.events, p), t.lo_ns, t.hi_ns)
                for p in planes]
    if all(v is None for v in per_chip):
        return None
    return sum(v or 0.0 for v in per_chip) * 1e-6 / len(planes) / t.ticks
