"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

:func:`load` flattens the ``.xplane.pb`` file into :class:`Event` rows;
everything else works on those rows, so tests can feed it a synthetic set.
Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, programs those of its ``XLA Modules`` line.
Host spans (the harness's ``jax.profiler.TraceAnnotation``) are events of
the host planes, on the same clock.

A cell's chips are the planes ``/device:TPU:<id>`` of its devices' ids
(:func:`plane`).  Readers given those ``planes`` count a chip that ran
nothing as idle; given none, they read the device planes that hold events.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(logdir: str) -> list:
    """Every event of the newest trace under ``logdir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            events += [Event(plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns))
                       for ev in line.events]
    return events


def plane(device_id: int) -> str:
    """The trace plane of the device with JAX id ``device_id``."""
    return f"{DEVICE_PREFIX}{device_id}"


def device_planes(events) -> list:
    return sorted({e.plane for e in events
                   if e.plane.startswith(DEVICE_PREFIX)})


def on_planes(events, planes: list) -> list:
    """``events`` without the device events of planes not in ``planes``."""
    keep = set(planes)
    return [e for e in events
            if not e.plane.startswith(DEVICE_PREFIX) or e.plane in keep]


def ops(events, plane: str | None = None) -> list:
    return [e for e in events if e.plane.startswith(DEVICE_PREFIX)
            and e.line == OPS_LINE and (plane is None or e.plane == plane)]


def modules(events, plane: str | None = None) -> list:
    return [e for e in events if e.plane.startswith(DEVICE_PREFIX)
            and e.line == MODULES_LINE and (plane is None or e.plane == plane)]


def host_spans(events, name: str) -> list:
    return [e for e in events if not e.plane.startswith(DEVICE_PREFIX)
            and e.name == name]


def merged(intervals, lo: float, hi: float) -> list:
    """Union of ``(start, end)`` intervals clipped to [lo, hi], sorted."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events, lo: float, hi: float, planes: list | None = None) \
        -> float:
    """Seconds (in ns) in which an operation ran on a device, averaged
    over the ``planes``: the union of each plane's op intervals."""
    planes = device_planes(events) if planes is None else planes
    if not planes:
        return 0.0
    total = sum(sum(e - s for s, e in
                    merged(((o.start_ns, o.end_ns) for o in ops(events, p)),
                           lo, hi))
                for p in planes)
    return total / len(planes)


def gaps(events, lo: float, hi: float, planes: list | None = None) -> list:
    """Intervals inside [lo, hi] in which no operation ran on any of the
    ``planes``: where every chip of the cell was idle."""
    planes = device_planes(events) if planes is None else planes
    if not planes:
        return []
    busy = merged(((o.start_ns, o.end_ns) for p in planes
                   for o in ops(events, p)), lo, hi)
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def short_name(name: str) -> str:
    """``fusion.17`` of an op event named by its HLO text
    (``%fusion.17 = u32[...] fusion(...)``); ``jit_delete`` of a program
    event named ``jit_delete(10441745013664424049)``."""
    if name.startswith("%"):
        return name[1:].split(" ", 1)[0]
    return name.split("(", 1)[0]


def time_by_name(events, lo: float, hi: float) -> dict:
    """Device ns per operation, clipped to [lo, hi] and summed over the
    device planes, each operation named ``<program>/<op>`` by the program
    event of its plane that holds its start."""
    out: dict = {}
    for p in device_planes(events):
        mods = sorted((m.start_ns, m.end_ns, short_name(m.name))
                      for m in modules(events, p))
        starts = [m[0] for m in mods]
        for e in ops(events, p):
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d <= 0:
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            prog = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else "?"
            name = f"{prog}/{short_name(e.name)}"
            out[name] = out.get(name, 0.0) + d
    return out


def matching_ns(evs, needle: str, lo: float, hi: float) -> float:
    """Device ns of the events whose short name starts with ``needle``."""
    total = 0.0
    for e in evs:
        if short_name(e.name).startswith(needle):
            total += max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))
    return total


def name_gap(gap: tuple, spans: list, default: str) -> str:
    """The innermost of ``spans`` (``(name, start, end)``) that holds the
    gap's midpoint, else ``default``."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for name, s, e in spans:
        if s <= mid < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else default
