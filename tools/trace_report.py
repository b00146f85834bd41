"""Offline analysis/validation of a ServingEngine Perfetto trace.

Loads the Chrome trace-event JSON written by ``ServingEngine.export_trace``
(or ``Tracer.export``), validates the event stream — every ``B`` has a
matching same-name ``E`` on its track, timestamps are monotonic per
(pid, tid) track, metadata ``M`` events are ignored — and prints:

  * the **per-phase time breakdown** (total/mean/max duration per span
    name, plus share of the summed tick wall time);
  * the **stall count** (``pipeline_stall`` spans + ``write_fence``
    instants) and total stalled time;
  * the **slowest-tick attribution table**: for the top-N slowest ``tick``
    spans, where the time went (phase spans nested in that tick's window
    on its lane).

Exit status is non-zero on a malformed trace or a failed ``--assert-*``
check, so ``make trace-smoke`` can gate CI on trace correctness:

    python tools/trace_report.py /tmp/trace.json \
        --assert-spans tick,gather,writeback --assert-stalls 1

``--assert-spans`` takes a comma-separated list of span names that must
appear (default: none); ``--assert-stalls N`` requires at least N
pipeline stalls (use on a read-your-writes workload where the write-claim
fence must fire).
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict

# engine span vocabulary (tracing.SPAN_NAMES), used for breakdown ordering
PHASE_ORDER = ("submit", "gather", "route", "probe", "delete", "insert",
               "fused_tick", "wait", "writeback", "pipeline_stall", "admit",
               "telemetry", "sample", "grow", "split", "compact", "preload",
               "compile")


def load_events(path: str) -> tuple:
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    other = doc.get("otherData", {}) if isinstance(doc, dict) else {}
    return events, other


def validate(events: list) -> tuple:
    """Check B/E balance + per-track monotonicity; returns
    (spans, instants, problems) where spans are completed
    (name, tid, ts, dur, args) tuples reconstructed from the B/E stream."""
    problems: list = []
    last_ts: dict = {}
    stacks: dict = defaultdict(list)
    spans: list = []
    instants: list = []
    for ev in events:
        ph = ev.get("ph")
        if ph == "M":
            continue
        track = (ev.get("pid"), ev.get("tid"))
        ts = ev.get("ts")
        if ph in ("B", "E"):
            if ts < last_ts.get(track, float("-inf")):
                problems.append(
                    f"non-monotonic ts on track {track}: {ts} after "
                    f"{last_ts[track]} ({ev.get('name')})")
            last_ts[track] = ts
            if ph == "B":
                stacks[track].append(ev)
            elif not stacks[track]:
                problems.append(
                    f"unmatched E {ev.get('name')!r} on track {track}")
            else:
                b = stacks[track].pop()
                if b["name"] != ev["name"]:
                    problems.append(
                        f"interleaved B/E on track {track}: opened "
                        f"{b['name']!r}, closed {ev['name']!r}")
                spans.append((b["name"], track[1], b["ts"],
                              ts - b["ts"], b.get("args", {})))
        elif ph == "i":
            instants.append((ev.get("name"), track[1], ts,
                             ev.get("args", {})))
    for track, stack in stacks.items():
        if stack:
            problems.append(f"{len(stack)} unclosed B event(s) on track "
                            f"{track}: {[b['name'] for b in stack]}")
    return spans, instants, problems


def phase_breakdown(spans: list) -> dict:
    """name -> {count, total_us, mean_us, max_us} over duration spans."""
    acc: dict = defaultdict(lambda: {"count": 0, "total_us": 0.0,
                                     "max_us": 0.0})
    for name, _, _, dur, _ in spans:
        a = acc[name]
        a["count"] += 1
        a["total_us"] += dur
        if dur > a["max_us"]:
            a["max_us"] = dur
    for a in acc.values():
        a["mean_us"] = a["total_us"] / a["count"]
    return dict(acc)


def slowest_ticks(spans: list, top: int = 5) -> list:
    """Top-N slowest tick spans, each with its nested-phase attribution:
    phase spans on the SAME lane whose interval falls inside the tick's.
    Returns [(tick_id, lane, dur_us, {phase: us})] slowest first."""
    ticks = [s for s in spans if s[0] == "tick"]
    ticks.sort(key=lambda s: -s[3])
    out = []
    for name, lane, ts, dur, args in ticks[:top]:
        inside: dict = defaultdict(float)
        for n2, l2, ts2, d2, _ in spans:
            if n2 != "tick" and l2 == lane and ts2 >= ts \
                    and ts2 + d2 <= ts + dur + 1e-3:
                inside[n2] += d2
        out.append((args.get("tick", "?"), lane, dur, dict(inside)))
    return out


def report(path: str, top: int = 5) -> tuple:
    events, other = load_events(path)
    spans, instants, problems = validate(events)
    print(f"{path}: {len(events)} events, {len(spans)} spans, "
          f"{len(instants)} instants"
          + (f", {other.get('dropped', 0)} ring drops" if other else ""))
    for p in problems:
        print(f"  INVALID: {p}")

    by_phase = phase_breakdown(spans)
    tick_total = by_phase.get("tick", {}).get("total_us", 0.0)
    print("\nper-phase breakdown (sum over spans):")
    order = [n for n in PHASE_ORDER if n in by_phase] + \
        sorted(set(by_phase) - set(PHASE_ORDER) - {"tick"})
    for name in ["tick"] * ("tick" in by_phase) + order:
        a = by_phase[name]
        share = f"  {100.0 * a['total_us'] / tick_total:5.1f}% of tick" \
            if tick_total and name != "tick" else ""
        print(f"  {name:<16} n={a['count']:<6} total={a['total_us']:.0f}us "
              f"mean={a['mean_us']:.1f}us max={a['max_us']:.1f}us{share}")

    stall_spans = by_phase.get("pipeline_stall", {"count": 0,
                                                  "total_us": 0.0})
    fences = sum(1 for n, _, _, _ in instants if n == "write_fence")
    kills = sum(1 for n, _, _, _ in instants if n == "kill")
    print(f"\nstalls: {stall_spans['count']} pipeline_stall span(s) "
          f"({stall_spans['total_us']:.0f}us total), {fences} write_fence "
          f"instant(s), {kills} kill(s)")

    slow = slowest_ticks(spans, top)
    if slow:
        print(f"\nslowest {len(slow)} tick(s):")
        for tick_id, lane, dur, inside in slow:
            attr = ", ".join(f"{n}={us:.0f}us" for n, us in
                             sorted(inside.items(), key=lambda kv: -kv[1]))
            other_us = dur - sum(inside.values())
            print(f"  tick {tick_id} (lane {lane}): {dur:.0f}us — {attr}"
                  f", unattributed={other_us:.0f}us")
    return spans, instants, problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="validate + summarize a ServingEngine Perfetto trace")
    ap.add_argument("trace", help="trace-event JSON file "
                    "(ServingEngine.export_trace output)")
    ap.add_argument("--top", type=int, default=5,
                    help="slowest ticks to attribute (default 5)")
    ap.add_argument("--assert-spans", default="",
                    help="comma-separated span names that must appear")
    ap.add_argument("--forbid-spans", default="",
                    help="comma-separated span names that must NOT appear "
                         "(grow-smoke forbids 'grow' and 'pipeline_stall': "
                         "an extendible split must repair inline, neither "
                         "rebuilding the table nor flushing the pipeline)")
    ap.add_argument("--assert-stalls", type=int, default=0,
                    help="minimum pipeline_stall span count")
    args = ap.parse_args(argv)

    spans, instants, problems = report(args.trace, args.top)
    ok = not problems
    seen = {s[0] for s in spans}
    for want in filter(None, args.assert_spans.split(",")):
        if want.strip() not in seen:
            print(f"ASSERT FAILED: span {want.strip()!r} not in trace "
                  f"(saw {sorted(seen)})")
            ok = False
    for bad in filter(None, args.forbid_spans.split(",")):
        if bad.strip() in seen:
            print(f"ASSERT FAILED: forbidden span {bad.strip()!r} appears "
                  f"in trace")
            ok = False
    stalls = sum(1 for s in spans if s[0] == "pipeline_stall")
    if stalls < args.assert_stalls:
        print(f"ASSERT FAILED: {stalls} pipeline_stall span(s) < required "
              f"{args.assert_stalls}")
        ok = False
    print("\ntrace OK" if ok else "\ntrace FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
