"""router: the share of the fused tick's routed buffer rows that hold a
real key, all phases, over the traced window: the engine's ``routed_keys``
over its ``routed_elems`` counters (every chip's send buffers, num_shards
x capacity rows each, a phase).  An engine that counts no routed keys has
them counted from its op log over the traced ticks: a read or scanned key
is one probe, an update a delete and an insert, an rmw all three.  A table
on one chip routes nothing and reads nothing."""

KEYS_PER_OP = {"update": 2, "rmw": 3}


def read(run):
    t = run.trace
    if t is None or not t.ticks:
        return None
    sums: dict = {}
    for ev in run.engine.tracer.to_events():
        if ev["ph"] == "C" and ev["name"] in ("routed_elems", "routed_keys"):
            sums[ev["name"]] = sums.get(ev["name"], 0.0) + ev["args"]["value"]
    rows = sums.get("routed_elems")
    if not rows:
        return None
    keys = sums.get("routed_keys")
    if keys is None:
        stop = t.first_tick + t.ticks
        keys = sum(len(k) * KEYS_PER_OP.get(kind, 1)
                   for tick, kind, k, _, _ in run.engine.schedule
                   if t.first_tick <= tick < stop)
    return 100.0 * keys / rows
