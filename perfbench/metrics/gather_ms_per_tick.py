"""engine: host time of the engine's ``gather`` phase (one op per active
slot into the probe, delete and insert batches), from the engine's own
Tracer spans in the traced window, per tick."""


def read(run):
    t = run.trace
    if t is None or not t.ticks:
        return None
    spans = [e - s for n, s, e in t.engine_spans if n == "gather"]
    if not spans:
        return None
    return sum(spans) * 1e-6 / t.ticks
