"""harness: the 99th percentile of request latency over every request
completed in the measured window, submission to answer, on the harness
clock.  In a closed loop every client is in flight at once, so one stall
of the host delays about 1% of a window's requests and sets this number
(PERF.md): it is read per layer, beside ``ops_per_s``."""


def read(run):
    if not run.latencies_s:
        return None
    import numpy as np
    return float(np.percentile(np.asarray(run.latencies_s), 99)) * 1e3
