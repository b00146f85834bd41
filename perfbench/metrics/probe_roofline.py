"""probe kernel: share of the HBM roofline of the Pallas probe kernel
``hashmem_probe_perf`` in the traced window.  The least bytes are counted
from the traffic (``roofline.probe_least_bytes``: one page row per distinct
bucket its real keys hash to, per call) over the chip's peak bandwidth,
against the device time of the kernel's events."""
from perfbench import devtrace, roofline


def read(run):
    t = run.trace
    if t is None or not t.ticks:
        return None
    ns = devtrace.matching_ns(devtrace.ops(t.events), "hashmem_probe_perf",
                              t.lo_ns, t.hi_ns)
    if not ns:
        return None
    keys = run.probe_keys(t.first_tick, t.first_tick + t.ticks)
    least = roofline.probe_least_bytes(keys, run.cell.config["table"])
    bw = roofline.peak(run.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / bw / (ns * 1e-9)
