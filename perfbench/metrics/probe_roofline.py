"""probe kernel: share of the HBM roofline of the Pallas probe kernel
``hashmem_probe_perf`` in the traced window, over the cell's chips.  The
least bytes are counted from the traffic (``roofline.probe_least_bytes``:
one page row per distinct row its real keys hash to, per call, over the
deployment's shards x ``num_buckets`` rows, from the key's hash alone)
over the chip's peak bandwidth, against the kernel's device time summed
over the chips."""
from perfbench import devtrace, harness, roofline


def read(run):
    t = run.trace
    if t is None or not t.ticks:
        return None
    ns = devtrace.matching_ns(devtrace.ops(t.events), "hashmem_probe_perf",
                              t.lo_ns, t.hi_ns)
    if not ns:
        return None
    keys = run.probe_keys(t.first_tick, t.first_tick + t.ticks)
    table = run.cell.config["table"]
    rows = dict(table, num_buckets=harness.shards(run.cell.config)
                * table["num_buckets"])
    least = roofline.probe_least_bytes(keys, rows)
    bw = roofline.peak(run.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / bw / (ns * 1e-9)
