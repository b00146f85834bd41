"""Chip peaks, and the least bytes a probe batch has to read.

Peaks are keyed by ``device_kind`` as JAX reports it.  A device that is not
in the table is an error, never a default.
"""
from __future__ import annotations

import numpy as np

# Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s per chip.
# JAX names the chip "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

PAGE_ENTRY_BYTES = 8          # one uint32 key and one uint32 value


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind][what]


def bucket_of(keys: np.ndarray, num_buckets: int, salt: int) -> np.ndarray:
    """The table's bucket of each uint32 key: the murmur3 finalizer under
    the configuration's salt, modulo the bucket count."""
    u = np.uint32
    h = keys.astype(u) ^ u(salt)
    h = h ^ (h >> u(16))
    h = h * u(0x85EBCA6B)
    h = h ^ (h >> u(13))
    h = h * u(0xC2B2AE35)
    h = h ^ (h >> u(16))
    return (h % u(num_buckets)).astype(np.int64)


def probe_least_bytes(keys_per_call, table: dict) -> int:
    """Bytes a probe call has to read at least, summed over calls: one page
    row (``slots_per_page`` entries of 8 B) for each distinct bucket that
    the call's real keys hash to.  Keys that share a page count it once,
    so a kernel that fetches each page once for all its queries still reads
    at most 100% of its roofline."""
    row = table["slots_per_page"] * PAGE_ENTRY_BYTES
    total = 0
    for keys in keys_per_call:
        if len(keys):
            b = bucket_of(np.asarray(keys, np.uint32), table["num_buckets"],
                          table["salt"])
            total += len(np.unique(b)) * row
    return total
