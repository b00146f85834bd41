"""jit'd wrappers for the HashMem probe kernels.

All probe entry points take the unified PageStore's interleaved (P, S, 2)
pool — one page fetch per chain step serves both the key compare and the
value readout.  Page schedules may carry interior -1 holes (fingerprint-
filtered pages); the Pallas wrappers derive a forward-filled fetch index so
those steps cost no row activation.  With ``interpret`` left at None the
platform a call is lowered for picks the path: the Pallas interpreter on
CPU, Mosaic on TPU (kernels/probe_common.py).

These kernels never see the bucket directory: extendible-mode probes
resolve their page schedule through the same bucket_head gather as rebuild
mode (core/probe.py module docstring), so the kernel interface — (pool,
queries, pages) — is identical under both resize modes and across splits.
"""
from __future__ import annotations

import jax

from repro.core import layout
from repro.kernels.probe_area import probe_pages_area
from repro.kernels.probe_bitserial import probe_pages_bitserial
from repro.kernels.probe_perf import probe_pages_perf
from repro.kernels import ref

__all__ = [
    "probe_perf", "probe_area", "probe_bitserial", "probe_ref",
    "bitplane_update", "bitplane_rebuild",
]

probe_perf = jax.jit(probe_pages_perf)
probe_area = jax.jit(probe_pages_area)
probe_bitserial = jax.jit(probe_pages_bitserial, static_argnames=("key_bits",))
probe_ref = jax.jit(ref.probe_pages_ref)
probe_bitplanes_ref = jax.jit(ref.probe_bitplanes_ref, static_argnames=("key_bits",))

# bit-plane maintenance for the mutation engine: batched incremental update
# (insert/delete write sets) and the full from-scratch rebuild (grow/compact)
bitplane_update = jax.jit(layout.update_bitplanes_batch,
                          static_argnames=("key_bits",))
bitplane_rebuild = jax.jit(layout.pack_bitplanes, static_argnames=("key_bits",))
