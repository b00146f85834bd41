"""Logical-axis sharding rules (DP / FSDP / TP / EP / SP) for the production
mesh: single-pod (16,16) ("data","model") and multi-pod (2,16,16)
("pod","data","model").

Rules map logical axis names from model init (layers.Axes) to mesh axes.
A rule is dropped (replicated) per-array-dimension when the dimension size
does not divide the mesh-axis product — e.g. whisper-tiny's 6 heads on a
16-way 'model' axis, or GQA kv_heads=8 (< 16): Megatron-style replication.
"""
from __future__ import annotations


import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.layers import Axes

# logical axis -> mesh axes (tuple = joint sharding)
RULES = {
    "batch": ("pod", "data"),
    "embed": ("data",),          # FSDP weight shard
    "mlp": ("model",),           # TP
    "heads": ("model",),
    "kv_heads": ("model",),
    "vocab": ("model",),
    "expert": ("data",),         # EP
    # paged-KV grouped layout: pages jointly sharded over the whole mesh
    # (batch groups x channels; paper §6 channel parallelism)
    "kv_pages": ("pod", "data", "model"),
    "act_seq": ("model",),       # sequence-parallel residual stream
    # replicated:
    "layers": (), "state": (), "conv": (), "dt_rank": (), "head_dim": (),
    "seq": (), "gates": (),
}


def mesh_axes_for(mesh: Mesh, logical: str):
    axes = tuple(a for a in RULES.get(logical, ()) if a in mesh.axis_names)
    return axes


def spec_for(mesh: Mesh, axes: Axes, shape) -> P:
    """PartitionSpec for one array given its logical axes + shape, with
    divisibility fallback to replication."""
    parts = []
    used = set()
    for name, dim in zip(tuple(axes), shape):
        maxes = tuple(a for a in mesh_axes_for(mesh, name) if a not in used)
        size = int(np.prod([mesh.shape[a] for a in maxes])) if maxes else 1
        if maxes and dim % size == 0:
            parts.append(maxes if len(maxes) > 1 else maxes[0])
            used.update(maxes)
        else:
            parts.append(None)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def param_specs(cfg, mesh: Mesh):
    """PartitionSpec tree matching init_params(cfg)."""
    from repro.models import model
    shapes = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    axes = model.param_axes(cfg)
    return jax.tree.map(
        lambda a, s: spec_for(mesh, a, s.shape),
        axes, shapes, is_leaf=lambda x: isinstance(x, Axes))


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def batch_spec(mesh: Mesh, global_batch: int):
    """Dim-entry for the batch dimension (tuple of mesh axes, or None)."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    size = int(np.prod([mesh.shape[a] for a in axes]))
    if global_batch % size == 0:
        return axes
    # long_500k batch=1: replicate batch, parallelism comes from kv pages
    return None


def batch_specs(cfg, mesh: Mesh, batch_tree):
    """Input sharding specs for a train/prefill batch dict."""
    bs = {k: None for k in batch_tree}
    out = {}
    for k, v in batch_tree.items():
        spec = [batch_spec(mesh, v.shape[0])]
        spec += [None] * (len(v.shape) - 1)
        out[k] = P(*spec)
    return out


# ---------------------------------------------------------------------------
# Stacked-HashMem placement (serving-engine mesh shards; core/rlu.py)
# ---------------------------------------------------------------------------

def stacked_hashmem_specs(hm_stacked, axis: str = "model"):
    """PartitionSpec tree for a stacked shard pytree (leading dim =
    num_shards): every leaf shards its leading axis over ``axis``, which
    places exactly one HashMem shard per device along the mesh axis."""
    return jax.tree.map(lambda _: P(axis), hm_stacked)


class ShardCtx:
    """Activation sharding constraints threaded through the model.

    seq_shard=True applies Megatron-style sequence parallelism to the
    residual stream between layer units (keeps the lax.scan carry — the
    dominant live activation — at 1/|model| per chip).
    """

    def __init__(self, mesh: Mesh, seq_shard: bool = False):
        self.mesh = mesh
        self.seq_shard = seq_shard
        self._baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def residual(self, x):
        """x (B,S,d) constraint at unit boundaries."""
        if not self.seq_shard:
            return x
        B, S, _ = x.shape
        bspec = self._baxes if B % int(np.prod(
            [self.mesh.shape[a] for a in self._baxes])) == 0 else None
        sspec = "model" if S % self.mesh.shape["model"] == 0 else None
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(bspec, sspec)))
