"""Layer-stack assembly: heterogeneous super-block scan.

Hybrid architectures repeat a fixed unit pattern (jamba: 8 layers = 7 mamba +
1 attention, MoE on odd layers; llama4: dense/MoE alternation; xlstm: 1 sLSTM
+ 7 mLSTM).  We scan over stacked *units* (lax.scan keeps the HLO small for
48-layer 400B configs) and unroll the unit's heterogeneous layers in Python.

Decode threads per-layer states through the same scan; attention layers use
the HashMem paged KV cache (core/paged_kv.py), optionally channel-parallel
via shard_map when ``ctx.axis`` is set.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import paged_kv
from repro.models import attention, mamba, mlp, moe, xlstm
from repro.models.layers import norm_init, rms_norm


# ---------------------------------------------------------------------------
# Unit structure
# ---------------------------------------------------------------------------

def scan_unit_size(cfg) -> int:
    u = 1
    if cfg.family == "hybrid":
        u = math.lcm(u, cfg.attn_every)
    if cfg.num_experts:
        u = math.lcm(u, cfg.moe_every)
    if cfg.slstm_every:
        u = math.lcm(u, cfg.slstm_every)
    if cfg.d_ff_dense:
        u = math.lcm(u, cfg.moe_every)
    return u


def layer_kind(cfg, i: int) -> str:
    """'attn' | 'mamba' | 'mlstm' | 'slstm' for global layer index i."""
    if cfg.family == "ssm":
        return "slstm" if cfg.is_slstm_layer(i) else "mlstm"
    if cfg.family == "hybrid":
        return "attn" if cfg.is_attn_layer(i) else "mamba"
    return "attn"


def ffn_kind(cfg, i: int) -> Optional[str]:
    """'moe' | 'dense' | None (xlstm blocks have no separate FFN)."""
    if cfg.family == "ssm":
        return None
    return "moe" if cfg.is_moe_layer(i) else "dense"


@dataclass(frozen=True)
class DecodeCtx:
    """Paged-decode context: page pool geometry + channel topology.

    batch_axes: mesh axes the decode batch is sharded over (sequences are
    grouped per shard); channel_axes: mesh axes pages are spread over (the
    paper's memory channels).  Empty batch_axes (long-context B=1) makes
    every mesh axis a channel.  pages_per_shard follows the grouped pool
    layout in core/paged_kv.py.  mesh=None -> single-device gather path.
    """
    page_tokens: int
    n_pages: int          # block-table width (logical pages per sequence)
    pool_pages: int       # physical pool size (global)
    batch_axes: tuple = ()
    channel_axes: tuple = ()
    pages_per_shard: int = 0
    mesh: Optional[object] = None

    @property
    def sharded(self) -> bool:
        return self.mesh is not None and bool(self.channel_axes)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_layer(key, cfg, i: int):
    kind = layer_kind(cfg, i)
    fk = ffn_kind(cfg, i)
    ks = jax.random.split(key, 3)
    p = {"norm1": norm_init(cfg.d_model)}
    if kind == "attn":
        p["attn"] = attention.init(ks[0], cfg)
    elif kind == "mamba":
        p["mamba"] = mamba.init(ks[0], cfg)
    elif kind == "mlstm":
        p["mlstm"] = xlstm.init_mlstm(ks[0], cfg)
    elif kind == "slstm":
        p["slstm"] = xlstm.init_slstm(ks[0], cfg)
    if fk is not None:
        p["norm2"] = norm_init(cfg.d_model)
        if fk == "moe":
            p["ffn_moe"] = moe.init(ks[1], cfg)
        else:
            ff = cfg.d_ff_dense or cfg.d_ff
            p["ffn"] = mlp.init_swiglu(ks[1], cfg.d_model, ff)
    return p


def init_stack(key, cfg, num_layers: Optional[int] = None):
    """Stacked unit params: every leaf gets a leading (n_units,) axis."""
    L = num_layers or cfg.num_layers
    unit = scan_unit_size(cfg)
    assert L % unit == 0, (L, unit)
    n_units = L // unit
    keys = jax.random.split(key, L).reshape(n_units, unit, -1)
    units = []
    for u in range(n_units):
        unit_p = {f"j{j}": init_layer(keys[u, j], cfg, u * unit + j)
                  for j in range(unit)}
        units.append(unit_p)
    from repro.models.layers import Axes, is_leaf
    stacked = jax.tree.map(
        lambda *xs: (jnp.stack([x[0] for x in xs]), Axes(("layers",) + tuple(xs[0][1]))),
        *units, is_leaf=is_leaf)
    return stacked, n_units, unit


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _apply_layer(p, cfg, i, x, positions, *, causal=True, shard_ctx=None):
    kind = layer_kind(cfg, i)
    fk = ffn_kind(cfg, i)
    aux = {}
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind == "attn":
        q, k, v = attention.qkv(p["attn"], cfg, h, positions)
        o = attention.chunked_attention(q, k, v, cfg, causal=causal)
        sub = attention.out_proj(p["attn"], cfg, o)
    elif kind == "mamba":
        sub = mamba.apply(p["mamba"], cfg, h)
    elif kind == "mlstm":
        sub = xlstm.apply_mlstm(p["mlstm"], cfg, h)
    else:
        sub = xlstm.apply_slstm(p["slstm"], cfg, h)
    x = x + sub
    if fk is not None:
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        if fk == "moe":
            if cfg.moe_impl == "ep" and shard_ctx is not None:
                y, aux = moe.apply_ep(
                    p["ffn_moe"], cfg, h2, shard_ctx.mesh,
                    batch_axes=("pod", "data"))
            else:
                y, aux = moe.apply(p["ffn_moe"], cfg, h2)
        else:
            y = mlp.swiglu(p["ffn"], h2)
        x = x + y
    return x, aux


def apply_stack(params_stack, cfg, x, positions, *, causal=True,
                shard_ctx=None):
    """x (B,S,d) -> (x, aux_sums).  lax.scan over stacked units."""
    unit = scan_unit_size(cfg)

    def unit_body(carry, unit_params):
        x, aux_sum = carry
        if shard_ctx is not None:
            x = shard_ctx.residual(x)
        for j in range(unit):
            x, aux = _apply_layer(unit_params[f"j{j}"], cfg, j, x, positions,
                                  causal=causal, shard_ctx=shard_ctx)
            for k_, v_ in aux.items():
                aux_sum[k_] = aux_sum.get(k_, 0.0) + v_
        return (x, aux_sum), None

    if cfg.remat:
        unit_body = jax.checkpoint(unit_body)

    aux0 = {}
    if cfg.num_experts and cfg.family != "ssm":
        aux0 = {"moe_aux": jnp.float32(0), "moe_z": jnp.float32(0),
                "moe_dropped": jnp.float32(0)}
    from repro.models.scan_utils import maybe_scan
    (x, aux), _ = maybe_scan(unit_body, (x, aux0), params_stack,
                             unroll=not cfg.scan_layers)
    return x, aux


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_layer_decode_state(cfg, i: int, B: int, ctx: DecodeCtx,
                            kv_dtype=jnp.bfloat16):
    kind = layer_kind(cfg, i)
    if kind == "attn":
        k_pool, v_pool = paged_kv.init_pool(
            ctx.pool_pages, ctx.page_tokens, cfg.num_kv_heads, cfg.head_dim,
            kv_dtype)
        return {"k_pool": k_pool, "v_pool": v_pool}
    if kind == "mamba":
        return mamba.init_state(cfg, B)
    if kind == "mlstm":
        return xlstm.init_mlstm_state(cfg, B)
    return xlstm.init_slstm_state(cfg, B)


def init_decode_states(cfg, B: int, ctx: DecodeCtx, kv_dtype=jnp.bfloat16,
                       num_layers: Optional[int] = None):
    """Stacked (n_units, ...) decode states matching init_stack layout."""
    L = num_layers or cfg.num_layers
    unit = scan_unit_size(cfg)
    n_units = L // unit
    per_unit = {f"j{j}": init_layer_decode_state(cfg, j, B, ctx, kv_dtype)
                for j in range(unit)}
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_units,) + x.shape).copy(), per_unit)


def _paged_attn_sub(p_attn, cfg, h, state, block_table, pos, ctx):
    """Single-token attention sublayer against the paged cache."""
    positions = pos[:, None]                                    # (B,1)
    q, k_new, v_new = attention.qkv(p_attn, cfg, h, positions)
    kd = state["k_pool"].dtype
    k_new, v_new = k_new.astype(kd), v_new.astype(kd)
    if not ctx.sharded:
        k_pool, v_pool = paged_kv.append(
            state["k_pool"], state["v_pool"], block_table, pos, k_new, v_new)
        o = paged_kv.paged_decode_attention(
            q, k_pool, v_pool, block_table, pos, cfg)
    else:
        ba, ca = ctx.batch_axes, ctx.channel_axes
        pps = ctx.pages_per_shard

        def inner(k_pool, v_pool, q, k_new, v_new, block_table, pos):
            k_pool, v_pool = paged_kv.append_sharded(
                k_pool, v_pool, block_table, pos, k_new, v_new, ba, ca, pps)
            o = paged_kv.decode_attention_sharded(
                q, k_pool, v_pool, block_table, pos, cfg, ba, ca, pps)
            return k_pool, v_pool, o

        pool_spec = P(tuple(ba) + tuple(ca))     # grouped page layout
        bspec = P(ba if ba else None)
        k_pool, v_pool, o = jax.shard_map(
            inner, mesh=ctx.mesh,
            in_specs=(pool_spec, pool_spec, bspec, bspec, bspec, bspec, bspec),
            out_specs=(pool_spec, pool_spec, bspec),
            check_vma=False,
        )(state["k_pool"], state["v_pool"], q, k_new, v_new, block_table, pos)
    sub = attention.out_proj(p_attn, cfg, o)
    return sub, {"k_pool": k_pool, "v_pool": v_pool}


def _apply_layer_decode(p, cfg, i, x, state, block_table, pos, ctx):
    kind = layer_kind(cfg, i)
    fk = ffn_kind(cfg, i)
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind == "attn":
        sub, state = _paged_attn_sub(p["attn"], cfg, h, state, block_table,
                                     pos, ctx)
    elif kind == "mamba":
        sub, state = mamba.decode_step(p["mamba"], cfg, state, h)
    elif kind == "mlstm":
        sub, state = xlstm.decode_mlstm(p["mlstm"], cfg, state, h)
    else:
        sub, state = xlstm.decode_slstm(p["slstm"], cfg, state, h)
    x = x + sub
    if fk is not None:
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        if fk == "moe":
            y, _ = moe.apply(p["ffn_moe"], cfg, h2)
        else:
            y = mlp.swiglu(p["ffn"], h2)
        x = x + y
    return x, state


def decode_stack(params_stack, cfg, x, states, block_table, pos, ctx):
    """One decode step through all units.  x (B,1,d)."""
    unit = scan_unit_size(cfg)

    def unit_body(x, scans):
        unit_params, unit_state = scans
        new_state = {}
        for j in range(unit):
            x, s = _apply_layer_decode(unit_params[f"j{j}"], cfg, j, x,
                                       unit_state[f"j{j}"], block_table, pos, ctx)
            new_state[f"j{j}"] = s
        return x, new_state

    from repro.models.scan_utils import maybe_scan
    x, new_states = maybe_scan(unit_body, x, (params_stack, states),
                               unroll=not cfg.scan_layers)
    return x, new_states
