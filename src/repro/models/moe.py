"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Dispatch is MegaBlocks/MaxText-style: routed (token, expert) pairs are sorted
by expert, positioned within their expert group, capacity-clipped, and
scattered into an (E, C, d) buffer — no (T, E, C) one-hot tensor is ever
materialized (that would be ~4e13 elements for llama4-maverick train_4k).

The HashMem connection (DESIGN.md §3): an expert buffer with capacity C IS a
hash bucket with bounded slots — overflow tokens are dropped exactly like the
paper's over-utilized buckets overflow to extra pages; the aux load-balance
loss plays the paper's §6 'Hash Function' role of evening out bucket load.
A hash-routing mode (router='hash', Roller et al.) uses repro.core.hashing
directly and needs no router params.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init


def init(key, cfg, layer_ff=None):
    d, E, ff = cfg.d_model, cfg.num_experts, layer_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, (d, E), ("embed", "expert")),
        "gate": dense_init(ks[1], d, (E, d, ff), ("expert", "embed", "mlp")),
        "up": dense_init(ks[2], d, (E, d, ff), ("expert", "embed", "mlp")),
        "down": dense_init(ks[3], ff, (E, ff, d), ("expert", "mlp", "embed")),
    }
    if cfg.num_shared_experts:
        from repro.models.mlp import init_swiglu
        p["shared"] = init_swiglu(ks[4], d, ff * cfg.num_shared_experts)
    return p


def _capacity(cfg, T):
    return max(int(T * cfg.top_k / cfg.num_experts * cfg.capacity_factor), cfg.top_k)


def apply(params, cfg, x, *, router_mode: str = "learned"):
    """x (B,S,d) -> (y (B,S,d), aux dict with load-balance/z losses)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    T = B * S
    xf = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    if router_mode == "hash":
        # hash routing (Roller et al.): expert = h(token position hash) — uses
        # the paper's hash family; router params unused for selection.
        from repro.core.hashing import murmur3_fmix
        hashed = murmur3_fmix(jnp.arange(T, dtype=jnp.uint32))
        idx = (hashed[:, None] % jnp.uint32(E)).astype(jnp.int32)
        idx = jnp.concatenate(
            [((idx + j) % E) for j in range(k)], axis=1)                # (T,k)
        gates = jnp.full((T, k), 1.0 / k, jnp.float32)
    else:
        gates, idx = jax.lax.top_k(probs, k)                            # (T,k)
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # --- aux losses (Switch/GShard) ---
    me = jnp.mean(probs, axis=0)                                        # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / (T * k))
    aux_loss = cfg.aux_loss_coef * E * jnp.sum(me * ce)
    z_loss = cfg.router_z_coef * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    # --- sort-based dispatch ---
    C = _capacity(cfg, T)
    e_flat = idx.reshape(T * k)
    t_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    w_flat = gates.reshape(T * k)
    order = jnp.argsort(e_flat)
    e_s, t_s, w_s = e_flat[order], t_flat[order], w_flat[order]
    start = jnp.searchsorted(e_s, e_s, side="left")
    pos = jnp.arange(T * k, dtype=jnp.int32) - start.astype(jnp.int32)
    keep = pos < C
    dst = jnp.where(keep, e_s * C + pos, E * C)                         # OOB drop

    buf = jnp.zeros((E * C, d), x.dtype).at[dst].set(xf[t_s], mode="drop")
    buf = buf.reshape(E, C, d)

    # --- expert computation (SwiGLU), E parallel ---
    dt = x.dtype
    g = jnp.einsum("ecd,edf->ecf", buf, params["gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, params["up"].astype(dt))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["down"].astype(dt))
    out_buf = out_buf.reshape(E * C, d)

    # --- combine ---
    routed = out_buf[jnp.minimum(dst, E * C - 1)]                       # (T*k, d)
    contrib = routed * (w_s * keep).astype(routed.dtype)[:, None]
    y = jnp.zeros((T, d), x.dtype).at[t_s].add(contrib)

    if "shared" in params:
        from repro.models.mlp import swiglu
        y = y + swiglu(params["shared"], xf[None]).reshape(T, d)

    frac_dropped = 1.0 - jnp.sum(keep) / (T * k)
    return y.reshape(B, S, d), {"moe_aux": aux_loss, "moe_z": z_loss,
                                "moe_dropped": frac_dropped}


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map all_to_all) — the optimized path.
#
# Tokens are already (batch x seq)-sharded 256-way by the sequence-parallel
# residual stream; experts live on 'data' rows (E_loc = E / |data|).  Each
# device routes ONLY its local tokens: one all_to_all over 'data' moves every
# routed token exactly once (the GSPMD global-sort baseline moves the full
# token set per model-replica — 16x more wire bytes; see EXPERIMENTS.md
# §Perf).  Expert weights enter the shard_map with their ff dim unsharded,
# so GSPMD all-gathers them over 'model' at the boundary (FSDP-style).
# Capacity is per-shard (standard for distributed MoE).
# ---------------------------------------------------------------------------

def _local_route(params, cfg, xf):
    """Local top-k routing.  xf (T_loc, d) -> gates, idx, aux parts."""
    E, k = cfg.num_experts, cfg.top_k
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / (xf.shape[0] * k))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return gates, idx, me, ce, z


def apply_ep(params, cfg, x, mesh, batch_axes=("data",), model_axis="model"):
    """x (B,S,d) globally; runs the dispatch inside shard_map over the whole
    mesh.  Requires E % |data| == 0 and (B*S) % |mesh| == 0."""
    E, k = cfg.num_experts, cfg.top_k
    d = x.shape[-1]
    baxes = tuple(a for a in batch_axes if a in mesh.axis_names)
    # expert-parallel group: largest suffix of the batch axes that divides E
    # (e.g. jamba's 16 experts on a (2,16,16) mesh -> EP over 'data' only,
    # replicated across pods)
    while baxes and E % int(np.prod([mesh.shape[a] for a in baxes])):
        baxes = baxes[1:]
    Dd = int(np.prod([mesh.shape[a] for a in baxes])) if baxes else 1
    E_loc = E // Dd
    xspec = tuple(a for a in batch_axes if a in mesh.axis_names)

    def inner(x_loc, router, gate_w, up_w, down_w):
        B_loc, S_loc, _ = x_loc.shape
        T_loc = B_loc * S_loc
        xf = x_loc.reshape(T_loc, d)
        gates, idx, me, ce, z = _local_route({"router": router}, cfg, xf)

        C = max(int(T_loc * k / E * cfg.capacity_factor), 1)
        e_flat = idx.reshape(T_loc * k)
        t_flat = jnp.repeat(jnp.arange(T_loc, dtype=jnp.int32), k)
        w_flat = gates.reshape(T_loc * k)
        order = jnp.argsort(e_flat)
        e_s, t_s, w_s = e_flat[order], t_flat[order], w_flat[order]
        start = jnp.searchsorted(e_s, e_s, side="left")
        pos = jnp.arange(T_loc * k, dtype=jnp.int32) - start.astype(jnp.int32)
        keep = pos < C
        dst = jnp.where(keep, e_s * C + pos, E * C)
        send = jnp.zeros((E * C, d), x.dtype).at[dst].set(xf[t_s], mode="drop")
        send = send.reshape(Dd, E_loc * C, d)

        # route tokens to expert owners (one hop over the EP axes)
        recv = jax.lax.all_to_all(send, baxes, 0, 0, tiled=False) \
            if baxes else send
        ebatch = recv.reshape(Dd, E_loc, C, d).transpose(1, 0, 2, 3) \
            .reshape(E_loc, Dd * C, d)

        dt = x.dtype
        g = jnp.einsum("ecd,edf->ecf", ebatch, gate_w.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", ebatch, up_w.astype(dt))
        hact = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        out = jnp.einsum("ecf,efd->ecd", hact, down_w.astype(dt))

        back = out.reshape(E_loc, Dd, C, d).transpose(1, 0, 2, 3) \
            .reshape(Dd, E_loc * C, d)
        got = (jax.lax.all_to_all(back, baxes, 0, 0, tiled=False)
               if baxes else back).reshape(E * C, d)
        routed = got[jnp.minimum(dst, E * C - 1)]
        contrib = routed * (w_s * keep).astype(routed.dtype)[:, None]
        y = jnp.zeros((T_loc, d), x.dtype).at[t_s].add(contrib)

        all_axes = tuple(mesh.axis_names)
        aux = cfg.aux_loss_coef * E * jnp.sum(
            jax.lax.pmean(me, all_axes) * jax.lax.pmean(ce, all_axes))
        zl = cfg.router_z_coef * jax.lax.pmean(z, all_axes)
        dropped = 1.0 - jax.lax.pmean(jnp.sum(keep) / (T_loc * k), all_axes)
        return y.reshape(B_loc, S_loc, d), aux, zl, dropped

    P_ = jax.sharding.PartitionSpec
    bspec = xspec if xspec else None
    sspec = model_axis if x.shape[1] % mesh.shape[model_axis] == 0 else None
    espec = baxes if baxes else None
    y, aux, zl, dropped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P_(bspec, sspec, None),           # x: batch + seq sharded
                  P_(),                             # router (replicated)
                  P_(espec, None, None),            # experts on EP rows,
                  P_(espec, None, None),            # ff gathered over model
                  P_(espec, None, None)),
        out_specs=(P_(bspec, sspec, None), P_(), P_(), P_()),
        check_vma=False,
    )(x, params["router"], params["gate"], params["up"], params["down"])

    if "shared" in params:
        from repro.models.mlp import swiglu
        y = y + swiglu(params["shared"], x)
    return y, {"moe_aux": aux, "moe_z": zl, "moe_dropped": dropped}
