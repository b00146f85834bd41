"""Serving CLI: thin front-end over the request engine (repro.serving).

Two modes:

  * ``decode`` (default) — batched LM decode with the HashMem-managed paged
    KV cache.  Slot lifecycle and admission come from the serving engine's
    ``SlotPool``; all page-table traffic in a step is COALESCED — one
    batched HashMem delete for every sequence finishing in the step
    (``free_seqs``) and one batched insert for every sequence admitted in
    it (``alloc_seqs``) — and ``PageTableManager.tick()`` runs the
    compaction triggers on the step clock, not just on frees.

  * ``kv`` — the multi-tenant continuous-batching KV engine under a
    YCSB-style load (repro.serving.engine + loadgen): per-tenant workloads
    A-F, admission quotas, step-level op coalescing, JSON metrics.

CPU-scale usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --requests 12 --batch 4 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --mode kv \
      --workloads A,B,E --requests 64 --slots 16
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ServeConfig, get_config, smoke_config
from repro.configs.base import ShapeConfig
from repro.core.paged_kv import PageTableManager
from repro.launch.compile_cache import enable_compile_cache
from repro.distributed import steps as dsteps
from repro.launch.mesh import make_mesh
from repro.models import model
from repro.serving.engine import SlotPool


def serve(cfg, mesh, *, batch=4, horizon=256, page_tokens=32, requests=8,
          max_new=16, prompt_len=8, seed=0, backend="ref", verbose=True,
          compact_chain_len=None):
    shape = ShapeConfig("serve", horizon, batch, "decode")
    scfg = ServeConfig(model=cfg, shape=shape, kv_page_tokens=page_tokens)
    serve_step, jitted, ctx, pshard = dsteps.build_serve_step(cfg, scfg, mesh)
    Dm = 1
    for a in ctx.channel_axes:
        Dm *= mesh.shape[a]
    n_groups = 1
    for a in ctx.batch_axes:
        n_groups *= mesh.shape[a]
    b_loc = batch // n_groups

    params = model.init_params(cfg, jax.random.PRNGKey(seed))
    states = model.init_decode_states(params, cfg, batch, ctx,
                                      kv_dtype=jnp.float32)
    step_fn = jitted(states)

    mgr = PageTableManager(ctx.pool_pages, num_channels=Dm,
                           num_groups=n_groups, backend=backend,
                           compact_chain_len=compact_chain_len)
    rng = np.random.default_rng(seed)

    pool = SlotPool(batch)
    block_tables = np.zeros((batch, ctx.n_pages), np.int32)
    pos = np.zeros((batch,), np.int32)
    tokens = np.zeros((batch, 1), np.int32)
    done = []
    t0 = time.time()
    steps_run = 0

    def place(newly):
        """Coalesced admission: ONE page-table insert for every sequence
        admitted this step, then per-slot decode-state reset."""
        if not newly:
            return
        phys = mgr.alloc_seqs([(req["id"], ctx.n_pages, slot // b_loc)
                               for slot, req in newly])
        for slot, req in newly:
            block_tables[slot] = phys[req["id"]]
            pos[slot] = 0
            tokens[slot, 0] = req["prompt"][0]
            req["fed"] = 1

    for i in range(requests):
        pool.submit({"id": i,
                     "prompt": rng.integers(0, cfg.vocab_size,
                                            prompt_len).tolist(),
                     "out": []})
    place(pool.active())

    while not pool.idle():
        bt = jnp.asarray(block_tables)
        nt, logits, states = step_fn(params, states, jnp.asarray(tokens),
                                     jnp.asarray(pos), bt)
        nt = np.asarray(nt)
        steps_run += 1
        finished = []
        for b, req in pool.active():
            pos[b] += 1
            if req["fed"] < len(req["prompt"]):
                tokens[b, 0] = req["prompt"][req["fed"]]   # prompt feeding
                req["fed"] += 1
            else:
                req["out"].append(int(nt[b]))
                tokens[b, 0] = int(nt[b])
                if len(req["out"]) >= max_new or pos[b] >= horizon - 1:
                    finished.append((b, req))
        # tombstone + recycle: ONE batched delete for the whole step
        mgr.free_seqs([req["id"] for _, req in finished])
        for b, req in finished:
            pool.release(b)
            done.append(req)
        place(pool.refill())
        mgr.tick()             # step-clock compaction (not only on frees)

    dt_val = time.time() - t0
    if verbose:
        print(f"served {len(done)} requests in {steps_run} decode steps, "
              f"{dt_val:.1f}s; live pages after drain: {mgr.live_pages()}; "
              f"page-table grows={mgr.grow_events} "
              f"compactions={mgr.compact_events}")
        for req in done[:4]:
            print(f"  req {req['id']}: prompt {req['prompt'][:4]}... -> "
                  f"out {req['out'][:8]}")
    return done, mgr, steps_run


def serve_kv(*, workloads="A", tenants=None, requests=64, slots=16,
             shards=1, record_count=1024, ops_per_request=4,
             max_pending=0, tenant_slots=0, seed=0, backend="perf",
             mesh_shards=0, pipeline=1, fused_tick=None, verbose=True,
             trace_out=None, metrics_prom=None):
    """Thin driver over the multi-tenant KV serving engine: one tenant per
    workload letter (comma-separated), YCSB load phase, then a drained
    continuous-batching run.  ``mesh_shards`` > 0 routes the table through
    the RLU mesh path (one shard per device on a 1-D 'model' mesh — needs
    that many jax devices, e.g. via
    XLA_FLAGS=--xla_force_host_platform_device_count=N); ``pipeline`` > 1
    enables multi-tick op pipelining; ``fused_tick=False`` falls back from
    the fused whole-tick megakernel (the mesh default: ONE shard_map per
    tick) to one shard_map call per phase.  ``trace_out`` turns on tick
    tracing and writes Chrome/Perfetto trace-event JSON there after the
    drain (open in https://ui.perfetto.dev or inspect with
    tools/trace_report.py); ``metrics_prom`` writes the Prometheus text
    exposition of the run's metrics.  Returns (engine, snapshot)."""
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import build_ycsb_engine

    wls = [w.strip().upper() for w in workloads.split(",") if w.strip()]
    n_tenants = tenants or len(wls)
    mesh = make_serving_mesh(mesh_shards) if mesh_shards else None
    eng, gens = build_ycsb_engine(
        [wls[i % len(wls)] for i in range(n_tenants)], slots=slots,
        shards=shards, record_count=record_count,
        ops_per_request=ops_per_request, backend=backend, seed=seed,
        max_pending=max_pending, tenant_slots=tenant_slots, mesh=mesh,
        pipeline_depth=pipeline, fused_tick=fused_tick,
        trace=bool(trace_out))
    per = requests // n_tenants
    reqs = [r for g in gens for r in g.requests(per)]
    eng.submit_all(reqs)
    snap = eng.run()
    if trace_out:
        n = eng.export_trace(trace_out, workloads=workloads)
        if verbose:
            print(f"wrote {n} trace events -> {trace_out}")
    if metrics_prom:
        with open(metrics_prom, "w") as f:
            f.write(eng.metrics.to_prom())
        if verbose:
            print(f"wrote Prometheus exposition -> {metrics_prom}")
    if verbose:
        print(json.dumps({**snap, "engine": eng.stats()}, indent=2,
                         default=str))
    return eng, snap


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="decode", choices=["decode", "kv"])
    ap.add_argument("--arch", default=None, help="(decode mode) model arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--horizon", type=int, default=256)
    ap.add_argument("--page-tokens", type=int, default=32)
    ap.add_argument("--backend", default="perf",
                    choices=["ref", "perf", "area", "bitserial"])
    ap.add_argument("--mesh", type=int, nargs="*", default=None)
    ap.add_argument("--compact-chain-len", type=int, default=None,
                    help="page-table compaction when any bucket chain "
                         "exceeds this many pages (skewed frees); default: "
                         "tombstone-fraction trigger only")
    # kv-mode knobs (repro.serving)
    ap.add_argument("--workloads", default="A",
                    help="(kv mode) comma-separated YCSB letters, one "
                         "tenant per entry, e.g. A,B,E")
    ap.add_argument("--slots", type=int, default=16,
                    help="(kv mode) concurrent request slots")
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--record-count", type=int, default=1024)
    ap.add_argument("--ops-per-request", type=int, default=4)
    ap.add_argument("--mesh-shards", type=int, default=0,
                    help="(kv mode) >0: mesh-backed shards, one per device "
                         "on a 1-D 'model' mesh (set XLA_FLAGS to force "
                         "host devices); 0: host-routed shards")
    ap.add_argument("--pipeline", type=int, default=1,
                    help="(kv mode) multi-tick op pipelining depth "
                         "(1 = off)")
    ap.add_argument("--no-fused-tick", action="store_true",
                    help="(kv mode) use one shard_map call per phase "
                         "instead of the fused whole-tick megakernel "
                         "(mesh default)")
    ap.add_argument("--trace-out", default=None,
                    help="(kv mode) enable tick tracing and write "
                         "Chrome/Perfetto trace-event JSON here "
                         "(tools/trace_report.py reads it)")
    ap.add_argument("--metrics-prom", default=None,
                    help="(kv mode) write the Prometheus text exposition "
                         "of the run's metrics here")
    args = ap.parse_args()
    enable_compile_cache()

    if args.mode == "kv":
        serve_kv(workloads=args.workloads, requests=args.requests,
                 slots=args.slots, shards=args.shards,
                 record_count=args.record_count,
                 ops_per_request=args.ops_per_request,
                 backend=args.backend, mesh_shards=args.mesh_shards,
                 pipeline=args.pipeline,
                 fused_tick=False if args.no_fused_tick else None,
                 trace_out=args.trace_out, metrics_prom=args.metrics_prom)
        return

    if args.arch is None:
        ap.error("--arch is required in decode mode")
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_mesh(tuple(args.mesh) if args.mesh else (1, 1),
                     ("data", "model"))
    serve(cfg, mesh, batch=args.batch, requests=args.requests,
          max_new=args.max_new, horizon=args.horizon,
          page_tokens=args.page_tokens, backend=args.backend,
          compact_chain_len=args.compact_chain_len)


if __name__ == "__main__":
    main()
