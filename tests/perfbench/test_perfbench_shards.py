"""A cell whose table is split over several chips: its configuration's
``shards``, the per-device shard builds under the engine's router, the
mesh engine, and the trace readers over every chip of the cell."""
import json
import os
import random
import subprocess
import sys
import textwrap
import time
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import devtrace, harness, roofline
from perfbench.devtrace import Event
from perfbench_tiny import REPO, SEED, make_root

TPU = [devtrace.plane(i) for i in range(4)]
KIND = "TPU v5 lite"


def add_sharded_cell(root, name="tiny2", shards=2, chips=2):
    """A configuration of one 1,000-record table split over ``shards``
    shards of 64 buckets each, and its YCSB B cell ``<name>.b``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "perfbench/configs/tiny1.json").read_text())
    cfg.update(name=name, shards=shards)
    (root / f"perfbench/configs/{name}.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"perfbench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": f"{name}.b", "config": name,
                               "traffic": "tiny_tiny1_b", "chips": chips,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cfg


# the untraced and traced runs of the two-shard cell, and one whose shard
# builds place keys by another router than the engine routes them with
RUNS = textwrap.dedent("""
    import json, sys, time
    from pathlib import Path
    from perfbench import harness
    harness.enable_cache = lambda root: None
    root = Path(sys.argv[1])

    def run(tag, trace=False):
        try:
            res = harness.run(root, "tiny2.b", {seed}, 1.0, trace,
                              time.perf_counter())[0]
        except Exception as e:
            res = {{"raised": repr(e)}}
        print("RESULT " + json.dumps(dict(res, tag=tag)), flush=True)

    run("plain")
    run("traced", trace=True)
    engine_router = harness.router()
    harness.router = lambda: "mod" if engine_router != "mod" else "highbits"
    run("other_router")
""").format(seed=SEED)


@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    """(the two-shard configuration, {tag: result}) from one process that
    sees two forced CPU devices."""
    root = make_root(tmp_path_factory.mktemp("bench"))
    cfg = add_sharded_cell(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    r = subprocess.run([sys.executable, "-c", RUNS, str(root)], cwd=root,
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    results = [json.loads(line[len("RESULT "):])
               for line in r.stdout.splitlines() if line.startswith("RESULT ")]
    return cfg, {res.pop("tag"): res for res in results}


def test_sharded_cell_runs_correct_over_both_chips(sharded):
    cfg, runs = sharded
    res = runs["plain"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["wrong_answers"]["value"] == 0
    assert res["checks"]["unanswered_ops"]["value"] == 0
    assert set(res["metrics"]) == {"ops_per_s", "table_bytes_per_user_byte",
                                   "setup_s"}
    assert res["device"]["count"] == 2
    # YCSB B's updates keep the live pairs at the records: both shards'
    # tables over the records' bytes
    import jax

    from repro.core import hashmap
    one = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
        hashmap.create(harness.table_config(cfg))))
    want = 2 * one / (cfg["records_per_tenant"] * roofline.PAGE_ENTRY_BYTES)
    assert res["metrics"]["table_bytes_per_user_byte"]["value"] == \
        pytest.approx(want)


def test_sharded_cell_traced_run_is_correct(sharded):
    res = sharded[1]["traced"]
    assert res["correct"]
    assert {"tick_ms", "gather_ms_per_tick",
            "request_p99_ms.closed_loop"} <= set(res["metrics"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_build_under_another_router_is_not_correct(sharded):
    """Shards built by another router than the engine routes with put keys
    where the engine never looks: the check must see it."""
    res = sharded[1]["other_router"]
    assert "raised" in res or (res["correct"] is False
                               and res["checks"]["wrong_answers"]["value"] > 0)


@pytest.mark.parametrize("shards,chips", [(2, 1), (1, 4), (4, 2)])
def test_chips_that_differ_from_shards_are_refused_before_set_up(
        tmp_path, monkeypatch, shards, chips):
    root = make_root(tmp_path)
    add_sharded_cell(root, shards=shards, chips=chips)
    if shards == 1:           # a configuration that leaves shards out
        path = root / "perfbench/configs/tiny2.json"
        cfg = json.loads(path.read_text())
        del cfg["shards"]
        path.write_text(json.dumps(cfg))

    def no_set_up(*args, **kwargs):
        raise AssertionError("set-up ran")
    monkeypatch.setattr(harness, "set_up", no_set_up)
    monkeypatch.setattr(harness, "enable_cache", no_set_up)
    with pytest.raises(ValueError, match=rf"{chips} chip.*{shards} shard"):
        harness.run(root, "tiny2.b", SEED, 1.0, False, time.perf_counter())


def test_shard_capacity_holds_every_shard_of_the_paper_table():
    """The room a shard build makes holds what the engine's router gives
    each shard of keys 0..N-1, here for 4 shards of 4M records."""
    from repro.core import rlu
    cfg = json.loads((REPO / "perfbench/configs/paper100m.json").read_text())
    cfg.update(shards=4, records_per_tenant=1 << 22)
    keys = np.arange(1 << 22, dtype=np.uint32)
    owners = rlu.owner_of_np(keys, harness.table_config(cfg), 4,
                             harness.router())
    counts = np.bincount(owners, minlength=4)
    assert counts.sum() == 1 << 22
    assert counts.max() <= harness.shard_capacity(cfg)
    harness.check_owned(cfg, counts.tolist())
    with pytest.raises(RuntimeError):
        harness.check_owned(cfg, [counts[0] - 1] + counts[1:].tolist())


# ---------------------------------------------------------------------------
# readers over several chips, on synthetic events
# ---------------------------------------------------------------------------

def _op(plane, name, start, dur):
    return Event(plane, devtrace.OPS_LINE,
                 f"%{name} = u32[512]{{0}} fusion(u32[512]{{0}} %p.1)",
                 start, dur)


def _mod(plane, name, start, dur):
    return Event(plane, devtrace.MODULES_LINE, f"{name}(7)", start, dur)


# chip 0 busy [100,400), chip 1 busy [300,500) and [800,900); a chip that
# is not the cell's runs [0,1000)
MULTI = [
    _mod(TPU[0], "jit_tick", 90, 320),
    _op(TPU[0], "hashmem_probe_perf.1", 100, 200),
    _op(TPU[0], "fusion.1", 250, 150),
    _mod(TPU[1], "jit_mesh", 80, 440),
    _op(TPU[1], "hashmem_probe_perf.1", 300, 100),
    _op(TPU[1], "fusion.1", 400, 100),
    _mod(TPU[1], "jit_other", 790, 120),
    _op(TPU[1], "copy.2", 800, 100),
    _op(TPU[3], "fusion.9", 0, 1000),
    Event("/host:CPU", "python", "perfbench_window", 0, 1000),
    Event("/host:CPU", "python", "tick", 50, 600),
]


def _view(device_ids, events=MULTI, ticks=2):
    planes = [devtrace.plane(i) for i in device_ids]
    return harness.TraceView(devtrace.on_planes(events, planes), 0.0, 1000.0,
                             ticks, 0, [], device_ids)


def _record(view, config, schedule=()):
    cell = harness.Cell("t", len(view.device_ids or [0]), config,
                        {}, [], [])
    return harness.RunRecord(cell, KIND, view.device_ids or [0], 1.0,
                             view.ticks, 0, [0.1],
                             SimpleNamespace(schedule=list(schedule)), [],
                             trace=view)


TABLE = {"num_buckets": 64, "slots_per_page": 512, "salt": 0x9E3779B9}


def test_on_planes_keeps_the_cells_chips_and_the_host():
    kept = devtrace.on_planes(MULTI, TPU[:2])
    assert {e.plane for e in kept} == {TPU[0], TPU[1], "/host:CPU"}
    assert len(kept) == len(MULTI) - 1


def test_busy_and_idle_share_average_over_the_cells_chips():
    view = _view([0, 1])
    assert devtrace.busy_ns(view.events, 0, 1000, view.planes) == \
        (300 + 300) / 2
    idle = harness.metric_reader(REPO, "device_idle_pct")
    assert idle(_record(view, {"table": TABLE})) == \
        100.0 * (1 - 300 / 1000)
    # a third chip of the cell that ran nothing counts idle
    view3 = _view([0, 1, 2])
    assert devtrace.busy_ns(view3.events, 0, 1000, view3.planes) == 200
    assert idle(_record(view3, {"table": TABLE})) == 100.0 * (1 - 0.2)


def test_gaps_are_where_every_chip_is_idle():
    view = _view([0, 1])
    assert devtrace.gaps(view.events, 0, 1000, view.planes) == \
        [(0, 100), (500, 800), (900, 1000)]
    # a chip of the cell with no events leaves the others' gaps as they are
    assert devtrace.gaps(MULTI, 0, 1000, [TPU[1], TPU[2]]) == \
        [(0, 300), (500, 800), (900, 1000)]
    idle = harness.breakdown(view)["idle_gaps"]
    assert [n for n, _ in idle] == ["harness:loop", "harness:tick"]
    assert [s for _, s in idle] == pytest.approx([400e-9, 100e-9])


def test_ops_are_named_by_their_own_chips_program():
    """Chip 1's ops at 300 and 400 lie inside chip 0's program, which
    started after chip 1's; they are named by chip 1's."""
    view = _view([0, 1])
    assert devtrace.time_by_name(view.events, 0, 1000) == {
        "jit_tick/hashmem_probe_perf.1": 200, "jit_tick/fusion.1": 150,
        "jit_mesh/hashmem_probe_perf.1": 100, "jit_mesh/fusion.1": 100,
        "jit_other/copy.2": 100}


def test_probe_roofline_sums_bytes_and_kernel_time_over_the_chips():
    """Least bytes: distinct rows of each call's keys over shards x
    num_buckets rows; kernel time: summed over the cell's chips."""
    keys = [list(range(0, 300)), list(range(300, 350))]
    sched = [(t, "read", (k,), None, {}) for t, ks in enumerate(keys)
             for k in ks]
    config = {"table": TABLE, "shards": 2}
    got = harness.metric_reader(REPO, "probe_roofline")(
        _record(_view([0, 1]), config, sched))
    rows = [len(set(roofline.bucket_of(np.asarray(ks), 128,
                                       TABLE["salt"]).tolist()))
            for ks in keys]
    least = sum(rows) * 512 * 8
    assert got == 100.0 * least / 819e9 / (300 * 1e-9)


# ---------------------------------------------------------------------------
# one chip reads what the readers read before they took several chips
# ---------------------------------------------------------------------------

def _one_plane_busy(events, lo, hi):
    planes = devtrace.device_planes(events)
    if not planes:
        return 0.0
    total = sum(sum(e - s for s, e in devtrace.merged(
        ((o.start_ns, o.end_ns) for o in devtrace.ops(events, p)), lo, hi))
        for p in planes)
    return total / len(planes)


def _one_plane_gaps(events, lo, hi):
    planes = devtrace.device_planes(events)
    if not planes:
        return []
    busy = devtrace.merged(((o.start_ns, o.end_ns)
                            for o in devtrace.ops(events, planes[0])), lo, hi)
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _one_plane_time_by_name(events, lo, hi):
    import bisect
    mods = sorted((m.start_ns, m.end_ns, devtrace.short_name(m.name))
                  for m in devtrace.modules(events))
    starts = [m[0] for m in mods]
    out: dict = {}
    for e in devtrace.ops(events):
        d = min(e.end_ns, hi) - max(e.start_ns, lo)
        if d <= 0:
            continue
        i = bisect.bisect_right(starts, e.start_ns) - 1
        prog = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else "?"
        name = f"{prog}/{devtrace.short_name(e.name)}"
        out[name] = out.get(name, 0.0) + d
    return out


def _one_plane_idle(t):
    busy = _one_plane_busy(t.events, t.lo_ns, t.hi_ns)
    return 100.0 * (1.0 - busy / (t.hi_ns - t.lo_ns)) if busy else None


def _one_plane_roofline(run):
    t = run.trace
    ns = devtrace.matching_ns(devtrace.ops(t.events), "hashmem_probe_perf",
                              t.lo_ns, t.hi_ns)
    keys = run.probe_keys(t.first_tick, t.first_tick + t.ticks)
    least = roofline.probe_least_bytes(keys, run.cell.config["table"])
    return 100.0 * least / 819e9 / (ns * 1e-9)


@pytest.mark.parametrize("seed", range(5))
def test_one_chip_reads_as_the_one_plane_readers(seed):
    """On random one-chip traces every reader gives the very number the
    readers gave before they read several chips."""
    rng = random.Random(seed)
    events, t = [], 0.0
    for _ in range(200):
        t += rng.uniform(0, 50)
        if rng.random() < 0.3:
            events.append(_mod(TPU[0], rng.choice(["jit_probe", "jit_insert"]),
                               t, rng.uniform(1, 80)))
        name = rng.choice(["hashmem_probe_perf.1", "fusion.2", "copy.3"])
        events.append(_op(TPU[0], name, t + rng.uniform(0, 5),
                          rng.uniform(0.5, 40)))
    lo, hi = rng.uniform(0, 500), t - rng.uniform(0, 500)
    spans = [("engine:gather", lo + 10, lo + 900)]
    events.append(Event("/host:CPU", "python", "tick", lo, hi - lo))
    view = harness.TraceView(devtrace.on_planes(events, [TPU[0]]), lo, hi,
                             40, 3, spans, [0])
    assert devtrace.busy_ns(view.events, lo, hi, view.planes) == \
        _one_plane_busy(events, lo, hi)
    assert devtrace.gaps(view.events, lo, hi, view.planes) == \
        _one_plane_gaps(events, lo, hi)
    assert devtrace.time_by_name(view.events, lo, hi) == \
        _one_plane_time_by_name(events, lo, hi)
    sched = [(3 + i % 40, "read", (rng.randrange(1 << 20),), None, {})
             for i in range(2000)]
    run = _record(view, {"table": TABLE}, sched)
    assert harness.metric_reader(REPO, "device_idle_pct")(run) == \
        _one_plane_idle(view)
    assert harness.metric_reader(REPO, "probe_roofline")(run) == \
        _one_plane_roofline(run)
