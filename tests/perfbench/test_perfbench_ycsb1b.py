"""The four-chip deployment ``ycsb1b`` in miniature, and the readers of its
mesh metrics.

The miniature is the configuration and traffic files of
``ycsb1b.mixed_zipf`` with the table cut to 4,000 records over four shards
of 64 buckets and the clients to 64; it runs through the real harness on
four forced CPU devices, untraced and traced.  The device-trace readers,
which find nothing on the CPU, read a synthetic trace of two chips."""
import json
import os
import re
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

from perfbench import harness
from perfbench.devtrace import Event
from perfbench_tiny import REPO, SEED, make_root
from repro.serving.tracing import Tracer
from test_perfbench_shards import TPU, _mod, _op, _record, _view

CELL = "ycsb1b.mixed_zipf"
MINI = "mini1b.mixed_zipf"


def add_mini(root):
    """The cell ``mini1b.mixed_zipf``: ``ycsb1b``'s files, cut to size,
    reporting every metric ``ycsb1b.mixed_zipf`` reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / "perfbench/configs/ycsb1b.json").read_text())
    cfg.update(name="mini1b", records_per_tenant=4000,
               table=dict(cfg["table"], num_buckets=64, slots_per_page=128,
                          overflow_pages=64, max_chain=4))
    traffic = json.loads(
        (REPO / "perfbench/traffic/mixed_zipf.json").read_text())
    traffic.update(clients=64, requests_per_client=8)
    (root / "perfbench/configs/mini1b.json").write_text(json.dumps(cfg))
    (root / "perfbench/traffic/mini_zipf.json").write_text(
        json.dumps(traffic))
    bench["configs"].append({"name": "mini1b", "source": "test",
                             "file": "perfbench/configs/mini1b.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": MINI, "config": "mini1b",
                               "traffic": "mini_zipf", "chips": 4,
                               "why": "test"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(MINI)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


RUNS = textwrap.dedent("""
    import json, sys, time
    from pathlib import Path
    from perfbench import harness
    harness.enable_cache = lambda root: None
    root = Path(sys.argv[1])
    for tag, trace in (("plain", False), ("traced", True)):
        res = harness.run(root, "{cell}", {seed}, 1.0, trace,
                          time.perf_counter())[0]
        print("RESULT " + json.dumps(dict(res, tag=tag)), flush=True)
""").format(cell=MINI, seed=SEED)


@pytest.fixture(scope="module")
def mini(tmp_path_factory):
    """({tag: result}, the runs' log) from one process that sees four
    forced CPU devices."""
    root = make_root(tmp_path_factory.mktemp("bench"))
    add_mini(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    r = subprocess.run([sys.executable, "-c", RUNS, str(root)], cwd=root,
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    results = [json.loads(line[len("RESULT "):])
               for line in r.stdout.splitlines() if line.startswith("RESULT ")]
    return {res.pop("tag"): res for res in results}, r.stderr


def test_the_cell_is_declared_with_its_four_metrics():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = harness.load_cell(REPO, CELL)
    assert cell.chips == harness.shards(cell.config) == 4
    assert cell.config["records_per_tenant"] == 10 ** 9
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in new} == {
        "route_ms_per_tick", "mesh_tick_device_ms_per_tick",
        "all_to_all_exposed_ms_per_tick", "route_buffer_fill"}
    assert {m["name"] for m in cell.per_layer} >= {m["name"] for m in new}


@pytest.mark.parametrize("tag", ["plain", "traced"])
def test_miniature_runs_correct_over_four_chips(mini, tag):
    res = mini[0][tag]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["wrong_answers"]["value"] == 0
    assert res["checks"]["unanswered_ops"]["value"] == 0
    assert res["device"]["count"] == 4


def test_no_window_compiles(mini):
    """The engine compiled every tick program before serving: neither
    window compiled."""
    counts = re.findall(r"perfbench: window_s=.* compiles=(\d+)", mini[1])
    assert counts == ["0", "0"], mini[1]


def test_traced_miniature_reads_the_host_side_mesh_metrics(mini):
    metrics = mini[0]["traced"]["metrics"]
    assert metrics["route_ms_per_tick"]["value"] > 0
    assert 0 < metrics["route_buffer_fill"]["value"] <= 100
    # the CPU has no TPU plane: the device readers find nothing
    assert "mesh_tick_device_ms_per_tick" not in metrics
    assert "all_to_all_exposed_ms_per_tick" not in metrics


# chip 0: the tick [0,400) with an all-to-all [100,200) that a fusion
# [150,250) half overlaps; chip 1: the tick [50,350) with an all-to-all
# [200,260) alone, and another program [600,700)
TWO = [
    _mod(TPU[0], "jit_tick_mesh", 0, 400),
    _op(TPU[0], "all_to_all.70", 100, 100),
    _op(TPU[0], "fusion.3", 150, 100),
    _op(TPU[0], "hashmem_probe_perf.1", 250, 100),
    _mod(TPU[1], "jit_tick_mesh", 50, 300),
    _op(TPU[1], "all-to-all.2", 200, 60),
    _mod(TPU[1], "jit_dynamic_slice", 600, 100),
    _op(TPU[1], "copy.1", 600, 100),
    Event("/host:CPU", "python", "perfbench_window", 0, 1000),
]


def _read(name, events, device_ids=(0, 1), engine=None):
    run = _record(_view(list(device_ids), events), {"table": {}})
    if engine is not None:
        run.engine = engine
    return harness.metric_reader(REPO, name)(run)


def test_exposed_all_to_all_leaves_out_what_other_ops_overlap():
    """(50 exposed on chip 0 + 60 on chip 1) ns / 2 chips / 2 ticks; a
    third chip of the cell that ran nothing counts in the chips."""
    got = _read("all_to_all_exposed_ms_per_tick", TWO)
    assert got == pytest.approx((50 + 60) / 2 / 2 * 1e-6)
    got3 = _read("all_to_all_exposed_ms_per_tick", TWO, (0, 1, 2))
    assert got3 == pytest.approx((50 + 60) / 3 / 2 * 1e-6)
    no_a2a = [e for e in TWO if "all" not in e.name]
    assert _read("all_to_all_exposed_ms_per_tick", no_a2a) is None


def test_mesh_tick_device_time_sums_the_tick_over_the_chips():
    """(400 + 300) ns / 2 chips / 2 ticks; a trace whose mesh programs
    are all ``jit_shard_fn`` reads that name; another program is not
    read."""
    want = (400 + 300) / 2 / 2 * 1e-6
    assert _read("mesh_tick_device_ms_per_tick", TWO) == pytest.approx(want)
    old = [Event(e.plane, e.line, e.name.replace("jit_tick_mesh",
                                                 "jit_shard_fn"),
                 e.start_ns, e.dur_ns) for e in TWO]
    assert _read("mesh_tick_device_ms_per_tick", old) == pytest.approx(want)
    other = [e for e in TWO if "jit_tick_mesh" not in e.name]
    assert _read("mesh_tick_device_ms_per_tick", other) is None


def test_route_buffer_fill_reads_the_engine_counters():
    """Real keys over routed rows from the engine's Tracer counters; an
    engine that counts no keys has them from its op log over the traced
    ticks (two of them from tick 0); no routed rows read nothing."""
    tr = Tracer()
    for rows, keys in ((64, 16), (128, 8)):
        tr.counter("routed_elems", rows)
        tr.counter("routed_keys", keys)
    eng = SimpleNamespace(tracer=tr, schedule=[])
    assert _read("route_buffer_fill", TWO, engine=eng) == \
        pytest.approx(100 * 24 / 192)
    old = Tracer()
    old.counter("routed_elems", 64)
    sched = [(0, "read", (1,), None, {}), (0, "update", (2,), 5, {}),
             (1, "rmw", (3,), 6, {}), (1, "scan", (4, 5, 6), None, {}),
             (2, "read", (7,), None, {})]
    eng = SimpleNamespace(tracer=old, schedule=sched)
    assert _read("route_buffer_fill", TWO, engine=eng) == \
        pytest.approx(100 * (1 + 2 + 3 + 3) / 64)
    eng = SimpleNamespace(tracer=Tracer(), schedule=sched)
    assert _read("route_buffer_fill", TWO, engine=eng) is None
