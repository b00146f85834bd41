"""The harness's set-up, serve and check phases on a tiny table on the CPU
(Pallas in interpret mode), its data-driven cells, and faults planted under
the timed path that ``correct`` has to catch."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from perfbench import harness
from perfbench_tiny import REPO, SEED, make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    """No persistent compilation cache on the CPU: XLA:CPU's cached
    programs carry the compiling machine's features and warn on load."""
    monkeypatch.setattr(harness, "enable_cache", lambda root: None)


def _run(root, workload, trace=False, seconds=1.0):
    return harness.run(root, workload, SEED, seconds, trace,
                       time.perf_counter())[0]


@pytest.mark.parametrize("workload", ["tiny.mixed", "tiny.read", "tiny1.b"])
def test_untraced_run_reports_end_to_end_metrics(root, workload):
    res = _run(root, workload)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"ops_per_s", "table_bytes_per_user_byte",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["wrong_answers"] == {"value": 0, "limit": 0}


def test_traced_run_reports_per_layer_metrics(root):
    res = _run(root, "tiny.mixed", trace=True)
    assert res["correct"]
    # the CPU has no TPU plane: device readers find nothing and stay out
    assert {"tick_ms", "gather_ms_per_tick", "max_chain_pages",
            "request_p99_ms.closed_loop"} == set(res["metrics"])
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_declared_metric_that_reads_nothing_fails_on_the_chip(
        root, monkeypatch):
    """A per-layer metric the cell declares that reads nothing fails the
    run on the chip, naming what its reader looks for."""
    monkeypatch.setattr(harness, "on_chip", lambda devices: True)
    with pytest.raises(harness.SilentMetric, match="hashmem_probe_perf"):
        _run(root, "tiny.mixed", trace=True)


def test_same_seed_same_streams(root):
    cell = harness.load_cell(root, "tiny.mixed")
    _, tenants = harness.make_engine(
        cell.config, cell.traffic,
        harness.build_table(cell.config, (1, 2)))
    a = harness.make_clients(cell.config, cell.traffic, tenants, SEED)
    b = harness.make_clients(cell.config, cell.traffic, tenants, SEED)
    c = harness.make_clients(cell.config, cell.traffic, tenants, SEED + 1)
    assert [x.reqs for x in a] == [x.reqs for x in b]
    assert [x.reqs for x in a] != [x.reqs for x in c]
    assert len(a) == cell.traffic["clients"]


def test_added_files_run_without_edits(root, tmp_path):
    """A configuration, a traffic mix and a metric reader that are new
    files, named only by new BENCHMARK.json entries, are found and run."""
    extra = make_root(tmp_path)
    bench = json.loads((extra / "BENCHMARK.json").read_text())
    cfg = json.loads((extra / "perfbench/configs/tiny.json").read_text())
    cfg.update(name="tiny3", tenants=3, records_per_tenant=700)
    (extra / "perfbench/configs/tiny3.json").write_text(json.dumps(cfg))
    (extra / "perfbench/traffic/tiny_b.json").write_text(json.dumps({
        "loop": "closed", "workloads": ["B"], "distribution": "uniform",
        "clients": 6, "ops_per_request": 2, "requests_per_client": 5,
        "warmup_ticks": 2}))
    (extra / "perfbench/metrics/requests_done.py").write_text(
        "def read(run):\n    return len(run.latencies_s) or None\n")
    bench["configs"].append({"name": "tiny3", "source": "test",
                             "file": "perfbench/configs/tiny3.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny3.b", "config": "tiny3",
                               "traffic": "tiny_b", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "requests_done", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "harness", "moves": "ops_per_s",
                               "workloads": ["tiny3.b"]})
    (extra / "BENCHMARK.json").write_text(json.dumps(bench))
    res = _run(extra, "tiny3.b", trace=True)
    assert res["correct"]
    assert res["metrics"]["requests_done"]["value"] > 0
    assert harness.keyspace(harness.load_cell(extra, "tiny3.b").config).size \
        == 2100


def _plant(monkeypatch, name, wrap):
    """Replace the engine's jitted ``name`` program with ``wrap(program)``."""
    from repro.serving import engine
    orig = engine._jitted

    def jitted(kind):
        return wrap(orig(kind)) if kind == name else orig(kind)
    monkeypatch.setattr(engine, "_jitted", jitted)


def _state_unchanged(insert):
    def f(hm, keys, vals, valid):
        _, ok = insert(hm, keys, vals, valid)
        return hm, ok
    return f


def _half_left_out(probe):
    def f(hm, keys):
        vals, found = probe(hm, keys)
        real = int(np.sum(np.asarray(keys) != 0xFFFFFFF0))
        keep = np.arange(len(keys)) < real // 2
        return np.where(keep, vals, 0), np.asarray(found) & keep
    return f


def _answer_altered(probe):
    def f(hm, keys):
        vals, found = probe(hm, keys)
        return np.asarray(vals) ^ 1, found
    return f


@pytest.mark.parametrize("program,fault,workload", [
    ("insert", _state_unchanged, "tiny.mixed"),
    ("probe", _half_left_out, "tiny.read"),
    ("probe", _answer_altered, "tiny.read"),
])
def test_planted_faults_are_not_correct(root, monkeypatch, program, fault,
                                        workload):
    _plant(monkeypatch, program, fault)
    res = _run(root, workload, seconds=0.5)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_run_refuses_without_a_tpu(tmp_path):
    """Without a TPU, and in a directory holding only BENCHMARK.json and
    perfbench/, the command exits non-zero and prints no result."""
    for root in (REPO, make_root(tmp_path)):
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            [sys.executable, "perfbench/run.py", "--workload",
             "paper100m.ycsb_b", "--seed", "1", "--seconds", "1",
             "--trace", "0"], cwd=root, env=env, capture_output=True,
            text=True, timeout=300)
        assert r.returncode != 0 and r.stdout == ""


def test_control_readings_separate_program_and_control(root):
    """control.py's readings at a tiny size: the program reads 0 wrong
    answers, the stale-read control some."""
    from perfbench import control
    r = control.readings(root, "tiny.mixed", SEED, 0.5)
    assert r["program_wrong"] == 0 and r["unanswered_ops"] == 0
    assert r["control"] == "stale_reads" and r["control_wrong"] > 0


def test_op_log_seals_answered_ticks_off_the_collector():
    """Entries of answered ticks are sealed into plain tuples the collector
    stops tracking; iteration gives back every entry as the engine made it,
    a scan's answer lists as tuples, and the open tick untouched."""
    import gc
    log = harness.OpLog()
    made = [(0, "read", (5,), None, {"op": "read", "key": 5}),
            (0, "scan", (6, 7), None, {"op": "scan", "key": 6}),
            (1, "update", (8,), 3, {"op": "update", "key": 8})]
    for e in made:
        log.append(e)
    made[0][4].update(value=9, found=True)
    made[1][4].update(n=2, values=[1, 2], found=[True, True])
    log.seal(1)
    made[2][4].update(replaced=True, ok=True)
    assert len(log) == 3 and len(log.sealed) == 2 and len(log.open) == 1
    for _ in range(3):            # a pass untracks one level of tuples
        gc.collect()
    assert not any(gc.is_tracked(e) for e in log.sealed)
    got = list(log)
    assert got[0] == made[0] and got[2] is made[2]
    assert got[1][4] == dict(made[1][4], values=(1, 2), found=(True, True))
    log.seal(2)
    assert [e[:4] for e in log] == [e[:4] for e in made] and not log.open
