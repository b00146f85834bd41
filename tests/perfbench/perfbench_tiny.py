"""A tiny copy of the benchmark to drive on the CPU: the real harness and
files, plus a configuration of 2 tenants x 1,000 records in a 64-bucket
table (the paper's configuration, cut down, with the engine's tenant
folding on) and two mixes over it, added as new files and entries."""
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
SEED = 12345678901


def make_root(root: Path) -> Path:
    """``root`` holding BENCHMARK.json and perfbench/ with the tiny cells
    ``tiny.mixed`` (YCSB A and F on two tenants), ``tiny.read`` (C) and
    ``tiny1.b`` (B on one table with no tenants, as the paper's)."""
    shutil.copytree(REPO / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    paper = json.loads(
        (REPO / "perfbench/configs/paper100m.json").read_text())
    table = dict(paper["table"], num_buckets=64, slots_per_page=128,
                 overflow_pages=64, max_chain=4)
    for name, tenants, bits in (("tiny", 2, 8), ("tiny1", 1, 0)):
        cfg = dict(paper, name=name, tenants=tenants, tenant_bits=bits,
                   records_per_tenant=1000, table=table)
        (root / f"perfbench/configs/{name}.json").write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"perfbench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    for cell, wls in (("tiny.mixed", ["A", "F"]), ("tiny.read", ["C"]),
                      ("tiny1.b", ["B"])):
        mix = "tiny_" + cell.replace(".", "_")
        (root / f"perfbench/traffic/{mix}.json").write_text(json.dumps({
            "loop": "closed", "workloads": wls, "distribution": "zipfian",
            "theta": 0.99, "clients": 8, "ops_per_request": 4,
            "requests_per_client": 8, "warmup_ticks": 3}))
        bench["workloads"].append({"name": cell, "config": cell.split(".")[0],
                                   "traffic": mix, "chips": 1,
                                   "why": "test"})
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += ["tiny.mixed", "tiny1.b"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
