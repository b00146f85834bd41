"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (harness contract) for:
  fig4  — bucket-length distribution (paper Fig. 4)
  fig5  — CPU data-structure probe times, measured (paper Fig. 5)
  fig6  — HashMem modeled speedups vs paper's claims (paper Fig. 6)
  kern  — probe-kernel VMEM footprints + interpret-mode timings (§4.3 analogue)
  roofline — per-cell terms from dry-run artifacts, if present (§Roofline)
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _emit(name, us, derived):
    print(f"{name},{us},{derived}")


def main() -> None:
    from benchmarks import fig4_buckets, fig5_cpu_baselines, fig6_hashmem
    from benchmarks import kernel_bench
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    for r in fig4_buckets.run(n_words=30_000):
        _emit(r["name"], "",
              f"cv={r['cv']:.3f};max={r['max_len']};"
              f"under={r['frac_under_half']:.2f};over={r['frac_over_2x']:.2f}")

    measured = fig5_cpu_baselines.run(n=1 << 20)
    for r in measured:
        _emit(r["name"], f"{r['us_per_probe']:.4f}", "measured on container")

    for r in fig6_hashmem.run(measured_cpu=measured):
        derived = ";".join(f"{k}={v}" for k, v in r.items() if k != "name")
        _emit(r["name"], f"{r.get('ns_per_probe', 0) / 1e3:.5f}"
              if "ns_per_probe" in r else "", derived)

    for r in kernel_bench.run():
        _emit(r["name"], f"{r.get('us_per_probe', '')}",
              ";".join(f"{k}={v}" for k, v in r.items()
                       if k not in ("name", "us_per_probe")))

    # roofline from the self-consistent optimized grid (falls back to the
    # default dry-run dir); baseline-vs-opt comparison: benchmarks/perf_compare
    root = os.path.join(os.path.dirname(__file__), "..", "artifacts")
    art = os.path.join(root, "dryrun_opt")
    if not os.path.isdir(art):
        art = os.path.join(root, "dryrun")
    if os.path.isdir(art) and len(os.listdir(art)) > 10:
        from benchmarks import roofline
        rows = roofline.assemble(art_dir=art)
        for r in rows:
            if not r.get("ok") or r.get("flops_dev") is None:
                continue
            _emit(f"roofline_{r['arch']}_{r['shape']}", "",
                  f"dominant={r['dominant']};bound_s={r['bound_s']:.4e};"
                  f"roofline_frac={r.get('roofline_frac', 0):.4f};"
                  f"useful={r.get('useful_ratio', 0):.3f}")


if __name__ == "__main__":
    main()
