"""chip_smoke.py's phases at a tiny size on CPU (Pallas in interpret mode),
so the script cannot rot between chip runs.  Its ``main()`` refuses to run
without a TPU; the phases are driven directly here."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs.base import HashMemConfig

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = HashMemConfig(num_buckets=8, slots_per_page=128, overflow_pages=8,
                     max_chain=4)


def _run(backend: str):
    store = chip_smoke.build_store(TINY, records=48, slots=8, seed=3,
                                   backend=backend)
    reqs = chip_smoke.serve(store, 16)
    return store, reqs


@pytest.mark.parametrize("backend", ["ref", "perf"])
def test_store_serve_check_tiny(backend):
    store, reqs = _run(backend)
    assert sum(len(v) for v in store.values) == 8 * 48
    assert all(r.done() for r in reqs)
    assert sum(len(r.results) for r in reqs) == chip_smoke.OPS * len(reqs)
    res = chip_smoke.check(store)
    assert res["mismatches"] == 0, res["first"]
    # every op of A/B/C/F answers: a read 1 check, an update 2, an rmw 3
    assert res["checked"] >= len(reqs) * chip_smoke.OPS


def test_check_counts_a_wrong_answer():
    store, _ = _run("ref")
    reads = [res for _, kind, _, _, res in store.engine.schedule
             if kind == "read" and res["found"]]
    reads[0]["value"] ^= 1
    res = chip_smoke.check(store)
    assert res["mismatches"] == 1


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "Nothing was run" in out.err


def test_four_chip_phase_on_forced_host_devices():
    """The --chips 4 phase, tiny, on 4 forced CPU devices: each device holds
    its quarter of the pool and the mesh answers equal the one-device
    host-shard answers."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = textwrap.dedent("""
        import chip_smoke
        from repro.configs.base import HashMemConfig
        chip_smoke.four_chips(
            5, 4, HashMemConfig(num_buckets=32, slots_per_page=128,
                                overflow_pages=32, max_chain=4),
            records=48, slots=8, requests=16)
    """)
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "requests_differing=0" in r.stdout


@pytest.mark.parametrize("env_dir", [None, "placed/from/outside"])
def test_compile_cache_placement(env_dir, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache is the fixed <checkout>/.jax_cache."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        got = enable_compile_cache()
        if env_dir is None:
            assert got == os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
            assert (jax.config.jax_persistent_cache_min_compile_time_secs
                    == min_s)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
