"""``chip_smoke.py`` rehearsed on the CPU: every phase at a tiny size, with
the Pallas kernels in interpret mode (the platform's choice off a TPU), so
the script's control flow and references stay exercised by the suite; and
its refusal to report anything without a TPU."""

import importlib
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(ROOT))
    try:
        yield importlib.import_module("chip_smoke")
    finally:
        sys.path.remove(str(ROOT))


def test_phase_serve_tiny(smoke):
    out = smoke.phase_serve(0, n_windows=192, n_requests=6, shards=2,
                            qps=500.0)
    assert out["mismatches"] == 0 and out["traces_timed"] == 0
    assert out["requests"] == 6 and out["hits"] > 0
    json.dumps(out)


def test_phase_float_lb_tiny(smoke):
    out = smoke.phase_float_lb(0, n_windows=160, n_queries=4, shards=2)
    assert out["mismatches"] == 0 and out["hits"] > 0
    assert out["lb_pruned"] > 0


def test_phase_matcher_tiny(smoke):
    out = smoke.phase_matcher(0, n_seqs=4, length=160)
    assert out["mismatches"] == 0 and out["hits"] == out["host_hits"] > 0


def test_phase_kernel_parity_tiny(smoke):
    out = smoke.phase_kernel_parity(0, B=16, L=10, tile=4)
    assert out["bands"] == 5
    assert set(out["modes"]) == set(smoke.WAVEFRONT_NAMES)
    assert all(m["hit_mismatches"] == 0 for m in out["modes"].values())


def test_jit_cache_check_rejects_interpreted_entries(smoke):
    from repro.kernels import registry
    registry.get("dtw").batch(*_pair(), exec="pallas")
    with pytest.raises(AssertionError, match="interpreted"):
        smoke.check_jit_cache()


def _pair():
    import numpy as np
    rng = np.random.default_rng(0)
    return (rng.normal(size=(4, 5, 2)).astype(np.float32),
            rng.normal(size=(4, 5, 2)).astype(np.float32))


def test_compile_cache_placed_from_outside(monkeypatch):
    import jax
    from repro.launch import compile_cache
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable() is None
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert (jax.config.jax_persistent_cache_min_compile_time_secs
                == compile_cache.MIN_COMPILE_SECS)
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev[1])


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err
    assert captured.out == ""
