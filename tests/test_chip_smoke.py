"""``chip_smoke.py`` off the chip: its phases at a small size (soa-device
must equal soa), its refusal to run without a TPU, and the compile cache
placement its entry point sets."""

import importlib.util
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_match_host_engine_at_small_size(chip_smoke):
    lines = []
    out = chip_smoke.run_phases(n=2_000, n_expire=500, n_queries=100,
                                log=lines.append)
    # run_phases raises on any key, label, point-label or delta mismatch
    assert len(lines) == 5
    assert out["compiles_after_first_batch"] <= 2 * 15  # 2*log2(n*t)
    assert out["slot_capacity"] >= out["slots_used"] > 0
    assert out["ari"] > 0.85


def test_main_refuses_without_a_tpu(chip_smoke, capsys):
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    assert chip_smoke.main() != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_placement(env_set, tmp_path, monkeypatch):
    from repro.compile_cache import enable_compile_cache

    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(ROOT / ".jax_cache")
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])
