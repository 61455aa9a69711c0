"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script's phases take their sizes as arguments; here they run the
full mega-grid and the daemon's stream at 256 stores per cell on the
8 virtual CPU devices, with every check they make on the chip. The
device check itself must refuse the CPU. Also pins where the entry
points put JAX's persistent compilation cache.
"""

import importlib.util
from pathlib import Path

import jax
import pytest

from repro import compile_cache
from repro.core.scenarios import mega_grid

ROOT = Path(__file__).resolve().parents[1]
N = 256


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_refuses_cpu(smoke):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit, match="needs a TPU"):
        smoke.require_tpu(1)


def test_sweep_phase_small(smoke):
    out = smoke.phase_sweep(mega_grid(), n_stores=N)
    assert out["cells"] == 12_960
    assert out["warm_compiles"] == 0
    assert out["oracle_cells"] >= 32


def test_serve_phase_small(smoke):
    out = smoke.phase_serve(n_stores=N, n_queries=60)
    assert out["compiles"] == 0
    assert 0 < out["hit_ratio"] < 1


def test_shard_phase_small(smoke):
    out = smoke.phase_shards(mega_grid(), n_stores=N, n_shards=4)
    assert out["bytes_ratio"] < 0.3


def test_oracle_sample_covers_every_stratum(smoke):
    specs = mega_grid()
    picked = smoke.oracle_sample(specs, 32, seed=0, sb_default=72)
    assert len(picked) >= 32 and len(set(picked)) == len(picked)
    strata = {(specs[i].config, specs[i].sb_size) for i in picked}
    assert strata == {(s.config, s.sb_size) for s in specs}


def test_compile_cache_dir(monkeypatch):
    assert (compile_cache.CHECKOUT_ROOT / "chip_smoke.py").is_file()
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(ROOT / ".jax_cache")
        assert compile_cache.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
