"""chip_smoke.py refuses a host without a TPU, and its two legs hold parity
when rehearsed on the CPU at a tiny size (the chip run is the real one)."""

import os

import jax
import pytest

import chip_smoke
from trivy_tpu import enable_compile_cache
from trivy_tpu.fanal.analyzers import secret as secret_analyzer


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_refuses_a_host_without_a_tpu(monkeypatch, capsys, cache_config):
    for k, v in (("TPU_VISIBLE_CHIPS", "0"),
                 ("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1"),
                 ("TPU_PROCESS_BOUNDS", "1,1,1")):
        monkeypatch.setenv(k, v)
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err
    assert '"ok"' not in out


def test_fs_leg_parity_on_cpu(tmp_path, monkeypatch):
    monkeypatch.setattr(secret_analyzer, "_scanner_cache", {})
    out = chip_smoke.fs_leg(str(tmp_path), 3, 2 << 20, 1, secret_backend="xla")
    assert out["parity_vs_cpu"]
    assert out["bytes_scanned"] >= 2 << 20
    assert out["license_findings"] > 0
    assert out["batches_per_device"][0] > 0


def test_sbom_leg_parity_on_cpu(tmp_path):
    out = chip_smoke.sbom_leg(str(tmp_path), 3, 1000)
    assert out["parity_vs_host"]
    assert out["packages"] == 1000
    assert out["vulnerabilities"] > 0 and out["resident_rows"] > 0


@pytest.mark.parametrize("env_dir", ["", "/somewhere/else"])
def test_compile_cache_home(monkeypatch, cache_config, env_dir):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    jax.config.update("jax_compilation_cache_dir", None)
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert enable_compile_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir is None  # JAX reads env
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(repo, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
