"""Process-level launch settings: where the persistent compilation cache
goes, the per-device peak-rate table, and chip_smoke.py's refusal to
report success without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import xla_env
from repro.launch.mesh import PEAKS, peak_rates

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_honours_env(monkeypatch, cache_config, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert xla_env.configure_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no directory of its own
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_checkout_when_unset(monkeypatch,
                                                    cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = xla_env.configure_compile_cache()
    second = xla_env.configure_compile_cache()
    assert first == second == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_peak_rates_keyed_by_device_kind():
    v5e = peak_rates("TPU v5 lite")
    assert v5e == PEAKS["TPU v5 lite"]
    assert v5e["peak_flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no peak rates"):
        peak_rates("TPU v9 imaginary")


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True,
                       timeout=300, cwd=ROOT)
    assert r.returncode != 0, r.stdout[-2000:]
    assert '"ok": true' not in r.stdout
    for line in r.stdout.splitlines():
        if line.startswith("{"):
            assert not json.loads(line).get("ok"), line
    assert "no TPU" in r.stderr, r.stderr[-2000:]
