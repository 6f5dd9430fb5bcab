"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and each
of its phases passes at a tiny size with the kernels in interpret mode."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    words=256, maj_rows=8, mrc_rows=8, mismatch_bytes=16 * 1024,
    add_lanes=256, tenants=2, heal_rows=4, erase_rows=40, sweep_rows=2,
    smoke_model=True, prompts=2, prompt_len=4, new_tokens=3)

PHASES = dict(chip_smoke.PHASES)
PHASES["mesh-sweep"] = lambda sm: chip_smoke.mesh_sweep_phase(
    sm, n_devices=1)


def test_chip_smoke_exits_nonzero_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


@pytest.mark.parametrize("name", sorted(PHASES))
def test_chip_smoke_phase_tiny(name, tmp_path, capsys):
    with chip_smoke.Smoke(TINY, 0, str(tmp_path),
                          require_kernels=False) as sm:
        sm.phase(name, PHASES[name])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith(f"[{name}]") and "compile_s=" in line


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(from_env, tmp_path, monkeypatch):
    """The cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, and no
    directory is set in code; without it, to the fixed checkout path."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,
                                            enable_compile_cache)

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = enable_compile_cache()
        if from_env:
            assert path == str(tmp_path)
            assert (jax.config.jax_compilation_cache_dir
                    == saved["jax_compilation_cache_dir"])
        else:
            assert path == CHECKOUT_CACHE_DIR == os.path.join(
                REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
