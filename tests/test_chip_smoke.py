"""chip_smoke.py's phases on CPU at the ``tiny`` preset.

The script's ``main`` demands a TPU; its phases do not, so the control
flow, the checks and the cross-backend comparison are exercised here with
the kernels interpreted.  The four-device comparison runs in a child
process on four forced host devices.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(batch=4, seq_len=16)


@pytest.mark.parametrize("phase", [
    "phase_gspmd_exact", "phase_gspmd_hist", "phase_local",
])
def test_phase_passes_its_checks_on_cpu(phase):
    out = getattr(chip_smoke, phase)("tiny", **TINY)
    assert len(out["loss"]) == chip_smoke.ROUNDS
    assert len(out["step_ms"]) == chip_smoke.ROUNDS


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 1
    captured = capsys.readouterr()
    assert "no TPU found" in captured.err
    assert captured.out == ""


@pytest.mark.parametrize("from_env", [True, False], ids=["env-dir", "repo-dir"])
def test_compile_cache_lands_in_its_directory(tmp_path, from_env):
    """use_compile_cache() yields to JAX_COMPILATION_CACHE_DIR; without it
    the compiles land in <repo>/.jax_cache."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    want = str(tmp_path) if from_env else os.path.join(ROOT, ".jax_cache")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    code = (
        "import chip_smoke, jax, jax.numpy as jnp\n"
        "chip_smoke.use_compile_cache()\n"
        "def cache_probe(x): return x * 3 + 1\n"
        "jax.jit(cache_probe)(jnp.arange(7.0)).block_until_ready()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip().splitlines()[-1] == want
    assert any(f.startswith("jit_cache_probe-") for f in os.listdir(want))


def test_four_device_comparison_on_host_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    code = (
        "import chip_smoke; "
        f"chip_smoke.four_chip_compare('tiny', batch={TINY['batch']}, "
        f"seq_len={TINY['seq_len']})"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    assert "4 clients, one per device" in proc.stdout
    assert "device words == host Golomb bytes" in proc.stdout
    assert "host rebuild of the exchanged mean from all 4 clients" in proc.stdout
