"""The command itself: no chip, too few chips, or no program -> a
non-zero exit and no result line."""
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.tests.conftest import ROOT


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


ARGS = ("--workload", "lenet5-sbc2-local4", "--seed", "3000000007", "--seconds", "1",
        "--trace", "0")


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(ROOT, *ARGS)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, *ARGS)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_fewer_chips_than_the_cell_asks_for(cpu_only):
    import jax

    more = len(jax.devices()) + 1
    with pytest.raises(harness.NoChip, match=f"asks for {more} chips"):
        harness.chip_devices(more, require_tpu=False)
