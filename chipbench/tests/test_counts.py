"""Operation counts kept with the benchmark, and the peaks table."""
import json
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.peaks import peaks_for

BENCH = Path(__file__).resolve().parent.parent


def cell(name):
    return harness.load_cell(name)


def test_lenet5_flops_from_its_shapes():
    c = cell("lenet5-sbc2-local4")
    conv1 = 28 * 28 * 20 * 5 * 5 * 1
    conv2 = 14 * 14 * 50 * 5 * 5 * 20
    fc1, fc2 = 7 * 7 * 50 * 500, 500 * 10
    want = 2 * (3 * (conv1 + conv2 + fc1 + fc2) - conv1)  # no input grad for conv1
    assert c.mod.flops_per_sample(c.cfg, c.traffic) == want
    assert want == pytest.approx(38.3e6, rel=0.01)


def test_peaks():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    rows = json.loads((BENCH / "peaks.json").read_text())
    assert all(r.get("source") for r in rows.values())
