"""Shared fixtures: a small bench beside the real one, on the CPU.

``tiny_bench`` writes a bench directory whose cells run the program's
LeNet5 at a small batch with two clients, with the real cells' limits,
so every test drives the harness end to end in seconds.
"""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def real_limits(cell):
    return json.loads((BENCH / "workloads" / f"{cell}.json").read_text())["limits"]


MIXES = {
    "lenet-l2": dict(backend="local", compressor="sbc", sparsity=0.01, delay=2,
                     clients=2, batch=8, measure_wire=True),
    "lenet-d2": dict(backend="local", compressor="none", delay=1, clients=2, batch=8,
                     measure_wire=False),
}
CELLS = {  # name: (mix, chips, the real cell whose limits apply)
    "lenet-local": ("lenet-l2", 1, "lenet5-sbc2-local4"),
    "lenet-dense": ("lenet-d2", 1, "lenet5-dense-local4"),
}


def write_bench(root):
    root = Path(root)
    for sub in ("configs", "workloads", "traffic_mixes"):
        (root / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "layer_metrics", root / "layer_metrics", dirs_exist_ok=True)
    for f in ("lenet5.json", "lenet5.py"):
        shutil.copy(BENCH / "configs" / f, root / "configs" / f)
    for name, mix in MIXES.items():
        (root / "traffic_mixes" / f"{name}.json").write_text(json.dumps({"name": name, **mix}))
    for name, (mix, chips, real) in CELLS.items():
        (root / "workloads" / f"{name}.json").write_text(json.dumps(
            {"name": name, "config": "lenet5", "traffic": mix, "chips": chips,
             "why": "test", "limits": real_limits(real)}))
    bench_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        m.pop("workloads", None)
    return bench_json


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    return root, write_bench(root)


@pytest.fixture(scope="session", autouse=False)
def cpu_only():
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("these tests drive the harness on the CPU")
    return True


def run_tiny(tiny_bench, cell, seed=2**33 + 5, seconds=0.3, patch=None, trace=False):
    from chipbench import harness

    root, bench_json = tiny_bench
    return harness.run_cell(cell, seed, seconds, trace, bench=root, bench_json=bench_json,
                            require_tpu=False, log=lambda m: None, patch=patch)


os.environ.setdefault("TPU_LOG_DIR", "disabled")
