"""Shared fixtures: a small bench beside the real one, on the CPU.

``tiny_bench`` writes a bench directory whose cells run the program's
LeNet5 at a small batch with two clients, with the real cells' limits,
so every test drives the harness end to end in seconds.  ``token_bench``
writes one whose only cell runs the paper's CharLSTM on the ``tokens``
input kind, from new files alone: the configuration and its reference in
``tests/data/``, a mix with ``seq_len``, and a workload.
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


def write_files(root, configs, mixes, cells):
    """A bench directory under ``root``: the real per-layer readers and
    input kinds, the ``configs`` files copied from ``(directory, name)``, a
    traffic file per mix and a workload per cell (``name: (config, mix,
    chips, limits)``).
    Returns BENCHMARK.json with every metric in every cell."""
    root = Path(root)
    for sub in ("configs", "workloads", "traffic_mixes"):
        (root / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("layer_metrics", "inputs"):
        shutil.copytree(BENCH / sub, root / sub, dirs_exist_ok=True)
    for where, f in configs:
        shutil.copy(where / f, root / "configs" / f)
    for name, mix in mixes.items():
        (root / "traffic_mixes" / f"{name}.json").write_text(json.dumps({"name": name, **mix}))
    for name, (config, mix, chips, limits) in cells.items():
        (root / "workloads" / f"{name}.json").write_text(json.dumps(
            {"name": name, "config": config, "traffic": mix, "chips": chips,
             "why": "test", "limits": limits}))
    bench_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        m.pop("workloads", None)
    return bench_json


def write_bench(root):
    return write_files(
        root, [(BENCH / "configs", f) for f in ("lenet5.json", "lenet5.py")], MIXES,
        {name: ("lenet5", mix, chips, real_limits(real))
         for name, (mix, chips, real) in CELLS.items()})


DATA = BENCH / "tests" / "data"
TOKEN_MIX = dict(backend="local", compressor="sbc", sparsity=0.01, delay=2, clients=2,
                 batch=4, seq_len=16, measure_wire=False)
# From CPU readings of this cell: sound runs read update_norm_gap up to
# 7.5e-4 and support_gap up to 8e-5 over 8 seeds; the bfloat16 control
# 0.110-0.118 and 16.3-16.6 (3 seeds), no exchange 0.36-0.39 and 0.48, a
# doubled leaf 0.106-0.111 (2 seeds).
TOKEN_LIMITS = {"update_norm_gap": 0.05, "support_gap": 0.01}


def write_token_bench(root):
    return write_files(
        root, [(DATA, "charlstm.json"), (DATA, "charlstm.py")], {"lstm-tok": TOKEN_MIX},
        {"charlstm-tok": ("charlstm", "lstm-tok", 1, TOKEN_LIMITS)})


@pytest.fixture(scope="session")
def token_bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("token_bench")
    return root, write_token_bench(root)


@pytest.fixture
def charlstm_on_adam(monkeypatch):
    """The program's charlstm preset trains with SGD; the reference knows
    Adam alone, so the preset is swapped for a copy on the configuration's
    Adam rate."""
    import dataclasses

    from repro.configs import charlstm

    lr = json.loads((DATA / "charlstm.json").read_text())["local_opt"]["lr"]
    monkeypatch.setattr(charlstm, "CONFIG",
                        dataclasses.replace(charlstm.CONFIG, local_opt="adam", base_lr=lr))


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
