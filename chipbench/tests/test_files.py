"""The benchmark's files: every cell resolves, names and units keep to
their alphabet, and each metric and configuration has what reads it."""
import json
import re
from pathlib import Path

import pytest

from chipbench import harness

BENCH = Path(__file__).resolve().parent.parent
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_and_units():
    names = [c["name"] for c in SPEC["configs"]] + CELLS
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["traffic"] for w in SPEC["workloads"]]
    names += [k for c in SPEC["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len(set(CELLS)) == len(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_a_valid_spec(cell):
    from repro.run import RunSpec

    entry = next(w for w in SPEC["workloads"] if w["name"] == cell)
    c = harness.load_cell(cell)
    assert (c.workload["config"], c.workload["traffic"], c.chips) == (
        entry["config"], entry["traffic"], entry["chips"])
    assert c.workload["why"] == entry["why"]
    t = c.traffic
    spec = RunSpec(preset=c.cfg["preset"], backend=t["backend"], compressor=t["compressor"],
                   delay=t["delay"], clients=t["clients"], batch=t["batch"],
                   measure_wire=t.get("measure_wire", False))
    assert spec.backend == t["backend"]
    from chipbench.check import compare

    names, _ = compare.numbers(
        {"losses": [1.0], "grad": [1.0, 2.0], "change": [1.0, 2.0], "support": [1, 2]},
        {"losses": [1.0], "grad": [1.0, 2.0], "change": [1.0, 2.0], "support": [1, 2],
         "paths": ["a", "b"]})
    assert c.workload["limits"] and set(c.workload["limits"]) <= set(names)
    cfg_entry = next(x for x in SPEC["configs"] if x["name"] == c.cfg["name"])
    assert Path(BENCH.parent / cfg_entry["file"]) == BENCH / "configs" / f"{c.cfg['name']}.json"


def test_every_config_is_used_and_every_metric_has_a_reader():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for m in SPEC["per_layer"]:
        assert (BENCH / "layer_metrics" / f"{m['name']}.py").exists(), m["name"]
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_gspmd_cell_with_delay_is_refused():
    w = {"name": "x", "chips": 1}
    t = {"backend": "gspmd", "compressor": "sbc", "delay": 10, "clients": 1}
    cfg = {"name": "lenet5", "inputs": "images"}
    kind = harness.input_kind("images")
    with pytest.raises(ValueError, match="delay"):
        harness.validate(w, t, cfg, kind)
    with pytest.raises(ValueError, match="bias correction"):
        harness.validate(w, dict(t, delay=1), cfg, kind)
    harness.validate(w, dict(t, backend="local"), cfg, kind)
    with pytest.raises(ValueError, match="one chip"):
        harness.validate(dict(w, chips=4), dict(t, backend="local"), cfg, kind)


def test_config_file_states_the_program_and_its_source():
    for entry in SPEC["configs"]:
        cfg = json.loads((BENCH.parent / entry["file"]).read_text())
        assert cfg["name"] == entry["name"]
        assert cfg["source"].split()[0] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
        assert all(k in cfg for k in entry["reduced"])
        assert len(cfg["departures"]) >= len(entry["reduced"])
