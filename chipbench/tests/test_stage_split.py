"""The stage split on a synthetic reduced trace and stage map whose answers
are known: own time of ops nested in a ``while``, the unscoped bucket,
the division by rounds, the host spans' self time, and the metrics'
entries in ``BENCHMARK.json``."""
import json
import types
from pathlib import Path

import pytest

from chipbench import harness, stage_split
from chipbench.trace_reduce import Op, Reduced

BENCH = Path(__file__).resolve().parent.parent
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
SBC, DENSE = "lenet5-sbc2-local4", "lenet5-dense-local4"


def op(start, end, name):
    return Op(start, end, f"%{name} = f32[4]{{0}} {name.split('.')[0]}(...)", {})


# per round: a while (its own time 30 ns) around two body ops, then a copy
# the map places nowhere and an op the map leaves unscoped; two rounds
def round_ops(t0):
    return [op(t0, t0 + 100, "while.1"), op(t0 + 10, t0 + 40, "fusion.1"),
            op(t0 + 50, t0 + 90, "fusion.2"), op(t0 + 100, t0 + 120, "copy.9"),
            op(t0 + 120, t0 + 125, "sort.3")]


RED = Reduced(ops={"/device:TPU:0": round_ops(0) + round_ops(1000)},
              host=[(0, 500, "bench.step"), (1000, 1500, "bench.step")])
SMAP = {"while.1": "forward_backward", "fusion.1": "forward_backward",
        "fusion.2": "optimizer", "sort.3": None, "select.1": "select"}
SPANS = [  # encode holds a fetch in round 0, not in round 1
    {"name": "dispatch", "id": 0, "parent": None, "dur_us": 300.0, "args": {"round": 0}},
    {"name": "fetch", "id": 2, "parent": 1, "dur_us": 40.0, "args": {"round": 0}},
    {"name": "encode", "id": 1, "parent": None, "dur_us": 100.0, "args": {"round": 0}},
    {"name": "dispatch", "id": 3, "parent": None, "dur_us": 500.0, "args": {"round": 1}},
    {"name": "fetch", "id": 4, "parent": None, "dur_us": 20.0, "args": {"round": 1}},
    {"name": "encode", "id": 5, "parent": None, "dur_us": 80.0, "args": {"round": 1}},
]


def ctx():
    return types.SimpleNamespace(reduced=RED, window=RED.window(), rounds=2)


@pytest.fixture
def program(monkeypatch):
    import repro.obs

    monkeypatch.setattr(stage_split, "_stage_map", lambda c: SMAP)
    monkeypatch.setattr(repro.obs, "profiled_spans", lambda: SPANS)
    monkeypatch.setattr(stage_split, "_last", (None, None))


def test_device_split_takes_each_ops_own_time():
    got = stage_split.device_split(RED, RED.window(), SMAP)
    assert got == pytest.approx({"forward_backward": 2 * 60e-9,
                                 "optimizer": 2 * 40e-9, None: 2 * 25e-9})
    assert sum(got.values()) == pytest.approx(2 * 125e-9)  # the busy time


def test_metrics_are_per_round_and_partition_busy_time(program):
    c = ctx()
    assert stage_split.device_ms(c, "forward_backward") == pytest.approx(60e-6)
    assert stage_split.device_ms(c, "optimizer") == pytest.approx(40e-6)
    assert stage_split.device_ms(c, None) == pytest.approx(25e-6)  # unscoped
    # a stage the map names but no op of the window ran: zero; a stage
    # the round does not have: nothing to read
    assert stage_split.device_ms(c, "select") == 0.0
    assert stage_split.device_ms(c, "quantize") is None
    assert stage_split.host_ms(c, "dispatch") == pytest.approx(0.4)
    assert stage_split.host_ms(c, "fetch") == pytest.approx(0.03)
    assert stage_split.host_ms(c, "encode") == pytest.approx((60 + 80) / 2e3)


def test_split_is_kept_per_trace(program, monkeypatch):
    c = ctx()
    first = stage_split.split(c)
    monkeypatch.setattr(stage_split, "_stage_map",
                        lambda c: pytest.fail("read the program twice"))
    assert stage_split.split(c) is first


def test_spans_must_cover_the_windows_rounds(program):
    c = types.SimpleNamespace(reduced=RED, window=RED.window(), rounds=3)
    with pytest.raises(ValueError, match="2 rounds, the traced window 3"):
        stage_split.split(c)


def test_a_program_without_stages_or_spans_gives_nothing(monkeypatch):
    import repro.obs
    from repro.run import build

    monkeypatch.delattr(build.Run, "stage_map")
    monkeypatch.delattr(repro.obs, "profiled_spans")
    monkeypatch.setattr(stage_split, "_last", (None, None))
    c = ctx()
    assert stage_split.device_ms(c, "forward_backward") is None
    assert stage_split.device_ms(c, None) is None
    assert stage_split.host_ms(c, "dispatch") is None


def test_readers_print_each_metric(program):
    names = [(m["name"], m["unit"]) for m in SPEC["per_layer"]
             if m["name"].startswith(("device.", "host.")) and m["unit"] == "ms/round"]
    got = harness.read_layer_metrics(names, ctx())
    # the map names no quantize or apply stage: those lines are left out
    assert set(got) == {"device.forward_backward_ms", "device.optimizer_ms",
                        "device.select_ms", "device.unscoped_ms",
                        "host.dispatch_ms", "host.fetch_ms", "host.encode_ms"}
    assert got["device.unscoped_ms"]["value"] == pytest.approx(25e-6)


@pytest.mark.parametrize("name, layer, source, cells", [
    ("device.forward_backward_ms", "whole step (models, optim)", "device_trace", None),
    ("device.optimizer_ms", "whole step (models, optim)", "device_trace", None),
    ("device.select_ms", "channel / codec", "device_trace", [SBC]),
    ("device.quantize_ms", "channel / codec", "device_trace", [SBC, DENSE]),
    ("device.apply_ms", "whole step (models, optim)", "device_trace", None),
    ("device.unscoped_ms", "device", "device_trace", None),
    ("host.dispatch_ms", "host run loop", "program_counter", None),
    ("host.fetch_ms", "host run loop", "program_counter", [SBC]),
    ("host.encode_ms", "channel / codec", "program_counter", [SBC]),
])
def test_benchmark_entries(name, layer, source, cells):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == name)
    assert (entry["unit"], entry["better"], entry["moves"]) == (
        "ms/round", "lower", "samples_per_s")
    assert (entry["layer"], entry["source"], entry.get("workloads")) == (
        layer, source, cells)
    assert (BENCH / "layer_metrics" / f"{name}.py").exists()
