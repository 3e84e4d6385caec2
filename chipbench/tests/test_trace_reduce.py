"""The trace reduction on traces whose answers are known: a synthetic
trace written as text, and a small trace recorded on a TPU v5e
(``data/v5e_trace.xplane.pb.gz``, made by ``record_trace.py``)."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def xspace(devices, host):
    """devices: {name: [(start_ns, end_ns, op, category)]}; host: [(s, e, name)]."""
    planes = []
    for pid, (dev, ops) in enumerate(devices.items(), 1):
        names = sorted({op for _, _, op, _ in ops})
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                       for i, n in enumerate(names, 1))
        evs = "".join(
            f"events {{ metadata_id: {names.index(op) + 1} offset_ps: {s * 1000} "
            f"duration_ps: {(e - s) * 1000} "
            f'stats {{ metadata_id: 99 str_value: "{cat}" }} }}\n'
            for s, e, op, cat in ops)
        planes.append(
            f'planes {{ id: {pid} name: "{dev}" lines {{ id: 1 name: "XLA Ops" '
            f"timestamp_ns: 0 {evs} }} {meta} "
            'stat_metadata { key: 99 value { id: 99 name: "hlo_category" } } }')
    names = sorted({n for _, _, n in host})
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                   for i, n in enumerate(names, 1))
    evs = "".join(f"events {{ metadata_id: {names.index(n) + 1} offset_ps: {s * 1000} "
                  f"duration_ps: {(e - s) * 1000} }}\n" for s, e, n in host)
    planes.append(f'planes {{ id: 100 name: "/host:CPU" lines {{ id: 1 name: "python" '
                  f"timestamp_ns: 0 {evs} }} {meta} }}")
    return "\n".join(planes)


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    dev0 = [(100, 300, "fusion.1", "convolution"), (250, 400, "fusion.2", "loop fusion"),
            (120, 180, "fusion.9 = f32[8] fusion(%all-gather-done.3)", "loop fusion"),
            (500, 700, "all-gather-start", "all-gather"), (650, 800, "fusion.3", "loop fusion")]
    dev1 = [(100, 200, "fusion.1", "convolution"), (300, 600, "all-reduce.1", "all-reduce")]
    host = [(0, 600, "bench.step"), (600, 1000, "bench.wait"), (1000, 1000, "bench.step"),
            (50, 60, "other")]
    text = xspace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host)
    return tr.from_profile(ProfileData.from_text_proto(text))


def test_busy_union_and_idle(synthetic):
    red = synthetic
    win = red.window()
    assert win == (0, 1000)
    assert red.busy("/device:TPU:0", win) == [(100, 400), (500, 800)]
    assert tr.total(red.busy("/device:TPU:0", win)) == 600
    assert tr.total(red.busy("/device:TPU:1", win)) == 400
    assert [n for _, _, n in red.host] == ["bench.step", "bench.wait", "bench.step"]


def test_exposed_collective_time(synthetic):
    red, win = synthetic, synthetic.window()
    # device 0: all-gather 500-700, compute 650-800 covers its last 50 ns
    assert tr.subtract(red.collectives("/device:TPU:0", win),
                       red.compute("/device:TPU:0", win)) == 150
    # device 1: all-reduce 300-600 with no compute beside it
    assert tr.subtract(red.collectives("/device:TPU:1", win),
                       red.compute("/device:TPU:1", win)) == 300


def test_idle_gaps_are_named_by_host_spans(synthetic):
    red, win = synthetic, synthetic.window()
    gaps = tr.idle_gaps(red, "/device:TPU:0", win)
    assert gaps[0] == ("bench.wait", pytest.approx(200e-9))  # 800-1000
    assert sorted(g[1] for g in gaps) == pytest.approx([100e-9, 100e-9, 200e-9])
    top = tr.top_ops(red, win)
    # fusion.9 is nested in fusion.1 on device 0 and fusion.2 overlaps its
    # last 50 ns: fusion.1 keeps 90 ns there, and 100 ns on device 1
    assert dict(top) == pytest.approx({
        "fusion.1": 190e-9, "all-reduce.1": 300e-9, "fusion.2": 150e-9,
        "fusion.3": 150e-9, "all-gather-start": 150e-9,
        "fusion.9 = f32[8] fusion(%all-gather-done.3)": 60e-9})


def test_metric_readers(synthetic):
    from chipbench import harness

    red, win = synthetic, synthetic.window()
    ctx = harness.LayerContext(
        reduced=red, window=win, devices=red.devices, rounds=2, chips=2,
        traffic={"compressor": "sbc", "sparsity": 0.001, "delay": 10}, cfg={},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        samples_per_s=10.0, flops_per_sample=1e12,
        busy_s=500e-9, window_s=1000e-9,
        counters={"measured_bits_per_client": 12345.0})
    names = [("device.idle_share", "%"), ("step_mfu", "%"), ("up_bits_per_step", "bits/step")]
    metrics = harness.read_layer_metrics(names, ctx)
    assert metrics["device.idle_share"]["value"] == pytest.approx(50.0)
    assert metrics["step_mfu"]["value"] == pytest.approx(100 * 10 * 1e12 / (2 * 197e12))
    assert metrics["up_bits_per_step"]["value"] == pytest.approx(1234.5)
    # a reader with nothing to read leaves its metric out of the line
    ctx.counters = {}
    assert "up_bits_per_step" not in harness.read_layer_metrics(names, ctx)


def test_recorded_chip_trace():
    """Held to the profiler's own Perfetto export of the same trace, reduced
    apart by ``record_trace.from_perfetto``."""
    import json

    red = tr.load(str(DATA / "v5e_trace.xplane.pb.gz"))
    want = json.loads((DATA / "v5e_trace.json").read_text())
    assert red.devices == want["devices"]
    win = red.window()
    for dev in red.devices:
        busy = tr.total(red.busy(dev, win))
        assert 0 < busy < win[1] - win[0]
        # the export rounds times to fractions of a microsecond
        assert busy == pytest.approx(want["busy_ns"][dev], rel=1e-4, abs=1e3)
        exposed = tr.subtract(red.collectives(dev, win), red.compute(dev, win))
        assert exposed == pytest.approx(want["exposed_ns"][dev], rel=1e-3, abs=1e3)
    assert [n for _, _, n in red.host].count("bench.step") == want["steps"]
