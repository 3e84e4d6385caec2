"""The repro.obs telemetry layer (ISSUE 7).

Covers, in order:

  * BandwidthLedger.reconcile failure paths — the tolerance gate is load-
    bearing (every backend + CI calls it), so its message format and its
    trivial-pass cases are pinned here;
  * Tracer span structure (nesting, ordering, validation);
  * MetricsRegistry declared-name discipline and the ingest_ledger
    bit-exactness contract (telemetry wire/* gauges == ledger.totals());
  * the JSONL / Chrome-trace export schema round trip + repro.obs.view;
  * NULL_TELEMETRY zero-overhead semantics (no-ops, identity fence);
  * an end-to-end traced tiny run: round → stage span decomposition and
    gauges reconciled against the run's own ledger;
  * the device stages of the jitted round: ``stage_of`` on the op_name
    forms the compiler writes, the compiled-HLO join, the LeNet rounds'
    stage maps; and host spans reaching a profiler trace.
"""
from __future__ import annotations

import json

import pytest

from repro.core.ledger import BandwidthLedger, RoundRecord
from repro.obs import (
    METRIC_NAMES,
    MetricsRegistry,
    NULL_TELEMETRY,
    SCOPE_NAMES,
    Telemetry,
    Tracer,
    hlo_stage_map,
    make_telemetry,
    profiled_spans,
    render_table,
    scope,
    stage_of,
    validate_metric_events,
    validate_span_events,
    write_metrics_jsonl,
    write_trace_json,
)
from repro.obs.export import read_metrics_jsonl, read_trace_json
from repro.obs.view import check as view_check


def _rec(round_idx=0, cohort=(0, 1), up_bytes=100, up_m=800.0, up_a=800.0,
         down_bytes=0, down_m=0.0, down_a=0.0, down_recipients=0):
    return RoundRecord(
        round=round_idx, cohort=cohort, up_bytes=up_bytes,
        up_bits_measured=up_m, up_bits_analytic=up_a,
        down_bytes=down_bytes, down_bits_measured=down_m,
        down_bits_analytic=down_a, down_recipients=down_recipients,
    )


# ------------------------------------------------- ledger reconcile paths


class TestLedgerReconcile:
    def test_empty_ledger_reconciles(self):
        BandwidthLedger().reconcile(rel=0.0)  # no rounds, nothing to violate

    def test_zero_traffic_direction_trivially_passes(self):
        led = BandwidthLedger()
        led.record(_rec(up_m=1000.0, up_a=1000.0, down_m=0.0, down_a=0.0))
        led.reconcile(rel=1e-12)

    def test_upstream_violation_message(self):
        led = BandwidthLedger()
        led.record(_rec(round_idx=3, up_m=1500.0, up_a=1000.0))
        with pytest.raises(AssertionError) as ei:
            led.reconcile(rel=0.1)
        msg = str(ei.value)
        assert "round 3 upstream" in msg
        assert "measured 1500 bits vs analytic 1000" in msg
        assert "rel err 0.500 > 0.1" in msg

    def test_downstream_violation_named_separately(self):
        led = BandwidthLedger()
        led.record(_rec(round_idx=1, down_bytes=10, down_m=50.0, down_a=500.0,
                        down_recipients=2))
        with pytest.raises(AssertionError, match="round 1 downstream"):
            led.reconcile(rel=0.1)

    def test_first_violating_round_raises_not_the_last(self):
        led = BandwidthLedger()
        led.record(_rec(round_idx=0))  # fine
        led.record(_rec(round_idx=1, up_m=2000.0, up_a=1000.0))  # bad
        led.record(_rec(round_idx=2, up_m=9000.0, up_a=1000.0))  # worse
        with pytest.raises(AssertionError, match="round 1 "):
            led.reconcile(rel=0.1)

    def test_measured_zero_against_nonzero_analytic_fails(self):
        led = BandwidthLedger()
        led.record(_rec(up_m=0.0, up_a=640.0))
        with pytest.raises(AssertionError, match="rel err 1.000"):
            led.reconcile(rel=0.5)

    def test_tolerance_boundary(self):
        led = BandwidthLedger()
        led.record(_rec(up_m=1100.0, up_a=1000.0))  # rel err exactly 0.1
        led.reconcile(rel=0.1)  # > is strict: 0.1 is not > 0.1
        with pytest.raises(AssertionError):
            led.reconcile(rel=0.09)


# ------------------------------------------------------------------ tracer


class TestTracer:
    def test_nested_spans_record_parentage(self):
        tr = Tracer()
        with tr.span("round", round=0):
            with tr.span("encode", client=0):
                pass
            with tr.span("decode"):
                pass
        assert validate_span_events(tr.events) == []
        by_name = {e["name"]: e for e in tr.events}
        parent = by_name["round"]
        assert parent["parent"] is None and parent["depth"] == 0
        for child in ("encode", "decode"):
            assert by_name[child]["parent"] == parent["id"]
            assert by_name[child]["depth"] == 1
        assert by_name["encode"]["args"] == {"client": 0}

    def test_children_close_before_parent(self):
        tr = Tracer()
        with tr.span("round"):
            with tr.span("encode"):
                pass
        names = [e["name"] for e in tr.events]
        assert names == ["encode", "round"]  # completion order

    def test_validation_flags_unknown_name_and_orphan(self):
        errs = validate_span_events([
            {"type": "span", "name": "nonsense", "id": 0, "parent": 7,
             "depth": 1, "ts_us": 0.0, "dur_us": 1.0, "args": {}},
        ])
        assert any("not in SPAN_NAMES" in e for e in errs)
        assert any("parent 7 never closed" in e for e in errs)

    def test_fence_none_is_safe_and_identity(self):
        tr = Tracer()
        assert tr.fence(None) is None
        obj = [1, 2]
        assert NULL_TELEMETRY.fence(obj) is obj


# ----------------------------------------------------------------- metrics


class TestMetricsRegistry:
    def test_undeclared_name_raises_keyerror(self):
        reg = MetricsRegistry()
        with pytest.raises(KeyError, match="not declared in METRIC_NAMES"):
            reg.gauge("wire/typo_bits", 1.0)

    def test_kind_mismatch_raises_typeerror(self):
        reg = MetricsRegistry()
        with pytest.raises(TypeError, match="declared as a gauge"):
            reg.counter("train/loss")

    def test_ingest_ledger_is_bit_exact(self):
        led = BandwidthLedger()
        # float-summation-hostile values (0.1+0.2+0.3 != fsum of same):
        # bit-exactness holds because ingest replays the ledger's own
        # addends in order with the same sequential summation
        led.record(_rec(round_idx=0, up_bytes=3, up_m=0.1, up_a=0.1))
        led.record(_rec(round_idx=1, up_bytes=5, up_m=0.2, up_a=0.2))
        led.record(_rec(round_idx=2, up_bytes=7, up_m=0.3, up_a=0.3))
        reg = MetricsRegistry()
        reg.ingest_ledger(led)
        totals = led.totals()
        for col in ("up_bytes", "up_bits_measured", "up_bits_analytic",
                    "down_bytes", "down_bits_measured", "down_bits_analytic"):
            mine = sum(s["value"] for s in reg.series(f"wire/{col}"))
            assert mine == float(totals[col])
        assert [s["tags"]["round"] for s in reg.series("wire/up_bytes")] == \
            [0, 1, 2]
        assert sum(s["value"] for s in reg.series("obs/rounds")) == 3

    def test_summary_aggregates_by_kind(self):
        reg = MetricsRegistry()
        reg.gauge("train/loss", 3.0)
        reg.gauge("train/loss", 2.0)
        reg.counter("serve/verify_ok")
        reg.counter("serve/verify_ok")
        s = reg.summary()
        assert s["train/loss"]["last"] == 2.0 and s["train/loss"]["count"] == 2
        assert s["serve/verify_ok"]["sum"] == 2.0

    def test_every_declared_kind_is_valid(self):
        assert set(k for k, _ in METRIC_NAMES.values()) <= {
            "counter", "gauge", "hist"
        }


# ------------------------------------------------------------ export/view


class TestExportSchema:
    def _populated(self):
        tel = make_telemetry()
        with tel.span("round", round=0):
            with tel.span("encode"):
                pass
        tel.metrics.gauge("train/loss", 1.25, round=0)
        tel.metrics.counter("obs/rounds")
        return tel

    def test_metrics_jsonl_round_trip(self, tmp_path):
        tel = self._populated()
        path = str(tmp_path / "m.jsonl")
        write_metrics_jsonl(path, tel.metrics, meta={"backend": "test"})
        header, events = read_metrics_jsonl(path)
        assert header["schema"] == "repro-obs-v1"
        assert header["kind"] == "metrics" and header["backend"] == "test"
        assert validate_metric_events(events) == []
        assert {e["name"] for e in events} == {"train/loss", "obs/rounds"}
        with open(path) as f:
            first = json.loads(f.readline())
        assert first["schema"] == "repro-obs-v1"  # header is LINE 1

    def test_bad_header_raises(self, tmp_path):
        path = tmp_path / "bad.jsonl"
        path.write_text('{"schema": "other-v9"}\n')
        with pytest.raises(ValueError, match="bad header"):
            read_metrics_jsonl(str(path))

    def test_trace_json_is_chrome_loadable(self, tmp_path):
        tel = self._populated()
        path = str(tmp_path / "t.json")
        write_trace_json(path, tel.tracer, meta={"backend": "test"})
        evs = read_trace_json(path)
        assert all(e["ph"] in ("X", "i") for e in evs)
        assert {e["name"] for e in evs} == {"round", "encode"}
        x = [e for e in evs if e["name"] == "round"][0]
        assert {"ts", "dur", "pid", "tid"} <= set(x)

    def test_view_check_accepts_both_and_rejects_garbage(self, tmp_path,
                                                         capsys):
        tel = self._populated()
        m = str(tmp_path / "m.jsonl")
        t = str(tmp_path / "t.json")
        write_metrics_jsonl(m, tel.metrics)
        write_trace_json(t, tel.tracer)
        assert view_check([t, m]) == 0
        bad = tmp_path / "bad.json"
        bad.write_text('{"traceEvents": [{"ph": "X", "name": "bogus", '
                       '"ts": 0, "dur": 1, "pid": 0, "tid": 0}]}')
        assert view_check([str(bad)]) == 1
        capsys.readouterr()

    def test_render_table_alignment(self):
        out = render_table(["name", "n"], [("a", 1), ("bb", 22)])
        lines = out.splitlines()
        assert lines[0].split() == ["name", "n"]
        assert lines[2].endswith(" 1")  # numbers right-aligned


# ----------------------------------------------------- disabled telemetry


class TestNullTelemetry:
    def test_disabled_is_all_noops(self):
        assert not NULL_TELEMETRY.enabled
        with NULL_TELEMETRY.span("round", round=0) as s1:
            with NULL_TELEMETRY.span("encode") as s2:
                assert s1 is s2  # ONE shared null span, no allocation
        NULL_TELEMETRY.metrics.gauge("train/loss", 1.0)
        NULL_TELEMETRY.metrics.ingest_ledger(BandwidthLedger())
        assert NULL_TELEMETRY.metrics.events() == []
        assert NULL_TELEMETRY.tracer.events == ()

    def test_telemetry_default_is_disabled(self):
        assert not Telemetry().enabled
        assert make_telemetry().enabled


# ------------------------------------------------------- end-to-end traced


class TestTracedRun:
    @pytest.fixture(scope="class")
    def traced(self):
        from repro.run import RunSpec, build_run

        spec = RunSpec(preset="tiny", backend="local", rounds=2, batch=4,
                       seq_len=16, clients=2, sparsity=0.05,
                       measure_wire=True, telemetry=True)
        run = build_run(spec)
        _, hist = run.run()
        return run, hist

    def test_round_stage_decomposition(self, traced):
        run, _ = traced
        assert validate_span_events(run.telemetry.tracer.events) == []
        spans = [e for e in run.telemetry.tracer.events
                 if e["type"] == "span"]
        rounds = [e for e in spans if e["name"] == "round"]
        assert len(rounds) == 2
        kids = [e for e in spans if e["parent"] is not None]
        # the jitted round is one host dispatch; the metering that follows
        # fetches client 0's upload, then encodes it
        for r, rnd in enumerate(rounds):
            mine = [e["name"] for e in kids if e["parent"] == rnd["id"]]
            assert mine == ["dispatch", "fetch", "encode"], mine
            for e in kids:
                if e["parent"] == rnd["id"]:
                    assert e["args"]["round"] == r
        fetch = next(e for e in kids if e["name"] == "fetch")
        assert fetch["args"]["arrays"] > 0 and fetch["args"]["bytes"] > 0

    def test_gauges_reconcile_with_run_ledger(self, traced):
        run, _ = traced
        reg = run.telemetry.metrics
        totals = run.ledger.totals()
        for col in ("up_bytes", "up_bits_measured", "up_bits_analytic"):
            mine = sum(s["value"] for s in reg.series(f"wire/{col}"))
            assert mine == float(totals[col])

    def test_hist_keys_preserved_in_traced_mode(self, traced):
        _, hist = traced
        for key in ("loss", "round", "total_upload_bits",
                    "compression_rate", "measured_bits_per_client",
                    "measured_total_bits"):
            assert key in hist, key
        assert len(hist["loss"]) == 2

    def test_exports_validate(self, traced, tmp_path):
        run, _ = traced
        t = str(tmp_path / "run.trace.json")
        m = str(tmp_path / "run.metrics.jsonl")
        write_trace_json(t, run.telemetry.tracer)
        write_metrics_jsonl(m, run.telemetry.metrics)
        assert view_check([t, m]) == 0


# ------------------------------------------------- device stages and spans


class TestStageOf:
    @pytest.mark.parametrize("op_name, stage", [
        ("jit(_round_step)/vmap(sbc.quantize)/sub", "quantize"),
        ("jit(f)/vmap(sbc.forward_backward)/while/body/closed_call/"
         "sbc.forward_backward/transpose(jvp())/conv_general_dilated",
         "forward_backward"),
        ("jit(f)/sbc.forward_backward/while/body/sbc.optimizer/mul",
         "optimizer"),
        ("jit(f)/transpose(jvp(sbc.forward_backward))/dot_general",
         "forward_backward"),
        ("jit(f)/vmap(sbc.quantize)/vmap(sbc.select)/top_k", "select"),
        ("jit(f)/vmap()/broadcast_in_dim;jit(f)/sbc.apply/add", "apply"),
        ("jit(f)/sbc.apply/mul;jit(f)/sbc.quantize/sub", "apply"),
        ("jit(f)/vmap()/select_n", None),
        ("jit(f)/transpose(jvp())/select_and_scatter", None),
        ("jit(f)/xsbc.select/mul", None),
        ("jit(f)/sbc.unknown/mul", None),
    ])
    def test_innermost_declared_scope(self, op_name, stage):
        assert stage_of(op_name) == stage

    def test_scope_refuses_undeclared_names(self):
        with pytest.raises(KeyError, match="SCOPE_NAMES"):
            scope("exchange")


HLO = """HloModule m, is_scheduled=true

%fused (p.0: f32[4]) -> f32[4] {
  %p.0 = f32[4]{0} parameter(0)
  ROOT %inner = f32[4]{0} multiply(%p.0, %p.0), metadata={op_name="jit(f)/sbc.apply/mul"}
}

%body (w.0: (s32[], f32[4])) -> (s32[], f32[4]) {
  %w.0 = (s32[], f32[4]{0}) parameter(0)
  %i.0 = s32[] get-tuple-element(%w.0), index=0
  %x.0 = f32[4]{0} get-tuple-element(%w.0), index=1
  %one = s32[] constant(1)
  %i.1 = s32[] add(%i.0, %one)
  %x.1 = f32[4]{0} copy(%x.0)
  ROOT %t.0 = (s32[], f32[4]{0}) tuple(%i.1, %x.1)
}

%cond (w.1: (s32[], f32[4])) -> pred[] {
  %w.1 = (s32[], f32[4]{0}) parameter(0)
  %i.2 = s32[] get-tuple-element(%w.1), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%i.2, %n), direction=LT
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %g = f32[4]{0} sine(%a), metadata={op_name="jit(f)/vmap(sbc.forward_backward)/while/body/sin;jit(f)/sbc.optimizer/sin"}
  %c = f32[4]{1,0} copy(%g)
  %zero = s32[] constant(0)
  %t = (s32[], f32[4]{0}) tuple(%zero, %c)
  %loop = (s32[], f32[4]{0}) while(%t), condition=%cond, body=%body
  %out = f32[4]{0} get-tuple-element(%loop), index=1
  %pre = f32[4]{0} copy(%a)
  %fu = f32[4]{0} fusion(%pre), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/sbc.apply/mul"}
  ROOT %r = f32[4]{0} add(%out, %fu), metadata={op_name="jit(f)/sbc.quantize/add"}
}
"""


class TestHloStageMap:
    def test_joins_compiled_instructions_to_stages(self):
        m = hlo_stage_map(HLO)
        assert m["g"] == "forward_backward"  # first ';'-joined name wins
        assert m["c"] == "forward_backward"  # a layout copy: its producer's
        assert m["pre"] == "apply"  # no producer with a stage: its user's
        # a compiler-made loop with no metadata: the stage of what runs it
        assert m["loop"] == "forward_backward"
        assert m["i.1"] == m["x.1"] == m["lt"] == "forward_backward"
        assert m["fu"] == "apply" and m["r"] == "quantize"
        # fused bodies are not device ops; parameters, constants, tuples,
        # tuple reads and bitcasts are not compute
        assert "inner" not in m and "a" not in m and "zero" not in m
        assert "t" not in m and "out" not in m

    def test_no_entry_is_an_error(self):
        with pytest.raises(ValueError, match="ENTRY"):
            hlo_stage_map("HloModule m\n")


def _lenet_run(compressor, fast=False):
    from repro.run import RunSpec, build_run

    sbc = compressor == "sbc"
    return build_run(RunSpec(
        preset="lenet5", backend="local", compressor=compressor,
        sparsity=0.01, delay=2 if sbc else 1, clients=2, batch=4,
        measure_wire=sbc, fast=fast,
    ))


class TestRoundStageMap:
    @pytest.mark.parametrize("compressor, fast, stages", [
        ("sbc", False, {"forward_backward", "optimizer", "select",
                        "quantize", "apply"}),
        ("sbc", True, {"forward_backward", "optimizer", "select",
                       "quantize", "apply"}),  # the flat-buffer engine
        # the lossless codec still carries error feedback (quantize) but
        # selects nothing
        ("none", False, {"forward_backward", "optimizer", "quantize",
                         "apply"}),
    ])
    def test_every_stage_is_named_and_little_is_unscoped(self, compressor,
                                                         fast, stages):
        run = _lenet_run(compressor, fast)
        m = run.stage_map(run.init(), 0)
        assert set(m.values()) - {None} == stages
        assert set(m.values()) <= set(SCOPE_NAMES) | {None}
        unscoped = [k for k, v in m.items() if v is None]
        assert len(unscoped) <= 0.02 * len(m), unscoped

    def test_map_names_the_program_step_runs(self):
        import jax

        run = _lenet_run("none")
        state = run.init()
        m = run.stage_map(state, 0)
        args, static = run._round_args(state, 0)
        text = type(run.trainer).round_step.lower(
            run.trainer, *args, **static).compile().as_text()
        for name in m:
            assert f"%{name} = " in text
        jax.block_until_ready(run.step(state, 0))


class TestProfiledSpans:
    def test_spans_reach_the_profiler_with_telemetry_off(self, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        from repro.run import RunSpec, build_run

        run = build_run(RunSpec(preset="tiny", backend="local", rounds=2,
                                batch=4, seq_len=16, clients=2,
                                sparsity=0.05, measure_wire=True))
        assert not run.telemetry.enabled
        state = run.init()
        state, _ = run.step(state, 0)  # compiled, and seen with no session
        jax.block_until_ready(state)
        with jax.profiler.trace(str(tmp_path)):
            for r in (1, 2):
                state, _ = run.step(state, r)
                jax.block_until_ready(state)
        spans = profiled_spans()
        assert validate_span_events(spans) == []
        by_name = {}
        for e in spans:
            by_name.setdefault(e["name"], []).append(e["args"]["round"])
        assert by_name == {"dispatch": [1, 2], "fetch": [1, 2],
                           "encode": [1, 2]}
        assert all(e["parent"] is None for e in spans)

        xplane = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                           recursive=True)
        host = [(ev.name, dict(ev.stats))
                for plane in ProfileData.from_file(xplane[0]).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events
                if ev.name in ("dispatch", "fetch", "encode")]
        assert sorted((n, s["round"]) for n, s in host) == sorted(
            (n, r) for n, rs in by_name.items() for r in rs)

        state, _ = run.step(state, 3)  # no session: nothing recorded
        jax.block_until_ready(state)
        assert profiled_spans() == spans


def test_gspmd_round_stage_map_on_4_devices():
    """The gspmd round over 4 forced host devices, on each exchange path:
    its stage map names the local steps, selection, the gather of the
    packed uploads, their decode and the apply, and leaves nothing
    unscoped — the decomposition a four-chip cell reads."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "stage_map_gspmd_check.py")],
        capture_output=True, text=True, timeout=900, env=env,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    rows = {r["path"]: r for r in (
        json.loads(line[len("CHECK "):]) for line in out.splitlines()
        if line.startswith("CHECK "))}
    assert set(rows) == {"per_leaf", "flat", "flat_device_pack"}
    every = {"forward_backward", "optimizer", "select", "quantize",
             "all_gather", "decode", "apply"}
    for path, row in rows.items():
        assert row["clients"] == 4
        want = every | ({"pack"} if path == "flat_device_pack" else set())
        assert set(row["stages"]) == want, row
