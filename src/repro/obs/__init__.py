"""repro.obs — the unified telemetry layer (tracing + metrics + export).

One :class:`Telemetry` value bundles a tracer and a metrics registry and
travels through the stack: ``build_run`` attaches it to the backend's
:class:`~repro.core.channel.CommChannel` (every channel carries
``NULL_TELEMETRY`` until someone enables it), the fed server/scheduler
and the serve-side planner read it off the objects they already hold,
and the exporters in :mod:`repro.obs.export` turn it into a metrics
JSONL + a Perfetto ``trace.json`` at the end of the run.

Disabled telemetry is the shared :data:`NULL_TELEMETRY` singleton — no
recorded events, identity ``fence`` (no added device synchronization),
gated below 1% step-time overhead by ``benchmarks/run_api_overhead.py``.
Its spans still reach a profiler trace (:class:`~repro.obs.trace.ProfiledSpan`),
and the jitted round's device stages are named scopes
(:mod:`repro.obs.scopes`) joined to the trace through the compiled HLO.
"""
from __future__ import annotations

import dataclasses

from repro.obs.export import (
    SCHEMA,
    render_table,
    span_table,
    summary_table,
    write_metrics_jsonl,
    write_trace_json,
)
from repro.obs.metrics import (
    METRIC_NAMES,
    MetricsRegistry,
    NULL_METRICS,
    NullMetrics,
    validate_metric_events,
)
from repro.obs.scopes import SCOPE_NAMES, hlo_stage_map, scope, stage_of
from repro.obs.trace import (
    NULL_TRACER,
    NullTracer,
    ProfiledSpan,
    SPAN_NAMES,
    Tracer,
    profiled_spans,
    validate_span_events,
)


@dataclasses.dataclass(frozen=True)
class Telemetry:
    """Tracer + metrics registry, passed around as one handle."""

    tracer: object = NULL_TRACER
    metrics: object = NULL_METRICS

    @property
    def enabled(self) -> bool:
        return self.tracer.enabled or self.metrics.enabled

    def span(self, name: str, **args):
        """A host span, in the run's tracer when telemetry is on and in a
        profiler trace whenever one is recording."""
        return ProfiledSpan(self.tracer, name, args)

    def fence(self, x):
        return self.tracer.fence(x)


NULL_TELEMETRY = Telemetry()


def make_telemetry() -> Telemetry:
    """A fresh enabled bundle (one per run)."""
    return Telemetry(tracer=Tracer(), metrics=MetricsRegistry())


def finish_run(telemetry: Telemetry, trace: str = None,
               metrics_out: str = None, meta: dict = None,
               print_summary: bool = True) -> dict:
    """End-of-run export: write the requested files, print the console
    summary tables.  The one epilogue every launcher shares."""
    out = {}
    if not telemetry.enabled:
        return out
    if print_summary:
        if telemetry.tracer.events:
            print(span_table(telemetry.tracer))
        if telemetry.metrics.samples:
            print(summary_table(telemetry.metrics))
    if trace:
        out["trace"] = write_trace_json(trace, telemetry.tracer, meta=meta)
        print(f"wrote {out['trace']} (load in ui.perfetto.dev)")
    if metrics_out:
        out["metrics"] = write_metrics_jsonl(
            metrics_out, telemetry.metrics, meta=meta
        )
        print(f"wrote {out['metrics']}")
    return out


__all__ = [
    "METRIC_NAMES",
    "MetricsRegistry",
    "NULL_METRICS",
    "NULL_TELEMETRY",
    "NULL_TRACER",
    "NullMetrics",
    "NullTracer",
    "SCHEMA",
    "SCOPE_NAMES",
    "SPAN_NAMES",
    "Telemetry",
    "Tracer",
    "finish_run",
    "hlo_stage_map",
    "make_telemetry",
    "profiled_spans",
    "render_table",
    "scope",
    "span_table",
    "stage_of",
    "summary_table",
    "validate_metric_events",
    "validate_span_events",
    "write_metrics_jsonl",
    "write_trace_json",
]
