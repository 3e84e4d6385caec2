"""Round-structured tracing: nested host spans, on the profiler's clock.

The :class:`Tracer` records *complete spans* — named, nested intervals
with microsecond wall-clock timestamps.  One communication round
produces one ``round`` span whose children are the host stage spans of
that backend (the taxonomy lives in ``SPAN_NAMES`` and
docs/observability.md); the device stages inside a jitted round are
named scopes instead (:mod:`repro.obs.scopes`).

Every span a :class:`~repro.obs.Telemetry` handle opens is a
:class:`ProfiledSpan`: it always enters a ``jax.profiler.TraceAnnotation``
(tagged with its arguments, the round index among them), so a profiler
trace holds the program's spans beside the device ops on one clock —
whether the run's telemetry is on or off.  While a profiler session is
active it also records the span in one bounded, process-wide
:class:`Tracer`, read back with :func:`profiled_spans` by the process
that holds the trace.

Device timing is only meaningful if the traced interval actually waits
for the device: jax dispatch returns before the computation finishes, so
every span that closes over device work must call :meth:`Tracer.fence`
on the outputs before exiting (``jax.block_until_ready``).  The fence is
a no-op on the disabled tracer — tracing off means *no* added
synchronization, not just no recorded events.

Cheap by default: :data:`NULL_TRACER` is a singleton whose ``span()``
returns one shared no-op context manager and whose ``fence`` is
identity.  Instrumented call sites hold a tracer unconditionally (never
``if tracer:`` branches around jax calls), so with telemetry off a span
costs one ``TraceAnnotation`` with no session to record it (about a
microsecond) — gated below 1% of step time by
``benchmarks/run_api_overhead.py``.
"""
from __future__ import annotations

import collections
import json
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

# The span taxonomy — every span a repro component emits is named here
# (docs/observability.md documents each; tests/test_docs_consistency.py
# holds the two lists together so names cannot drift).
SPAN_NAMES: Dict[str, str] = {
    "round": "one communication round (parent of all stage spans)",
    "select_quantize": "client-side selection + quantization compute",
    "dispatch": "local/gspmd Run.step: rate resolve, batch, and the jitted "
                "round's call up to its return",
    "fetch": "the copy of client 0's compressed upload to the host "
             "(args: arrays, bytes)",
    "encode": "host-side wire encoding (SBW1 pack / Golomb streams)",
    "decode": "server-side unpack of client uploads",
    "apply": "aggregate + apply the round update to the master weights",
    "plan": "serve-side catch-up planning for one lag class",
    "encode_stacked": "serve-side SBD1 stacked catch-up encode",
    "verify": "serve-side bit-exactness verification of applied plans",
}


class _Span:
    """One open span; records a complete-span event on exit."""

    __slots__ = ("_tracer", "name", "args", "id", "parent_id", "depth", "t0")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args

    def __enter__(self) -> "_Span":
        tr = self._tracer
        self.id = tr._next_id
        tr._next_id += 1
        self.parent_id = tr._stack[-1].id if tr._stack else None
        self.depth = len(tr._stack)
        tr._stack.append(self)
        self.t0 = tr._now_us()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        tr = self._tracer
        t1 = tr._now_us()
        assert tr._stack and tr._stack[-1] is self, "span closed out of order"
        tr._stack.pop()
        tr.events.append({
            "type": "span",
            "name": self.name,
            "id": self.id,
            "parent": self.parent_id,
            "depth": self.depth,
            "ts_us": self.t0,
            "dur_us": t1 - self.t0,
            "args": self.args,
        })
        return False


class _NullSpan:
    """The shared no-op context manager the disabled tracer hands out."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Collects nested spans as JSONL-able event dicts; with
    ``max_events`` only the newest that many are kept."""

    enabled = True

    def __init__(self, max_events: Optional[int] = None) -> None:
        self.events = (collections.deque(maxlen=max_events)
                       if max_events else [])
        self._stack: List[_Span] = []
        self._next_id = 0
        self._epoch_ns = time.perf_counter_ns()

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._epoch_ns) / 1e3

    # -------------------------------------------------------------- recording

    def span(self, name: str, **args: Any) -> _Span:
        """Open a nested span: ``with tracer.span("encode", leaf=path): ...``"""
        return _Span(self, name, args)

    def fence(self, x: Any) -> Any:
        """Block until ``x``'s device computation finished, so the
        enclosing span's duration covers the work it names."""
        if x is not None:
            import jax

            jax.block_until_ready(x)
        return x

    # -------------------------------------------------------------- exporting

    def chrome_events(self) -> List[dict]:
        """Chrome/Perfetto ``traceEvents`` (complete-span ``ph: "X"``)."""
        return [{
            "ph": "X", "name": e["name"], "cat": "repro",
            "ts": e["ts_us"], "dur": e["dur_us"],
            "pid": 0, "tid": 0, "args": e["args"],
        } for e in self.events]

    def write_chrome(self, path: str) -> str:
        """Write a Perfetto-loadable ``trace.json`` (ui.perfetto.dev /
        chrome://tracing both open it)."""
        with open(path, "w") as f:
            json.dump({
                "traceEvents": self.chrome_events(),
                "displayTimeUnit": "ms",
            }, f)
        return path


class NullTracer:
    """All no-ops; ``fence`` is identity (adds NO synchronization)."""

    enabled = False
    events: tuple = ()

    __slots__ = ()

    def span(self, name: str, **args: Any) -> _NullSpan:
        return _NULL_SPAN

    def fence(self, x: Any) -> Any:
        return x


NULL_TRACER = NullTracer()

# the spans opened while a profiler session is active, for the process
# that holds the trace (bounded: a long session keeps the newest)
_PROFILED = Tracer(max_events=1 << 16)
_profiling = False  # whether the previous span saw a session


def profiled_spans() -> List[dict]:
    """The span events recorded in the newest profiler session, oldest
    first: each with its ``name``, ``args`` (the round among them),
    ``id``, ``parent`` and ``dur_us``.  A session starts afresh at its
    first span after a span opened with no session."""
    return list(_PROFILED.events)


def _mirror(name: str, args: dict):
    global _profiling
    on = TraceAnnotation.is_enabled()
    if on and not _profiling:
        _PROFILED.events.clear()
    _profiling = on
    return _PROFILED.span(name, **args) if on else None


class ProfiledSpan:
    """A span on the profiler's clock: a ``TraceAnnotation`` always, the
    run tracer's span, and — while a profiler session is active — a record
    in the process-wide profiled tracer.  ``with`` binds the run tracer's
    span."""

    __slots__ = ("_annotation", "_span", "_mirror")

    def __init__(self, tracer, name: str, args: dict):
        self._annotation = TraceAnnotation(name, **args)
        self._span = tracer.span(name, **args)
        self._mirror = _mirror(name, args)

    def __enter__(self):
        self._annotation.__enter__()
        if self._mirror is not None:
            self._mirror.__enter__()
        return self._span.__enter__()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._span.__exit__(exc_type, exc, tb)
        if self._mirror is not None:
            self._mirror.__exit__(exc_type, exc, tb)
        self._annotation.__exit__(exc_type, exc, tb)
        return False


def validate_span_events(events: List[dict]) -> List[str]:
    """Structural checks on recorded span events: every span closed with a
    non-negative duration, parents exist, children nest inside the parent's
    interval, names come from the taxonomy.  Returns error strings."""
    errs: List[str] = []
    spans: Dict[int, dict] = {}
    for i, e in enumerate(events):
        t = e.get("type")
        if t != "span":
            errs.append(f"event {i}: unknown trace event type {t!r}")
            continue
        for field in ("name", "id", "depth", "ts_us", "dur_us", "args"):
            if field not in e:
                errs.append(f"event {i}: span missing {field!r}")
        if e.get("dur_us", -1) < 0:
            errs.append(f"span {e.get('name')}: negative duration")
        if e.get("name") not in SPAN_NAMES:
            errs.append(f"span name {e.get('name')!r} not in SPAN_NAMES")
        if "id" in e:
            spans[e["id"]] = e
    for e in spans.values():
        pid = e.get("parent")
        if pid is None:
            continue
        p = spans.get(pid)
        if p is None:
            errs.append(f"span {e['name']} (id {e['id']}): parent {pid} "
                        "never closed")
            continue
        eps = 1.0  # µs of clock slack
        if e["ts_us"] < p["ts_us"] - eps or (
            e["ts_us"] + e["dur_us"] > p["ts_us"] + p["dur_us"] + eps
        ):
            errs.append(
                f"span {e['name']} (id {e['id']}) escapes its parent "
                f"{p['name']}'s interval"
            )
        if e["depth"] != p["depth"] + 1:
            errs.append(f"span {e['name']}: depth {e['depth']} under parent "
                        f"depth {p['depth']}")
    return errs
