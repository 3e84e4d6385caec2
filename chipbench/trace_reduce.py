"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read, with ``jax.profiler.ProfileData`` and nothing else.

  device ops   every event on a device plane's ``XLA Ops`` line: start and
               end (ns), the op's HLO text and its stats (a TPU trace
               carries no source or name-stack metadata on them);
  async ops    the events of the ``Async XLA Ops`` line: transfers and
               collectives in flight beside the ops;
  busy         per device, the union of its op intervals;
  collectives  per device, the intervals of collective operations, on
               either line;
  host spans   the benchmark's own ``TraceAnnotation`` intervals (names
               that start with ``bench.``).

All times are in the trace's own nanoseconds; device and host events
share the profiler's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os

COLLECTIVE_WORDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "collective-permute", "all-to-all")
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Op:
    start: float
    end: float
    name: str
    stats: dict


@dataclasses.dataclass
class Reduced:
    ops: dict  # device name -> [Op]
    host: list  # [(start, end, name)]
    async_ops: dict = dataclasses.field(default_factory=dict)  # device -> [Op]

    @property
    def devices(self):
        return sorted(self.ops)

    def window(self):
        """[first, last] of the benchmark's host spans."""
        return min(s for s, _, _ in self.host), max(e for _, e, _ in self.host)

    def busy(self, device, window=None):
        return clip(merge([(o.start, o.end) for o in self.ops[device]]), window)

    def collectives(self, device, window=None):
        ops = self.ops[device] + self.async_ops.get(device, [])
        return clip(merge([(o.start, o.end) for o in ops if is_collective(o)]), window)

    def compute(self, device, window=None):
        return clip(merge([(o.start, o.end) for o in self.ops[device]
                           if not is_collective(o)]), window)


def op_name(op):
    """The HLO instruction's own name: a TPU trace names each op event by
    its whole HLO text (``%sort.70 = (f32[...]) sort(...)``)."""
    return op.name.split(" = ", 1)[0].lstrip("%")


def is_collective(op):
    text = (op_name(op) + " " + str(op.stats.get("hlo_category", ""))).lower()
    return any(w in text for w in COLLECTIVE_WORDS)


def merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, window):
    if window is None:
        return intervals
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Length of the merged intervals ``a`` not covered by the merged,
    disjoint intervals ``b``."""
    out, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        covered, k = 0.0, j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
        out += (e - s) - covered
    return out


def _stats(ev):
    out = {}
    for item in ev.stats:
        if len(item) == 2:
            out[str(item[0])] = item[1]
    return out


def from_profile(pd):
    ops, async_ops, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                dest = {"XLA Ops": ops, "Async XLA Ops": async_ops}.get(line.name)
                if dest is not None:
                    dest.setdefault(plane.name, []).extend(
                        Op(ev.start_ns, ev.end_ns, ev.name, _stats(ev))
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.start_ns, ev.end_ns, ev.name) for ev in line.events
                            if ev.name.startswith(HOST_PREFIX))
    return Reduced(ops=ops, host=sorted(host), async_ops=async_ops)


def find_xplane(trace_dir):
    hits = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(hits, key=os.path.getmtime)


def load(path):
    """Reduce the newest ``.xplane.pb`` under a directory, or one file
    (gzipped when its name ends in ``.gz``)."""
    import gzip

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return from_profile(ProfileData.from_serialized_xspace(f.read()))
    return from_profile(ProfileData.from_file(path))


def idle_gaps(red, device, window, top=10):
    """The longest device-idle gaps in ``window``, each named by the host
    span that covers its midpoint (``host idle`` when none does)."""
    busy = red.busy(device, window)
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        names = [n for hs, he, n in red.host if hs <= mid <= he]
        out.append((names[-1] if names else "host idle", (e - s) * 1e-9))
    return out


def self_times(ops, window):
    """(op, seconds) of each op's own time in ``window``: its interval less
    the ops nested in it (a ``while`` holds its body's ops on the same
    line)."""
    out, stack = [], []  # stack of [op, start, end, child time]
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        s, e = max(o.start, window[0]), min(o.end, window[1])
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((top[0], (top[2] - top[1] - top[3]) * 1e-9))
        if e <= s:
            continue
        if stack:  # nested, or (not on one TPU stream) overlapping
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([o, s, e, 0.0])
    out += [(t[0], (t[2] - t[1] - t[3]) * 1e-9) for t in stack]
    return out


def top_ops(red, window, top=10, width=160):
    """Device seconds of own time per op over all devices in ``window``,
    most first; each op is named by the start of its HLO text."""
    acc = {}
    for dev in red.devices:
        for o, sec in self_times(red.ops[dev], window):
            key = o.name[:width]
            acc[key] = acc.get(key, 0.0) + sec
    return sorted(acc.items(), key=lambda kv: -kv[1])[:top]
