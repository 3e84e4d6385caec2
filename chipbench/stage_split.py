"""The traced window's time per round by stage of the program: what the
``device.<stage>_ms`` and ``host.<span>_ms`` per-layer metrics read.

Device: the program's own join from a compiled instruction to the named
scope of the round's stage (``Run.stage_map``).  The cell's program is
built again from its traffic and configuration (``harness.Program``), the
map is taken for the round the window ran, and each in-window device op's
own time (``trace_reduce.self_times``) goes to the stage of the
instruction it names (the window runs no other program on the device).
An op the map does not place counts as unscoped.
The round compiled for the map is the window's own program (same shapes,
static arguments and donation), so its instruction names are the
trace's; ``Run.stage_map`` keys that compile by its metadata, so a
checkout's first traced run of a cell compiles it afresh and later ones
read it from the persistent cache.  The weights and batch are seeded
(seed 0): the compiled program depends on their shapes, not their
values.

Host: the program's spans recorded while the profiler was on
(``repro.obs.profiled_spans()``), which is the traced part of the window
only.  Their distinct rounds must equal the window's rounds.

Everything is per round of the traced window, in milliseconds.  Against a
program that has no stage map or profiled spans, the readers find
nothing and return None.  One result is kept per trace.
"""
from __future__ import annotations

from chipbench import harness
from chipbench import trace_reduce as tr

HOST_SPANS = ("dispatch", "fetch", "encode")
_last = (None, None)  # (reduced trace, its split)


def _stage_map(ctx):
    """The program's ``{instruction: stage}`` for the window's round, or
    None where the program has no such map."""
    harness._program_on_path()
    from repro.run.build import Run

    if not hasattr(Run, "stage_map"):
        return None
    from chipbench import traffic as traffic_mod

    mod = harness.load_module(harness.BENCH / "configs" / ctx.cfg["reference"],
                              "chipbench_stage_ref")
    cell = harness.Cell(name=f"{ctx.cfg['name']}.{ctx.traffic['name']}", workload={},
                        traffic=ctx.traffic, cfg=ctx.cfg, mod=mod)
    prog = harness.Program(cell, 0)
    k_weights, k_data = harness.keys_for(0)
    prog.feed(traffic_mod.make_pool(cell.cfg, cell.traffic, k_data),
              traffic_mod.POOL_ROUNDS)
    state = prog.make_state(k_weights)
    return prog.run.stage_map(state, harness.SETUP_ROUNDS)


def device_split(red, window, smap):
    """Seconds of own device time per stage in ``window``, averaged over
    the devices; ops that ``smap`` does not place go under None."""
    out = {}
    for dev in red.devices:
        for op, sec in tr.self_times(red.ops[dev], window):
            stage = smap.get(tr.op_name(op))
            out[stage] = out.get(stage, 0.0) + sec
    n_dev = max(len(red.devices), 1)
    return {k: v / n_dev for k, v in out.items()}


def host_split(spans, rounds):
    """Microseconds of self time per host span name: each span's duration
    less its children's.  Raises when the spans' distinct rounds are not
    the window's."""
    spans = [e for e in spans if e["name"] in HOST_SPANS]
    if not spans:
        return {}
    seen = {e["args"].get("round") for e in spans}
    if len(seen) != rounds:
        raise ValueError(f"the program's spans cover {len(seen)} rounds, "
                         f"the traced window {rounds}")
    child = {}
    for e in spans:
        child[e["parent"]] = child.get(e["parent"], 0.0) + e["dur_us"]
    out = {}
    for e in spans:
        out[e["name"]] = out.get(e["name"], 0.0) + e["dur_us"] - child.get(e["id"], 0.0)
    return out


def split(ctx):
    """``{"device": {stage: ms per round}, "host": {span: ms per round},
    "stages": stages the map names}`` for this trace, or None parts where
    the program offers nothing to read."""
    global _last
    if _last[0] is ctx.reduced:
        return _last[1]
    smap = _stage_map(ctx)
    device = stages = None
    if smap is not None:
        device = {k: 1e3 * v / ctx.rounds
                  for k, v in device_split(ctx.reduced, ctx.window, smap).items()}
        stages = set(smap.values()) - {None}
    import repro.obs as obs

    host = None
    if hasattr(obs, "profiled_spans"):
        host = {k: 1e-3 * v / ctx.rounds
                for k, v in host_split(obs.profiled_spans(), ctx.rounds).items()}
    result = {"device": device, "host": host, "stages": stages}
    _last = (ctx.reduced, result)
    return result


def device_ms(ctx, stage):
    """A stage's device time per round; None where the program's round has
    no such stage.  ``stage`` None reads the unscoped time."""
    s = split(ctx)
    if s["device"] is None or (stage is not None and stage not in s["stages"]):
        return None
    return s["device"].get(stage, 0.0)


def host_ms(ctx, span):
    s = split(ctx)
    if not s["host"] or span not in s["host"]:
        return None
    return s["host"][span]
