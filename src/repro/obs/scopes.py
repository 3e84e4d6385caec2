"""Device stages of the jitted round: named scopes, and the join from a
compiled instruction back to its stage.

The local and gspmd backends compile a whole communication round into one
program, so no host span can see inside it.  Each stage of the round is
wrapped in :func:`scope` instead — a ``jax.named_scope`` whose name lands
in every lowered op's ``op_name`` metadata and survives into the compiled
module (fusions and ``while`` bodies included).  A profiler trace names
each device op by its compiled instruction (``%sort.5 = ...``), so

    scope  →  compiled-HLO metadata  →  instruction name in the trace

attributes every device op to a stage: :func:`hlo_stage_map` reads the
compiled module's text into ``{instruction: stage}`` and ``Run.stage_map``
returns that map for the round ``Run.step`` runs.

Scopes are metadata only: the optimised program is the same with and
without them.  Stage names carry the ``sbc.`` prefix so that a stage can
never be mistaken for a primitive in an ``op_name`` path (``sbc.select``
is not ``select_n`` or ``select_and_scatter``).  The taxonomy lives in
``SCOPE_NAMES`` (docs/observability.md documents each;
tests/test_docs_consistency.py holds the two together).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

import jax

SCOPE_PREFIX = "sbc."

SCOPE_NAMES: Dict[str, str] = {
    "forward_backward": "loss and gradient of the local steps, with their "
                        "scan loop",
    "optimizer": "the local optimizer's apply, and the client's update "
                 "ΔW_i = W_i' − W",
    "select": "the selector the policy resolves (SBC: the two-sided top-k)",
    "quantize": "error feedback and the quantizer: residual add, the "
                "binarisation mean, the ΔW* scatter, the residual update",
    "pack": "on-device Golomb packing of the surviving positions (gspmd)",
    "all_gather": "the packed positions and μ crossing the client axes "
                  "(gspmd)",
    "decode": "the gathered uploads back to one dense update (gspmd)",
    "apply": "the mean over clients, the weight update, the momentum mask "
             "and the round's metrics",
}

# compiled instructions that never run as a device op of their own
NOT_COMPUTE = frozenset(
    {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
)

_STAGE_RE = re.compile(r"(?<![\w.])" + re.escape(SCOPE_PREFIX) + r"(\w+)")


def scope(stage: str):
    """``with scope("select"): ...`` — the named scope of one stage."""
    if stage not in SCOPE_NAMES:
        raise KeyError(f"stage {stage!r} not declared in SCOPE_NAMES")
    return jax.named_scope(SCOPE_PREFIX + stage)


def stage_of(op_name: str) -> Optional[str]:
    """The innermost declared stage in an ``op_name`` path, or None.

    Handles the forms the compiler writes: transform wrappers around a
    scope (``vmap(sbc.select)``, ``transpose(jvp(sbc.forward_backward))``),
    ``while/body`` nesting, and ``;``-joined names on instructions the
    compiler merged — whose stage is that of the first joined name that
    has one.
    """
    for part in op_name.split(";"):
        found = [m for m in _STAGE_RE.findall(part) if m in SCOPE_NAMES]
        if found:
            return found[-1]
    return None


# ------------------------------------------------------------- HLO parsing

_COMP_RE = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR_RE = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_REF_RE = re.compile(r"%([\w.\-]+)")


def _closing(text: str, i: int) -> int:
    """Index just past the bracket group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        c = text[j]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def _parse_instr(rest: str) -> tuple:
    """``<shape> <opcode>(<operands>), <attrs>`` → (opcode, operands,
    attrs)."""
    i = _closing(rest, 0) if rest.startswith("(") else rest.index(" ")
    rest = rest[i:].lstrip()
    paren = rest.index("(")
    end = _closing(rest, paren)
    return rest[:paren], _REF_RE.findall(rest[paren:end]), rest[end:]


def _executed_children(opcode: str, attrs: str) -> List[str]:
    """Computations that run as device ops of their own when this
    instruction runs (not fused bodies or reducers)."""
    if opcode == "while":
        keys = ("body", "condition")
    elif opcode == "conditional":
        m = re.search(r"branch_computations=\{([^}]*)\}", attrs)
        if m:
            return _REF_RE.findall(m.group(1))
        keys = ("true_computation", "false_computation")
    elif opcode in ("call", "async-start"):
        keys = ("to_apply", "calls")
    else:
        return []
    out = []
    for k in keys:
        m = re.search(rf"\b{k}=%([\w.\-]+)", attrs)
        if m:
            out.append(m.group(1))
    return out


def hlo_stage_map(hlo_text: str) -> Dict[str, Optional[str]]:
    """``{instruction name: stage or None}`` for every compute instruction
    of a compiled module's text (``Compiled.as_text()``) that runs as a
    device op: the entry computation and every ``while`` body, condition,
    branch or call it reaches — not fused computations or reducers.

    An instruction's stage is :func:`stage_of` its ``op_name``.  The
    compiler adds instructions with no metadata (layout copies, prefetch
    ``copy-start``/``copy-done``, whole loops that move a buffer in
    chunks); each takes the stage of the operand that produced its input,
    else of the first user that has one, else of the instruction that
    runs its computation.
    """
    comps: Dict[str, list] = {}
    entry, cur = None, None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m and "=" not in line.split("(", 1)[0]:
            cur = comps.setdefault(m.group(2), [])
            if m.group(1):
                entry = m.group(2)
            continue
        m = _INSTR_RE.match(line)
        if m is None or cur is None:
            continue
        opcode, operands, attrs = _parse_instr(m.group(2))
        op = _OP_NAME_RE.search(attrs)
        cur.append((m.group(1), opcode, operands, attrs,
                    stage_of(op.group(1)) if op else None))
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")

    order, caller, todo = [], {entry: None}, [entry]
    while todo:
        name = todo.pop()
        if name in order or name not in comps:
            continue
        order.append(name)
        for ins in comps[name]:
            for child in _executed_children(ins[1], ins[3]):
                caller.setdefault(child, ins[0])
                todo.append(child)

    stage: Dict[str, Optional[str]] = {}
    for comp in order:  # a caller's computation comes before its callees
        instrs = comps[comp]
        users: Dict[str, List[str]] = {}
        for name, _, operands, _, st in instrs:
            stage[name] = st
            for o in operands:
                users.setdefault(o, []).append(name)
        movable = [ins for ins in instrs
                   if ins[1] not in ("parameter", "constant") and ins[4] is None]
        for name, _, operands, _, _ in movable:  # producers precede users
            stage[name] = next((stage[o] for o in operands if stage.get(o)),
                               None)
        inherited = stage.get(caller[comp])
        for name, _, _, _, _ in reversed(movable):
            if stage[name] is None:
                stage[name] = next(
                    (stage[u] for u in users.get(name, ()) if stage.get(u)),
                    inherited,
                )
    instrs = [ins for comp in order for ins in comps[comp]]
    return {name: stage[name] for name, opcode, _, _, _ in instrs
            if opcode not in NOT_COMPUTE}


def compiled_stage_map(lowered) -> Dict[str, Optional[str]]:
    """:func:`hlo_stage_map` of a lowered jitted function, compiled with
    its metadata in the persistent compile cache's key.  The key otherwise
    leaves metadata out: the same program built with other scopes (an
    older checkout) would be read back from the cache with its op names.
    """
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return hlo_stage_map(lowered.compile().as_text())
    finally:
        jax.config.update(key, before)
