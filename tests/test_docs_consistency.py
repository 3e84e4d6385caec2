"""Docs cannot silently rot: every registry name, codec spec, CLI flag,
and module reference in README/docs/DESIGN must exist in the code.

Two directions:
  * accuracy — names the docs mention must exist (flags in some launcher
    parser, stage names in the registries, `a|b|c` specs composable,
    referenced modules importable);
  * completeness — every registered selector/quantizer/encoder/codec/
    compressor name must be documented somewhere.
"""
import importlib
import re
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DOC_FILES = [REPO / "README.md", REPO / "DESIGN.md",
             *sorted((REPO / "docs").glob("*.md"))]

# launcher + harness modules that expose build_parser()
PARSER_MODULES = [
    "repro.run",
    "repro.launch.train",
    "repro.launch.dist",
    "repro.launch.fed",
    "repro.launch.serve",
    "repro.launch.dryrun",
    "repro.obs.view",
    "repro.scale",
    "benchmarks.run",
]


def doc_text() -> str:
    assert DOC_FILES[0].exists(), "README.md missing"
    return "\n".join(p.read_text() for p in DOC_FILES if p.exists())


def all_parser_flags() -> set:
    flags = set()
    for mod in PARSER_MODULES:
        ap = importlib.import_module(mod).build_parser()
        for action in ap._actions:
            flags.update(o for o in action.option_strings if o.startswith("--"))
    return flags


def registries():
    from repro.core import api
    from repro.core.codec import available_codecs
    from repro.core.stages import available_stages

    stages = available_stages()
    return {
        "selectors": set(stages["selectors"]),
        "quantizers": set(stages["quantizers"]),
        "encoders": set(stages["encoders"]),
        "codecs": set(available_codecs()),
        "compressors": set(api.available()),
    }


def test_documented_cli_flags_exist():
    """Every `--flag` in the docs parses in at least one launcher."""
    documented = set(re.findall(r"`(--[a-z][a-z0-9-]*)", doc_text()))
    assert documented, "docs mention no CLI flags — README table missing?"
    known = all_parser_flags()
    unknown = documented - known
    assert not unknown, f"docs mention nonexistent CLI flags: {sorted(unknown)}"


def test_shared_run_flags_are_documented():
    """Completeness: every flag on the SHARED add_run_flags parser (the
    surface every launcher builds on) must appear in README's CLI table —
    a new run flag cannot ship undocumented."""
    import argparse

    from repro.run.flags import add_run_flags

    ap = add_run_flags(argparse.ArgumentParser())
    flags = {
        o for action in ap._actions for o in action.option_strings
        if o.startswith("--") and o != "--help"
    }
    assert flags, "shared parser exposes no flags?"
    documented = set(re.findall(r"`(--[a-z][a-z0-9-]*)`", doc_text()))
    missing = flags - documented
    assert not missing, (
        f"shared add_run_flags() flags missing from the docs: "
        f"{sorted(missing)} — document them in README's CLI table"
    )


def test_registered_stage_and_codec_names_are_documented():
    """Completeness: every registered name appears in README/docs."""
    text = doc_text()
    missing = {
        kind: sorted(n for n in names if f"`{n}`" not in text)
        for kind, names in registries().items()
    }
    missing = {k: v for k, v in missing.items() if v}
    assert not missing, f"registered but undocumented names: {missing}"


def test_documented_spec_strings_compose():
    """Every `sel|quant|enc` spec in the docs is buildable from the
    registries (catches renames that orphan doc examples)."""
    regs = registries()
    specs = re.findall(r"`([a-z_0-9]+)\|([a-z_0-9]+)\|([a-z_0-9]+)`", doc_text())
    assert specs, "docs mention no codec spec strings"
    for sel, quant, enc in specs:
        assert sel in regs["selectors"], f"unknown selector {sel!r} in docs"
        assert quant in regs["quantizers"], f"unknown quantizer {quant!r} in docs"
        assert enc in regs["encoders"], f"unknown encoder {enc!r} in docs"


def test_referenced_modules_import():
    """`repro.launch.*` / `benchmarks.*` names in the docs must import."""
    text = doc_text()
    mods = set(re.findall(r"\b(repro\.launch\.[a-z_]+)\b", text))
    mods |= set(re.findall(r"\b(benchmarks\.[a-z_0-9]+)\b", text))
    assert mods
    for mod in sorted(mods):
        importlib.import_module(mod)


def test_benchmark_files_referenced_in_docs_exist():
    """`benchmarks/foo.py` / `docs/foo.md` paths in the docs must exist."""
    text = doc_text()
    for rel in set(re.findall(r"`((?:benchmarks|docs|experiments)/[\w./-]+)`", text)):
        assert (REPO / rel).exists(), f"docs reference missing file {rel!r}"


def test_observability_doc_covers_span_and_metric_registries():
    """Completeness both ways for the telemetry layer: every span name in
    SPAN_NAMES and every metric in METRIC_NAMES must appear (backticked)
    in docs/observability.md — a new instrumentation point cannot ship
    undocumented, and the doc cannot name spans/metrics that don't
    exist."""
    from repro.obs import METRIC_NAMES, SPAN_NAMES

    doc = (REPO / "docs" / "observability.md").read_text()
    missing = [n for n in SPAN_NAMES if f"`{n}`" not in doc]
    assert not missing, f"spans undocumented in docs/observability.md: {missing}"
    missing = [n for n in METRIC_NAMES if f"`{n}`" not in doc]
    assert not missing, f"metrics undocumented in docs/observability.md: {missing}"
    # accuracy: backticked span-like tokens in the doc's span table rows
    # must be registered names
    documented_spans = set(re.findall(r"^\| `([a-z_]+)` \|", doc, re.M))
    unknown = documented_spans - set(SPAN_NAMES) - set(METRIC_NAMES)
    assert not unknown, f"docs/observability.md names unknown spans: {unknown}"


def test_observability_doc_covers_stage_scopes():
    """The stage-scope table of docs/observability.md names every scope in
    SCOPE_NAMES, and no other."""
    from repro.obs import SCOPE_NAMES

    doc = (REPO / "docs" / "observability.md").read_text()
    documented = set(re.findall(r"^\| `sbc\.([a-z_]+)` \|", doc, re.M))
    assert documented == set(SCOPE_NAMES), (
        f"undocumented: {set(SCOPE_NAMES) - documented}, "
        f"unknown: {documented - set(SCOPE_NAMES)}")


def test_design_section_10_documents_flat_path():
    """DESIGN.md must carry the §10 FlatParamSpace layout contract the
    fast-path code points at."""
    design = (REPO / "DESIGN.md").read_text()
    assert "§10" in design and "FlatParamSpace" in design
    assert "fast=True" in design
