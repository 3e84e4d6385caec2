"""One repo-root resolver for every module that writes artifacts, and the
one place that points JAX's persistent compile cache.

``benchmarks/common.py`` and ``repro.launch.dryrun`` used to each carry
their own ``os.path.dirname(...)`` chains relative to ``__file__`` — path
math that silently breaks the moment a file moves one directory level.
All output-directory derivation now goes through this module:

    from repro.paths import experiments_dir
    OUT_DIR = experiments_dir("benchmarks")

The root is located structurally (the directory that holds ``src/repro``
plus the repo manifests), walking up from this file, so the helpers keep
working from an installed-src layout, a test process, or a launcher run
from any CWD.
"""
from __future__ import annotations

import os


def repo_root() -> str:
    """Absolute path of the repository root (the dir holding ``src/``)."""
    here = os.path.dirname(os.path.abspath(__file__))  # .../src/repro
    cand = os.path.dirname(os.path.dirname(here))  # .../
    if os.path.isdir(os.path.join(cand, "src", "repro")):
        return cand
    # fallback: walk upward until a directory with the src/repro layout
    cur = here
    while True:
        parent = os.path.dirname(cur)
        if parent == cur:
            return cand  # filesystem root reached; best effort
        if os.path.isdir(os.path.join(parent, "src", "repro")):
            return parent
        cur = parent


def experiments_dir(*parts: str, create: bool = False) -> str:
    """``<repo>/experiments/<parts...>`` (optionally mkdir -p'd)."""
    path = os.path.join(repo_root(), "experiments", *parts)
    if create:
        os.makedirs(path, exist_ok=True)
    return path


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<repo>/.jax_cache`` (gitignored) — a fixed path, so that the next
    process finds the entries again.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update(
        "jax_compilation_cache_dir", os.path.join(repo_root(), ".jax_cache")
    )
