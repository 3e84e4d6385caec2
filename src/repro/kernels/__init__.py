"""Pallas kernels of the SBC pipeline, and the one place that decides how
they run.

Every kernel entry point takes ``interpret=None`` and passes it through
:func:`resolve_interpret`: compiled by Mosaic on a TPU, executed by the
Pallas interpreter on any other backend (CPU tests).  Asking for the
interpreter on a TPU is an error — a chip run never interprets silently.
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``None`` → compile on TPU, interpret elsewhere.  An explicit
    ``False`` is honoured anywhere (compiling for a described TPU from a
    CPU host); an explicit ``True`` on a TPU raises."""
    if interpret is None:
        return not on_tpu()
    if interpret and on_tpu():
        raise ValueError(
            "interpret=True on a TPU backend: Pallas kernels must be "
            "compiled on the chip (pass interpret=None)"
        )
    return bool(interpret)
