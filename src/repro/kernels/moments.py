"""Masked first moments for sparse binarization (one tensor).

Given the top-k thresholds t⁺ and t⁻ (from the histogram passes), one
streaming HBM→VMEM pass computes, per paper Alg. 2 lines 3-4:

    sum⁺ = Σ x·[x ≥ t⁺]      cnt⁺ = Σ [x ≥ t⁺]
    sum⁻ = Σ x·[x ≤ −t⁻]     cnt⁻ = Σ [x ≤ −t⁻]

so that μ⁺ = sum⁺/cnt⁺ and μ⁻ = −sum⁻/cnt⁻: a one-segment launch of
:func:`repro.kernels.flat.seg_moments` with no tie set (t_hi = t, so
every entry at or above t counts).  Padding zeros are never selected
because t⁺, t⁻ > 0.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flat import seg_moments
from repro.kernels.hist2side import DEFAULT_BM, DEFAULT_LANES, _pad_2d


@functools.partial(jax.jit, static_argnames=("bm", "lanes", "interpret"))
def masked_moments(
    flat: jax.Array,
    t_pos: jax.Array,
    t_neg: jax.Array,
    *,
    bm: int = DEFAULT_BM,
    lanes: int = DEFAULT_LANES,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Returns (2,2) f32: [[sum⁺, cnt⁺], [sum⁻, cnt⁻]]."""
    tp, tn = jnp.asarray(t_pos, jnp.float32), jnp.asarray(t_neg, jnp.float32)
    xpad = _pad_2d(flat, bm, lanes)
    one = jnp.ones((), jnp.float32)
    picks = jnp.zeros((xpad.shape[0] // bm, 4), jnp.float32)
    return seg_moments(
        xpad, jnp.stack([tp, tp, one, one, tn, tn, one, one])[None], picks,
        blk_starts=(0,),
        bm=bm, lanes=lanes, interpret=interpret,
    )[0]
