"""Fused sparse-binarize apply + residual update (one tensor).

The final pass of SBC compression (paper Alg. 2 lines 5-8 + Eq. 2):

    mask  = pos_wins ? (x ≥ t⁺) : (x ≤ −t⁻)
    ΔW*   = μ · mask                     (μ already signed: +μ⁺ or −μ⁻)
    R_new = x − ΔW*                      (x is the residual-accumulated ΔW)

Unfused this is ~4 HBM round-trips (mask, select, subtract, write); fused it
is one read and two writes, which matters because compression streams the
ENTIRE parameter set once per communication round.  A one-segment launch
of :func:`repro.kernels.flat.seg_binarize_apply` with no tie set; padding zeros produce
ΔW* = 0 and R = 0 in the pad region, which is sliced off.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flat import seg_binarize_apply
from repro.kernels.hist2side import DEFAULT_BM, DEFAULT_LANES, _pad_2d


@functools.partial(jax.jit, static_argnames=("bm", "lanes", "interpret"))
def binarize_apply(
    flat: jax.Array,
    t_pos: jax.Array,
    t_neg: jax.Array,
    mu: jax.Array,
    pos_wins: jax.Array,
    *,
    bm: int = DEFAULT_BM,
    lanes: int = DEFAULT_LANES,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (ΔW*, R_new), both f32 of the original flat length."""
    n = flat.shape[0]
    tp, tn, mu, side = (jnp.asarray(v, jnp.float32)
                        for v in (t_pos, t_neg, mu, pos_wins))
    xpad = _pad_2d(flat, bm, lanes)
    one = jnp.ones((), jnp.float32)
    out, res = seg_binarize_apply(
        xpad, jnp.stack([tp, tp, one, one, tn, tn, one, one, mu, side])[None],
        jnp.zeros((xpad.shape[0] // bm, 2), jnp.float32), blk_starts=(0,),
        bm=bm, lanes=lanes, interpret=interpret,
    )
    return out.reshape(-1)[:n], res.reshape(-1)[:n]
