"""Two-sided log-magnitude range histogram of ΔW (one tensor).

This is the streaming pass of the TPU-native replacement for the paper's
O(n log n) top-p% sort (DESIGN.md §2).  One HBM→VMEM pass bins the positive
entries of ΔW (row 0) and the magnitudes of the negative entries (row 1)
into ``nbins`` log2-spaced buckets over the half-open magnitude range
``[lo, hi)``; out-of-range values are ignored (the caller tracks them via
survival counts from the previous, coarser pass).

Survival counts over the histogram give the top-k thresholds t⁺/t⁻ to one
bucket's resolution; a second zoomed-in pass over the winning bucket refines
them to nbins² effective resolution (see ops.seg_sbc_hist).

The flat tensor is zero-padded to whole (bm, lanes) blocks and run as a
one-segment launch of :func:`repro.kernels.flat.seg_hist2side`; zeros are
out of range for any lo > 0, so padding needs no mask.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flat import seg_hist2side

SPAN_OCTAVES = 30.0  # dynamic range of the coarse pass: [absmax·2⁻³⁰, absmax)

DEFAULT_BM = 8
DEFAULT_LANES = 128


def _pad_2d(flat: jax.Array, bm: int, lanes: int) -> jax.Array:
    n = flat.shape[0]
    padded = max(1, pl.cdiv(n, bm * lanes)) * bm * lanes
    x = jnp.zeros((padded,), jnp.float32).at[:n].set(flat.astype(jnp.float32))
    return x.reshape(-1, lanes)


@functools.partial(jax.jit, static_argnames=("nbins", "bm", "lanes", "interpret"))
def hist2side(
    flat: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    *,
    nbins: int = 128,
    bm: int = DEFAULT_BM,
    lanes: int = DEFAULT_LANES,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """(2, nbins) histogram: row 0 = positive entries, row 1 = |negatives|.

    ``lo``/``hi`` broadcast to shape (2,): per-side magnitude ranges.
    """
    lo2 = jnp.broadcast_to(jnp.asarray(lo, jnp.float32), (2,))
    hi2 = jnp.broadcast_to(jnp.asarray(hi, jnp.float32), (2,))
    params = jnp.stack([lo2[0], hi2[0], lo2[1], hi2[1]])[None]
    return seg_hist2side(
        _pad_2d(flat, bm, lanes), params, blk_starts=(0,), nbins=nbins,
        bm=bm, lanes=lanes, interpret=interpret,
    )[0]


def bucket_lower_edges(lo: jax.Array, hi: jax.Array, nbins: int) -> jax.Array:
    """Lower magnitude edge of every bucket, shape (nbins,), log2-spaced."""
    f = jnp.arange(nbins, dtype=jnp.float32) / nbins
    log_lo = jnp.log2(jnp.maximum(lo, 1e-38))
    log_hi = jnp.log2(jnp.maximum(hi, 2e-38))
    return 2.0 ** (log_lo + f * (log_hi - log_lo))
