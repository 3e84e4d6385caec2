"""Segment-aware Pallas kernels: the SBC pipeline over ONE flat buffer.

Each pass launches ONCE over the whole parameter set, laid out as a single
block-padded flat buffer by :class:`repro.core.flat.FlatParamSpace`
(DESIGN.md §10):

    leaf i occupies whole (bm, lanes) blocks [blk_starts[i], blk_starts[i+1]);
    the tail of its last block is zero-padded, so every grid step touches
    exactly one leaf.

The layout is static, so a grid step finds its segment from the static
``blk_starts`` table with a handful of scalar compares (no per-block side
array).  Per-segment scalars (thresholds, μ, side, …) ride in one small
``f32[nseg·P]`` table held whole in SMEM.  Reductions (histogram,
moments) write an ``(nseg, …)`` output whose block index follows the
segment: a segment's blocks are contiguous, so its output block stays
resident for exactly that run and is zeroed at the run's first block.
Each segment's blocks are visited in the same order as a one-segment
launch over that leaf, so the per-segment float accumulation order — and
the result, bit for bit — does not depend on what else shares the buffer.
The per-leaf entry points (:mod:`hist2side`, :mod:`moments`,
:mod:`binarize_apply`) are exactly that one-segment launch.

HBM traffic per pass is ~4 B/element read (the apply pass also writes
8 B/element).  Interpret mode is decided by
:func:`repro.kernels.resolve_interpret`.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _seg_of(i, blk_starts: Sequence[int]):
    """Segment owning grid step ``i``: the number of later segment starts
    at or before it (static table → unrolled scalar compares)."""
    seg = jnp.int32(0)
    for b in blk_starts[1:]:
        seg = seg + (i >= b).astype(jnp.int32)
    return seg


def _is_seg_start(i, blk_starts: Sequence[int]):
    first = i == blk_starts[0]
    for b in blk_starts[1:]:
        first = first | (i == b)
    return first


def _table(params: jax.Array) -> jax.Array:
    """(nseg, P) per-segment scalars → flat f32 SMEM table."""
    return params.astype(jnp.float32).reshape(-1)


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _seg_hist_kernel(params_ref, x_ref, hist_ref, *, nbins, blk_starts):
    i = pl.program_id(0)
    seg = _seg_of(i, blk_starts)

    @pl.when(_is_seg_start(i, blk_starts))
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    x = x_ref[...]  # (bm, lanes) f32, one segment's data (zero-padded tail)
    absx = jnp.abs(x)
    bins = jax.lax.broadcasted_iota(jnp.int32, (nbins, 1, 1), 0)

    # side 0 bins positive entries, side 1 bins |negative| entries, each
    # over its own [lo, hi) magnitude range from the segment's table row
    for side, sel in ((0, x > 0.0), (1, x < 0.0)):
        lo = params_ref[seg * 4 + 2 * side]
        hi = params_ref[seg * 4 + 2 * side + 1]
        in_range = sel & (absx >= lo) & (absx < hi)
        log_lo = jnp.log2(jnp.maximum(jnp.full((1, 1), lo), 1e-38))
        log_hi = jnp.log2(jnp.maximum(jnp.full((1, 1), hi), 2e-38))
        f = (jnp.log2(jnp.maximum(absx, 1e-38)) - log_lo) / (log_hi - log_lo)
        bucket = jnp.clip((f * nbins).astype(jnp.int32), 0, nbins - 1)
        match = (bucket[None] == bins) & in_range[None]  # (nbins, bm, lanes)
        # per-lane counts; the wrapper sums the lanes
        hist_ref[0, side, :, :] += jnp.sum(jnp.where(match, 1.0, 0.0), axis=1)


@functools.partial(
    jax.jit, static_argnames=("blk_starts", "nbins", "bm", "lanes", "interpret")
)
def seg_hist2side(
    xpad: jax.Array,
    params: jax.Array,
    *,
    blk_starts: tuple,
    nbins: int = 128,
    bm: int = 8,
    lanes: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """(nseg, 2, nbins) two-sided log-magnitude histograms, one flat launch.

    xpad:       f32[nblocks*bm, lanes] block-padded flat buffer.
    params:     f32[nseg, 4] rows ``(lo⁺, hi⁺, lo⁻, hi⁻)``.
    blk_starts: first block of every segment (static, ascending, from 0).
    """
    nblocks = xpad.shape[0] // bm
    nseg = len(blk_starts)
    per_lane = pl.pallas_call(
        functools.partial(_seg_hist_kernel, nbins=nbins, blk_starts=blk_starts),
        grid=(nblocks,),
        in_specs=[_SMEM, pl.BlockSpec((bm, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(
            (1, 2, nbins, lanes), lambda i: (_seg_of(i, blk_starts), 0, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((nseg, 2, nbins, lanes), jnp.float32),
        compiler_params=_SEQUENTIAL,
        interpret=resolve_interpret(interpret),
    )(_table(params), xpad)
    # integer counts: summing the lanes in int32 keeps them exact
    return jnp.sum(per_lane.astype(jnp.int32), axis=-1).astype(jnp.float32)


# ----------------------------------------------------------- exact-k picks
#
# Thresholds come from histograms, so they only bracket the k-th largest
# magnitude: with t ≤ t_hi the edges of the threshold bucket, every entry
# at or above t_hi ("tier 0") is kept and the entries in [t, t_hi)
# ("tier 1") are the ties.  To send exactly k, the pipeline keeps every
# s-th tie of the segment in flat order (up to its quota), so the kept
# ties spread over the whole segment — clustered picks would shorten the
# Golomb gaps below Eq. 5's expectation.  In block b, the tie of in-block
# rank j is kept iff j ≥ j0[b], j < lim[b] and s divides j − j0[b]; the
# per-block (j0, lim) come from per-block tier counts
# (:func:`repro.kernels.ops.seg_sbc_hist`), the stride s per segment.
#
# Per-block values travel as (8, 128) tiles of 1024 blocks each: tile
# ``b >> 10`` holds block b at flat slot ``b & 1023``.

_TILE = (8, 128)
_PER_TILE = _TILE[0] * _TILE[1]


def _to_tiles(v: jax.Array) -> jax.Array:
    """(nblocks, R) per-block values → (ntiles, R, 8, 128) f32 tiles."""
    nblocks, r = v.shape
    ntiles = max(1, -(-nblocks // _PER_TILE))
    pad = jnp.zeros((ntiles * _PER_TILE - nblocks, r), jnp.float32)
    v = jnp.concatenate([v.astype(jnp.float32), pad])
    return v.T.reshape(r, ntiles, *_TILE).transpose(1, 0, 2, 3)


def _from_tiles(t: jax.Array, nblocks: int) -> jax.Array:
    """Inverse of :func:`_to_tiles`: (ntiles, R, 8, 128) → (nblocks, R)."""
    r = t.shape[1]
    return t.transpose(1, 0, 2, 3).reshape(r, -1).T[:nblocks]


def _slot(i):
    """(8, 128) mask of block i's slot in its tile."""
    pos = (jax.lax.broadcasted_iota(jnp.int32, _TILE, 0) * _TILE[1]
           + jax.lax.broadcasted_iota(jnp.int32, _TILE, 1))
    return pos == (i & (_PER_TILE - 1))


def _tile_spec(r: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, r, *_TILE), lambda i: (i // _PER_TILE, 0, 0, 0))


def _total(v: jax.Array) -> jax.Array:
    """(1, 1) sum of a (bm, lanes) block."""
    return jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0, keepdims=True)


def _excl_rank(m: jax.Array) -> jax.Array:
    """Exclusive flat-order rank of the ones of a 0/1 f32 (bm, lanes)
    block: lane prefix and row offsets as 0/1 matmuls (exact in f32)."""
    bm, lanes = m.shape
    iota = jax.lax.broadcasted_iota
    upper = (iota(jnp.int32, (lanes, lanes), 0)
             < iota(jnp.int32, (lanes, lanes), 1)).astype(jnp.float32)
    lower = (iota(jnp.int32, (bm, bm), 0)
             > iota(jnp.int32, (bm, bm), 1)).astype(jnp.float32)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    row_tot = dot(m, jnp.ones((lanes, lanes), jnp.float32))
    return dot(m, upper) + dot(lower, row_tot)


def _tiers(x, t, t_hi, side: int):
    """Tier-0 and tier-1 masks of one side (side 1 looks at −x)."""
    v = x if side == 0 else -x
    return v >= t_hi, (v >= t) & (v < t_hi)


def _row(params_ref, i, blk_starts, ncols: int) -> list:
    """The ``ncols`` SMEM scalars of grid step i's segment."""
    base = _seg_of(i, blk_starts) * ncols
    return [params_ref[base + j] for j in range(ncols)]


def _seg_tier_counts_kernel(params_ref, x_ref, out_ref, *, blk_starts):
    i = pl.program_id(0)

    @pl.when((i & (_PER_TILE - 1)) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]
    t = _row(params_ref, i, blk_starts, 4)
    slot = _slot(i)
    for side in (0, 1):
        t0, t1 = _tiers(x, t[2 * side], t[2 * side + 1], side)
        for j, m in enumerate((t0, t1)):
            c = _total(jnp.where(m, 1.0, 0.0))
            out_ref[0, 2 * side + j] += jnp.where(slot, c, 0.0)


@functools.partial(
    jax.jit, static_argnames=("blk_starts", "bm", "lanes", "interpret")
)
def seg_tier_counts(
    xpad: jax.Array,
    params: jax.Array,
    *,
    blk_starts: tuple,
    bm: int = 8,
    lanes: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """i32[nblocks, 4] per-block counts ``(tier0⁺, tier1⁺, tier0⁻, tier1⁻)``.

    params: f32[nseg, 4] rows ``(t⁺, t_hi⁺, t⁻, t_hi⁻)``; tier 0 of a side
    is ``v ≥ t_hi``, tier 1 is ``t ≤ v < t_hi`` (v = x, resp. −x).
    """
    nblocks = xpad.shape[0] // bm
    ntiles = max(1, -(-nblocks // _PER_TILE))
    tiles = pl.pallas_call(
        functools.partial(_seg_tier_counts_kernel, blk_starts=blk_starts),
        grid=(nblocks,),
        in_specs=[_SMEM, pl.BlockSpec((bm, lanes), lambda i: (i, 0))],
        out_specs=_tile_spec(4),
        out_shape=jax.ShapeDtypeStruct((ntiles, 4, *_TILE), jnp.float32),
        compiler_params=_SEQUENTIAL,
        interpret=resolve_interpret(interpret),
    )(_table(params), xpad)
    return _from_tiles(tiles, nblocks).astype(jnp.int32)


def _picked(x, side: int, t, t_hi, s, inv_s, j0, lim):
    """Tier 0 plus the block's strided ties: in-block rank j with
    j0 ≤ j < lim and s | (j − j0).  The quotient is rounded to the nearest
    integer and checked by multiplying back, exact in f32 for j < 2²⁴."""
    t0, t1 = _tiers(x, t, t_hi, side)
    d = _excl_rank(jnp.where(t1, 1.0, 0.0)) - j0
    m = jnp.floor(d * inv_s + 0.5)
    return t0 | (t1 & (d >= 0.0) & (d + j0 < lim) & (m * s == d))


def _block_value(tiles_ref, r: int, i):
    return _total(jnp.where(_slot(i), tiles_ref[0, r], 0.0))


def _side_picked(x, t, tiles_ref, i, side: int, r: int):
    """``t``: the side's (t, t_hi, s, 1/s); tiles rows r, r+1: (j0, lim)."""
    return _picked(x, side, *t, j0=_block_value(tiles_ref, r, i),
                   lim=_block_value(tiles_ref, r + 1, i))


def _seg_moments_kernel(params_ref, x_ref, tiles_ref, out_ref, *, blk_starts):
    i = pl.program_id(0)

    @pl.when(_is_seg_start(i, blk_starts))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]
    t = _row(params_ref, i, blk_starts, 8)
    pos = _side_picked(x, t[0:4], tiles_ref, i, 0, 0)
    neg = _side_picked(x, t[4:8], tiles_ref, i, 1, 2)
    # per-lane partials of Σ⁺, n⁺, Σ⁻, n⁻; the wrapper sums the lanes
    for j, v in enumerate((jnp.where(pos, x, 0.0), jnp.where(pos, 1.0, 0.0),
                           jnp.where(neg, x, 0.0), jnp.where(neg, 1.0, 0.0))):
        out_ref[0, j:j + 1, :] += jnp.sum(v, axis=0, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("blk_starts", "bm", "lanes", "interpret")
)
def seg_moments(
    xpad: jax.Array,
    params: jax.Array,
    picks: jax.Array,
    *,
    blk_starts: tuple,
    bm: int = 8,
    lanes: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """(nseg, 2, 2) masked moments [[Σ⁺, n⁺], [Σ⁻, n⁻]] per segment over
    each side's tier 0 plus its kept ties.

    params: f32[nseg, 8] rows ``(t⁺, t_hi⁺, s⁺, 1/s⁺, t⁻, t_hi⁻, s⁻,
    1/s⁻)``; picks: [nblocks, 4] per-block ``(j0⁺, lim⁺, j0⁻, lim⁻)``.
    Padding zeros are never selected because t⁺, t⁻ > 0.
    """
    nblocks = xpad.shape[0] // bm
    nseg = len(blk_starts)
    per_lane = pl.pallas_call(
        functools.partial(_seg_moments_kernel, blk_starts=blk_starts),
        grid=(nblocks,),
        in_specs=[_SMEM, pl.BlockSpec((bm, lanes), lambda i: (i, 0)),
                  _tile_spec(4)],
        out_specs=pl.BlockSpec(
            (1, 4, lanes), lambda i: (_seg_of(i, blk_starts), 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((nseg, 4, lanes), jnp.float32),
        compiler_params=_SEQUENTIAL,
        interpret=resolve_interpret(interpret),
    )(_table(params), xpad, _to_tiles(picks))
    return jnp.sum(per_lane, axis=-1).reshape(nseg, 2, 2)


def _seg_apply_kernel(params_ref, x_ref, tiles_ref, out_ref, res_ref, *,
                      blk_starts):
    i = pl.program_id(0)
    x = x_ref[...]
    t = _row(params_ref, i, blk_starts, 10)
    mu, pos_wins = t[8], t[9] > 0.5

    # select between the two sides' f32 outputs (Mosaic has no select
    # over boolean vectors)
    out = jnp.where(
        pos_wins,
        jnp.where(_side_picked(x, t[0:4], tiles_ref, i, 0, 0), mu, 0.0),
        jnp.where(_side_picked(x, t[4:8], tiles_ref, i, 1, 0), mu, 0.0),
    )
    out_ref[...] = out
    res_ref[...] = x - out


@functools.partial(
    jax.jit, static_argnames=("blk_starts", "bm", "lanes", "interpret")
)
def seg_binarize_apply(
    xpad: jax.Array,
    params: jax.Array,
    picks: jax.Array,
    *,
    blk_starts: tuple,
    bm: int = 8,
    lanes: int = 128,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused (ΔW*, R) over the whole flat buffer — 1 read, 2 writes.

    params: f32[nseg, 10] rows ``(t⁺, t_hi⁺, s⁺, 1/s⁺, t⁻, t_hi⁻, s⁻,
    1/s⁻, μ, pos_wins)``; picks: [nblocks, 2] per-block ``(j0, lim)`` of
    the winning side.  Padding zeros yield ΔW* = 0 and R = 0 in the pad
    region (t⁺, t⁻ > 0).
    """
    nblocks = xpad.shape[0] // bm
    tile = pl.BlockSpec((bm, lanes), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_seg_apply_kernel, blk_starts=blk_starts),
        grid=(nblocks,),
        in_specs=[_SMEM, tile, _tile_spec(2)],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct(xpad.shape, jnp.float32),
            jax.ShapeDtypeStruct(xpad.shape, jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(_table(params), xpad, _to_tiles(picks))
