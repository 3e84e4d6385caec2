"""Public jit'd wrappers over the Pallas SBC kernels.

Pipeline (the TPU-native replacement for the paper's top-p% sort),
``seg_sbc_hist`` over a block-padded flat buffer of one or more segments:

  1. two ``seg_hist2side`` passes — a coarse (2, nbins) log-magnitude
     histogram over [absmax·2⁻³⁰, absmax); survival counts pick the bucket
     holding the k-th largest entry per side; a second histogram zoomed
     into that bucket narrows it to a bucket [t, t_hi) of nbins² effective
     resolution (~0.002 octaves at nbins=128).
  2. ``seg_tier_counts`` — per block, the entries at or above t_hi
     (kept) and inside [t, t_hi) (the ties); the ties are kept by per-block
     picks spread over the segment, so every segment keeps exactly k.
  3. ``seg_moments`` — μ⁺/μ⁻ over the kept entries (Alg. 2 l.4).
  4. ``seg_binarize_apply`` — fused ΔW* write + residual update (Eq. 2).

Five streaming passes vs. an O(n log n) sort; each is memory-bound at
~4 B/element read.  Interpret mode is decided by
:func:`repro.kernels.resolve_interpret` (compiled on TPU, interpreted
elsewhere).

``sbc_compress_hist`` is the one-segment pipeline and returns everything
the trainer's exchange needs.  ``sbc_compress_exact`` is the faithful
top-k path (:func:`repro.core.select.two_sided_topk`, bit-identical to
``lax.top_k``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.golomb import expected_position_bits
from repro.core.select import two_sided_topk
from repro.kernels.flat import (
    seg_binarize_apply,
    seg_hist2side,
    seg_moments,
    seg_tier_counts,
)
from repro.kernels.hist2side import (
    DEFAULT_BM,
    DEFAULT_LANES,
    SPAN_OCTAVES,
    _pad_2d,
    bucket_lower_edges,
)


def _side_threshold(
    hist_row: jax.Array, edges: jax.Array, k: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pick the bucket of the k-th largest entry from survival counts.

    Returns (bucket_lo_edge, bucket_hi_edge, count_above_bucket).
    If the side has fewer than k entries the threshold collapses to the
    lowest edge (select everything on that side).
    """
    nbins = hist_row.shape[0]
    # survival[b] = number of entries in bucket >= b
    survival = jnp.cumsum(hist_row[::-1])[::-1]
    feasible = survival >= k
    any_feasible = jnp.any(feasible)
    # largest feasible bucket index (survival is non-increasing)
    bstar = jnp.where(any_feasible, jnp.sum(feasible.astype(jnp.int32)) - 1, 0)
    lo_edge = jnp.where(any_feasible, edges[bstar], edges[0])
    hi_edge = jnp.where(
        bstar + 1 < nbins,
        edges[jnp.minimum(bstar + 1, nbins - 1)],
        edges[nbins - 1] * 2.0,
    )
    above = jnp.where(
        bstar + 1 < nbins,
        jnp.concatenate([survival[1:], jnp.zeros((1,))])[bstar],
        0.0,
    )
    return lo_edge, hi_edge, above


class SBCCompressed(NamedTuple):
    """Everything one SBC compression of a flat tensor produces."""

    delta_star: jax.Array  # dense ΔW* (f32[n])
    residual: jax.Array  # new residual = acc − ΔW* (f32[n])
    mean: jax.Array  # signed μ (f32[])
    count: jax.Array  # number of surviving entries m (f32[])
    nbits: jax.Array  # analytic wire bits: m·b̄_pos(p) + 32


def _exact_k_picks(counts, k, seg_of_blk, blk_starts, block, t, t_hi):
    """One side's tie handling: which ties each block keeps so that the
    segment keeps exactly ``min(k, n0 + n1)`` entries.

    ``counts`` is i32[nblocks, 2] (tier 0, tier 1) for this side.  Should
    tier 0 alone exceed k (a direct compare disagreeing with the
    histogram's binning at an edge), tier 0 becomes the tie set.  The
    segment keeps the ties of rank r ≡ 0 (mod s), r < q·s, with quota
    q = k − n0 and stride s = n1 // q; block b, whose ties start at
    segment rank C_b, keeps in-block ranks j ≡ j0 (mod s), j0 = −C_b mod s,
    below lim = q·s − C_b.  Returns ``(t, t_hi, s, j0, lim, kept)``.
    """
    nseg = t.shape[0]
    seg_sum = functools.partial(jax.ops.segment_sum, segment_ids=seg_of_blk,
                                num_segments=nseg, indices_are_sorted=True)
    n0, n1 = seg_sum(counts[:, 0]), seg_sum(counts[:, 1])
    swap = n0 > k
    t = jnp.where(swap, t_hi, t)
    t_hi = jnp.where(swap, jnp.inf, t_hi)
    c1 = jnp.where(swap[seg_of_blk], counts[:, 0], counts[:, 1])
    n1 = jnp.where(swap, n0, n1)
    n0 = jnp.where(swap, 0, n0)
    q = jnp.clip(k - n0, 0, n1)
    s = jnp.maximum(1, n1 // jnp.maximum(q, 1))
    before = jnp.cumsum(c1) - c1
    before = before - before[jnp.asarray(blk_starts)][seg_of_blk]
    sb = s[seg_of_blk]
    cap = 2 * block  # past any in-block rank
    j0 = jnp.minimum((sb - before % sb) % sb, cap)
    lim = jnp.clip((q * s)[seg_of_blk] - before, 0, cap)
    return t, t_hi, s, j0, lim, n0 + q


def seg_sbc_hist(
    acc_flat: jax.Array,
    bounds,
    ks,
    rates,
    *,
    bm: int,
    lanes: int,
    nbins: int = 128,
    interpret: Optional[bool] = None,
):
    """Histogram-threshold SBC over a block-padded flat buffer: every
    segment keeps exactly k entries (fewer only if one side has fewer
    than k nonzeros).

    ``bounds`` is the static per-segment ``(offset, size)`` table with
    block-aligned offsets; ``ks``/``rates`` the per-segment survivor counts
    and sparsity rates.  Two histogram passes bracket each side's k-th
    magnitude by a bucket [t, t_hi); one counting pass finds each block's
    tier sizes; the ties inside the bucket are kept by per-block picks
    (:func:`_exact_k_picks`); then masked moments and the fused apply.
    Returns ``(delta_star_flat, residual_flat, stats)``, stats per segment
    ``{mu, count, nbits}``.
    """
    n_blocks = acc_flat.shape[0] // (bm * lanes)
    xpad = acc_flat.reshape(n_blocks * bm, lanes)
    blk_starts = tuple(off // (bm * lanes) for off, _ in bounds)
    kw = dict(blk_starts=blk_starts, bm=bm, lanes=lanes, interpret=interpret)
    seg_of_blk = (jnp.searchsorted(jnp.asarray(blk_starts),
                                   jnp.arange(n_blocks), side="right") - 1)

    # per-segment |x| range for the coarse pass (max is order-independent)
    absmax = jnp.stack([
        jnp.max(jnp.abs(acc_flat[off:off + size])) for off, size in bounds
    ]) + 1e-30
    lo0 = absmax * 2.0**-SPAN_OCTAVES
    hi0 = absmax * 1.0001

    k = jnp.asarray(ks, jnp.int32)
    kf = k.astype(jnp.float32)
    vthresh = jax.vmap(_side_threshold)
    vedges = jax.vmap(lambda lo, hi: bucket_lower_edges(lo, hi, nbins))

    h1 = seg_hist2side(xpad, jnp.stack([lo0, hi0, lo0, hi0], axis=1),
                       nbins=nbins, **kw)
    edges0 = vedges(lo0, hi0)
    lo_p, hi_p, above_p = vthresh(h1[:, 0], edges0, kf)
    lo_n, hi_n, above_n = vthresh(h1[:, 1], edges0, kf)

    # pass 2 zooms into each side's bucket; its threshold bucket's upper
    # edge never exceeds the zoomed range
    h2 = seg_hist2side(xpad, jnp.stack([lo_p, hi_p, lo_n, hi_n], axis=1),
                       nbins=nbins, **kw)
    t_pos, th_pos, _ = vthresh(h2[:, 0], vedges(lo_p, hi_p), kf - above_p)
    t_neg, th_neg, _ = vthresh(h2[:, 1], vedges(lo_n, hi_n), kf - above_n)
    th_pos, th_neg = jnp.minimum(th_pos, hi_p), jnp.minimum(th_neg, hi_n)

    counts = seg_tier_counts(
        xpad, jnp.stack([t_pos, th_pos, t_neg, th_neg], axis=1), **kw)
    pick = functools.partial(_exact_k_picks, k=k, seg_of_blk=seg_of_blk,
                             blk_starts=blk_starts, block=bm * lanes)
    sides = [pick(counts[:, 2 * i:2 * i + 2], t=t, t_hi=th)
             for i, (t, th) in enumerate(((t_pos, th_pos), (t_neg, th_neg)))]
    tiers = jnp.stack([
        c.astype(jnp.float32) for t, th, st, _, _, _ in sides
        for c in (t, th, st, 1.0 / st.astype(jnp.float32))
    ], axis=1)
    (_, _, _, j0_p, lim_p, _), (_, _, _, j0_n, lim_n, _) = sides

    mom = seg_moments(xpad, tiers,
                      jnp.stack([j0_p, lim_p, j0_n, lim_n], axis=1), **kw)
    mu_pos = mom[:, 0, 0] / jnp.maximum(mom[:, 0, 1], 1.0)
    mu_neg = -mom[:, 1, 0] / jnp.maximum(mom[:, 1, 1], 1.0)
    pos_wins = mu_pos > mu_neg
    mu = jnp.where(pos_wins, mu_pos, -mu_neg)
    count = jnp.where(pos_wins, mom[:, 0, 1], mom[:, 1, 1])

    side = pos_wins.astype(jnp.float32)
    win = pos_wins[seg_of_blk]
    out_pad, res_pad = seg_binarize_apply(
        xpad, jnp.concatenate([tiers, mu[:, None], side[:, None]], axis=1),
        jnp.stack([jnp.where(win, j0_p, j0_n), jnp.where(win, lim_p, lim_n)],
                  axis=1),
        **kw,
    )
    ebits = jnp.asarray(
        [expected_position_bits(min(p, 1.0)) for p in rates], jnp.float32
    )
    stats = {"mu": mu, "count": count, "nbits": count * ebits + 32.0}
    return out_pad.reshape(-1), res_pad.reshape(-1), stats


@functools.partial(jax.jit, static_argnames=("p", "nbins", "interpret"))
def sbc_compress_hist(
    acc: jax.Array,
    *,
    p: float,
    nbins: int = 128,
    interpret: Optional[bool] = None,
) -> SBCCompressed:
    """Histogram-threshold SBC over a residual-accumulated flat update:
    a one-segment :func:`seg_sbc_hist` (exactly k survivors)."""
    n = acc.shape[0]
    k = max(1, min(n, int(round(p * n))))
    x = _pad_2d(acc, DEFAULT_BM, DEFAULT_LANES).reshape(-1)
    out, res, st = seg_sbc_hist(
        x, [(0, n)], [k], [p], bm=DEFAULT_BM, lanes=DEFAULT_LANES,
        nbins=nbins, interpret=interpret,
    )
    return SBCCompressed(out[:n], res[:n], st["mu"][0], st["count"][0],
                         st["nbits"][0])


@functools.partial(jax.jit, static_argnames=("p",))
def sbc_compress_exact(acc: jax.Array, *, p: float) -> SBCCompressed:
    """Faithful Alg. 2: the exact two-sided top-k (exactly k survivors)."""
    n = acc.shape[0]
    k = max(1, min(n, int(round(p * n))))
    x = acc.astype(jnp.float32)

    (val_pos, idx_pos), (val_neg, idx_neg) = two_sided_topk(x, k)
    mu_pos = jnp.mean(val_pos)
    mu_neg = jnp.mean(val_neg)
    pos_wins = mu_pos > mu_neg
    idx = jnp.where(pos_wins, idx_pos, idx_neg)
    mu = jnp.where(pos_wins, mu_pos, -mu_neg)

    out = jnp.zeros_like(x).at[idx].set(mu)
    nbits = jnp.asarray(k * expected_position_bits(p) + 32.0, jnp.float32)
    return SBCCompressed(out, x - out, mu, jnp.asarray(k, jnp.float32), nbits)


def dense_to_sparse(dense: jax.Array, k_cap: int) -> tuple[jax.Array, jax.Array]:
    """Extract (idx[k_cap], valid[k_cap]) from a dense masked tensor.

    For a mask whose survivor count is only bounded by ``k_cap``.  Padding
    slots carry valid=0 so scatter-adds are no-ops.
    """
    idx = jnp.nonzero(dense, size=k_cap, fill_value=0)[0].astype(jnp.int32)
    m = jnp.sum((dense != 0).astype(jnp.int32))
    valid = (jnp.arange(k_cap) < m).astype(jnp.float32)
    return idx, valid
