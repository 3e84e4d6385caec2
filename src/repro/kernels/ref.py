"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def hist2side_ref(flat: jax.Array, lo, hi, nbins: int = 128) -> jax.Array:
    """Oracle for kernels.hist2side.hist2side (identical binning rule).

    ``lo``/``hi`` broadcast to (2,): per-side magnitude ranges.
    """
    x = flat.astype(jnp.float32)
    absx = jnp.abs(x)
    lo = jnp.broadcast_to(jnp.asarray(lo, jnp.float32), (2,))
    hi = jnp.broadcast_to(jnp.asarray(hi, jnp.float32), (2,))
    rows = []
    for side, sel in ((0, x > 0.0), (1, x < 0.0)):
        in_range = sel & (absx >= lo[side]) & (absx < hi[side])
        log_lo = jnp.log2(jnp.maximum(lo[side], 1e-38))
        log_hi = jnp.log2(jnp.maximum(hi[side], 2e-38))
        f = (jnp.log2(jnp.maximum(absx, 1e-38)) - log_lo) / (log_hi - log_lo)
        bucket = jnp.clip((f * nbins).astype(jnp.int32), 0, nbins - 1)
        rows.append(jnp.zeros((nbins,)).at[bucket].add(jnp.where(in_range, 1.0, 0.0)))
    return jnp.stack(rows, axis=0)


def tiers_ref(flat: jax.Array, t, t_hi, side: int):
    """Tier-0 (v ≥ t_hi) and tier-1 (t ≤ v < t_hi) masks, v = ±x."""
    x = flat.astype(jnp.float32)
    v = x if side == 0 else -x
    return v >= t_hi, (v >= t) & (v < t_hi)


def tier_counts_ref(flat: jax.Array, t_pos, th_pos, t_neg, th_neg,
                    block: int) -> jax.Array:
    """Oracle for kernels.flat.seg_tier_counts over one segment:
    i32[nblocks, 4] per-block (tier0⁺, tier1⁺, tier0⁻, tier1⁻)."""
    nblocks = max(1, -(-flat.shape[0] // block))
    cols = []
    for side, (t, th) in enumerate(((t_pos, th_pos), (t_neg, th_neg))):
        for m in tiers_ref(flat, t, th, side):
            m = jnp.zeros((nblocks * block,), jnp.int32).at[:m.shape[0]].set(m)
            cols.append(m.reshape(nblocks, block).sum(axis=1))
    return jnp.stack(cols, axis=1)


def picked_ref(flat: jax.Array, t, t_hi, side: int, s, j0, lim, block: int):
    """Tier 0 plus, in every block b, the ties of in-block rank j (flat
    order) with j0[b] ≤ j < lim[b] and s | (j − j0[b]) — the selection
    rule of the tie-aware kernels."""
    t0, t1 = tiers_ref(flat, t, t_hi, side)
    n = flat.shape[0]
    nblocks = max(1, -(-n // block))
    m = jnp.zeros((nblocks * block,), jnp.int32).at[:n].set(t1)
    m = m.reshape(nblocks, block)
    d = jnp.cumsum(m, axis=1) - m - jnp.asarray(j0, jnp.int32)[:, None]
    keep = ((d >= 0) & (d % jnp.int32(s) == 0)
            & (d + jnp.asarray(j0, jnp.int32)[:, None]
               < jnp.asarray(lim, jnp.int32)[:, None]))
    return t0 | (t1 & keep.reshape(-1)[:n])


def masked_moments_ref(flat: jax.Array, t_pos, t_neg, ties=None,
                       block: int = 1024) -> jax.Array:
    """[[Σ⁺, n⁺], [Σ⁻, n⁻]].  ``ties`` is ``(th⁺, s⁺, th⁻, s⁻, picks)``
    with picks [nblocks, 4] = (j0⁺, lim⁺, j0⁻, lim⁻); without it (t_hi =
    t) every entry at or above its side's threshold counts."""
    x = flat.astype(jnp.float32)
    nblocks = max(1, -(-x.shape[0] // block))
    th_pos, s_pos, th_neg, s_neg, picks = ties or (
        t_pos, 1, t_neg, 1, jnp.zeros((nblocks, 4)))
    picks = jnp.asarray(picks)
    pos = picked_ref(x, t_pos, th_pos, 0, s_pos, picks[:, 0], picks[:, 1], block)
    neg = picked_ref(x, t_neg, th_neg, 1, s_neg, picks[:, 2], picks[:, 3], block)
    return jnp.array(
        [
            [jnp.sum(jnp.where(pos, x, 0.0)), jnp.sum(pos.astype(jnp.float32))],
            [jnp.sum(jnp.where(neg, x, 0.0)), jnp.sum(neg.astype(jnp.float32))],
        ],
        jnp.float32,
    )


def binarize_apply_ref(flat, t_pos, t_neg, mu, pos_wins, ties=None,
                       block: int = 1024):
    """``ties``: ``(th⁺, s⁺, th⁻, s⁻, picks)``, picks [nblocks, 2] = the
    winning side's (j0, lim)."""
    x = flat.astype(jnp.float32)
    nblocks = max(1, -(-x.shape[0] // block))
    th_pos, s_pos, th_neg, s_neg, picks = ties or (
        t_pos, 1, t_neg, 1, jnp.zeros((nblocks, 2)))
    j0, lim = jnp.asarray(picks)[:, 0], jnp.asarray(picks)[:, 1]
    mask = jnp.where(pos_wins > 0.5,
                     picked_ref(x, t_pos, th_pos, 0, s_pos, j0, lim, block),
                     picked_ref(x, t_neg, th_neg, 1, s_neg, j0, lim, block))
    out = jnp.where(mask, jnp.asarray(mu, jnp.float32), 0.0)
    return out, x - out


def sbc_exact_ref(flat: jax.Array, k: int) -> jax.Array:
    """Exact top-k SBC (paper Alg. 2) — the oracle the histogram pipeline
    approximates.  Returns the dense ΔW*."""
    val_pos, idx_pos = jax.lax.top_k(flat, k)
    val_neg, idx_neg = jax.lax.top_k(-flat, k)
    mu_pos = jnp.mean(val_pos)
    mu_neg = jnp.mean(val_neg)
    pos_wins = mu_pos > mu_neg
    idx = jnp.where(pos_wins, idx_pos, idx_neg)
    mean = jnp.where(pos_wins, mu_pos, -mu_neg)
    return jnp.zeros_like(flat).at[idx].set(mean)
