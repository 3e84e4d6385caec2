"""Exact two-sided top-k of one flat row without a length-n sort.

``two_sided_topk(x, k)`` returns ``(lax.top_k(x, k), lax.top_k(-x, k))``
bit for bit — the same values, the same indices, the same order (values
descending, equal values lower index first) — which is what SBC's
selection (Alg. 2 l.1-5) asks of every sparse tensor.  ``lax.top_k``
lowers to a full sort of the row together with an index payload; on a
TPU that costs a few ns per element and side, while this selection
streams the row a few dozen times and sorts only the k picks:

1. **Threshold.**  Each f32 maps to an order-preserving u32 key (flip the
   sign bit of non-negative values, invert negative ones), so +0.0 ranks
   above −0.0, +NaN above +inf and −NaN below −inf: the total order
   ``lax.top_k`` uses.  The negated row's key is the bitwise complement,
   so one 32-step bisection finds both sides' k-th largest key ``t``:
   each step is one fused count of ``key ≥ candidate`` over the row, the
   keys recomputed inside the reduce.
2. **Tie rule.**  Every element with key > t survives, then the first
   ``k − count(key > t)`` elements with key == t by index — what a stable
   sort keeps.
3. **Compaction.**  Per block of ``BLOCK`` elements, how many survive;
   a prefix over blocks gives each of the k slots its block (a two-level
   compare over block counts) and its rank inside the block; one row
   gather of that block and an in-block prefix count (a triangular
   matmul of the survivor mask) give the index.  No length-n scatter.
4. **Order.**  ``lax.sort`` of the k picks by (key descending, index),
   and the values are the keys mapped back to their bits.

Rows shorter than ``CROSSOVER`` keep ``lax.top_k``: sorting a couple of
thousand elements costs less than the threshold's passes.  The constant
comes from a chip sweep between the LeNet5 cell's leaf sizes (PERF.md
§6); it is not a setting.  So does k ≥ 2**24, past what the compaction's
f32 matmuls count exactly.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# rows with fewer elements keep lax.top_k (chip sweep, PERF.md §6)
CROSSOVER = 2500
# compaction block: one TPU lane row
BLOCK = 128
# slots located per step of the compaction loop (bounds its gathers)
SLOT_CHUNK = 2048

_SIGN = np.uint32(0x80000000)


def uses_threshold(n: int) -> bool:
    """True when a row of ``n`` elements takes the threshold path."""
    return n >= CROSSOVER


def order_key(x: jax.Array) -> jax.Array:
    """f32 → u32 with ``a`` before ``b`` in ``lax.top_k``'s order iff
    ``order_key(a) > order_key(b)``; ``order_key(-x) == ~order_key(x)``."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >= _SIGN, ~b, b | _SIGN)


def from_key(key: jax.Array) -> jax.Array:
    """Inverse of :func:`order_key`: the f32 whose key this is."""
    b = jnp.where(key >= _SIGN, key ^ _SIGN, ~key)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _kth_keys(x: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """The k-th largest key of x and of −x: a bisection from the top bit,
    each step one fused count of ``key ≥ candidate`` per side over the
    row (keys recomputed inside the reduce, never stored)."""

    def step(i, carry):
        tp, tn = carry
        bit = jnp.uint32(1) << (31 - i).astype(jnp.uint32)
        key = order_key(x)
        # count(key(−x) ≥ c) == count(key(x) ≤ ~c)
        np_ = jnp.sum((key >= (tp | bit)).astype(jnp.int32))
        nn_ = jnp.sum((key <= ~(tn | bit)).astype(jnp.int32))
        return (jnp.where(np_ >= k, tp | bit, tp),
                jnp.where(nn_ >= k, tn | bit, tn))

    zero = jnp.zeros((), jnp.uint32)
    return jax.lax.fori_loop(0, 32, step, (zero, zero))


def _lane_count(m: jax.Array) -> jax.Array:
    """How many lanes of each row of a mask are set, summed on the MXU
    (exact: 0/1 products, f32 sums below 2**24)."""
    ones = jnp.ones((m.shape[-1], 1), jnp.bfloat16)
    return jnp.dot(m.astype(jnp.bfloat16), ones,
                   preferred_element_type=jnp.float32)[..., 0].astype(jnp.int32)


def _compact(x: jax.Array, t, flip, k: int):
    """Indices of one side's k survivors, in index order, and their keys
    (keys of x xor ``flip``): key > t, then the first ``k − count(key > t)``
    elements with key == t by index."""
    n = x.shape[0]
    nf = n // BLOCK  # full blocks; a short last block is padded and masked
    full = x[:nf * BLOCK].reshape(nf, BLOCK)
    tail = jnp.pad(x[nf * BLOCK:], (0, BLOCK - (n - nf * BLOCK)))
    lane = jnp.arange(BLOCK, dtype=jnp.int32)
    tail_ok = lane < n - nf * BLOCK

    # per block: how many keys lie above t (g) and how many equal it (e)
    g, e = [], []
    for part, ok in ([(full, True)] if nf else []) + (
            [(tail[None], tail_ok)] if n % BLOCK else []):
        key = order_key(part) ^ flip
        g.append(_lane_count((key > t) & ok))
        e.append(_lane_count((key == t) & ok))
    g, e = jnp.concatenate(g), jnp.concatenate(e)
    # ties go to the lowest indices: block b takes what is left of them
    ties_before = jnp.cumsum(e) - e
    take = jnp.clip((k - jnp.sum(g)) - ties_before, 0, e)
    cum = jnp.cumsum(g + take)  # survivors up to and including block b

    # slot s lies in block #{b : cum[b] ≤ s}: a count over superblock
    # ends, then over the lanes of one superblock's row of (cum, take),
    # fetched by a one-hot matmul (exact: integers below 2**24)
    nb = cum.shape[0]
    sb = max(BLOCK, 1 << math.ceil(math.log2(math.sqrt(nb))))
    nsb = -(-nb // sb)
    pad = nsb * sb - nb
    cum_rows = jnp.pad(cum, (0, pad), constant_values=k).reshape(nsb, sb)
    table = jnp.concatenate(
        [cum_rows, jnp.pad(take, (0, pad)).reshape(nsb, sb)], axis=1
    ).astype(jnp.float32)
    ends = cum_rows[:, -1]
    tri = jnp.triu(jnp.ones((BLOCK, BLOCK), jnp.bfloat16))  # lane ≤ lane'

    def prefix(m):  # inclusive count along the lanes, on the MXU
        return jnp.dot(m.astype(jnp.bfloat16), tri,
                       preferred_element_type=jnp.float32)

    def locate(s):  # slots s: int32[c] → (index, key) of each
        below = ends[None, :] <= s[:, None]
        c_s = _lane_count(below)
        onehot = (jnp.arange(nsb)[None, :] == c_s[:, None]).astype(jnp.float32)
        rows = jnp.dot(onehot, table, precision=jax.lax.Precision.HIGHEST)
        row, take_row = rows[:, :sb].astype(jnp.int32), rows[:, sb:]
        in_c = row <= s[:, None]
        b_local = _lane_count(in_c)
        before = jnp.maximum(
            jnp.max(jnp.where(below, ends[None, :], 0), axis=1),
            jnp.max(jnp.where(in_c, row, 0), axis=1))
        here = jnp.arange(sb)[None, :] == b_local[:, None]
        take_b = jnp.sum(jnp.where(here, take_row, 0.0), axis=1)
        b_s = c_s * sb + b_local
        # the block's BLOCK elements; the short last block masks its pad
        in_tail = (b_s >= nf)[:, None]
        vals = tail[None, :] if nf == 0 else jnp.where(
            in_tail, tail[None, :],
            jnp.take(full, jnp.minimum(b_s, nf - 1), axis=0))
        ok = jnp.where(in_tail, tail_ok[None, :], True)
        key = order_key(vals) ^ flip
        eq = (key == t) & ok
        tie_rank = prefix(eq) - eq  # ties before this lane in the block
        keep = ((key > t) & ok) | (eq & (tie_rank < take_b[:, None]))
        q = (s - before).astype(jnp.float32)
        pos = _lane_count(prefix(keep) <= q[:, None])
        key_sel = jnp.max(jnp.where(lane[None, :] == pos[:, None], key, 0),
                          axis=1)
        return b_s * BLOCK + pos, key_sel

    # slots in chunks, so the gathered blocks stay small
    c = min(k, SLOT_CHUNK)
    m = -(-k // c)
    slots = jnp.minimum(jnp.arange(m * c, dtype=jnp.int32), k - 1)
    idx, key_sel = jax.lax.map(locate, slots.reshape(m, c))
    return idx.reshape(-1)[:k], key_sel.reshape(-1)[:k]


def _threshold_topk(x: jax.Array, k: int):
    t = jnp.stack(_kth_keys(x, k))
    flip = jnp.array([0, 0xFFFFFFFF], jnp.uint32)  # key(−x) == ~key(x)
    idx, key_sel = jax.vmap(_compact, in_axes=(None, 0, 0, None))(
        x, t, flip, k)
    # lax.top_k's order: key descending, then index ascending
    neg_key, idx = jax.lax.sort((~key_sel, idx), num_keys=2)
    return ((from_key(~neg_key[0]), idx[0]), (from_key(~neg_key[1]), idx[1]))


def two_sided_topk(x: jax.Array, k: int):
    """``((val_pos, idx_pos), (val_neg, idx_neg))``, bit-identical to
    ``(lax.top_k(x, k), lax.top_k(-x, k))`` for a 1-D f32 ``x``."""
    if not uses_threshold(x.shape[0]) or k >= 1 << 24:
        return jax.lax.top_k(x, k), jax.lax.top_k(-x, k)
    return _threshold_topk(x, k)
