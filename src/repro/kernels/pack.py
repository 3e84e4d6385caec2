"""Device-side Golomb position packing: bit streams → packed words.

The host encoder (:mod:`repro.core.golomb`) produces the paper's Alg. 3
bitstream with numpy; every byte the wire sees is therefore a host
round-trip, which is exactly the overhead that erases sparse-training
speedups in practice (Lin et al.; Eghlidi & Jaggi).  This module moves
byte production on-device:

  * :func:`seg_packbits` — the whole-flat-set pass: a Pallas kernel that
    folds a 0/1 bit-plane buffer into packed ``uint32`` words by
    bit-shift/mask accumulation, grid-launched over word blocks exactly
    like the ``seg_*`` passes in :mod:`repro.kernels.flat`;
  * :func:`seg_select_pack` — two-sided top-k MASKS straight to packed
    words and exact bit counts: the gap stream is built from the mask by
    XLA (cumsums + scatters, :func:`bits_from_mask`), so surviving
    positions never materialize as an index array, and one
    :func:`seg_packbits` launch folds every row's bits into words;
  * :func:`golomb_decode_rows` — the matching device decoder (pointer
    doubling over the next-codeword-start map, O(B·log k) fully
    parallel work), used by the sharded exchange to recover positions
    from all-gathered word buffers.

Bit-layout contract (what makes device output BYTE-identical to the host
``encode_positions_packed``): stream bit ``b`` lives in word ``b >> 5``
at bit position ``31 - (b & 31)``, so a big-endian view of the word
buffer, truncated to ``ceil(nbits/8)`` bytes, equals
``np.packbits(bits).tobytes()`` (see ``golomb.packed_words_to_bytes``).

Everything is static-shaped: a row with ``k`` survivors out of ``n``
candidates needs at most ``((n - k) >> b*) + k·(1 + b*)`` stream bits
(``Σ (d_i - 1) ≤ n - k`` bounds the unary runs), so the per-row word
capacity — and with it the whole concatenated stream layout — is known
at trace time.  Interpret mode is decided by
:func:`repro.kernels.resolve_interpret`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def row_bit_capacity(n: int, k: int, bstar: int) -> int:
    """Worst-case stream bits for k survivors of n slots (static bound)."""
    if k <= 0:
        return 0
    return ((n - k) >> bstar) + k * (1 + bstar)


def row_words(n: int, k: int, bstar: int) -> int:
    """uint32 words needed for one row's packed stream (static bound)."""
    return -(-row_bit_capacity(n, k, bstar) // 32)


# ------------------------------------------------------ bit-stream builders


def _codeword_bits(dm1: jax.Array, *, bstar: int, cap32: int) -> tuple:
    """Golomb codewords for gap-minus-one values ``dm1`` → 0/1 bit array.

    Per codeword: ``q = dm1 >> b*`` unary ones, a terminating 0, then b*
    big-endian remainder bits — the same layout as the host encoder.  The
    unary runs are one ±1 scatter + cumsum; the remainder bits are one
    vectorized scatter.  Returns ``(bits u32[cap32], nbits i32)`` with
    every bit past ``nbits`` zero (byte padding falls out for free).
    """
    k = dm1.shape[0]
    if k == 0:
        return jnp.zeros((cap32,), jnp.uint32), jnp.zeros((), jnp.int32)
    q = dm1 >> bstar
    lens = q + 1 + bstar
    starts = jnp.cumsum(lens) - lens  # exclusive
    nbits = starts[-1] + lens[-1]
    delta = (
        jnp.zeros((cap32 + 1,), jnp.int32)
        .at[starts].add(1, mode="drop")
        .at[starts + q].add(-1, mode="drop")
    )
    bits = (jnp.cumsum(delta)[:cap32] > 0).astype(jnp.uint32)
    if bstar:
        r = dm1 & ((1 << bstar) - 1)
        j = jnp.arange(bstar, dtype=jnp.int32)
        rem_pos = (starts + q + 1)[:, None] + j[None, :]
        rem_val = (r[:, None] >> (bstar - 1 - j)[None, :]) & 1
        bits = bits.at[rem_pos.reshape(-1)].add(
            rem_val.reshape(-1).astype(jnp.uint32), mode="drop"
        )
    return bits, nbits.astype(jnp.int32)


def bits_from_positions(pos: jax.Array, *, bstar: int, cap32: int) -> tuple:
    """Sorted ascending positions (one row) → Golomb stream bits."""
    dm1 = jnp.diff(pos.astype(jnp.int32), prepend=jnp.int32(-1)) - 1
    return _codeword_bits(dm1, bstar=bstar, cap32=cap32)


def bits_from_mask(mask: jax.Array, *, k: int, bstar: int, cap32: int) -> tuple:
    """Selection mask (one row) → Golomb stream bits, index-array-free.

    ``zb[i]`` counts unselected slots up to and including ``i``; for the
    r-th selected slot, ``zb`` jumps by exactly ``gap - 1`` from the
    (r−1)-th, so scattering ``zb`` by selection rank yields the
    gap-minus-one sequence directly — positions never materialize.
    """
    m = mask.astype(jnp.int32)
    zb = jnp.cumsum(1 - m)
    rank = jnp.cumsum(m)
    tgt = jnp.where(m == 1, rank - 1, k)
    z = jnp.zeros((k,), jnp.int32).at[tgt].set(zb, mode="drop")
    dm1 = z - jnp.concatenate([jnp.zeros((1,), jnp.int32), z[:-1]])
    return _codeword_bits(dm1, bstar=bstar, cap32=cap32)


# ------------------------------------------------------- seg_packbits pass


def _packbits_kernel(bits_ref, words_ref):
    # bits_ref: (32, 8, lanes) u32 bit planes of one (8, lanes) word block
    acc = bits_ref[0] << jnp.uint32(31)
    for j in range(1, 32):  # bit-shift/mask accumulation into uint32 words
        acc = acc | (bits_ref[j] << jnp.uint32(31 - j))
    words_ref[...] = acc


_WORD_ROWS = 8  # sublanes per word block


@functools.partial(jax.jit, static_argnames=("lanes", "interpret"))
def seg_packbits(
    bits_pl: jax.Array, *, lanes: int = 128, interpret: Optional[bool] = None
) -> jax.Array:
    """One flat launch: bit planes → packed ``uint32`` word buffer.

    bits_pl: u32[32, nwords] where ``bits_pl[j, w]`` is stream bit
    ``32·w + j`` (i.e. the row-major bit buffer reshaped ``(-1, 32)`` and
    transposed).  Returns u32[nwords] with bit ``b`` of the stream at word
    ``b >> 5``, bit position ``31 - (b & 31)``.  The grid walks
    ``(8, lanes)`` word blocks; the tail block is zero-padded here.
    """
    nwords = bits_pl.shape[1]
    nblocks = max(1, pl.cdiv(nwords, _WORD_ROWS * lanes))
    nrows = nblocks * _WORD_ROWS
    if nrows * lanes > nwords:
        bits_pl = jnp.concatenate(
            [bits_pl, jnp.zeros((32, nrows * lanes - nwords), bits_pl.dtype)],
            axis=1,
        )
    out = pl.pallas_call(
        _packbits_kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((32, _WORD_ROWS, lanes), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((_WORD_ROWS, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nrows, lanes), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(bits_pl.reshape(32, nrows, lanes))
    return out.reshape(-1)[:nwords]


def pack_bit_rows(
    bits: jax.Array, *, lanes: int = 128, interpret: Optional[bool] = None
) -> jax.Array:
    """u32[..., cap32] bit rows → u32[..., cap32/32] words via ONE
    :func:`seg_packbits` launch over the concatenation (``cap32`` is a
    multiple of 32)."""
    cap32 = bits.shape[-1]
    planes = bits.reshape(-1, 32).T
    words = seg_packbits(planes, lanes=lanes, interpret=interpret)
    return words.reshape(bits.shape[:-1] + (cap32 // 32,))


# ------------------------------------------------------ select→pack pass


@functools.partial(jax.jit, static_argnames=("k", "bstar", "interpret"))
def seg_select_pack(
    mask: jax.Array, *, k: int, bstar: int, interpret: Optional[bool] = None
) -> tuple:
    """Select→pack: two-sided top-k masks straight to packed words.

    mask: bool/int[rows, n] with exactly ``k`` selected slots per row.
    Each row's Golomb stream is built from its mask (no index array), and
    one :func:`seg_packbits` launch folds every row into ``uint32`` words.
    Returns ``(words u32[rows, W], nbits i32[rows])`` with
    ``W = row_words(n, k, b*)``.
    """
    n = mask.shape[1]
    cap32 = 32 * row_words(n, k, bstar)
    bits, nbits = jax.vmap(
        lambda m: bits_from_mask(m, k=k, bstar=bstar, cap32=cap32)
    )(mask)
    return pack_bit_rows(bits, interpret=interpret), nbits


# ------------------------------------------------------------ device decode


def _decode_row(words: jax.Array, *, k: int, bstar: int) -> jax.Array:
    """u32[W] packed stream (≥ k codewords) → i32[k] ascending positions.

    Sequential-looking, but log-parallel: the cursor recurrence
    ``c' = nz[c] + 1 + b*`` iterates ONE map, so codeword starts are
    ``f^r(0)`` and pointer doubling gives all k of them in ``log2 k``
    gather rounds instead of a k-step scan.
    """
    shifts = (31 - jnp.arange(32, dtype=jnp.uint32)).astype(jnp.uint32)
    bits = ((words[:, None] >> shifts[None, :]) & 1).astype(jnp.int32)
    bits = bits.reshape(-1)
    ext = bits.shape[0] + bstar + 2  # zero tail: nz always finds a 0
    bits_e = jnp.concatenate(
        [bits, jnp.zeros((ext + bstar - bits.shape[0],), jnp.int32)]
    )
    iota = jnp.arange(ext, dtype=jnp.int32)
    cand = jnp.where(bits_e[:ext] == 0, iota, ext - 1)
    nz = jax.lax.associative_scan(jnp.minimum, cand, reverse=True)
    rem = jnp.zeros((ext,), jnp.int32)
    for j in range(bstar):
        rem = rem + (bits_e[j : j + ext] << (bstar - 1 - j))
    nxt = jnp.minimum(nz + 1 + bstar, ext - 1)  # next-codeword-start map
    cursors = jnp.zeros((k,), jnp.int32)
    ranks = jnp.arange(k, dtype=jnp.int32)
    table = nxt
    for j in range(max(1, (k - 1).bit_length())):
        if (k - 1) >> j == 0:
            break
        cursors = jnp.where(((ranks >> j) & 1) == 1, table[cursors], cursors)
        table = table[table]  # f^(2^j) → f^(2^(j+1))
    z = nz[cursors]
    q = z - cursors
    dm1 = (q << bstar) + rem[jnp.minimum(z + 1, ext - 1)]
    return (jnp.cumsum(dm1 + 1) - 1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "bstar"))
def golomb_decode_rows(words: jax.Array, *, k: int, bstar: int) -> jax.Array:
    """u32[..., W] packed streams → i32[..., k] ascending positions."""
    fn = functools.partial(_decode_row, k=k, bstar=bstar)
    lead = words.shape[:-1]
    out = jax.vmap(fn)(words.reshape((-1,) + words.shape[-1:]))
    return out.reshape(lead + (k,))
