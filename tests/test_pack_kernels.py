"""Device-side Golomb packing (repro.kernels.pack) vs the host encoder.

The whole point of the fused select→pack kernels is BYTE identity: the
uint32 word buffers they emit, viewed big-endian and truncated to
``ceil(nbits/8)``, must equal ``golomb.encode_positions_packed`` for the
same positions — per row, for every row of a packed multi-row buffer.
These tests drive that contract over adversarial run-length shapes
(single survivor at either edge, all-selected rows, maximal gaps,
codewords straddling word boundaries) plus a hypothesis property over
random masks, and round-trip the pointer-doubling device decoder.

Everything runs in interpret mode, so the suite is backend-independent
(the ``kernels-interpret`` CI job runs exactly this file + the flat
fast-path suite).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # optional dev dep — fixed-grid fallback
    from _hypothesis_fallback import given, settings, st

import jax
import jax.numpy as jnp

from repro.core import golomb
from repro.kernels.pack import (
    bits_from_mask,
    bits_from_positions,
    golomb_decode_rows,
    pack_bit_rows,
    row_bit_capacity,
    row_words,
    seg_packbits,
    seg_select_pack,
)

# keep the (n, k, b*) combinations SMALL: every distinct triple is a fresh
# jit specialization of three kernels
N_GRID = (8, 64, 200)
P_GRID = (0.01, 0.05, 0.5)  # b* = 6, 4, 0


def _positions(n, k, seed):
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n, size=k, replace=False)).astype(np.int32)


def _host_bytes(pos, p):
    return golomb.encode_positions_packed(np.asarray(pos, np.int64), p)


def _device_bytes_from_positions(pos, n, p):
    """positions → bits_from_positions → seg_packbits → transport bytes."""
    b = golomb.golomb_bstar(p)
    cap32 = 32 * row_words(n, len(pos), b)
    bits, nbits = bits_from_positions(jnp.asarray(pos), bstar=b, cap32=cap32)
    words = pack_bit_rows(bits[None], interpret=True)[0]
    return golomb.packed_words_to_bytes(np.asarray(words), int(nbits)), int(nbits)


def _device_bytes_from_mask(pos, n, p):
    """mask → fused seg_select_pack → transport bytes."""
    b = golomb.golomb_bstar(p)
    mask = np.zeros((n,), np.int32)
    mask[np.asarray(pos)] = 1
    words, nbits = seg_select_pack(
        jnp.asarray(mask)[None], k=len(pos), bstar=b, interpret=True
    )
    return (
        golomb.packed_words_to_bytes(np.asarray(words[0]), int(nbits[0])),
        int(nbits[0]),
    )


# ------------------------------------------------------- adversarial shapes


class TestAdversarialRuns:
    """Hand-picked run-length patterns that stress every codeword path."""

    CASES = [
        # (n, p, positions) — single survivor at both edges and mid-row
        (64, 0.01, [0]),
        (64, 0.01, [63]),  # maximal single gap: longest unary run
        (64, 0.01, [31]),
        # all-selected: k = n, every gap 1, stream is k dense codewords
        (8, 0.5, list(range(8))),
        (64, 0.05, list(range(64))),
        # first/last + a big interior gap
        (64, 0.01, [0, 63]),
        (200, 0.01, [0, 1, 2, 197, 198, 199]),
        # codewords straddling uint32 word boundaries: b*=4 remainders
        # land across bit 32/64/96 for these spacings
        (200, 0.05, [6, 13, 20, 27, 34, 41, 48, 55]),
        # geometric-ish bursts + voids
        (200, 0.05, [0, 1, 2, 3, 50, 51, 52, 120, 199]),
    ]

    @pytest.mark.parametrize("n,p,pos", CASES)
    def test_bytes_identical_both_kernels(self, n, p, pos):
        ref, ref_bits = _host_bytes(pos, p)
        dev, dev_bits = _device_bytes_from_positions(pos, n, p)
        assert dev_bits == ref_bits
        assert dev == ref
        fused, fused_bits = _device_bytes_from_mask(pos, n, p)
        assert fused_bits == ref_bits
        assert fused == ref

    @pytest.mark.parametrize("n,p,pos", CASES)
    def test_decode_roundtrip(self, n, p, pos):
        b = golomb.golomb_bstar(p)
        k = len(pos)
        cap32 = 32 * row_words(n, k, b)
        bits, _ = bits_from_positions(jnp.asarray(np.asarray(pos, np.int32)),
                                      bstar=b, cap32=cap32)
        words = pack_bit_rows(bits[None], interpret=True)
        back = golomb_decode_rows(words, k=k, bstar=b)
        np.testing.assert_array_equal(np.asarray(back[0]), np.asarray(pos))

    def test_empty_row_is_empty_stream(self):
        """k = 0 matches the host's (b'', 0) empty-encode contract."""
        assert row_bit_capacity(64, 0, 6) == 0
        bits, nbits = bits_from_positions(
            jnp.zeros((0,), jnp.int32), bstar=6, cap32=32
        )
        assert int(nbits) == 0
        assert not np.asarray(bits).any()
        assert golomb.packed_words_to_bytes(np.zeros((1,), np.uint32), 0) == b""
        assert _host_bytes([], 0.01) == (b"", 0)

    def test_capacity_bound_is_sharp_enough(self):
        """The static bound dominates the real stream for the worst
        single-gap row AND the all-selected row."""
        for n, p in [(64, 0.01), (200, 0.05), (8, 0.5)]:
            b = golomb.golomb_bstar(p)
            for pos in ([n - 1], list(range(n))):
                _, bits = _host_bytes(pos, p)
                assert bits <= row_bit_capacity(n, len(pos), b)


# ----------------------------------------------------- multi-row buffers


class TestMultiRowBuffers:
    """One packed buffer, many rows: each row's word slice must be
    byte-identical to its own host encode (no bleed across the static
    per-row word boundaries) — the (leaf, shard, row) contract the
    sharded exchange relies on."""

    def test_rows_stay_byte_identical(self):
        n, p, rows = 200, 0.05, 6
        b = golomb.golomb_bstar(p)
        k = 7
        cap32 = 32 * row_words(n, k, b)
        pos_rows = [_positions(n, k, seed) for seed in range(rows)]
        bits = jnp.stack(
            [
                bits_from_positions(jnp.asarray(pr), bstar=b, cap32=cap32)[0]
                for pr in pos_rows
            ]
        )
        words = np.asarray(pack_bit_rows(bits, interpret=True))
        assert words.shape == (rows, cap32 // 32)
        for r, pr in enumerate(pos_rows):
            ref, ref_bits = _host_bytes(pr, p)
            got = golomb.packed_words_to_bytes(words[r], ref_bits)
            assert got == ref, f"row {r}"

    def test_fused_rows_and_decode(self):
        n, p, rows, k = 64, 0.05, 5, 4
        b = golomb.golomb_bstar(p)
        pos_rows = [_positions(n, k, 100 + seed) for seed in range(rows)]
        mask = np.zeros((rows, n), np.int32)
        for r, pr in enumerate(pos_rows):
            mask[r, pr] = 1
        words, nbits = seg_select_pack(jnp.asarray(mask), k=k, bstar=b,
                                       interpret=True)
        back = golomb_decode_rows(words, k=k, bstar=b)
        for r, pr in enumerate(pos_rows):
            ref, ref_bits = _host_bytes(pr, p)
            assert int(nbits[r]) == ref_bits
            got = golomb.packed_words_to_bytes(np.asarray(words[r]),
                                               int(nbits[r]))
            assert got == ref, f"row {r}"
            np.testing.assert_array_equal(np.asarray(back[r]), pr)


# --------------------------------------------------------- property tests


@given(
    n=st.sampled_from(N_GRID),
    kfrac=st.sampled_from([1, 2, 7]),  # k = max(1, n // kfrac): dense→sparse
    p=st.sampled_from(P_GRID),
    seed=st.integers(0, 3),
)
@settings(max_examples=24, deadline=None)
def test_roundtrip_property(n, kfrac, p, seed):
    """Random masks: device bytes == host bytes (both kernels), decoder
    recovers the exact index set, and nbits never exceeds the static
    capacity bound."""
    k = max(1, n // kfrac)
    pos = _positions(n, k, seed)
    b = golomb.golomb_bstar(p)
    ref, ref_bits = _host_bytes(pos, p)
    assert ref_bits <= row_bit_capacity(n, k, b)

    dev, dev_bits = _device_bytes_from_positions(pos, n, p)
    assert (dev_bits, dev) == (ref_bits, ref)
    fused, fused_bits = _device_bytes_from_mask(pos, n, p)
    assert (fused_bits, fused) == (ref_bits, ref)

    cap32 = 32 * row_words(n, k, b)
    bits, _ = bits_from_positions(jnp.asarray(pos), bstar=b, cap32=cap32)
    words = pack_bit_rows(bits[None], interpret=True)
    back = golomb_decode_rows(words, k=k, bstar=b)
    np.testing.assert_array_equal(np.asarray(back[0]), pos)


def test_bits_from_mask_equals_bits_from_positions():
    """The index-free mask→gaps path produces the identical bit buffer."""
    n, p, k = 200, 0.05, 9
    b = golomb.golomb_bstar(p)
    cap32 = 32 * row_words(n, k, b)
    pos = _positions(n, k, 7)
    mask = np.zeros((n,), np.int32)
    mask[pos] = 1
    bp, nbp = bits_from_positions(jnp.asarray(pos), bstar=b, cap32=cap32)
    bm, nbm = bits_from_mask(jnp.asarray(mask), k=k, bstar=b, cap32=cap32)
    assert int(nbp) == int(nbm)
    np.testing.assert_array_equal(np.asarray(bp), np.asarray(bm))


def test_seg_packbits_matches_np_packbits():
    """The bit-layout contract itself: seg_packbits == np.packbits on a
    big-endian word view, for an arbitrary bit buffer."""
    rng = np.random.default_rng(0)
    lanes = 128
    nwords = 2 * lanes
    bits = rng.integers(0, 2, size=32 * nwords).astype(np.uint32)
    planes = jnp.asarray(bits.reshape(-1, 32).T)
    words = np.asarray(seg_packbits(planes, lanes=lanes, interpret=True))
    ref = np.packbits(bits.astype(np.uint8)).tobytes()
    assert words.astype(">u4").tobytes() == ref


# ------------------------------------------------- sharded space integration


def test_sharded_space_pack_matches_host_per_row():
    """ShardedFlatParamSpace.exchange_local(device_pack=True): identical
    mean/own/residual, and every (segment, row) slice of the packed word
    buffer is byte-identical to host-encoding that row's positions."""
    from repro.core.flat import ShardedFlatParamSpace

    shapes = [(2, 40, 8), (123,), (40,), (7, 3)]
    kinds = ("sparse", "sparse", "dense", "skip")
    entries = [
        dict(path=f"leaf{i}", shape=s, rows=s[0] if len(s) > 1 else 1,
             kind=kd, rate=0.05, n_shards=1, global_size=int(np.prod(s)))
        for i, (s, kd) in enumerate(zip(shapes, kinds))
    ]
    space = ShardedFlatParamSpace.build(
        entries, client_axes=(), shard_axes=(), n_clients=1,
        shards_per_client=1,
    )
    bodies = [
        0.1 * jax.random.normal(jax.random.PRNGKey(i), seg.shape)
        for i, seg in enumerate(space.segments)
    ]
    res = jnp.zeros((space.n_pad,), jnp.float32)
    mean0, own0, nr0 = jax.jit(space.exchange_local)(bodies, res)
    mean1, own1, nr1, words, nbits, _ = space.exchange_local(
        bodies, res, device_pack=True
    )
    for a, c in ((mean0, mean1), (own0, own1), (nr0, nr1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    words_np = np.asarray(words)
    nbits_np = np.asarray(nbits)
    own_np = np.asarray(own1)
    mi = 0
    for s, (b, w, off) in zip(space._sparse, space._pack_info):
        block = own_np[s.offset:s.offset + s.rows * s.n_loc].reshape(
            s.rows, s.n_loc
        )
        for r in range(s.rows):
            rowpos = np.flatnonzero(block[r])
            assert rowpos.size == s.k
            ref, ref_bits = golomb.encode_positions_packed(rowpos, s.rate)
            assert int(nbits_np[mi]) == ref_bits, (s.path, r)
            got = golomb.packed_words_to_bytes(
                words_np[off + r * w: off + (r + 1) * w], ref_bits
            )
            assert got == ref, (s.path, r)
            mi += 1
    assert mi == space.n_mu == len(nbits_np)
