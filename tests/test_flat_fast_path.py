"""Flat-buffer fast path (core/flat.py, DESIGN.md §10).

Three layers of guarantees:

  * the EXACT engine is bit-identical to the legacy per-leaf path — same
    LeafCompressed trees, same SBW1 bytes, same residuals, same RNG
    trajectory — across rounds, under vmap, and on the edge cases the
    layout makes interesting (non-block-multiple "padded tail" leaves,
    all-zero leaves, skip/dense segments);
  * the segment-aware Pallas kernels (kernels/flat.py, interpret mode)
    match the pure-jnp oracles in kernels/ref.py and the per-leaf kernels
    bit for bit at matching tile shapes;
  * the HIST engine reproduces per-leaf ``ops.sbc_compress_hist`` per
    segment and keeps the acc == ΔW* + R residual identity.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flat as flatmod
from repro.core.api import get_compressor
from repro.core.policy import (
    DENSE_SMALL_PATTERN,
    CompressionPolicy,
    CompressorState,
    PolicyRule,
)
from repro.core.wire import wire_for
from repro.kernels import ops, ref
from repro.kernels.flat import (
    seg_binarize_apply,
    seg_hist2side,
    seg_moments,
    seg_tier_counts,
)
from repro.kernels.hist2side import SPAN_OCTAVES, hist2side
from repro.kernels.moments import masked_moments

BM, LANES = 8, 128


def tree_like():
    """A pytree exercising every flat segment kind and edge case:
    2-D matrices, a dense-ridden bias, a skipped leaf, a non-block-multiple
    tail (17), and an all-zero leaf."""
    return {
        "layer0": {"w": jnp.zeros((50, 40)), "bias": jnp.zeros((40,))},
        "layer1": {"w": jnp.zeros((123,)), "frozen": jnp.zeros((7, 3))},
        "tail": jnp.zeros((17,)),
        "zero": jnp.zeros((65,)),
    }


def sbc_policy(fast: bool) -> CompressionPolicy:
    return CompressionPolicy(
        default=get_compressor("sbc").codec,
        rules=(PolicyRule(r"frozen", codec="skip"),
               PolicyRule(DENSE_SMALL_PATTERN, codec="dense32")),
        name="sbc+rules",
        fast=fast,
    )


def rand_delta(seed: int = 3):
    params = tree_like()
    delta = jax.tree.map(
        lambda x: 0.1 * jax.random.normal(jax.random.PRNGKey(seed), x.shape),
        params,
    )
    delta["zero"] = jnp.zeros((65,))  # all-zero leaf keeps its edge case
    return params, delta


def assert_trees_bitwise(a, b, what=""):
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(la) == len(lb)
    for (pa, xa), (_, xb) in zip(la, lb):
        na, nb = np.asarray(xa), np.asarray(xb)
        assert na.shape == nb.shape and na.tobytes() == nb.tobytes(), (
            f"{what} mismatch at {jax.tree_util.keystr(pa)}"
        )


class TestExactEngine:
    def test_bit_identical_over_rounds(self):
        params, delta = rand_delta()
        res_legacy = sbc_policy(fast=False).resolve(params)
        res_fast = sbc_policy(fast=True).resolve(params)
        assert res_fast.fast_compatible
        sl = res_legacy.init_state(params)
        sf = res_fast.init_state(params)
        # fast residual is ONE flat buffer, not a pytree
        assert hasattr(sf.residual, "ndim") and sf.residual.ndim == 1
        rates = res_legacy.rates(0.05, 0)
        space = res_fast.flat_space(params)
        wire = wire_for(res_legacy, params, 0.05)

        for _ in range(3):  # residual feedback must stay in lockstep
            ctL, dnL, sl = res_legacy.compress(delta, sl, rates)
            ctF, dnF, sf = res_fast.compress(delta, sf, rates)
            assert_trees_bitwise(ctL, ctF, "ctree")
            assert_trees_bitwise(dnL, dnF, "dense")
            assert np.asarray(space.flatten(sl.residual)).tobytes() == \
                np.asarray(sf.residual).tobytes()
            assert wire.pack(jax.device_get(ctL)) == wire.pack(jax.device_get(ctF))
            assert np.array_equal(np.asarray(sl.rng), np.asarray(sf.rng))

    def test_all_zero_leaf(self):
        """top_k on an all-zero leaf ties everywhere: both paths pick the
        first k indices of the losing-side tiebreak and a μ of exactly +0.0
        (the sign bit is packed as f32, so it must match bitwise too —
        covered by test_bit_identical_over_rounds; this pins the values)."""
        params, delta = rand_delta()
        res_fast = sbc_policy(fast=True).resolve(params)
        ct, dn, _ = res_fast.compress(delta, res_fast.init_state(params),
                                      res_fast.rates(0.05, 0))
        mu = np.asarray(ct["zero"].mean)
        assert mu == 0.0 and not np.signbit(mu)
        k = ct["zero"].idx.shape[0]
        np.testing.assert_array_equal(np.sort(np.asarray(ct["zero"].idx)),
                                      np.arange(k))
        assert not np.asarray(dn["zero"]).any()

    def test_vmapped_client_axis(self):
        params, _ = rand_delta()
        C = 3
        deltas = jax.tree.map(
            lambda x: 0.1 * jax.random.normal(jax.random.PRNGKey(7), (C,) + x.shape),
            params,
        )
        res_legacy = sbc_policy(fast=False).resolve(params)
        res_fast = sbc_policy(fast=True).resolve(params)
        rates = res_legacy.rates(0.05, 0)
        rngs = jax.random.split(jax.random.PRNGKey(5), C)
        sl = CompressorState(
            residual=jax.tree.map(
                lambda x: jnp.zeros((C,) + x.shape, x.dtype),
                res_legacy.init_state(params).residual,
            ),
            rng=rngs, step=jnp.zeros((C,), jnp.int32),
        )
        n_pad = res_fast.flat_space(params).n_pad
        sf = CompressorState(
            residual=jnp.zeros((C, n_pad), jnp.float32),
            rng=rngs, step=jnp.zeros((C,), jnp.int32),
        )
        ctL, dnL, _ = jax.vmap(lambda d, s: res_legacy.compress(d, s, rates))(deltas, sl)
        ctF, dnF, _ = jax.vmap(lambda d, s: res_fast.compress(d, s, rates))(deltas, sf)
        assert_trees_bitwise(ctL, ctF, "vmapped ctree")
        assert_trees_bitwise(dnL, dnF, "vmapped dense")

    def test_unsupported_codec_falls_back_to_per_leaf(self):
        """A fast=True policy whose codec has no flat form must silently
        use the legacy path (pytree residual, identical output)."""
        params, delta = rand_delta()
        pol = CompressionPolicy.single(get_compressor("topk").codec, name="topk")
        res_slow = pol.resolve(params)
        res_fast = dataclasses.replace(pol, fast=True).resolve(params)
        assert not res_fast.fast_compatible
        assert res_fast.flat_space(params) is None
        sl = res_slow.init_state(params)
        sf = res_fast.init_state(params)
        assert jax.tree_util.tree_structure(sl.residual) == \
            jax.tree_util.tree_structure(sf.residual)
        ctL, _, _ = res_slow.compress(delta, sl, 0.05)
        ctF, _, _ = res_fast.compress(delta, sf, 0.05)
        assert_trees_bitwise(ctL, ctF, "fallback ctree")

    def test_non_f32_leaves_fall_back_to_per_leaf(self):
        """bf16 trees stay on the legacy path: the flat residual is f32,
        but the per-leaf engine re-quantizes the residual to the leaf
        dtype each round (DESIGN.md §8 configs) — the fast path must not
        silently change that trajectory."""
        params = {"w": jnp.zeros((64, 8), jnp.bfloat16),
                  "v": jnp.zeros((33,), jnp.bfloat16)}
        delta = jax.tree.map(
            lambda x: (0.1 * jax.random.normal(jax.random.PRNGKey(0), x.shape)
                       ).astype(x.dtype),
            params,
        )
        pol = CompressionPolicy.single(get_compressor("sbc").codec)
        res_fast = dataclasses.replace(pol, fast=True).resolve(params)
        assert res_fast.flat_space(params) is None
        sf = res_fast.init_state(params)
        # pytree residual, leaf-dtype preserved (legacy behavior)
        assert jax.tree_util.tree_structure(sf.residual) == \
            jax.tree_util.tree_structure(params)
        res_slow = pol.resolve(params)
        ctL, _, slL = res_slow.compress(delta, res_slow.init_state(params), 0.05)
        ctF, _, sfF = res_fast.compress(delta, sf, 0.05)
        assert_trees_bitwise(ctL, ctF, "bf16 fallback ctree")
        assert_trees_bitwise(slL.residual, sfF.residual, "bf16 residual")

    def test_decompress_and_total_bits_work_on_fast_output(self):
        params, delta = rand_delta()
        res_fast = sbc_policy(fast=True).resolve(params)
        ct, dn, _ = res_fast.compress(delta, res_fast.init_state(params),
                                      res_fast.rates(0.05, 0))
        rec = res_fast.decompress(ct, params)
        assert_trees_bitwise(rec, dn, "decompress")
        assert float(res_fast.total_bits(ct)) > 0


class TestSegKernels:
    """Flat segment kernels vs the per-leaf kernels and ref.py oracles."""

    # (sizes) per segment: padded tail + block-multiple + all-zero
    SIZES = (1000, BM * LANES, 65, 17)

    def _layout(self, seed=0):
        per_block = BM * LANES
        rng = np.random.default_rng(seed)
        segs = []
        off = 0
        for i, s in enumerate(self.SIZES):
            x = (rng.standard_normal(s) * 2.0).astype(np.float32)
            if s == 65:
                x[:] = 0.0  # all-zero segment
            segs.append((off, s, x))
            off += max(1, -(-s // per_block)) * per_block
        xpad = np.zeros((off,), np.float32)
        for o, s, x in segs:
            xpad[o:o + s] = x
        blk_starts = tuple(o // per_block for o, _, _ in segs)
        return segs, xpad.reshape(-1, LANES), blk_starts

    def test_seg_hist2side_matches_per_leaf_and_ref(self):
        segs, xpad, starts = self._layout()
        nbins = 32
        los = np.array([max(np.abs(x).max(), 1e-30) * 2.0**-SPAN_OCTAVES
                        for _, _, x in segs], np.float32)
        his = np.array([max(np.abs(x).max(), 1e-30) * 1.0001
                        for _, _, x in segs], np.float32)
        params = np.stack([los, his, los, his], axis=1)
        got = seg_hist2side(jnp.asarray(xpad), jnp.asarray(params),
                            blk_starts=starts, nbins=nbins, bm=BM, lanes=LANES)
        for i, (_, _, x) in enumerate(segs):
            want_leaf = hist2side(jnp.asarray(x), los[i], his[i],
                                  nbins=nbins, bm=BM, lanes=LANES)
            want_ref = ref.hist2side_ref(jnp.asarray(x), los[i], his[i], nbins=nbins)
            np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want_leaf))
            np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want_ref))

    # per segment (t⁺, t_hi⁺, t⁻, t_hi⁻): a real tie bucket on each side,
    # an empty one (t_hi = t), and the swapped form (t_hi = ∞)
    TIERS = np.array([[0.7, 1.5, 0.9, 1.6], [0.5, 0.5, 0.6, 0.6],
                      [0.1, 0.2, 0.1, 0.2], [0.3, np.inf, 0.2, 1.0]],
                     np.float32)

    STRIDES = np.array([[1, 3], [2, 1], [7, 2], [1, 1]], np.float32)

    def _picks(self, segs, ncols, seed):
        """Random per-block (j0, lim) pairs, some past a block's ties."""
        per_block = BM * LANES
        nblocks = sum(max(1, -(-s // per_block)) for _, s, _ in segs)
        rng = np.random.default_rng(seed)
        cols = []
        for _ in range(ncols // 2):
            cols += [rng.integers(0, 40, nblocks), rng.integers(0, 400, nblocks)]
        return np.stack(cols, axis=1).astype(np.float32)

    def _table(self):
        """(nseg, 8) rows (t⁺, t_hi⁺, s⁺, 1/s⁺, t⁻, t_hi⁻, s⁻, 1/s⁻)."""
        t, st = self.TIERS, self.STRIDES
        return np.stack([t[:, 0], t[:, 1], st[:, 0], 1 / st[:, 0],
                         t[:, 2], t[:, 3], st[:, 1], 1 / st[:, 1]], axis=1)

    def _own(self, segs, i, picks):
        """Segment i alone, as a one-segment launch sees it."""
        per_block = BM * LANES
        o, s, x = segs[i]
        b0 = o // per_block
        nb = max(1, -(-s // per_block))
        xpad = np.zeros((nb * per_block,), np.float32)
        xpad[:s] = x
        return xpad.reshape(-1, LANES), picks[b0:b0 + nb], b0, nb

    def _ties(self, i, picks):
        t_pos, th_pos, t_neg, th_neg = self.TIERS[i]
        s_pos, s_neg = self.STRIDES[i]
        return (t_pos, t_neg), (th_pos, int(s_pos), th_neg, int(s_neg), picks)

    def test_seg_tier_counts_matches_ref(self):
        segs, xpad, starts = self._layout(3)
        got = np.asarray(seg_tier_counts(
            jnp.asarray(xpad), jnp.asarray(self.TIERS), blk_starts=starts,
            bm=BM, lanes=LANES))
        for i, (o, s, x) in enumerate(segs):
            b0 = o // (BM * LANES)
            want = np.asarray(ref.tier_counts_ref(
                jnp.asarray(x), *self.TIERS[i], block=BM * LANES))
            np.testing.assert_array_equal(got[b0:b0 + len(want)], want)
        assert got.dtype == np.int32 and got.shape == (xpad.shape[0] // BM, 4)

    def test_seg_moments_matches_per_leaf_and_ref(self):
        segs, xpad, starts = self._layout(1)
        picks = self._picks(segs, 4, 1)
        table = self._table()
        got = seg_moments(jnp.asarray(xpad), jnp.asarray(table),
                          jnp.asarray(picks), blk_starts=starts, bm=BM,
                          lanes=LANES)
        for i, (_, _, x) in enumerate(segs):
            own, own_picks, _, _ = self._own(segs, i, picks)
            want_leaf = seg_moments(jnp.asarray(own), jnp.asarray(table[i:i + 1]),
                                    jnp.asarray(own_picks), blk_starts=(0,),
                                    bm=BM, lanes=LANES)[0]
            (t_pos, t_neg), ties = self._ties(i, own_picks)
            want_ref = ref.masked_moments_ref(jnp.asarray(x), t_pos, t_neg,
                                              ties, block=BM * LANES)
            np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want_leaf))
            np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want_ref),
                                       rtol=1e-4)
        # the per-leaf wrapper is the no-tie launch
        x = jnp.asarray(segs[0][2])
        np.testing.assert_allclose(
            np.asarray(masked_moments(x, 0.7, 0.9, bm=BM, lanes=LANES)),
            np.asarray(ref.masked_moments_ref(x, 0.7, 0.9)), rtol=1e-4)

    def test_seg_binarize_apply_matches_ref(self):
        segs, xpad, starts = self._layout(2)
        mu = np.array([0.55, -0.45, 0.2, 0.1], np.float32)
        side = np.array([1.0, 0.0, 1.0, 0.0], np.float32)
        params = np.concatenate([self._table(), mu[:, None], side[:, None]],
                                axis=1)
        picks = self._picks(segs, 2, 2)
        out, res = seg_binarize_apply(jnp.asarray(xpad), jnp.asarray(params),
                                      jnp.asarray(picks), blk_starts=starts,
                                      bm=BM, lanes=LANES)
        out, res = np.asarray(out).reshape(-1), np.asarray(res).reshape(-1)
        for i, (o, s, x) in enumerate(segs):
            _, own_picks, _, _ = self._own(segs, i, picks)
            (t_pos, t_neg), ties = self._ties(i, own_picks)
            w_out, w_res = ref.binarize_apply_ref(
                jnp.asarray(x), t_pos, t_neg, mu[i], side[i], ties,
                block=BM * LANES)
            np.testing.assert_array_equal(out[o:o + s], np.asarray(w_out))
            np.testing.assert_array_equal(res[o:o + s], np.asarray(w_res))
        # padding region: ΔW* = 0 and R = 0 (caller slices it off)
        pad = np.ones((xpad.size,), bool)
        for o, s, _ in segs:
            pad[o:o + s] = False
        assert not out[pad].any() and not res[pad].any()


class TestHistEngine:
    def test_matches_per_leaf_sbc_compress_hist(self):
        """Flat hist pipeline == per-leaf ``ops.sbc_compress_hist`` per
        segment: identical block partition → identical accumulation order →
        μ, counts, ΔW*, and residuals match bit for bit; every segment
        keeps exactly k."""
        params = {"a": jnp.zeros((70, 80)), "b": jnp.zeros((333,)),
                  "c": jnp.zeros((17,)), "z": jnp.zeros((50,))}
        delta = jax.tree.map(
            lambda x: 0.1 * jax.random.normal(jax.random.PRNGKey(11), x.shape),
            params,
        )
        delta["z"] = jnp.zeros((50,))
        pol = dataclasses.replace(
            CompressionPolicy.single(get_compressor("sbc").codec), fast=True
        )
        res = pol.resolve(params)
        space = flatmod.FlatParamSpace.for_resolved(res, params, bm=BM, lanes=LANES)
        state = res.init_state(params)
        rates = res.rates(0.05, 0)
        dense_tree, new_state, stats = space.compress_hist(
            delta, state, rates, nbins=32
        )

        from repro.core.golomb import expected_position_bits

        for i, name in enumerate(["a", "b", "c", "z"]):
            x = delta[name].reshape(-1).astype(jnp.float32)
            n = x.shape[0]
            k = max(1, min(n, int(round(rates[i] * n))))
            want = ops.sbc_compress_hist(x, p=rates[i], nbins=32)
            assert np.asarray(dense_tree[name]).reshape(-1).tobytes() == \
                np.asarray(want.delta_star).tobytes()
            assert np.asarray(stats["mu"][i]).tobytes() == \
                np.asarray(want.mean).tobytes()
            assert float(stats["count"][i]) == float(want.count)
            assert float(stats["count"][i]) == (k if name != "z" else 0)
            want_bits = float(want.count) * expected_position_bits(rates[i]) + 32.0
            np.testing.assert_allclose(float(stats["nbits"][i]), want_bits,
                                       rtol=1e-5)

        # Eq. 2 residual identity over the whole buffer
        acc = space.flatten(delta)
        recon = space.flatten(dense_tree) + new_state.residual
        np.testing.assert_allclose(np.asarray(acc), np.asarray(recon),
                                   rtol=1e-6, atol=1e-7)

    @pytest.mark.parametrize("scale_hi", [1.0, 3.0])
    def test_ties_keep_exactly_k_spread_over_the_segment(self, scale_hi):
        """Adam's first step makes |ΔW| equal (≈ lr) almost everywhere: the
        whole tie bucket is far larger than k.  Every segment still keeps
        exactly k, μ is the mean of exactly the kept entries, every kept
        entry is at least as large as every dropped one of its side up to
        the bucket, and the picks spread over the segment (mean gap ≈ n/k)
        instead of clustering at its start."""
        rng = np.random.default_rng(7)
        sizes = {"a": (300, 40), "b": (9000,), "c": (700,)}
        delta = {}
        for name, shape in sizes.items():
            v = np.full(shape, 1e-3, np.float32)
            v *= np.sign(rng.standard_normal(shape)).astype(np.float32)
            flat = v.reshape(-1)
            flat[rng.integers(0, flat.size, 5)] *= scale_hi
            delta[name] = jnp.asarray(v)
        params = jax.tree.map(jnp.zeros_like, delta)
        pol = dataclasses.replace(
            CompressionPolicy.single(get_compressor("sbc").codec), fast=True
        )
        res = pol.resolve(params)
        space = flatmod.FlatParamSpace.for_resolved(res, params, bm=BM, lanes=LANES)
        rates = res.rates(0.01, 0)
        dense_tree, _, stats = space.compress_hist(
            delta, res.init_state(params), rates)
        for i, name in enumerate(sorted(sizes)):
            x = np.asarray(delta[name]).reshape(-1)
            out = np.asarray(dense_tree[name]).reshape(-1)
            k = max(1, min(x.size, int(round(rates[i] * x.size))))
            kept = np.flatnonzero(out)
            assert kept.size == k == float(stats["count"][i])
            mu = float(stats["mu"][i])
            np.testing.assert_allclose(mu, x[kept].mean(), rtol=1e-6)
            sign = np.sign(mu)
            dropped = np.setdiff1d(np.flatnonzero(np.sign(x) == sign), kept)
            assert np.abs(x[kept]).min() >= np.abs(x[dropped]).max()
            assert np.diff(kept).mean() > 0.5 * x.size / k

    def test_rejects_non_sbc_policies(self):
        params, delta = rand_delta()
        res = sbc_policy(fast=True).resolve(params)  # has dense/skip leaves
        space = res.flat_space(params)
        with pytest.raises(ValueError, match="all-SBC"):
            space.compress_hist(delta, res.init_state(params),
                                res.rates(0.05, 0))
