"""Codec stages: Selector → Quantizer → Encoder (DESIGN.md §2).

The paper's methods decompose into three orthogonal choices per tensor:

  *which* entries survive            → :class:`Selector`
  *how* surviving values are coded   → :class:`Quantizer`
  *how* surviving positions are coded→ :class:`Encoder`

SBC (Alg. 2) is ``topk_signed → binarize → golomb``; Gradient Dropping is
``topk → identity → raw16``; signSGD is ``dense → sign → none``; and so on.
Each stage is a small registered functional unit so new methods are one
composition away instead of one monolithic compressor away.

Every stage is jit/vmap-friendly: selection sizes ``k`` are static functions
of ``(n, p)``, and all per-entry work is fixed-shape.  The host-side byte
serialization of each stage lives in :mod:`repro.core.wire`, keyed by the
stage names recorded here.

The shared intermediate representation is :class:`LeafCompressed` — one
fixed-shape pytree per flattened tensor, decompressible by the single
generic rule in :func:`decompress_leaf` (codec-independent):

  dense payload present → it IS the reconstruction;
  per-entry vals present → scatter vals at idx;
  otherwise              → scatter the per-tensor scalar at idx.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.golomb import expected_position_bits
from repro.core.select import two_sided_topk


class LeafCompressed(NamedTuple):
    """Compressed form of ONE flattened tensor (the stage IR).

    Exactly one value encoding is "live" per codec; dead fields are
    zero-size arrays so the pytree structure stays static under jit.

    idx:  int32[k]   positions of surviving entries (empty for dense/skip)
    vals: f32[k] | f32[0]   per-entry values (identity-quantized codecs)
    mean: f32[]      per-tensor scalar (SBC ±μ, sign/ternary/qsgd scale)
    dense: f32[n] | f32[0]  dense payload (dense-selector codecs)
    nbits: f32[]     analytic wire size of this leaf for this round (Eq. 1)
    """

    idx: jax.Array
    vals: jax.Array
    mean: jax.Array
    dense: jax.Array
    nbits: jax.Array


class Selection(NamedTuple):
    """Selector output: surviving positions + their raw values.

    Dense selectors return ``idx`` empty and ``vals`` of length n — the
    position stream costs 0 bits and the encoder is bypassed.
    """

    idx: jax.Array  # int32[k] (int32[0] when dense or skip)
    vals: jax.Array  # f32[k]  (f32[n] when dense, f32[0] when skip)


def k_for(n: int, p: float) -> int:
    """Number of surviving entries at sparsity rate p (at least 1)."""
    return max(1, min(n, int(round(p * n))))


# ------------------------------------------------------------------ selectors


@dataclasses.dataclass(frozen=True)
class Selector:
    """Picks which coordinates of a flat f32[n] tensor survive.

    fn(flat, p, rng) -> Selection with a k that is static in (n, p).
    ``dense``: every coordinate survives (positions are free).
    ``skip``:  nothing survives, nothing is transmitted.
    """

    name: str
    fn: Callable[[jax.Array, float, Optional[jax.Array]], Selection]
    dense: bool = False
    skip: bool = False
    stochastic: bool = False
    # stage is expressible in the flat-buffer fast path (core/flat.py §10);
    # a codec takes the fast path only when all three of its stages are
    flat_fast: bool = False

    def __call__(self, flat: jax.Array, p: float, rng) -> Selection:
        return self.fn(flat, p, rng)


_SELECTORS: Dict[str, Callable[..., Selector]] = {}


def register_selector(name: str):
    def deco(factory):
        _SELECTORS[name] = factory
        return factory

    return deco


def get_selector(name: str, **kw) -> Selector:
    if name not in _SELECTORS:
        raise KeyError(f"unknown selector {name!r}; have {sorted(_SELECTORS)}")
    return _SELECTORS[name](**kw)


@register_selector("dense")
def make_dense_selector(**_) -> Selector:
    def fn(flat, p, rng):
        del p, rng
        return Selection(idx=jnp.zeros((0,), jnp.int32), vals=flat)

    return Selector("dense", fn, dense=True, flat_fast=True)


@register_selector("skip")
def make_skip_selector(**_) -> Selector:
    def fn(flat, p, rng):
        del flat, p, rng
        return Selection(
            idx=jnp.zeros((0,), jnp.int32), vals=jnp.zeros((0,), jnp.float32)
        )

    return Selector("skip", fn, skip=True, flat_fast=True)


@register_selector("topk")
def make_topk_selector(**_) -> Selector:
    """Magnitude top-k (Gradient Dropping / DGC selection)."""

    def fn(flat, p, rng):
        del rng
        k = k_for(flat.shape[0], p)
        _, idx = jax.lax.top_k(jnp.abs(flat), k)
        return Selection(idx=idx.astype(jnp.int32), vals=flat[idx])

    return Selector("topk", fn)


@register_selector("topk_signed")
def make_topk_signed_selector(**_) -> Selector:
    """SBC's one-sided selection (Alg. 2 l.1-5): top-k of ΔW and of −ΔW,
    keep whichever side has the larger mean magnitude.  Composed with the
    ``binarize`` quantizer this is exactly Sparse Binary Compression."""

    def fn(flat, p, rng):
        del rng
        k = k_for(flat.shape[0], p)
        (val_pos, idx_pos), (val_neg, idx_neg) = two_sided_topk(flat, k)
        pos_wins = jnp.mean(val_pos) > jnp.mean(val_neg)
        idx = jnp.where(pos_wins, idx_pos, idx_neg).astype(jnp.int32)
        # flat[idx] without the gather: −val_neg is flat's own bits
        vals = jnp.where(pos_wins, val_pos, -val_neg)
        return Selection(idx=idx, vals=vals)

    return Selector("topk_signed", fn, flat_fast=True)


@register_selector("threshold")
def make_threshold_selector(tau: float = 0.0, **_) -> Selector:
    """Fixed-threshold selection (Strom '15 family): capacity-k slots, but
    entries with |ΔW| < τ transmit an explicit zero.  With τ = 0 this
    degenerates to plain top-k.  Static-shape under jit: the slot count is
    k_for(n, p); the threshold only masks values, never changes shapes."""

    def fn(flat, p, rng):
        del rng
        k = k_for(flat.shape[0], p)
        _, idx = jax.lax.top_k(jnp.abs(flat), k)
        vals = flat[idx]
        vals = jnp.where(jnp.abs(vals) >= tau, vals, 0.0)
        return Selection(idx=idx.astype(jnp.int32), vals=vals)

    return Selector("threshold", fn)


@register_selector("randomk")
def make_randomk_selector(**_) -> Selector:
    """Random-k mask (sketched updates, Konečný et al. '16)."""

    def fn(flat, p, rng):
        n = flat.shape[0]
        k = k_for(n, p)
        idx = jax.random.choice(rng, n, shape=(k,), replace=False).astype(jnp.int32)
        return Selection(idx=idx, vals=flat[idx])

    return Selector("randomk", fn, stochastic=True)


@register_selector("variance")
def make_variance_selector(block: int = 256, **_) -> Selector:
    """Approximated variance-based selection (Tsuzuku et al. '18): keep the
    entries whose magnitude is large *relative to the local noise level*,
    not merely large in absolute terms.  The ambiguity criterion √V is
    approximated by a blockwise second-moment proxy over the accumulated
    (momentum-normalized) update: each entry's score is |ΔW| divided by
    the RMS of its ``block``-sized neighbourhood, so a coordinate that
    stands out from a quiet block beats a middling coordinate inside a
    loud one.  Deterministic and static-k (exactly ``k_for(n, p)``
    survivors), so it rides the standard sparse wire format unchanged."""

    def fn(flat, p, rng):
        del rng
        n = flat.shape[0]
        k = k_for(n, p)
        b = min(block, n)
        nb = -(-n // b)
        x = jnp.pad(flat, (0, nb * b - n)).reshape(nb, b)
        rms = jnp.sqrt(jnp.mean(x * x, axis=1, keepdims=True) + 1e-24)
        score = (jnp.abs(x) / rms).reshape(-1)[:n]
        _, idx = jax.lax.top_k(score, k)
        return Selection(idx=idx.astype(jnp.int32), vals=flat[idx])

    return Selector("variance", fn)


@register_selector("expert_topk")
def make_expert_topk_selector(experts: int = 8, **_) -> Selector:
    """Per-expert balanced top-k for MoE leaves shaped ``(E, …)``.

    Routing already sparsified the gradient: only the routed experts hold
    signal, and a hot expert would crowd every other expert out of a
    plain global top-k.  Selection therefore ranks candidates in three
    tiers — (1) each expert's local top-⌈k/E⌉ (its fair quota), (2) the
    remaining non-zero coordinates of routed experts, (3) exact zeros
    (unrouted experts) — and takes the global top-k in tier order.  So
    every routed expert keeps its quota, an unrouted all-zero expert
    donates its slots to routed experts instead of shipping zeros
    (skip-if-unrouted), and total survivors are exactly ``k_for(n, p)``
    — byte-compatible with the static-k wire contract.  Leaves whose
    length is not divisible by ``experts`` degrade to plain top-k."""

    def fn(flat, p, rng):
        del rng
        n = flat.shape[0]
        k = k_for(n, p)
        e = experts if (experts > 1 and n % experts == 0) else 1
        if e == 1:
            _, idx = jax.lax.top_k(jnp.abs(flat), k)
            return Selection(idx=idx.astype(jnp.int32), vals=flat[idx])
        n_loc = n // e
        q = min(n_loc, k)  # candidates per expert (enough to redistribute)
        quota = -(-k // e)
        bscore, bidx = jax.lax.top_k(jnp.abs(flat).reshape(e, n_loc), q)
        # tiered score bands, non-overlapping since span > max score
        span = jnp.max(bscore) + 1.0
        nz = bscore > 0.0
        in_quota = (jnp.arange(q) < quota)[None, :]
        adj = bscore + 2.0 * span * (nz & in_quota) + span * (nz & ~in_quota)
        base = jnp.arange(e, dtype=jnp.int32)[:, None] * n_loc
        cand = (bidx.astype(jnp.int32) + base).reshape(-1)
        _, sel = jax.lax.top_k(adj.reshape(-1), k)  # e·q ≥ k always
        idx = cand[sel]
        return Selection(idx=idx, vals=flat[idx])

    return Selector("expert_topk", fn)


# ----------------------------------------------------------------- quantizers


@dataclasses.dataclass(frozen=True)
class Quantizer:
    """Codes the surviving values.

    fn(selection, rng) -> (vals_q, scalar):
      vals_q: f32 array shaped like selection.vals, or f32[0] when the
              quantizer collapses all values into the per-tensor scalar;
      scalar: f32[] per-tensor constant (μ, scale, norm; 0 when unused).

    value_bits(k) -> analytic wire bits for k surviving values, including
    any per-tensor scalar overhead.
    """

    name: str
    fn: Callable[[Selection, Optional[jax.Array]], tuple]
    value_bits: Callable[[int], float]
    stochastic: bool = False
    levels: int = 0  # quantization-level count (wire code width); 0 = n/a
    flat_fast: bool = False  # expressible in the flat fast path (§10)

    def __call__(self, sel: Selection, rng) -> tuple:
        return self.fn(sel, rng)


_QUANTIZERS: Dict[str, Callable[..., Quantizer]] = {}


def register_quantizer(name: str):
    def deco(factory):
        _QUANTIZERS[name] = factory
        return factory

    return deco


def get_quantizer(name: str, **kw) -> Quantizer:
    if name not in _QUANTIZERS:
        raise KeyError(f"unknown quantizer {name!r}; have {sorted(_QUANTIZERS)}")
    return _QUANTIZERS[name](**kw)


@register_quantizer("identity")
def make_identity_quantizer(**_) -> Quantizer:
    """Values pass through at full 32-bit precision."""

    def fn(sel, rng):
        del rng
        return sel.vals.astype(jnp.float32), jnp.zeros((), jnp.float32)

    return Quantizer("identity", fn, value_bits=lambda k: 32.0 * k, flat_fast=True)


@register_quantizer("binarize")
def make_binarize_quantizer(**_) -> Quantizer:
    """±μ binarization (SBC Alg. 2 l.4-6): ALL surviving values collapse to
    their single signed mean — 0 value bits per entry, one 32-bit scalar."""

    def fn(sel, rng):
        del rng
        mu = jnp.mean(sel.vals).astype(jnp.float32)
        return jnp.zeros((0,), jnp.float32), mu

    return Quantizer("binarize", fn, value_bits=lambda k: 32.0, flat_fast=True)


@register_quantizer("sign")
def make_sign_quantizer(**_) -> Quantizer:
    """Scaled sign (signSGD/SIGNUM): 1 bit per entry + one 32-bit scale.
    Compressors act on weight-DELTAS, so the bare sign must carry a
    magnitude — mean(|Δ|), one scalar per tensor (DESIGN.md §8).

    Exact zeros quantize to +scale (sign ties go positive): a 1-bit wire
    symbol has no zero, and the sender must emit exactly what a receiver
    can reconstruct from the bitstream."""

    def fn(sel, rng):
        del rng
        v = sel.vals
        scale = jnp.mean(jnp.abs(v)).astype(jnp.float32)
        return jnp.where(v >= 0, scale, -scale).astype(jnp.float32), scale

    return Quantizer("sign", fn, value_bits=lambda k: 1.0 * k + 32.0)


@register_quantizer("two_means")
def make_two_means_quantizer(**_) -> Quantizer:
    """1-bit SGD (Seide et al. '14): per-tensor μ⁺/μ⁻ column means —
    1 bit per entry + two 32-bit scalars."""

    def fn(sel, rng):
        del rng
        v = sel.vals
        pos = v >= 0
        npos = jnp.maximum(jnp.sum(pos), 1)
        nneg = jnp.maximum(v.shape[0] - jnp.sum(pos), 1)
        mu_pos = jnp.sum(jnp.where(pos, v, 0.0)) / npos
        mu_neg = jnp.sum(jnp.where(pos, 0.0, v)) / nneg  # negative number
        out = jnp.where(pos, mu_pos, mu_neg).astype(jnp.float32)
        return out, mu_pos.astype(jnp.float32)

    return Quantizer("two_means", fn, value_bits=lambda k: 1.0 * k + 64.0)


@register_quantizer("ternary")
def make_ternary_quantizer(**_) -> Quantizer:
    """TernGrad (Wen et al. '17): stochastic ternary {−s, 0, +s}."""

    def fn(sel, rng):
        v = sel.vals
        s = jnp.max(jnp.abs(v)) + 1e-12
        keep = jax.random.bernoulli(rng, jnp.abs(v) / s)
        return (s * jnp.sign(v) * keep).astype(jnp.float32), s.astype(jnp.float32)

    return Quantizer(
        "ternary", fn, value_bits=lambda k: math.log2(3.0) * k + 32.0, stochastic=True
    )


@register_quantizer("stochastic")
def make_stochastic_quantizer(levels: int = 15, **_) -> Quantizer:
    """QSGD (Alistarh et al. '17): stochastic uniform quantization on the
    L2 ball with ``levels`` levels; the per-tensor norm rides in the scalar."""

    def fn(sel, rng):
        v = sel.vals
        norm = jnp.linalg.norm(v) + 1e-12
        scaled = jnp.abs(v) / norm * levels
        floor = jnp.floor(scaled)
        quant = floor + jax.random.bernoulli(rng, scaled - floor)
        out = (norm * jnp.sign(v) * quant / levels).astype(jnp.float32)
        return out, norm.astype(jnp.float32)

    bits_per = math.log2(2.0 * levels + 1.0)
    return Quantizer(
        "stochastic", fn, value_bits=lambda k: bits_per * k + 32.0,
        stochastic=True, levels=levels,
    )


# ------------------------------------------------------------------- encoders


@dataclasses.dataclass(frozen=True)
class Encoder:
    """Position stream coding.  Only the *analytic* model lives here;
    the exact byte serialization is in :mod:`repro.core.wire` keyed by
    ``name``.  position_bits(n, k, p) -> analytic wire bits."""

    name: str
    position_bits: Callable[[int, int, float], float]
    flat_fast: bool = False  # expressible in the flat fast path (§10)


_ENCODERS: Dict[str, Callable[..., Encoder]] = {}


def register_encoder(name: str):
    def deco(factory):
        _ENCODERS[name] = factory
        return factory

    return deco


def get_encoder(name: str, **kw) -> Encoder:
    if name not in _ENCODERS:
        raise KeyError(f"unknown encoder {name!r}; have {sorted(_ENCODERS)}")
    return _ENCODERS[name](**kw)


@register_encoder("none")
def make_none_encoder(**_) -> Encoder:
    """Dense / skip codecs: positions are predetermined, 0 bits."""
    return Encoder("none", lambda n, k, p: 0.0, flat_fast=True)


@register_encoder("golomb")
def make_golomb_encoder(**_) -> Encoder:
    """Optimal Golomb position coding (paper Alg. 3, Eq. 5)."""
    return Encoder(
        "golomb", lambda n, k, p: k * expected_position_bits(min(p, 1.0)),
        flat_fast=True,
    )


@register_encoder("bitmask")
def make_bitmask_encoder(**_) -> Encoder:
    """One bit per coordinate; beats Golomb only when p ≳ 0.3."""
    return Encoder("bitmask", lambda n, k, p: 1.0 * n)


@register_encoder("raw16")
def make_raw16_encoder(**_) -> Encoder:
    """The paper's naive fixed-width 16-bit positions (Table I baselines)."""
    return Encoder("raw16", lambda n, k, p: 16.0 * k)


@register_encoder("raw32")
def make_raw32_encoder(**_) -> Encoder:
    return Encoder("raw32", lambda n, k, p: 32.0 * k)


@register_encoder("seed")
def make_seed_encoder(**_) -> Encoder:
    """Random-k positions derivable from a shared 32-bit seed (Konečný et
    al. '16) — one scalar regardless of k.  NOTE: the packed wire format
    (repro.core.wire) still ships explicit raw32 indices so a receiver
    without the shared seed can decode; the analytic model reflects the
    shared-seed in-process exchange."""
    return Encoder("seed", lambda n, k, p: 32.0)


# ---------------------------------------------------------------- decompress


def decompress_leaf(comp: LeafCompressed, n: int) -> jax.Array:
    """Generic, codec-independent reconstruction of one flat tensor.

    Branch is static (zero-size fields are compile-time shapes), so this
    stays jit-friendly for every registered codec.
    """
    if comp.dense.shape[0]:
        return comp.dense
    if comp.vals.shape[0]:
        return jnp.zeros((n,), jnp.float32).at[comp.idx].set(comp.vals)
    # scalar-collapsed values (SBC ±μ); a skip codec has idx empty → zeros
    return jnp.zeros((n,), jnp.float32).at[comp.idx].set(comp.mean)


def available_stages() -> dict:
    return {
        "selectors": sorted(_SELECTORS),
        "quantizers": sorted(_QUANTIZERS),
        "encoders": sorted(_ENCODERS),
    }
