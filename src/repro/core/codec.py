"""Codec = Selector → Quantizer → Encoder composition (DESIGN.md §2).

A :class:`Codec` glues three registered stages into one per-leaf
compression method with the uniform :class:`~repro.core.stages.LeafCompressed`
IR.  Codecs are cheap frozen dataclasses; the spec string form

    "selector|quantizer|encoder"      e.g. "topk_signed|binarize|golomb"

is what policies, configs, and the wire layer use to name them.  Named
shorthands ("sbc", "topk", "signsgd", …) are registered by
:mod:`repro.core.sbc` / :mod:`repro.core.baselines` through
:func:`register_codec`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import stages
from repro.core.stages import (
    Encoder,
    LeafCompressed,
    Quantizer,
    Selector,
    decompress_leaf,
    get_encoder,
    get_quantizer,
    get_selector,
    k_for,
)
from repro.obs.scopes import scope


@dataclasses.dataclass(frozen=True)
class Codec:
    """One composed compression method for one tensor.

    ``use_residual``: whether error feedback (Eq. 2) wraps this codec.
    Unbiased stochastic quantizers (terngrad/qsgd) and sign-voting run
    residual-free, everything else accumulates what it did not send.
    """

    selector: Selector
    quantizer: Quantizer
    encoder: Encoder
    use_residual: bool = True

    @property
    def spec(self) -> str:
        return f"{self.selector.name}|{self.quantizer.name}|{self.encoder.name}"

    @property
    def stochastic(self) -> bool:
        return self.selector.stochastic or self.quantizer.stochastic

    @property
    def skip(self) -> bool:
        return self.selector.skip

    @property
    def flat_kind(self):
        """Segment kind in the flat-buffer fast path (core/flat.py §10):
        "sbc" | "dense" | "skip", or None when any stage has no flat form
        (a ``fast=True`` policy then falls back to the per-leaf path)."""
        if not (self.selector.flat_fast and self.quantizer.flat_fast
                and self.encoder.flat_fast):
            return None
        if self.selector.skip:
            return "skip"
        if self.selector.dense and self.quantizer.name == "identity":
            return "dense"
        if self.spec == "topk_signed|binarize|golomb":
            return "sbc"
        return None

    # ------------------------------------------------------------- per leaf

    def compress_leaf(
        self, flat: jax.Array, p: float, rng: Optional[jax.Array]
    ) -> LeafCompressed:
        """flat f32[n] → LeafCompressed.  ``p`` is this leaf's sparsity rate."""
        n = flat.shape[0]
        if rng is not None:
            # independent draws per stage: a stochastic selector composed
            # with a stochastic quantizer must not share randomness
            s_rng, q_rng = jax.random.split(rng)
        else:
            s_rng = q_rng = None
        with scope("select"):
            sel = self.selector(flat, p, s_rng)
        vals_q, scalar = self.quantizer(sel, q_rng)
        if self.selector.skip:
            return LeafCompressed(
                idx=sel.idx,
                vals=jnp.zeros((0,), jnp.float32),
                mean=jnp.zeros((), jnp.float32),
                dense=jnp.zeros((0,), jnp.float32),
                nbits=jnp.zeros((), jnp.float32),
            )
        if self.selector.dense:
            k = n
            nbits = self.quantizer.value_bits(k)  # positions cost 0 bits
            return LeafCompressed(
                idx=jnp.zeros((0,), jnp.int32),
                vals=jnp.zeros((0,), jnp.float32),
                mean=scalar,
                dense=vals_q,
                nbits=jnp.asarray(nbits, jnp.float32),
            )
        k = sel.idx.shape[0]
        nbits = self.encoder.position_bits(n, k, p) + self.quantizer.value_bits(k)
        return LeafCompressed(
            idx=sel.idx,
            vals=vals_q,
            mean=scalar,
            dense=jnp.zeros((0,), jnp.float32),
            nbits=jnp.asarray(nbits, jnp.float32),
        )

    def decompress_leaf(self, comp: LeafCompressed, n: int) -> jax.Array:
        return decompress_leaf(comp, n)


# ------------------------------------------------------------ codec registry


_CODECS: Dict[str, Any] = {}


def register_codec(name: str):
    """Register a named codec factory (kwargs → Codec)."""

    def deco(factory):
        _CODECS[name] = factory
        return factory

    return deco


def make_codec(spec: Union[str, Codec], **kwargs: Any) -> Codec:
    """Build a codec from a named shorthand, a "sel|quant|enc" spec string,
    or pass an already-built Codec through."""
    if isinstance(spec, Codec):
        return spec
    if spec in _CODECS:
        return _CODECS[spec](**kwargs)
    if "|" in spec:
        sel, quant, enc = spec.split("|")
        return Codec(
            selector=get_selector(sel, **kwargs),
            quantizer=get_quantizer(quant, **kwargs),
            encoder=get_encoder(enc, **kwargs),
            use_residual=kwargs.get("use_residual", True),
        )
    raise KeyError(
        f"unknown codec {spec!r}; named codecs: {sorted(_CODECS)}; "
        f"or compose stages as 'selector|quantizer|encoder' from "
        f"{stages.available_stages()}"
    )


def available_codecs() -> list:
    return sorted(_CODECS)


# The two structural codecs every policy can reference.
@register_codec("dense32")
def make_dense32(use_residual: bool = True, **_) -> Codec:
    """Dense 32-bit passthrough — the per-leaf dense-fallback codec."""
    return Codec(
        get_selector("dense"), get_quantizer("identity"), get_encoder("none"),
        use_residual=use_residual,
    )


@register_codec("skip")
def make_skip(**_) -> Codec:
    """Transmit nothing for this leaf (frozen/excluded parameters).
    With use_residual=True the untransmitted update accumulates in the
    residual, so a later non-skip round flushes it (§III hybrid schedules)."""
    return Codec(
        get_selector("skip"), get_quantizer("identity"), get_encoder("none"),
        use_residual=True,
    )


def leaf_k(codec: Codec, n: int, p: float) -> int:
    """Static survivor count of ``codec`` on an n-entry leaf at rate p."""
    if codec.skip:
        return 0
    if codec.selector.dense:
        return n
    return k_for(n, p)
