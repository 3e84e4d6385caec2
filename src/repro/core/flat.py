"""Device-resident flat-buffer compression fast path (DESIGN.md §10).

:class:`FlatParamSpace` flattens a parameter pytree ONCE into a single
contiguous block-padded f32 buffer with static per-leaf segment metadata
(offset, size, sparsity rate, survivor count) and then runs the whole
per-round compression as ONE cached jitted call, instead of the per-leaf
Python loop of jnp dispatches in :meth:`ResolvedPolicy.compress`.

Two engines share the layout:

``compress``   the *exact* engine — per-segment two-sided top-k selection
               (``lax.top_k`` on static segment slices), one fused scatter
               building ΔW* for every leaf at once, and a single flat
               residual update.  Output is **bit-identical** to the legacy
               per-leaf path: same LeafCompressed trees (same indices, same
               μ down to the sign of −0.0), same SBW1 bytes after
               ``Wire.pack``, same residuals.  This is what ``fast=True``
               policies dispatch to.

``compress_hist``  the *device* engine — the segment-aware Pallas kernels
               (:func:`repro.kernels.ops.seg_sbc_hist`): two-pass histogram
               threshold, tier counts, masked moments, fused
               binarize+residual, each launched ONCE over the flat buffer.
               Exactly k survivors per segment; the threshold is a
               histogram bucket, so WHICH entries survive may differ from
               top-k within it (ties kept spread by position); compiled on
               TPU, interpreted elsewhere
               (:func:`repro.kernels.resolve_interpret`).

Layout contract (stable; documented in DESIGN.md §10):

  * leaf i's flat segment lives at ``[offset_i, offset_i + size_i)`` where
    ``offset_i`` is block-aligned (blocks of ``bm·lanes`` elements) and the
    tail up to the next block boundary is zero;
  * the error-feedback residual is stored IN THIS LAYOUT as one f32 array —
    compressor state never round-trips through the per-leaf pytree between
    rounds;
  * pytrees cross the boundary only at ``flatten``/``unflatten``.

The speedup is structural, not numeric: the eager per-leaf path (how
``fed.server.ParameterServer.broadcast`` turns around a round) pays one
dispatch per jnp op per leaf; the flat path pays one cached jitted call
for the whole parameter set.  ``benchmarks/compress_e2e.py`` measures both.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.golomb import golomb_bstar
from repro.core.stages import LeafCompressed, k_for
from repro.obs.scopes import scope
from repro.kernels.ops import seg_sbc_hist
from repro.kernels.pack import (
    bits_from_positions,
    golomb_decode_rows,
    row_words,
    seg_packbits,
)

PyTree = Any


def _flatten_padded(leaves, offsets: Sequence[int], n_pad: int) -> jax.Array:
    """Flatten ``leaves`` into the block-padded layout: leaf i at
    ``offsets[i]``, zeros between leaves and up to ``n_pad`` (one
    concatenate; no position-map constant, which at 10⁸ parameters would
    be embedded in the compiled program) — shared by
    :class:`FlatParamSpace` and the sharded space."""
    pieces, pos = [], 0
    for leaf, off in zip(leaves, offsets):
        if off > pos:
            pieces.append(jnp.zeros((off - pos,), jnp.float32))
        flat = jnp.asarray(leaf).reshape(-1).astype(jnp.float32)
        pieces.append(flat)
        pos = off + flat.shape[0]
    if n_pad > pos:
        pieces.append(jnp.zeros((n_pad - pos,), jnp.float32))
    return jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]


def supports(resolved) -> bool:
    """True when every leaf of the resolved policy has a flat-fast codec
    (``Codec.flat_kind`` is not None for every plan)."""
    return all(p.codec.flat_kind is not None for p in resolved.plans)


class Segment(NamedTuple):
    """Static per-leaf slot in the flat buffer."""

    path: str
    shape: Tuple[int, ...]
    dtype: Any
    size: int
    offset: int  # block-aligned start in the padded flat buffer
    kind: str  # "sbc" | "dense" | "skip"
    use_residual: bool


@dataclasses.dataclass(eq=False)
class FlatParamSpace:
    """One policy bound to one pytree layout, flattened to a single buffer.

    Built lazily by :meth:`ResolvedPolicy.flat_space` the first time a
    ``fast=True`` policy compresses; construction needs only leaf shapes,
    so it works under tracing.  ``bm``/``lanes`` fix the block size of the
    padded layout (and the Pallas tile of the ``compress_hist`` engine) —
    they must match between the two engines because the residual buffer is
    shared.
    """

    resolved: Any  # ResolvedPolicy (duck-typed; no import cycle)
    segments: Tuple[Segment, ...]
    bm: int = 8
    lanes: int = 128

    def __post_init__(self) -> None:
        per_block = self.bm * self.lanes
        self.n_blocks = sum(
            max(1, -(-s.size // per_block)) for s in self.segments
        )
        self.n_pad = self.n_blocks * per_block
        self.n_total = sum(s.size for s in self.segments)
        res_mask = np.zeros((self.n_pad,), bool)
        dense_mask = np.zeros((self.n_pad,), bool)
        for s in self.segments:
            if s.use_residual:
                res_mask[s.offset:s.offset + s.size] = True
            if s.kind == "dense":
                dense_mask[s.offset:s.offset + s.size] = True
        self._res_mask = res_mask
        self._dense_mask = dense_mask
        # pad slots self-maintain zeros under acc/dense/residual updates, so
        # the mask-free fast branch only needs every LEAF to use residuals
        self._all_residual = all(s.use_residual for s in self.segments)
        self._jitted: Dict[tuple, Any] = {}

    # ------------------------------------------------------------- building

    @classmethod
    def for_resolved(
        cls, resolved, like: PyTree, *, bm: int = 8, lanes: int = 128
    ) -> "FlatParamSpace":
        """Bind ``resolved`` to the concrete leaf shapes of ``like``."""
        leaves = resolved._leaves_of(like)
        per_block = bm * lanes
        segs: List[Segment] = []
        off = 0
        for plan, leaf in zip(resolved.plans, leaves):
            kind = plan.codec.flat_kind
            if kind is None:
                raise ValueError(
                    f"leaf {plan.path!r} codec {plan.codec.spec!r} has no "
                    "flat fast path; guard with repro.core.flat.supports()"
                )
            shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
            size = int(np.prod(shape)) if shape else 1
            segs.append(Segment(
                path=plan.path, shape=shape, dtype=leaf.dtype, size=size,
                offset=off, kind=kind, use_residual=plan.codec.use_residual,
            ))
            off += max(1, -(-size // per_block)) * per_block
        return cls(resolved=resolved, segments=tuple(segs), bm=bm, lanes=lanes)

    # --------------------------------------------------------- flat plumbing

    def flatten(self, tree: PyTree) -> jax.Array:
        """Pytree → one block-padded f32 buffer (the §10 layout)."""
        return self._flatten_leaves(self.resolved._leaves_of(tree))

    def _flatten_leaves(self, leaves) -> jax.Array:
        return _flatten_padded(
            leaves, [s.offset for s in self.segments], self.n_pad
        )

    def unflatten(self, flat: jax.Array, cast: bool = True) -> PyTree:
        """Flat buffer → pytree (inverse of :meth:`flatten`)."""
        out = []
        for seg in self.segments:
            piece = flat[seg.offset:seg.offset + seg.size].reshape(seg.shape)
            out.append(piece.astype(seg.dtype) if cast else piece)
        return jax.tree.unflatten(self.resolved.treedef, out)

    def zeros_residual(self) -> jax.Array:
        return jnp.zeros((self.n_pad,), jnp.float32)

    def _check_rates(self, rates) -> Tuple[float, ...]:
        if not isinstance(rates, tuple):
            rates = (float(rates),) * len(self.segments)
        if len(rates) != len(self.segments):
            raise ValueError(
                f"got {len(rates)} rates for {len(self.segments)} leaves"
            )
        return tuple(float(r) for r in rates)

    def _ks(self, rates: Tuple[float, ...]) -> Tuple[int, ...]:
        return tuple(
            0 if s.kind == "skip"
            else s.size if s.kind == "dense"
            else k_for(s.size, p)
            for s, p in zip(self.segments, rates)
        )

    # ------------------------------------------------------------ exact path

    def compress(self, delta: PyTree, state, rates) -> tuple:
        """Drop-in, bit-identical replacement for the per-leaf
        ``ResolvedPolicy.compress`` — same (ctree, dense_tree, new_state)
        contract, with ``new_state.residual`` kept in the flat layout."""
        rates = self._check_rates(rates)
        fn = self._jitted.get(("exact", rates))
        if fn is None:
            fn = jax.jit(lambda leaves, res, rng:
                         self._compress_exact(leaves, res, rng, rates))
            self._jitted[("exact", rates)] = fn
        leaves = self.resolved._leaves_of(delta)
        residual = state.residual if self.resolved.any_residual else None
        ctree_leaves, dense_leaves, new_res, next_rng = fn(
            leaves, residual, state.rng
        )
        new_state = state._replace(
            residual=new_res if new_res is not None else state.residual,
            rng=next_rng,
            step=state.step + 1,
        )
        return (
            jax.tree.unflatten(self.resolved.treedef, ctree_leaves),
            jax.tree.unflatten(self.resolved.treedef, dense_leaves),
            new_state,
        )

    def _compress_exact(self, leaves, residual, rng, rates):
        segs, ks = self.segments, self._ks(rates)
        # residual-accumulate in ONE flat op (Eq. 2 gather phase)
        delta_flat = self._flatten_leaves(leaves)
        if residual is None:
            acc_flat = delta_flat
        elif self._all_residual:
            acc_flat = delta_flat + residual
        else:
            acc_flat = delta_flat + jnp.where(
                jnp.asarray(self._res_mask), residual, 0.0
            )

        # per-segment exact two-sided top-k (paper Alg. 2 l.1-5).  The
        # selection math is identical to the topk_signed selector, so idx,
        # μ, and the pos/neg side decision match the legacy path bit for bit.
        comp_leaves: List[Optional[LeafCompressed]] = [None] * len(segs)
        gidx, gmu = [], []
        for i, (seg, k, p) in enumerate(zip(segs, ks, rates)):
            acc = acc_flat[seg.offset:seg.offset + seg.size]
            if seg.kind == "skip":
                comp_leaves[i] = LeafCompressed(
                    idx=jnp.zeros((0,), jnp.int32),
                    vals=jnp.zeros((0,), jnp.float32),
                    mean=jnp.zeros((), jnp.float32),
                    dense=jnp.zeros((0,), jnp.float32),
                    nbits=jnp.zeros((), jnp.float32),
                )
                continue
            if seg.kind == "dense":
                codec = self.resolved.plans[i].codec
                comp_leaves[i] = LeafCompressed(
                    idx=jnp.zeros((0,), jnp.int32),
                    vals=jnp.zeros((0,), jnp.float32),
                    mean=jnp.zeros((), jnp.float32),
                    dense=acc,
                    nbits=jnp.asarray(codec.quantizer.value_bits(k), jnp.float32),
                )
                continue
            with scope("select"):
                val_pos, idx_pos = jax.lax.top_k(acc, k)
                val_neg, idx_neg = jax.lax.top_k(-acc, k)
                pos_wins = jnp.mean(val_pos) > jnp.mean(val_neg)
                idx = jnp.where(pos_wins, idx_pos, idx_neg).astype(jnp.int32)
            # μ re-gathers the winning side's ORIGINAL values, exactly like
            # the topk_signed selector + binarize quantizer composition —
            # down to the sign of −0.0 on an all-zero leaf
            mu = jnp.mean(acc[idx])
            codec = self.resolved.plans[i].codec
            nbits = (codec.encoder.position_bits(seg.size, k, p)
                     + codec.quantizer.value_bits(k))
            comp_leaves[i] = LeafCompressed(
                idx=idx,
                vals=jnp.zeros((0,), jnp.float32),
                mean=mu.astype(jnp.float32),
                dense=jnp.zeros((0,), jnp.float32),
                nbits=jnp.asarray(nbits, jnp.float32),
            )
            gidx.append(idx + seg.offset)
            gmu.append(jnp.broadcast_to(mu, (k,)))

        # ΔW* for EVERY sparse leaf in one fused scatter; dense segments
        # pass their acc through via ONE static-mask select (not a chain of
        # per-leaf update-slices); skip segments stay zero.
        dense_flat = jnp.zeros((self.n_pad,), jnp.float32)
        if gidx:
            dense_flat = dense_flat.at[jnp.concatenate(gidx)].set(
                jnp.concatenate(gmu)
            )
        if self._dense_mask.any():
            dense_flat = jnp.where(
                jnp.asarray(self._dense_mask), acc_flat, dense_flat
            )

        # single flat residual update (Eq. 2 scatter phase)
        new_res = None
        if residual is not None:
            if self._all_residual:
                new_res = acc_flat - dense_flat
            else:
                new_res = jnp.where(
                    jnp.asarray(self._res_mask), acc_flat - dense_flat, residual
                )

        dense_leaves = [
            dense_flat[s.offset:s.offset + s.size].reshape(s.shape).astype(s.dtype)
            for s in segs
        ]
        # advance the RNG exactly like the per-leaf path (one split per
        # leaf + carry), so fast/legacy state trajectories stay identical
        next_rng = jax.random.split(rng, len(segs) + 1)[0]
        return comp_leaves, dense_leaves, new_res, next_rng

    # ----------------------------------------------------------- hist engine

    def compress_hist(
        self,
        delta: PyTree,
        state,
        rates,
        *,
        nbins: int = 128,
    ) -> tuple:
        """Histogram-threshold SBC over the flat buffer — the Pallas engine.

        Per-segment semantics match :func:`repro.kernels.ops.sbc_compress_hist`
        (exactly k survivors per segment; residual identity acc = ΔW* + R),
        but every pass launches ONCE over the whole parameter set.
        Requires an all-"sbc" policy.  Returns ``(dense_tree, new_state,
        stats)`` with per-segment ``stats = {mu, count, nbits}``.
        """
        if any(s.kind != "sbc" for s in self.segments):
            raise ValueError(
                "compress_hist needs an all-SBC policy; dense/skip leaves "
                "belong to the exact engine"
            )
        rates = self._check_rates(rates)
        key = ("hist", rates, nbins)
        fn = self._jitted.get(key)
        if fn is None:
            fn = jax.jit(lambda leaves, res: self._compress_hist(
                leaves, res, rates, nbins))
            self._jitted[key] = fn
        leaves = self.resolved._leaves_of(delta)
        residual = state.residual if self.resolved.any_residual else None
        dense_flat, new_res, stats = fn(leaves, residual)
        new_state = state._replace(
            residual=new_res if new_res is not None else state.residual,
            rng=jax.random.split(state.rng, len(self.segments) + 1)[0],
            step=state.step + 1,
        )
        return self.unflatten(dense_flat), new_state, stats

    def _compress_hist(self, leaves, residual, rates, nbins):
        delta_flat = self._flatten_leaves(leaves)
        acc_flat = delta_flat if residual is None else delta_flat + residual
        dense_flat, res_flat, stats = seg_sbc_hist(
            acc_flat,
            bounds=[(s.offset, s.size) for s in self.segments],
            ks=self._ks(rates),
            rates=rates,
            bm=self.bm,
            lanes=self.lanes,
            nbins=nbins,
        )
        new_res = res_flat if residual is not None else None
        return dense_flat, new_res, stats


# ===================================================================== sharded


class DistSegment(NamedTuple):
    """Static per-(leaf, shard) slot in the per-device local flat buffer.

    ``shape`` is the LOCAL body shape of one shard of the leaf (no client
    dim); replicated leaves carry their full shape on every shard.  The
    per-row survivor count ``k`` uses the dist backend's rule
    ``max(1, min(n_loc, round(p · n_loc)))`` so selection matches the
    per-leaf ``_sbc_local`` exchange bit for bit.
    """

    path: str
    shape: Tuple[int, ...]  # local body shape (one shard)
    rows: int  # L (scan superblock dim; 1 for unscanned leaves)
    n_loc: int  # per-row local length
    offset: int  # block-aligned start in the local flat buffer
    kind: str  # "sparse" | "dense" | "skip"
    rate: float  # per-leaf sparsity rate (static)
    k: int  # per-row survivors (0 for dense/skip)
    n_shards: int  # distinct shards of the GLOBAL leaf (for Eq. 1 bits)
    global_size: int


@dataclasses.dataclass(eq=False)
class ShardedFlatParamSpace:
    """The §11 sharded twin of :class:`FlatParamSpace` (DESIGN.md §11).

    One per-DEVICE block-padded flat buffer holding every local leaf
    shard; the global residual/acc buffer has shape
    ``(n_clients, shards_per_client, n_pad)`` and carries a
    ``NamedSharding`` of ``P(client_axes, shard_axes, None)`` over the
    mesh, so each device owns exactly its ``(1, 1, n_pad)`` slice.  All
    ``exchange_local*`` methods are meant to run INSIDE ``shard_map``:
    each device compresses its own shard of the one flat buffer and the
    exchange is one ``all_gather`` of packed (positions, μ) flat
    segments — not per-leaf collectives.

    Selection/aggregation math mirrors the per-leaf ``_sbc_local`` /
    ``_dense_local`` shard_map kernels of ``repro.launch.dist`` exactly
    (same per-row top-k, same client-order scatter accumulation, same
    sequential per-axis collectives), so the aggregated update, the
    residual, and the Eq. 1/Eq. 5 bit counts are bit-identical to the
    per-leaf path.
    """

    segments: Tuple[DistSegment, ...]
    client_axes: Tuple[str, ...]
    shard_axes: Tuple[str, ...]
    n_clients: int
    shards_per_client: int
    bm: int = 8
    lanes: int = 128

    def __post_init__(self) -> None:
        per_block = self.bm * self.lanes
        sizes = [s.rows * s.n_loc for s in self.segments]
        self.n_blocks = sum(max(1, -(-sz // per_block)) for sz in sizes)
        self.n_pad = self.n_blocks * per_block
        self.n_total = sum(sizes)
        dense_mask = np.zeros((self.n_pad,), bool)
        for s, sz in zip(self.segments, sizes):
            if s.kind == "dense":
                dense_mask[s.offset:s.offset + sz] = True
        self._dense_idx = np.flatnonzero(dense_mask).astype(np.int32)
        # static maps for the packed sparse exchange: every (row, k-slot)
        # of every sparse segment gets one position slot; ``_pos_row``
        # maps it to its row's slot in the packed μ stream
        self._sparse = tuple(s for s in self.segments if s.kind == "sparse")
        pos_row: List[np.ndarray] = []
        mu_slot = 0
        for s in self._sparse:
            pos_row.append(
                np.repeat(np.arange(mu_slot, mu_slot + s.rows, dtype=np.int32),
                          s.k)
            )
            mu_slot += s.rows
        self.n_mu = mu_slot
        self._pos_row = (
            np.concatenate(pos_row) if pos_row else np.zeros((0,), np.int32)
        )
        self.n_pos = int(self._pos_row.shape[0])
        # device-pack layout: one packed uint32 Golomb stream per
        # (segment, row), capacity-padded to whole words so the
        # concatenated word buffer — and every row's slice of it — is
        # static.  ``(b*, words/row, word offset)`` per sparse segment.
        winfo: List[Tuple[int, int, int]] = []
        woff = 0
        for s in self._sparse:
            b = golomb_bstar(s.rate)
            w = row_words(s.n_loc, s.k, b)
            winfo.append((b, w, woff))
            woff += s.rows * w
        self._pack_info = tuple(winfo)
        self.n_pack_words = woff

    # ------------------------------------------------------------- building

    @classmethod
    def build(
        cls,
        entries: Sequence[dict],
        *,
        client_axes: Tuple[str, ...],
        shard_axes: Tuple[str, ...],
        n_clients: int,
        shards_per_client: int,
        bm: int = 8,
        lanes: int = 128,
    ) -> "ShardedFlatParamSpace":
        """``entries``: per-leaf dicts with keys ``path``, ``shape``
        (local body shape), ``rows``, ``kind``, ``rate``, ``n_shards``,
        ``global_size`` (plain data — the launch layer computes local
        shapes from the mesh + PartitionSpecs, core stays mesh-free)."""
        per_block = bm * lanes
        segs: List[DistSegment] = []
        off = 0
        for e in entries:
            size = int(np.prod(e["shape"])) if e["shape"] else 1
            rows = int(e["rows"])
            n_loc = size // rows
            k = (
                max(1, min(n_loc, int(round(e["rate"] * n_loc))))
                if e["kind"] == "sparse" else 0
            )
            segs.append(DistSegment(
                path=e["path"], shape=tuple(e["shape"]), rows=rows,
                n_loc=n_loc, offset=off, kind=e["kind"],
                rate=float(e["rate"]), k=k, n_shards=int(e["n_shards"]),
                global_size=int(e["global_size"]),
            ))
            off += max(1, -(-size // per_block)) * per_block
        return cls(
            segments=tuple(segs), client_axes=tuple(client_axes),
            shard_axes=tuple(shard_axes), n_clients=int(n_clients),
            shards_per_client=int(shards_per_client), bm=bm, lanes=lanes,
        )

    # --------------------------------------------------------- flat plumbing

    def flatten_local(self, bodies) -> jax.Array:
        """Local leaf shards (in segment order) → one local flat buffer."""
        return _flatten_padded(
            bodies, [s.offset for s in self.segments], self.n_pad
        )

    def unflatten_local(self, flat: jax.Array) -> List[jax.Array]:
        """Local flat buffer → list of local body arrays (segment order)."""
        return [
            flat[s.offset:s.offset + s.rows * s.n_loc].reshape(s.shape)
            for s in self.segments
        ]

    def zeros_residual(self) -> jax.Array:
        """The flat sharded error-feedback state (host-side layout)."""
        return jnp.zeros(
            (self.n_clients, self.shards_per_client, self.n_pad), jnp.float32
        )

    # ------------------------------------------------------- bit accounting

    def bits_per_client(self) -> float:
        """Static Eq. 1 wire bits per client per round, summed over the
        per-(segment, shard) counts: sparse segments pay
        ``rows · n_shards · (k · b̄_pos(p) + 32)`` (Eq. 5 Golomb positions
        + one 32-bit μ per (row, shard)), dense segments 32 bits/entry,
        skipped segments 0 — the same totals as the per-leaf loop."""
        from repro.core.golomb import expected_position_bits

        total = 0.0
        for s in self.segments:
            if s.kind == "sparse":
                total += s.rows * s.n_shards * (
                    s.k * expected_position_bits(s.rate) + 32.0
                )
            elif s.kind == "dense":
                total += 32.0 * s.global_size
        return total

    # ------------------------------------------------------- exact exchange

    def exchange_local(
        self,
        bodies,
        res_flat: Optional[jax.Array],
        *,
        device_pack: bool = False,
    ) -> tuple:
        """Inside shard_map: compress this device's shard of every leaf
        and exchange.  Returns ``(mean_flat, own_flat, new_res_flat)`` —
        the aggregated update, this client's ΔW*, and the new residual,
        all in the local flat layout.

        Per-(segment, shard, row) exact two-sided top-k (paper Alg. 2,
        identical math to ``_sbc_local``); THE exchange is one
        ``all_gather`` of the packed global positions + one of the packed
        μ stream per client axis, followed by one fused scatter per
        client (scanned in client order, so float accumulation matches
        the per-leaf path bit for bit).  Dense segments ride one
        ``pmean`` of the packed dense slice; skip segments move nothing
        and keep their full update in the residual.

        ``device_pack=True`` replaces the position gather with the wire
        form itself: every (segment, row)'s surviving positions are
        Golomb-packed on-device into ``uint32`` words (one
        :func:`~repro.kernels.pack.seg_packbits` launch over the whole
        local stream), the all_gather moves those word buffers
        (≈ b̄(p) bits/position instead of 32), and receivers recover
        positions with the pointer-doubling device decoder.  Returns three
        extra outputs ``(words u32[n_pack_words], nbits i32[n_mu],
        mu f32[n_mu])`` — this shard's upload: packed streams
        (byte-identical to the host ``encode_positions_packed``), exact
        per-row bit counts (the per-client wire metering) and per-row μ.  The aggregated update,
        residual, and ΔW* are bit-identical to ``device_pack=False``.
        """
        with scope("quantize"):
            acc = self.flatten_local(bodies)
            if res_flat is not None:
                acc = res_flat + acc

        pos_parts, mu_parts, idx_parts = [], [], []
        for s in self._sparse:
            x = acc[s.offset:s.offset + s.rows * s.n_loc].reshape(
                s.rows, s.n_loc
            )
            k = s.k

            def one_layer(_, x_row, k=k):
                val_pos, idx_pos = jax.lax.top_k(x_row, k)
                val_neg, idx_neg = jax.lax.top_k(-x_row, k)
                mu_pos, mu_neg = jnp.mean(val_pos), jnp.mean(val_neg)
                pos_wins = mu_pos > mu_neg
                idx = jnp.where(pos_wins, idx_pos, idx_neg).astype(jnp.int32)
                mu = jnp.where(pos_wins, mu_pos, -mu_neg).astype(jnp.float32)
                return None, (idx, mu)

            with scope("select"):
                _, (idx, mu) = jax.lax.scan(one_layer, None, x)
                base = s.offset + np.arange(s.rows, dtype=np.int32) * s.n_loc
                pos_parts.append((idx + jnp.asarray(base)[:, None]).reshape(-1))
            mu_parts.append(mu)
            idx_parts.append(idx)

        with scope("quantize"):
            own = jnp.zeros((self.n_pad,), jnp.float32)
            if pos_parts:
                pos = jnp.concatenate(pos_parts)
                mu = jnp.concatenate(mu_parts)
                pos_row = jnp.asarray(self._pos_row)
                own = own.at[pos].set(jnp.take(mu, pos_row))
            if self._dense_idx.size:
                dense_idx = jnp.asarray(self._dense_idx)
                dvals = acc[dense_idx]
                own = own.at[dense_idx].set(dvals)

        words = nbits = None
        if device_pack:
            with scope("pack"):
                words, nbits = self._pack_local(idx_parts)

        if self.client_axes and self.n_clients > 1 and pos_parts:
            # THE exchange: the packed (positions, μ) streams cross the
            # client axes once, not once per leaf.  With device_pack the
            # position stream IS the wire form — packed uint32 Golomb
            # word buffers (≈ b̄(p) bits/position) instead of raw 32-bit
            # index arrays.
            gsrc = words if device_pack else pos
            gmu = mu
            with scope("all_gather"):
                for ax in self.client_axes:
                    gsrc = jax.lax.all_gather(gsrc, ax)
                    gmu = jax.lax.all_gather(gmu, ax)
            with scope("decode"):
                gmu = gmu.reshape(self.n_clients, self.n_mu)
                if device_pack:
                    gpos = self._decode_gathered(
                        gsrc.reshape(self.n_clients, self.n_pack_words)
                    )
                else:
                    gpos = gsrc.reshape(self.n_clients, self.n_pos)

                def add_client(buf, ci):
                    vals = jnp.take(gmu[ci], pos_row) / self.n_clients
                    return buf.at[gpos[ci]].add(vals), None

                mean, _ = jax.lax.scan(
                    add_client, jnp.zeros((self.n_pad,), jnp.float32),
                    jnp.arange(self.n_clients),
                )
        else:
            mean = own
        if self._dense_idx.size and self.client_axes:
            with scope("apply"):
                dv = dvals
                for ax in self.client_axes:
                    dv = jax.lax.pmean(dv, ax)
                mean = mean.at[dense_idx].set(dv)

        with scope("quantize"):
            new_res = acc - own if res_flat is not None else None
        if device_pack:
            return mean, own, new_res, words, nbits, mu
        return mean, own, new_res

    # ------------------------------------------------- device wire packing

    def _pack_local(self, idx_parts: List[jax.Array]) -> tuple:
        """This shard's survivors → (packed u32 words, per-row bit counts).

        Builds every (segment, row)'s Golomb bit stream at its static
        offset in one concatenated bit buffer, then folds bits into
        ``uint32`` words with ONE ``seg_packbits`` launch over the whole
        flat set — the wire bytes for this shard, produced on-device.
        """
        if not idx_parts:
            return (jnp.zeros((0,), jnp.uint32), jnp.zeros((0,), jnp.int32))
        chunks, nb_parts = [], []
        for s, (b, w, _), idx_s in zip(self._sparse, self._pack_info, idx_parts):
            bits_s, nb_s = jax.vmap(
                lambda p, b=b, cap=32 * w: bits_from_positions(
                    p, bstar=b, cap32=cap
                )
            )(jnp.sort(idx_s, axis=1))
            chunks.append(bits_s.reshape(-1))
            nb_parts.append(nb_s)
        planes = jnp.concatenate(chunks).reshape(-1, 32).T
        words = seg_packbits(planes, lanes=self.lanes)
        return words, jnp.concatenate(nb_parts)

    def _decode_gathered(self, gw: jax.Array) -> jax.Array:
        """Gathered word buffers u32[C, n_pack_words] → global positions
        i32[C, n_pos] via the pointer-doubling Golomb decoder, segment by
        segment (each has its own static k, b*, and row stride)."""
        gpos_parts = []
        for s, (b, w, off) in zip(self._sparse, self._pack_info):
            seg_w = gw[:, off:off + s.rows * w].reshape(
                self.n_clients, s.rows, w
            )
            ploc = golomb_decode_rows(seg_w, k=s.k, bstar=b)
            base = s.offset + np.arange(s.rows, dtype=np.int32) * s.n_loc
            gpos_parts.append(
                (ploc + jnp.asarray(base)[None, :, None]).reshape(
                    self.n_clients, -1
                )
            )
        return jnp.concatenate(gpos_parts, axis=1)

    # -------------------------------------------------------- hist exchange

    def exchange_local_hist(
        self,
        bodies,
        res_flat: Optional[jax.Array],
        *,
        nbins: int = 128,
    ) -> tuple:
        """Inside shard_map: the segment-aware Pallas passes
        (:mod:`repro.kernels.flat`) over this device's local flat buffer
        — one launch per pass per device, per-(segment, shard) μ±.

        Exactly k survivors per (leaf, shard) segment (histogram
        thresholds, like ``ops.sbc_compress_hist``); the exchange is a ``pmean`` of the
        binarized ΔW* over the client axes (no packed positions stream —
        that needs the exact engine).  Requires an all-sparse policy.
        """
        if any(s.kind != "sparse" for s in self.segments):
            raise ValueError(
                "exchange_local_hist needs an all-SBC policy; dense/skip "
                "leaves belong to the exact engine"
            )
        with scope("quantize"):
            acc = self.flatten_local(bodies)
            if res_flat is not None:
                acc = res_flat + acc
        # the kernel passes select and binarize in one sequence; its time
        # reads as select
        with scope("select"):
            own, res, _stats = seg_sbc_hist(
                acc,
                bounds=[(s.offset, s.rows * s.n_loc) for s in self.segments],
                ks=[k_for(s.rows * s.n_loc, s.rate) for s in self.segments],
                rates=[s.rate for s in self.segments],
                bm=self.bm,
                lanes=self.lanes,
                nbins=nbins,
            )
        mean = own
        with scope("apply"):
            for ax in self.client_axes:
                mean = jax.lax.pmean(mean, ax)
        new_res = res if res_flat is not None else None
        return mean, own, new_res
