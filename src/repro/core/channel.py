"""One ``CommChannel`` surface over the three compress → exchange →
aggregate → account loops (DESIGN.md §12).

The repo grew three parallel implementations of the paper's communication
round — the vmapped local trainer (:mod:`repro.train.trainer`), the GSPMD
``shard_map`` backend (:mod:`repro.launch.dist`), and the wire-level
federated stack (:mod:`repro.fed`) — each with its own fast-path dispatch
ladder, residual-state shape, and bit accounting.  This module extracts
that loop behind one protocol so a single declarative
:class:`~repro.run.RunSpec` can drive any backend:

  :class:`LocalVmapChannel`    per-client compression as a leading vmap
                               axis; exchange = mean over clients (the
                               CPU-scale paper reproduction).
  :class:`ShardedGspmdChannel` per-shard compression inside ``shard_map``;
                               exchange = packed (positions, μ)
                               all-gather / pmean over the client mesh
                               axes (§4/§11).
  :class:`FedWireChannel`      real packed SBW1 bytes both directions
                               through a parameter server (§9).

Every channel owns

  ``init_state``      allocate the per-client compressor state (residual,
                      RNG, step) in this backend's native layout — flat
                      §10/§11 buffers when the fast path is active,
                      per-leaf pytrees otherwise (the dispatch ladder that
                      used to be copy-pasted per backend lives HERE);
  ``round_exchange``  one round's compress + exchange + aggregate;
  ``bits``            the static Eq. 1/Eq. 5 analytic accounting;
  ``ledger``          a :class:`~repro.core.ledger.BandwidthLedger` of
                      measured-vs-analytic traffic, uniform across
                      backends for the first time.

All three dispatch the §10/§11 flat fast paths and the per-leaf exact path
behind this one surface, bit-identical to the pre-channel code (the parity
matrix in ``tests/test_channel_parity.py`` holds them to that).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import (
    Any,
    Dict,
    NamedTuple,
    Optional,
    Protocol,
    Sequence,
    Tuple,
    Union,
    runtime_checkable,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import Compressor
from repro.core.golomb import encode_positions, expected_position_bits
from repro.core.ledger import BandwidthLedger, RoundRecord
from repro.obs import NULL_TELEMETRY
from repro.obs.scopes import scope
from repro.core.policy import CompressionPolicy, CompressorState, ResolvedPolicy
from repro.core.select import two_sided_topk, uses_threshold
from repro.core.wire import Wire, wire_for

def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


PyTree = Any


class ChannelBits(NamedTuple):
    """Static analytic wire accounting for one round (Eq. 1 terms)."""

    per_client: float  # upstream bits one client sends per round
    dense: float  # the 32-bit dense equivalent


@runtime_checkable
class CommChannel(Protocol):
    """The backend-agnostic compress→exchange→aggregate→account surface.

    Implementations differ in *where* the exchange runs (vmap mean /
    mesh collective / real bytes), but all expose the same four members,
    which is what :func:`repro.run.build_run` programs against.
    """

    ledger: BandwidthLedger

    def init_state(self, params: PyTree, rng: jax.Array) -> Any:
        """Allocate this backend's per-client compressor state."""
        ...

    def round_exchange(self, *args: Any, **kw: Any) -> Any:
        """One communication round's compress + exchange + aggregate."""
        ...

    def bits(self, *args: Any, **kw: Any) -> ChannelBits:
        """Static Eq. 1/Eq. 5 analytic accounting for one round."""
        ...


# ------------------------------------------------------- policy resolution

# bounded: policies holding fresh closures (e.g. per-call dgc_policy
# schedules) hash by identity, so unbounded growth would pin every
# ResolvedPolicy (and its flat spaces / jit caches) for process lifetime
_RESOLVE_CACHE: Dict[Any, ResolvedPolicy] = {}
_RESOLVE_CACHE_MAX = 64


def _layout_key(params: PyTree) -> Optional[tuple]:
    try:
        flat, treedef = jax.tree.flatten(params)
        return (
            treedef,
            tuple(
                (tuple(getattr(x, "shape", np.shape(x))),
                 str(getattr(x, "dtype", type(x))))
                for x in flat
            ),
        )
    except TypeError:
        return None


def resolve_cached(policy: CompressionPolicy, params: PyTree) -> ResolvedPolicy:
    """Resolve ``policy`` against ``params``' layout ONCE per topology.

    The federated server/pool used to re-resolve the up/down policies on
    every rebuild (``ParameterServer.__post_init__`` on profile changes);
    sharing the bound :class:`ResolvedPolicy` here also shares its flat
    spaces and jit caches across server, pool, and ledger metering.
    """
    layout = _layout_key(params)
    try:
        key = (policy, layout) if layout is not None else None
        hash(key)
    except TypeError:
        key = None
    if key is None:
        return policy.resolve(params)
    got = _RESOLVE_CACHE.get(key)
    if got is None:
        got = policy.resolve(params)
        while len(_RESOLVE_CACHE) >= _RESOLVE_CACHE_MAX:  # FIFO eviction
            _RESOLVE_CACHE.pop(next(iter(_RESOLVE_CACHE)))
        _RESOLVE_CACHE[key] = got
    return got


def analytic_bits(resolved: ResolvedPolicy, leaves: Sequence,
                  rates: Sequence[float]) -> ChannelBits:
    """Static Eq. 1 accounting for ONE client's upload at ``rates``:
    per sparse leaf ``position_bits(n, k, p) + value_bits(k)``, dense
    leaves pay the quantizer's value bits for the full leaf, skipped
    leaves nothing — the one pricing walk every channel shares."""
    from repro.core.stages import k_for

    per_client = dense = 0.0
    for plan, leaf, p in zip(resolved.plans, leaves, rates):
        n = int(np.prod(getattr(leaf, "shape", np.shape(leaf))) or 1)
        dense += 32.0 * n
        codec = plan.codec
        if codec.skip:
            continue
        if codec.selector.dense:
            per_client += float(codec.quantizer.value_bits(n))
            continue
        k = k_for(n, p)
        per_client += float(
            codec.encoder.position_bits(n, k, p) + codec.quantizer.value_bits(k)
        )
    return ChannelBits(per_client=per_client, dense=dense)


def threshold_share(resolved: ResolvedPolicy, leaves: Sequence) -> float:
    """Share of the sparse leaves' elements whose two-sided top-k takes
    the threshold path of :mod:`repro.core.select` (the rest keep
    ``lax.top_k``); 0 when no leaf is sparse."""
    sparse = routed = 0
    for plan, leaf in zip(resolved.plans, leaves):
        codec = plan.codec
        if codec.skip or codec.selector.dense:
            continue
        n = int(np.prod(getattr(leaf, "shape", np.shape(leaf))) or 1)
        sparse += n
        if codec.selector.name == "topk_signed" and uses_threshold(n):
            routed += n
    return routed / sparse if sparse else 0.0


# ============================================================ local backend


class LocalExchange(NamedTuple):
    """One vmapped round's exchange outputs (all traced)."""

    mean_delta: PyTree  # ΔW = mean_i ΔW*_i (Alg. 1 l.17)
    transmitted: PyTree  # per-client dense ΔW*_i (leading C axis)
    state: CompressorState  # advanced per-client compressor state
    bits_per_client: jax.Array  # analytic Eq. 1 bits, mean over clients
    compressed0: Optional[PyTree]  # client 0's LeafCompressed tree, or None


@dataclasses.dataclass(eq=False)  # id-hash → usable under jit-static closure
class LocalVmapChannel:
    """Per-client compression along a leading vmap axis; the exchange is a
    mean over that axis — extracted from ``DSGDTrainer.round_step``
    (Alg. 1 l.11-17), bit-identical to the pre-channel trainer."""

    compressor: Compressor
    n_clients: int
    residual_dtype: Any = jnp.float32

    def __post_init__(self) -> None:
        self.ledger = BandwidthLedger()
        self.telemetry = NULL_TELEMETRY  # build_run swaps in an enabled one
        self._resolved: Optional[ResolvedPolicy] = None
        self._wires: Dict[tuple, Wire] = {}

    # ------------------------------------------------------------- protocol

    def resolved(self, params: PyTree) -> ResolvedPolicy:
        if self._resolved is None:
            self._resolved = resolve_cached(self.compressor.policy, params)
            self.telemetry.metrics.gauge(
                "select/threshold_share",
                threshold_share(self._resolved, self._resolved._leaves_of(params)))
        return self._resolved

    def init_state(self, params: PyTree, rng: jax.Array) -> CompressorState:
        """Per-client state with a leading C axis; the residual rides the
        §10 flat layout when the policy's fast path is active."""
        comp = self.compressor.init_state(
            jax.tree.map(lambda x: x.astype(self.residual_dtype), params)
        )
        stack = lambda tree: jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.n_clients,) + x.shape).copy(), tree
        )
        return CompressorState(
            residual=stack(comp.residual),
            rng=jax.random.split(rng, self.n_clients),
            step=jnp.zeros((self.n_clients,), jnp.int32),
        )

    def round_exchange(
        self,
        deltas: PyTree,  # per-client ΔW_i, leading C axis (traced)
        state: CompressorState,
        rates: Union[float, Tuple[float, ...]],
        *,
        return_compressed: bool = False,
    ) -> LocalExchange:
        """Compress every client's update with error feedback and average
        (traced; called inside the trainer's jitted round)."""

        def compress_one(delta, comp_state):
            # the selector's own scope sits inside (Codec.compress_leaf)
            with scope("quantize"):
                ctree, dense, new_state = self.compressor.compress(
                    delta, comp_state, rates
                )
                bits = self.compressor.total_bits(ctree)
            return ctree, dense, new_state, bits

        ctrees, dense, new_state, bits = jax.vmap(compress_one)(deltas, state)
        with scope("apply"):
            mean_delta = jax.tree.map(lambda d: jnp.mean(d, axis=0), dense)
            comp0 = (
                jax.tree.map(lambda x: x[0], ctrees) if return_compressed else None
            )
            return LocalExchange(
                mean_delta=mean_delta,
                transmitted=dense,
                state=new_state,
                bits_per_client=jnp.mean(bits),
                compressed0=comp0,
            )

    def bits(self, params: PyTree, rates: Tuple[float, ...],
             n_delay: int = 1) -> ChannelBits:
        """Static Eq. 1 accounting at ``rates`` (host-side floats)."""
        resolved = self.resolved(params)
        b = analytic_bits(resolved, resolved._leaves_of(params), rates)
        return ChannelBits(per_client=b.per_client, dense=b.dense * n_delay)

    # ------------------------------------------------------------ metering

    def wire(self, params: PyTree, rate: float, round_idx: int) -> Wire:
        resolved = self.resolved(params)
        key = resolved.rates(rate, round_idx)
        if key not in self._wires:
            self._wires[key] = wire_for(resolved, params, rate, round_idx)
        return self._wires[key]

    def record_round(
        self,
        round_idx: int,
        *,
        params: PyTree,
        compressed0: PyTree,
        rate: float,
        bits_analytic_per_client: float,
        device_pack: bool = False,
    ) -> float:
        """Meter client 0's real packed upload and extrapolate ×C into the
        ledger (every client's analytic size is identical; measured sizes
        are one geometric draw each).  Returns client 0's measured bits.

        With ``device_pack`` the Golomb position streams are produced by
        the fused select→pack Pallas kernel (byte-identical to the host
        encoder — held by tests/test_channel_parity.py)."""
        # one blocking copy per array, in the order the pack reads them
        arrays = jax.tree.leaves(compressed0)
        with self.telemetry.span("fetch", round=round_idx, arrays=len(arrays),
                                 bytes=sum(x.nbytes for x in arrays)):
            host0 = jax.tree.map(np.asarray, compressed0)
        with self.telemetry.span("encode", round=round_idx, client=0):
            w = self.wire(params, rate, round_idx)
            blob, bits = w.pack_with_bits(host0, device_pack=device_pack)
        measured = float(bits)
        up_bytes = len(blob) * self.n_clients
        self.ledger.record_up(
            round_idx,
            clients=tuple(range(self.n_clients)),
            up_bytes=up_bytes,
            up_bits_measured=measured * self.n_clients,
            up_bits_analytic=float(bits_analytic_per_client) * self.n_clients,
        )
        return measured


# ============================================================ gspmd backend


def _sbc_local(acc_flat: jax.Array, p: float, client_axes, n_clients: int,
               out_dtype=jnp.float32):
    """Inside shard_map: exact per-shard SBC (paper Alg. 2) + sparse exchange.

    acc_flat: (L, n_loc) — residual-accumulated ΔW, THIS device's shard
    (any float dtype; per-layer math runs in f32).
    Returns (mean_delta (L, n_loc), own_delta_star (L, n_loc)) in out_dtype.

    Layers are processed through a lax.scan so only ONE layer's f32
    working set is live at a time (§Perf lowmem iteration — the vmap
    formulation materialized 3 full-leaf f32 buffers).
    """
    L, n_loc = acc_flat.shape
    k = max(1, min(n_loc, int(round(p * n_loc))))

    def one_layer(_, x_row):
        with scope("select"):
            x = x_row.astype(jnp.float32)
            (val_pos, idx_pos), (val_neg, idx_neg) = two_sided_topk(x, k)
            mu_pos, mu_neg = jnp.mean(val_pos), jnp.mean(val_neg)
            pos_wins = mu_pos > mu_neg
            idx = jnp.where(pos_wins, idx_pos, idx_neg).astype(jnp.int32)
        with scope("quantize"):
            mu = jnp.where(pos_wins, mu_pos, -mu_neg).astype(jnp.float32)
            own_row = jnp.zeros((n_loc,), out_dtype).at[idx].set(mu.astype(out_dtype))
        return None, (idx, mu, own_row)

    with scope("select"):  # the scan over layers
        _, (idx, mu, own) = jax.lax.scan(one_layer, None, acc_flat)

    if client_axes and n_clients > 1:
        # THE exchange: tiny (idx, μ) tensors cross the client axes.
        gidx, gmu = idx, mu
        with scope("all_gather"):
            for ax in client_axes:
                gidx = jax.lax.all_gather(gidx, ax)
                gmu = jax.lax.all_gather(gmu, ax)
        with scope("decode"):
            gidx = gidx.reshape(n_clients, L, k)
            gmu = gmu.reshape(n_clients, L)

            def dense_layer(_, args):
                rows_i, mus_i = args  # (C, k), (C,)
                row = jnp.zeros((n_loc,), jnp.float32)

                def add(acc, ci):
                    return acc.at[rows_i[ci]].add(mus_i[ci] / n_clients), None

                row, _ = jax.lax.scan(add, row, jnp.arange(n_clients))
                return None, row.astype(out_dtype)

            _, dense = jax.lax.scan(
                dense_layer, None, (gidx.transpose(1, 0, 2), gmu.transpose(1, 0))
            )
    else:
        dense = own
    return dense, own


def _dense_local(acc_flat, client_axes, n_clients):
    """Dense baseline: pmean over clients == all-reduce of the full ΔW."""
    out = acc_flat
    with scope("apply"):
        for ax in client_axes:
            out = jax.lax.pmean(out, ax)
    return out, acc_flat


class GspmdLeaf(NamedTuple):
    """One leaf's static plan in the GSPMD channel (mesh-free data — the
    launch layer derives it from the mesh + PartitionSpecs)."""

    path: str
    global_shape: Tuple[int, ...]
    dtype: Any
    scanned: bool  # leading scan/stack superblock dim
    mode: str  # "sparse" | "dense" | "skip"
    rate: float  # static per-leaf sparsity rate
    n_shards: int  # distinct shards of the global leaf
    shard_grid: Tuple[int, ...]  # per-dim shard counts (for host metering)


def _iter_shard_blocks(arr: np.ndarray, grid: Tuple[int, ...]):
    """Yield the GSPMD equal-block shards of a global array, in grid order."""
    grid = tuple(grid) + (1,) * (arr.ndim - len(grid))
    sizes = [d // g for d, g in zip(arr.shape, grid)]
    for idx in itertools.product(*[range(g) for g in grid]):
        yield arr[tuple(slice(i * s, (i + 1) * s) for i, s in zip(idx, sizes))]


@dataclasses.dataclass(eq=False)
class ShardedGspmdChannel:
    """Per-shard compression inside ``shard_map``; the exchange crosses the
    client mesh axes as packed (positions, μ) all-gathers (sparse), pmean
    all-reduces (dense), or nothing (skip) — extracted from
    ``repro.launch.dist.make_dist_train``'s exchange bodies + bit
    accounting, bit-identical to the pre-channel lowering.

    ``flat_space`` is the §11 :class:`ShardedFlatParamSpace` when the flat
    fast path applies, else None (per-leaf exchange).  The methods named
    ``exchange*`` are shard_map BODIES: the launch layer owns the mesh and
    wraps them with the right in/out specs.
    """

    leaves: Tuple[GspmdLeaf, ...]
    client_axes: Tuple[str, ...]
    n_clients: int
    residual_dtype: Any = jnp.float32
    flat_space: Any = None  # ShardedFlatParamSpace | None
    flat_engine: str = "exact"  # "exact" | "hist"
    device_pack: bool = False  # pack Golomb wire streams on-device (§11)

    def __post_init__(self) -> None:
        if self.flat_engine not in ("exact", "hist"):
            raise ValueError(f"unknown flat_engine {self.flat_engine!r}")
        if self.flat_engine == "hist" and self.flat_space is None:
            raise ValueError(
                "flat_engine='hist' needs the sharded flat fast path "
                "(fast=True with all-f32 leaves and an f32 residual_dtype)"
            )
        if self.device_pack and (
            self.flat_space is None or self.flat_engine != "exact"
        ):
            raise ValueError(
                "device_pack needs the sharded flat fast path with the "
                "exact engine (fast=True, flat_engine='exact', all-f32 "
                "leaves) — the hist engine and the per-leaf exchange have "
                "no packed position stream to produce on-device"
            )
        self.ledger = BandwidthLedger()
        self.telemetry = NULL_TELEMETRY  # build_run swaps in an enabled one

    # ------------------------------------------------------------- protocol

    def init_state(self, params: PyTree, rng: jax.Array = None) -> PyTree:
        """The per-client error-feedback residual in this channel's native
        layout: ONE flat sharded f32 buffer on the fast path (§11), a
        stacked per-leaf pytree otherwise."""
        if self.flat_space is not None:
            return self.flat_space.zeros_residual()
        return jax.tree.map(
            lambda x: jnp.zeros((self.n_clients,) + x.shape, self.residual_dtype),
            params,
        )

    def round_exchange(self, residual: PyTree, deltas: PyTree,
                       *, mesh, in_specs, res_spec, need_own: bool) -> tuple:
        """One round's compress + exchange under ``shard_map``.

        ``deltas`` is the per-client ΔW tree (leading client axis) and
        ``residual`` this channel's state from :meth:`init_state`; returns
        ``(mean_tree, new_residual, own_tree_or_None)``.  ``need_own``
        materializes each client's ΔW*_i (momentum masking / metering).
        """
        delta_leaves, treedef = jax.tree.flatten(deltas)
        own_specs = (
            tuple(in_specs) if need_own else tuple(type(s)() for s in in_specs)
        )
        packed = None
        if self.device_pack:
            # extra outputs: this round's upload of EVERY (client, shard)
            # — device-packed Golomb word buffers, exact per-row bit
            # counts and per-row μ — same layout/sharding as the flat
            # residual
            mean_leaves, new_residual, own_leaves, packed = shard_map(
                lambda res, *leaves: self.exchange_flat(res, leaves, need_own),
                mesh=mesh, in_specs=(res_spec,) + tuple(in_specs),
                out_specs=(tuple(in_specs), res_spec, own_specs,
                           (res_spec, res_spec, res_spec)),
            )(residual, *delta_leaves)
        elif self.flat_space is not None:
            mean_leaves, new_residual, own_leaves = shard_map(
                lambda res, *leaves: self.exchange_flat(res, leaves, need_own),
                mesh=mesh, in_specs=(res_spec,) + tuple(in_specs),
                out_specs=(tuple(in_specs), res_spec, own_specs),
            )(residual, *delta_leaves)
        else:
            # residual add (Alg. 1 l.10): acc = R + ΔW
            with scope("quantize"):
                acc = jax.tree.map(
                    lambda r, d: (r.astype(jnp.float32) + d.astype(jnp.float32)).astype(
                        self.residual_dtype
                    ),
                    residual,
                    deltas,
                )
            acc_leaves = jax.tree.leaves(acc)
            mean_leaves, res_leaves, own_leaves = shard_map(
                lambda *leaves: self.exchange_per_leaf(leaves, need_own),
                mesh=mesh, in_specs=tuple(in_specs),
                out_specs=(tuple(in_specs), tuple(in_specs), own_specs),
            )(*acc_leaves)
            new_residual = jax.tree.unflatten(treedef, res_leaves)
        mean_tree = jax.tree.unflatten(treedef, mean_leaves)
        own_tree = (
            jax.tree.unflatten(treedef, own_leaves) if need_own else None
        )
        if self.device_pack:
            return mean_tree, new_residual, own_tree, packed
        return mean_tree, new_residual, own_tree

    # -------------------------------------------------- shard_map bodies

    def exchange_per_leaf(self, leaves: Sequence[jax.Array],
                          need_own: bool) -> tuple:
        """Per-leaf body: compress own shard with the LEAF'S codec, exchange,
        and emit (mean ΔW, NEW residual = acc − own) — own itself never
        leaves the shard_map unless the caller needs it (§Perf B9)."""
        means, residuals, owns = [], [], []
        for leaf, gl in zip(leaves, self.leaves):
            body = leaf[0]  # client dim is locally 1 (sharded over clients)
            L = body.shape[0] if gl.scanned and body.ndim > 1 else 1
            flat = body.reshape(L, -1)
            if gl.mode == "sparse":
                dense, own = _sbc_local(flat, gl.rate, self.client_axes,
                                        self.n_clients, out_dtype=leaf.dtype)
            elif gl.mode == "dense":
                dense, own = _dense_local(flat.astype(jnp.float32),
                                          self.client_axes, self.n_clients)
            else:  # skip: no traffic; the residual keeps the full update
                dense = jnp.zeros_like(flat, dtype=leaf.dtype)
                own = dense
            with scope("quantize"):
                new_res = (flat.astype(jnp.float32) - own.astype(jnp.float32)).astype(
                    self.residual_dtype
                )
            with scope("apply"):
                means.append(dense.reshape(body.shape).astype(leaf.dtype)[None])
            with scope("quantize"):
                residuals.append(new_res.reshape(body.shape).astype(leaf.dtype)[None])
                owns.append(own.reshape(body.shape).astype(leaf.dtype)[None]
                            if need_own else jnp.zeros((1,) * leaf.ndim, leaf.dtype))
        return tuple(means), tuple(residuals), tuple(owns)

    def exchange_flat(self, res: jax.Array, leaves: Sequence[jax.Array],
                      need_own: bool) -> tuple:
        """§11 flat body: residual add + compression + the packed
        (positions, μ) collective all run on ONE flat buffer per device,
        one launch per pass."""
        space = self.flat_space
        bodies = [leaf[0] for leaf in leaves]
        packed = None
        if self.device_pack:
            mean_f, own_f, new_res_f, words, nbits, mu = space.exchange_local(
                bodies, res[0, 0], device_pack=True
            )
            packed = (words[None, None], nbits[None, None], mu[None, None])
        else:
            fn = (space.exchange_local if self.flat_engine == "exact"
                  else space.exchange_local_hist)
            mean_f, own_f, new_res_f = fn(bodies, res[0, 0])
        with scope("apply"):
            means = tuple(
                m.astype(leaf.dtype)[None] for m, leaf in
                zip(space.unflatten_local(mean_f), leaves)
            )
        with scope("quantize"):
            if need_own:
                owns = tuple(
                    o.astype(leaf.dtype)[None] for o, leaf in
                    zip(space.unflatten_local(own_f), leaves)
                )
            else:
                owns = tuple(
                    jnp.zeros((1,) * leaf.ndim, leaf.dtype) for leaf in leaves
                )
        if self.device_pack:
            return means, new_res_f[None, None], owns, packed
        return means, new_res_f[None, None], owns

    # ------------------------------------------------------- bit accounting

    def bits(self) -> ChannelBits:
        """Static Eq. 1 bits per round per client: per sparse leaf
        ``L·S_shards·(k_loc·b̄_pos(p_leaf) + 32)``, dense 32 bits/entry,
        skip 0 — summed from the §11 per-(segment, shard) table when the
        fast path is active (same totals)."""
        per_client = dense = 0.0
        for gl in self.leaves:
            size = int(np.prod(gl.global_shape) or 1)
            L = gl.global_shape[0] if gl.scanned and len(gl.global_shape) > 1 else 1
            n_loc = max(1, size // (L * gl.n_shards))
            if gl.mode == "sparse":
                k_loc = max(1, min(n_loc, int(round(gl.rate * n_loc))))
                per_client += L * gl.n_shards * (
                    k_loc * expected_position_bits(gl.rate) + 32.0
                )
            elif gl.mode == "dense":
                per_client += 32.0 * size
            dense += 32.0 * size
        if self.flat_space is not None:
            # same totals, summed from the per-(segment, shard) table (§11)
            per_client = self.flat_space.bits_per_client()
        return ChannelBits(per_client=per_client, dense=dense)

    # ------------------------------------------------------------ metering

    def measured_bits(self, own_tree: PyTree) -> float:
        """Real wire bits of ONE client's transmitted update: per
        (leaf, shard, row), Golomb-encode the ACTUAL surviving positions
        (paper Alg. 3's bitstream, one geometric draw vs Eq. 5) plus one
        32-bit μ; dense leaves pay 32 bits/entry, skip leaves nothing.
        Host-side numpy over the client's dense ΔW*."""
        total = 0.0
        for gl, leaf in zip(self.leaves, jax.tree.leaves(own_tree)):
            arr = np.asarray(leaf)
            if gl.mode == "dense":
                total += 32.0 * arr.size
                continue
            if gl.mode == "skip":
                continue
            for block in _iter_shard_blocks(arr, gl.shard_grid):
                L = block.shape[0] if gl.scanned and block.ndim > 1 else 1
                for row in block.reshape(L, -1):
                    pos = np.flatnonzero(row)
                    total += float(encode_positions(pos, gl.rate).size) + 32.0
        return total

    def measured_bits_per_client(self, packed_nbits) -> list:
        """Real wire bits of EVERY client's upload, from the device-packed
        streams' exact bit counts.

        ``packed_nbits`` is the second ``round_exchange`` packed output:
        i32[n_clients, shards_per_client, n_mu] per-(client, shard, row)
        Golomb position bits.  Each client pays its own position streams
        + one 32-bit μ per (shard, row) + 32 bits/entry for dense leaves
        — no host re-encode, no client-0 sampling.  Unlike the sampled
        :meth:`measured_bits` (which infers positions from the nonzeros
        of the reconstructed ΔW*), these counts meter the stream as
        transmitted, including positions whose μ is exactly zero.
        """
        nb = np.asarray(jax.device_get(packed_nbits))
        dense = sum(
            32.0 * int(np.prod(gl.global_shape) or 1)
            for gl in self.leaves if gl.mode == "dense"
        )
        # The S axis is DEVICES per client, not distinct shards: a segment
        # replicated over a shard axis (n_shards < S) is packed identically
        # on every replica, so weight each μ-row by n_shards/S to count
        # every distinct stream exactly once (matching the sampled host
        # path, which iterates shard_grid blocks).
        S = nb.shape[1]
        sparse = self.flat_space._sparse
        row_w = (
            np.concatenate(
                [np.full((s.rows,), s.n_shards / S) for s in sparse]
            )
            if sparse else np.zeros((0,))
        )
        pos_bits = (nb.astype(np.float64) * row_w[None, None, :]).sum(axis=(1, 2))
        mu_bits = 32.0 * float(row_w.sum()) * S  # one μ per distinct (shard, row)
        return [float(pos_bits[c]) + mu_bits + dense for c in range(nb.shape[0])]

    def record_round(
        self,
        round_idx: int,
        *,
        own_client0: PyTree = None,
        packed_nbits=None,
    ) -> float:
        """Meter the round's uploads into the ledger; returns bits/client.

        With ``packed_nbits`` (device_pack active): EVERY client's real
        packed stream is metered from the device-side bit counts — the
        ledger row is a true cohort sum and the return value the cohort
        mean.  Without it, CLIENT 0's upload is host-encoded and
        extrapolated ×C (one geometric draw, explicitly a sample — see
        docs/wire-format.md).
        """
        analytic = self.bits().per_client
        if packed_nbits is not None:
            with self.telemetry.span("encode", round=round_idx):
                per_client = self.measured_bits_per_client(packed_nbits)
            for ci, b in enumerate(per_client):
                self.telemetry.metrics.gauge(
                    "wire/client_bits_measured", b,
                    round=round_idx, client=ci,
                )
            total = float(sum(per_client))
            self.ledger.record_up(
                round_idx,
                clients=tuple(range(self.n_clients)),
                up_bytes=sum(int(-(-b // 8)) for b in per_client),
                up_bits_measured=total,
                up_bits_analytic=analytic * self.n_clients,
            )
            return total / self.n_clients
        with self.telemetry.span("encode", round=round_idx, client=0):
            measured = self.measured_bits(own_client0)
        self.telemetry.metrics.gauge(
            "wire/own_client0_bits_measured", measured,
            round=round_idx, client=0,
        )
        self.ledger.record_up(
            round_idx,
            clients=tuple(range(self.n_clients)),
            up_bytes=int(-(-measured // 8)) * self.n_clients,
            up_bits_measured=measured * self.n_clients,
            up_bits_analytic=analytic * self.n_clients,
        )
        return measured


# ============================================================== fed backend


@dataclasses.dataclass(eq=False)
class FedWireChannel:
    """Wire-level channel: real packed SBW1 buffers cross in BOTH
    directions through a :class:`~repro.fed.server.ParameterServer`, with
    a cohort of :class:`~repro.fed.clients.ClientPool` members on the
    other end — extracted from ``RoundScheduler.step`` (DESIGN.md §9).

    The server and pool share ONE cached :class:`ResolvedPolicy` per
    (policy, topology) via :func:`resolve_cached`, so profile changes or
    server rebuilds no longer re-resolve the up/down policies, and the
    per-round rate tuples of schedule-free policies are memoized
    (``ResolvedPolicy.rates``).
    """

    server: Any  # repro.fed.server.ParameterServer
    pool: Any  # repro.fed.clients.ClientPool

    def __post_init__(self) -> None:
        self.ledger = BandwidthLedger()
        self.telemetry = NULL_TELEMETRY  # build_run swaps in an enabled one
        # DeltaLog-backed downstream (server.delta_horizon set): per-client
        # last-synced round + one CatchupPlanner over the server's log
        self._last_sync: Dict[int, int] = {}
        self._planner: Any = None
        # a mid-round kill (ServerKilled at post_aggregate) parks the
        # aggregated-but-unbroadcast round here; checkpointable, finished
        # by _finish_round on resume
        self._pending: Optional[dict] = None

    # ------------------------------------------------------------- protocol

    def init_state(self, params: Optional[PyTree] = None,
                   rng: Optional[jax.Array] = None) -> None:
        """Allocate the pool's per-client state from the server replica."""
        self.pool.init(params if params is not None else self.server.estimate,
                       rng)

    def round_exchange(
        self,
        round_idx: int,
        cohort: Sequence[int],
        start_params: PyTree,
        staleness: Optional[np.ndarray] = None,
        faults: Any = None,
        straggler_timeout: Optional[float] = None,
        kill_step: Optional[str] = None,
    ) -> dict:
        """One federated round: run the cohort, pack real uploads, decode +
        aggregate server-side, compress the broadcast, meter both
        directions into the ledger.

        Elasticity (DESIGN.md §14): ``faults`` is a
        :class:`~repro.fed.faults.FaultSchedule` whose slow/corrupt entries
        apply to this round; ``straggler_timeout`` aborts uploads whose
        simulated duration ``profile.delay × slowdown`` exceeds it.  A
        failed participation (straggler abort or decode-rejected corrupt
        upload) rolls the member's pool state back to its pre-round
        snapshot and meters the spent bytes as ``up_bytes_wasted``; the
        ``up_*`` columns cover ACCEPTED uploads only, so partial
        aggregation reconciles like a survivors-only round.
        ``kill_step="post_aggregate"`` raises
        :class:`~repro.fed.faults.ServerKilled` after aggregation with the
        unfinished round parked in ``self._pending`` (resumed via
        :meth:`_finish_round`)."""
        from repro.fed.faults import NO_FAULTS, ServerKilled, straggler_ids
        from repro.fed.server import ClientUpdate

        fsched = faults if faults is not None else NO_FAULTS
        if staleness is None:
            staleness = np.zeros((len(cohort),), np.int64)

        log = getattr(self.server, "delta_log", None)
        catchup = None
        if log is not None:
            # the broadcast rides the DeltaLog: each cohort member PULLS
            # the cheapest catch-up (replay / stacked / full) from its
            # last-synced round up to the current head before training —
            # one plan/encode per distinct lag class, bytes shared within
            # the class — instead of paying a fresh per-member broadcast
            from repro.serve.broadcast import CatchupPlanner

            if self._planner is None or self._planner.log is not log:
                self._planner = CatchupPlanner(log, telemetry=self.telemetry)
            plans: Dict[int, Any] = {}
            down_bytes = 0
            down_m = down_a = 0.0
            for cid in cohort:
                frm = self._last_sync.get(int(cid), -1)
                plan = plans.get(frm)
                if plan is None:
                    plan = plans[frm] = self._planner.plan(frm)
                down_bytes += plan.nbytes
                down_m += plan.bits_measured
                down_a += plan.bits_analytic
                self._last_sync[int(cid)] = log.head
            catchup = (down_bytes, down_m, down_a)

        # at-risk members (stragglers to abort, uploads to corrupt) get a
        # pre-round snapshot: a failed participation must leave residual/
        # momentum/rng bit-identical to never having run
        delays = {int(c): self.pool.profile_of(int(c)).delay for c in cohort}
        stragglers = straggler_ids(
            fsched, round_idx, cohort, delays, straggler_timeout
        )
        corrupts = fsched.corrupts_at(round_idx) & {int(c) for c in cohort}
        at_risk = sorted(stragglers | corrupts)
        snap = self.pool.snapshot_clients(at_risk) if at_risk else None

        tel = self.telemetry
        tel.metrics.gauge("fed/cohort_size", len(cohort), round=round_idx)
        with tel.span("select_quantize", round=round_idx, cohort=len(cohort)):
            result = self.pool.run_cohort(round_idx, cohort, start_params)
            tel.fence(result.losses if hasattr(result, "losses") else None)

        uploads, blob_len, wasted = [], {}, 0
        with tel.span("encode", round=round_idx, cohort=len(cohort)):
            for i, cid in enumerate(result.client_ids):
                wire = self.server.up_wire(result.rates[i], round_idx)
                blob = wire.pack(result.ctrees[i])
                if int(cid) in stragglers:
                    # timed out mid-upload: the work and bytes are spent,
                    # but the server never sees them
                    wasted += len(blob)
                    continue
                if int(cid) in corrupts:
                    blob = fsched.corrupt_blob(blob, round_idx, int(cid))
                blob_len[int(cid)] = len(blob)
                uploads.append(
                    ClientUpdate(
                        client_id=cid, blob=blob, rate=result.rates[i],
                        weight=result.weights[i], staleness=int(staleness[i]),
                    )
                )
        info = self.server.receive(uploads, round_idx)
        accepted = [int(c) for c in info["accepted"]]
        rejected = [int(c) for c in info["rejected"]]
        up_bytes = sum(blob_len[c] for c in accepted)
        wasted += sum(blob_len[c] for c in rejected)
        failed = sorted(stragglers | set(rejected))
        if snap is not None and failed:
            self.pool.restore_clients(snap, only=failed)
        acc_set = set(accepted)
        acc_pos = [
            i for i, c in enumerate(result.client_ids) if int(c) in acc_set
        ]
        pending = {
            "round_idx": int(round_idx),
            "cohort": [int(c) for c in cohort],
            "accepted": accepted,
            "rejected": rejected,
            "stragglers": sorted(stragglers),
            "up_bytes": int(up_bytes),
            "up_bytes_wasted": int(wasted),
            "up_bits_measured": float(info["up_bits_measured"]),
            "up_bits_analytic": float(
                np.sum(np.asarray(result.bits_analytic)[acc_pos])
            ) if acc_pos else 0.0,
            "loss": float(
                np.mean(np.asarray(result.losses)[acc_pos])
            ) if acc_pos else float("nan"),
            "update_norm": float(info["update_norm"]),
            "weights": [float(w) for w in info["weights"]],
            "staleness": [int(s) for s in staleness],
            "catchup": catchup,
        }
        if kill_step == "post_aggregate":
            self._pending = pending
            raise ServerKilled(round_idx, "post_aggregate")
        return self._finish_round(pending)

    def _finish_round(self, pending: dict) -> dict:
        """Broadcast + ledger entry for an aggregated round — the second
        half of :meth:`round_exchange`, callable on its own to resume a
        round interrupted by a ``post_aggregate`` server kill."""
        self._pending = None
        round_idx = pending["round_idx"]
        bc = self.server.broadcast(round_idx)
        recipients = len(pending["cohort"])
        if pending["catchup"] is None:
            down_bytes = len(bc.blob) * recipients
            down_m = bc.bits_measured * recipients
            down_a = bc.bits_analytic * recipients
        else:
            down_bytes, down_m, down_a = pending["catchup"]
        self.ledger.record(
            RoundRecord(
                round=round_idx,
                cohort=tuple(pending["accepted"]),
                up_bytes=pending["up_bytes"],
                up_bits_measured=pending["up_bits_measured"],
                up_bits_analytic=pending["up_bits_analytic"],
                down_bytes=down_bytes,
                down_bits_measured=down_m,
                down_bits_analytic=down_a,
                down_recipients=recipients,
                up_bytes_wasted=pending["up_bytes_wasted"],
            )
        )
        return {
            "round": round_idx,
            "loss": pending["loss"],
            "update_norm": pending["update_norm"],
            "staleness": pending["staleness"],
            "weights": pending["weights"],
            "up_bytes": pending["up_bytes"],
            "down_bytes": down_bytes,
            "accepted": pending["accepted"],
            "rejected": pending["rejected"],
            "stragglers": pending["stragglers"],
            "up_bytes_wasted": pending["up_bytes_wasted"],
        }

    def bits(self, rate: Optional[float] = None,
             round_idx: int = 0) -> ChannelBits:
        """Analytic Eq. 1 upstream bits for ONE client at ``rate`` (default:
        the pool's first profile) against the dense 32-bit equivalent."""
        params = self.server.params
        resolved = self.server._up_resolved
        if rate is None:
            rate = self.pool.profiles[0].sparsity
        return analytic_bits(
            resolved, resolved._leaves_of(params),
            resolved.rates(rate, round_idx),
        )
