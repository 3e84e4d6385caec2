"""GSPMD distributed DSGD: sharded train_step / serve_step builders.

Design (DESIGN.md §4):

* **Clients.**  ``cfg.client_mode``:
    - 'data': one client per data coordinate (×pod) — per-client ΔW never
      crosses the data axis; the ONLY cross-client traffic is the sparse
      exchange.  Small/mid archs (params replicated over 'data').
    - 'pod':  one client per pod; grads all-reduce densely *inside* a pod
      (fast ICI), SBC compresses the cross-pod exchange (slow DCN).  ≥20B
      archs (params FSDP-sharded over 'data').

* **Shard-wise compression** (the TPU-native re-think of paper Alg. 2):
  compression runs inside ``shard_map`` — every device applies exact
  top-k + binarization to ITS OWN shard of ΔW, so the paper's O(n log n)
  global sort becomes an embarrassingly-local per-shard top-k, and the μ±
  means are per-(tensor, shard) instead of per-tensor (finer granularity,
  same wire format: one 32-bit scalar per shard).  The exchange is an
  explicit ``jax.lax.all_gather`` of (idx[k] int32, μ f32) over the client
  axes — the ×p bandwidth saving is therefore visible in the lowered HLO
  collective schedule, not just in a wire-format codec.

* **Dense baseline** (``compressor='none'``): the exchange is a mean over
  the client axis of the full ΔW — lowers to the dense all-reduce that the
  paper's Eq. 1 baseline counts.

* **Per-leaf policies** (DESIGN.md §3): an optional
  :class:`~repro.core.policy.CompressionPolicy` resolves every param leaf
  to one of this backend's exchange kernels (sparse SBC / dense all-reduce
  / skip) with its own sparsity rate, so DGC-style "dense biases + 0.1%
  matrices" recipes lower to a mixed collective schedule.

The compress → exchange → aggregate → account loop itself lives in
:class:`repro.core.channel.ShardedGspmdChannel` (DESIGN.md §12): this
module owns the *mesh* — model/param shardings, client topology, batch
specs — derives the channel's mesh-free per-leaf plan from the
PartitionSpecs, and wraps the channel's shard_map bodies with the right
in/out specs.  ``build_dist_train`` is the canonical builder (what
``repro.run.build_run(RunSpec(backend="gspmd"))`` calls);
``make_dist_train`` survives as a deprecated bit-identical shim.

Bit accounting is static (shapes and per-leaf rates are compile-time): per
sparse leaf, ``L·S_shards·(k_loc·b̄_pos(p_leaf) + 32)`` wire bits per client
per round; dense leaves count 32 bits/entry; skipped leaves count 0
(``channel.bits()``), and ``measure=True`` Golomb-encodes client 0's real
per-shard position streams into the channel ledger next to it.
"""
from __future__ import annotations

import math
import warnings
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.channel import (  # noqa: F401  (re-exported shard_map kernels)
    GspmdLeaf,
    ShardedGspmdChannel,
    _dense_local,
    _sbc_local,
    shard_map,
)
from repro.core.codec import Codec, make_codec
from repro.core.flat import ShardedFlatParamSpace
from repro.core.policy import CompressionPolicy, path_str
from repro.models import hints
from repro.models.model import Model, build_model
from repro.optim.optimizers import get_optimizer

PyTree = Any


# ----------------------------------------------------------- client topology


def client_topology(cfg: ModelConfig, mesh: Mesh) -> tuple[int, tuple[str, ...]]:
    """(n_clients, client mesh axes).  See module docstring."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if cfg.client_mode == "pod":
        return (sizes["pod"], ("pod",)) if "pod" in sizes else (1, ())
    axes = tuple(a for a in ("pod", "data") if a in sizes)
    return math.prod(sizes[a] for a in axes), axes


def _lead_spec(client_axes: tuple[str, ...]):
    if not client_axes:
        return None
    return client_axes[0] if len(client_axes) == 1 else client_axes


# ------------------------------------------------------------- spec plumbing


def stacked_specs(inner_specs: PyTree, client_axes: tuple[str, ...]) -> PyTree:
    """Specs for a (C,)+param-shaped tree (residual / momentum / adam)."""
    lead = _lead_spec(client_axes)
    return jax.tree.map(
        lambda s: P(lead, *s), inner_specs, is_leaf=lambda s: isinstance(s, P)
    )


def opt_state_specs(opt_name: str, param_specs: PyTree, client_axes) -> PyTree:
    inner = stacked_specs(param_specs, client_axes)
    if opt_name == "sgd":
        return ()
    if opt_name == "momentum":
        return inner
    if opt_name == "adam":
        from repro.optim.optimizers import AdamState

        return AdamState(inner, inner)
    raise ValueError(opt_name)


def _axes_of(entry) -> tuple:
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def _shards_of(spec: P, mesh_sizes: dict[str, int]) -> int:
    total = 1
    for entry in spec:
        for ax in _axes_of(entry):
            total *= mesh_sizes.get(ax, 1)
    return total


def _shard_grid(shape, spec: P, mesh_sizes: dict[str, int]) -> tuple[int, ...]:
    """Per-dim shard counts of a leaf under ``spec`` (GSPMD equal blocks)."""
    entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    return tuple(
        math.prod(mesh_sizes.get(a, 1) for a in _axes_of(entry))
        for entry in entries
    )


def _local_shape(shape, spec: P, mesh_sizes: dict[str, int]) -> tuple[int, ...]:
    """One shard's shape of a leaf under ``spec`` (GSPMD equal blocks)."""
    return tuple(
        dim // g for dim, g in zip(shape, _shard_grid(shape, spec, mesh_sizes))
    )


# ------------------------------------------------- sharded flat param space


def _sharded_flat_space(
    cfg: ModelConfig,
    mesh: Mesh,
    flat_p,
    flat_specs,
    scanned,
    modes,
    leaf_rates,
    client_axes: tuple[str, ...],
    n_clients: int,
) -> Optional[ShardedFlatParamSpace]:
    """The §11 sharded flat layout for this (cfg, mesh, policy) — or None
    when the fast path does not apply (non-f32 leaves / non-f32 residual
    fall back to the per-leaf exchange, same rule as PR 3's single-device
    fast path)."""
    if jnp.dtype(cfg.residual_dtype) != jnp.float32:
        return None
    if any(leaf.dtype != jnp.float32 for _, leaf in flat_p):
        return None
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    shard_axes = tuple(a for a in mesh.axis_names if a not in client_axes)
    entries = []
    for (path, leaf), spec, is_scan, mode, p_leaf in zip(
        flat_p, flat_specs, scanned, modes, leaf_rates
    ):
        local = _local_shape(leaf.shape, spec, mesh_sizes)
        rows = local[0] if is_scan and len(local) > 1 else 1
        entries.append(dict(
            path="/".join(
                k.key if hasattr(k, "key") else str(k) for k in path
            ),
            shape=local,
            rows=rows,
            kind=mode,
            rate=p_leaf,
            n_shards=_shards_of(spec, mesh_sizes),
            global_size=leaf.size,
        ))
    return ShardedFlatParamSpace.build(
        entries,
        client_axes=client_axes,
        shard_axes=shard_axes,
        n_clients=n_clients,
        shards_per_client=math.prod(mesh_sizes[a] for a in shard_axes)
        if shard_axes else 1,
    )


# ------------------------------------------------------------ train builder


class DistTrainFns(NamedTuple):
    train_step: Callable  # (state, batch) -> (state, metrics)
    init_state: Callable  # rng -> state (unsharded; dry-run never calls it)
    state_shardings: Any
    batch_shardings: Callable  # batch pytree -> shardings pytree
    abstract_state: Any
    bits_per_client: float  # static Eq. 1 wire bits per round
    bits_dense: float
    # §11 sharded flat fast path (None when the per-leaf exchange runs):
    flat_space: Any = None  # ShardedFlatParamSpace bound to (cfg, mesh)
    residual_to_tree: Optional[Callable] = None  # flat residual → pytree
    channel: Any = None  # the ShardedGspmdChannel driving the exchange


def _dist_leaf_mode(codec: Codec) -> str:
    """Map a codec onto the shard_map exchange kernels this backend has.

    'sparse' → per-shard SBC + (idx, μ) all-gather; 'dense' → pmean
    all-reduce; 'skip' → no traffic.  Other codec compositions have no
    TPU-native exchange kernel yet and fail loudly.
    """
    if codec.skip:
        return "skip"
    if codec.selector.dense and codec.quantizer.name == "identity":
        return "dense"
    if codec.spec == "topk_signed|binarize|golomb":
        return "sparse"
    raise NotImplementedError(
        f"dist backend has no exchange kernel for codec {codec.spec!r}; "
        "supported: sbc (topk_signed|binarize|golomb), dense32, skip"
    )


def make_dist_train(
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    compressor: str = "sbc",
    sparsity: float = 0.001,
    policy: Optional[CompressionPolicy] = None,
    model: Optional[Model] = None,
    opts: frozenset = frozenset(),
    fast: Optional[bool] = None,
    flat_engine: str = "exact",
) -> DistTrainFns:
    """Legacy name for :func:`build_dist_train` (the seed API surface).

    Survives as a documented bit-identical shim; new code should build the
    backend declaratively via ``repro.run.build_run(RunSpec(
    backend="gspmd", ...))`` or call :func:`build_dist_train`.
    """
    warnings.warn(
        "make_dist_train() is the legacy GSPMD surface; build it "
        "declaratively via repro.run.build_run(RunSpec(backend='gspmd', "
        "...)) or call repro.launch.dist.build_dist_train() (same "
        "lowering, bit-identical)",
        DeprecationWarning,
        stacklevel=2,
    )
    return build_dist_train(
        cfg, mesh, compressor=compressor, sparsity=sparsity, policy=policy,
        model=model, opts=opts, fast=fast, flat_engine=flat_engine,
    )


def build_dist_train(
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    compressor: str = "sbc",
    sparsity: float = 0.001,
    policy: Optional[CompressionPolicy] = None,
    model: Optional[Model] = None,
    opts: frozenset = frozenset(),
    fast: Optional[bool] = None,
    flat_engine: str = "exact",
    measure: bool = False,
    device_pack: bool = False,
) -> DistTrainFns:
    """Build the sharded DSGD train_step for (cfg, mesh).

    State = {'params', 'opt', 'residual'}; batch has a leading client axis
    of size ``client_topology(cfg, mesh)[0]``.

    ``policy`` — optional per-leaf :class:`CompressionPolicy` (path-regex
    rules; DESIGN.md §3).  Each leaf resolves to one of this backend's
    exchange kernels (see :func:`_dist_leaf_mode`) with its own sparsity
    rate.  Without a policy, ``compressor`` picks one codec for every leaf
    ("sbc" or any dense codec name), matching the seed behavior.

    ``fast`` — None keeps the policy's own ``fast`` flag; True/False
    forces the §11 sharded flat exchange on or off.  When active, every
    device compresses its shard of ONE block-padded flat buffer inside
    ``shard_map`` (:class:`~repro.core.flat.ShardedFlatParamSpace`), the
    error-feedback residual is stored flat-sharded, and the exchange is
    one all_gather of packed (positions, μ) flat segments.  Output is
    bit-identical to the per-leaf exchange; non-f32 leaves (or a non-f32
    ``cfg.residual_dtype``) fall back to the per-leaf path silently,
    same as PR 3's single-device fast path.

    ``flat_engine`` — 'exact' (default; two-sided per-row top-k) or
    'hist' (the segment-aware Pallas passes, histogram thresholds with
    exactly k survivors per (leaf, shard), dense pmean exchange); 'hist'
    needs an all-SBC policy and an active fast path.

    ``measure`` — every round, additionally emit client 0's transmitted
    ΔW* (``metrics['own_client0']`` — explicitly a CLIENT-0 SAMPLE, not
    a cohort sum; see docs/wire-format.md) so the channel ledger can
    Golomb-encode the
    real per-shard position streams next to the analytic Eq. 1 bits.

    ``device_pack`` — pack each client's Golomb position streams into
    wire words ON DEVICE (fused select→pack Pallas kernels, §11): the
    all_gather exchanges packed uint32 buffers (~b̄(p) bits/position)
    instead of 32-bit index arrays, and exact per-(client, shard, row)
    bit counts come back with the step so the ledger meters EVERY
    client's real upload (``metrics['packed_nbits']``) — no host
    re-encode, no client-0 sampling.  Needs the flat fast path with the
    exact engine.

    ``opts`` — §Perf beyond-baseline toggles (baseline = empty set):
      'expert_parallel'  experts shard over 'data', dispatch follows
      'seq_every2'       sequence-parallel hint on every 2nd block only
    """
    from repro.models.model import make_param_specs

    model = model or build_model(cfg)
    n_clients, client_axes = client_topology(cfg, mesh)
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    opt_kw = {} if cfg.local_opt == "sgd" else {"state_dtype": cfg.residual_dtype}
    opt = get_optimizer(cfg.local_opt, **opt_kw)
    if policy is None:
        default = "sbc" if compressor == "sbc" else "dense"
        policy = CompressionPolicy.single(make_codec(default), name=compressor)
    # the cfg's dispatch mode decides the MoE weight sharding rules
    # ('flat_ep'/'grouped' → EP rules; 'flat_fsdp' → baseline fsdp rules)
    ep_rules = cfg.moe_dispatch in ("flat_ep", "grouped")

    # ---- abstract state + shardings
    a_params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_specs = make_param_specs(a_params, mesh, fsdp=cfg.fsdp,
                               expert_parallel=ep_rules)
    flat_p = jax.tree_util.tree_flatten_with_path(a_params)[0]
    scanned = [
        "stack/scan" in "/".join(k.key if hasattr(k, "key") else str(k) for k in path)
        for path, _ in flat_p
    ]
    flat_specs = jax.tree.leaves(p_specs, is_leaf=lambda s: isinstance(s, P))
    lead = _lead_spec(client_axes)
    flat_r_specs = [P(lead, *s) for s in flat_specs]

    # ---- per-leaf policy resolution (codec + sparsity rate by path regex).
    # Rates are compile-time constants here: a per-round schedule would be
    # silently frozen at its round-0 value, so reject it loudly (re-build
    # the train fns per rate change, or use the vmap trainer instead).
    plans = [policy.plan_for(path_str(path)) for path, _ in flat_p]
    scheduled = [pl.path for pl in plans if pl.schedule is not None]
    if scheduled:
        raise NotImplementedError(
            "the GSPMD backend compiles per-leaf sparsity rates statically; "
            f"policy rules attach per-round schedules to {scheduled[:3]}… — "
            "rebuild the train fns when the rate changes, or use the local "
            "backend instead"
        )
    modes = [_dist_leaf_mode(pl.codec) for pl in plans]
    leaf_rates = [pl.rate(sparsity, 0) for pl in plans]

    # ---- the channel: §11 sharded flat fast path when it applies, the
    # per-leaf exchange otherwise (the dispatch ladder lives in core now)
    want_fast = policy.fast if fast is None else bool(fast)
    space = None
    if want_fast:
        space = _sharded_flat_space(
            cfg, mesh, flat_p, flat_specs, scanned, modes, leaf_rates,
            client_axes, n_clients,
        )
    channel = ShardedGspmdChannel(
        leaves=tuple(
            GspmdLeaf(
                path=path_str(path),
                global_shape=tuple(leaf.shape),
                dtype=leaf.dtype,
                scanned=is_scan,
                mode=mode,
                rate=p_leaf,
                n_shards=_shards_of(spec, mesh_sizes),
                shard_grid=_shard_grid(leaf.shape, spec, mesh_sizes),
            )
            for (path, leaf), spec, is_scan, mode, p_leaf in zip(
                flat_p, flat_specs, scanned, modes, leaf_rates
            )
        ),
        client_axes=client_axes,
        n_clients=n_clients,
        residual_dtype=cfg.residual_dtype,
        flat_space=space,
        flat_engine=flat_engine,
        device_pack=device_pack,
    )
    shard_axes = tuple(a for a in mesh.axis_names if a not in client_axes)
    res_spec = P(lead, _lead_spec(shard_axes), None)

    def stack_c(tree):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_clients,) + x.shape).copy(), tree
        )

    def init_state(rng):
        params = model.init(rng)
        return {
            "params": params,
            "opt": stack_c(opt.init(params)),
            "residual": channel.init_state(params),
        }

    a_state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    state_specs = {
        "params": p_specs,
        "opt": opt_state_specs(cfg.local_opt, p_specs, client_axes),
        "residual": res_spec if space is not None else jax.tree.unflatten(
            jax.tree.structure(p_specs, is_leaf=lambda s: isinstance(s, P)), flat_r_specs
        ),
    }
    ns = lambda spec: NamedSharding(mesh, spec)
    state_shardings = jax.tree.map(ns, state_specs, is_leaf=lambda s: isinstance(s, P))

    # ---- static Eq. 1 bit accounting per round per client (channel-owned)
    bits = channel.bits()

    # ---- batch shardings
    inner = "data" if cfg.client_mode == "pod" else None

    def batch_shardings(batch_tree):
        def one(x):
            return ns(P(lead, inner, *([None] * (x.ndim - 2))))

        return jax.tree.map(one, batch_tree)

    # ---- the step
    need_mask = cfg.local_opt != "sgd"  # momentum masking needs ΔW*_i
    need_own = need_mask or measure

    def train_step(state, batch):
        params = state["params"]

        def local(opt_state, client_batch):
            loss, g = jax.value_and_grad(model.loss_fn)(params, client_batch)
            p2, os2 = opt.apply(opt_state, g, params, cfg.base_lr, jnp.zeros((), jnp.int32))
            delta = jax.tree.map(
                lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32)).astype(
                    cfg.residual_dtype
                ),
                p2,
                params,
            )
            return delta, os2, loss

        deltas, opt_states, losses = jax.vmap(local)(state["opt"], batch)

        # ---- compress + exchange + residual, one channel call (§12)
        packed = None
        if device_pack:
            mean_tree, new_residual, own_tree, packed = channel.round_exchange(
                state["residual"], deltas,
                mesh=mesh, in_specs=tuple(flat_r_specs), res_spec=res_spec,
                need_own=need_own,
            )
        else:
            mean_tree, new_residual, own_tree = channel.round_exchange(
                state["residual"], deltas,
                mesh=mesh, in_specs=tuple(flat_r_specs), res_spec=res_spec,
                need_own=need_own,
            )

        # every client reconstructs the identical mean update; take client 0
        mean_delta = jax.tree.map(lambda m: m[0], mean_tree)

        new_params = jax.tree.map(
            lambda p, d: (p.astype(jnp.float32) + d.astype(jnp.float32)).astype(p.dtype),
            params,
            mean_delta,
        )
        # momentum masking (supplement A) at transmitted coordinates
        if need_mask:
            transmitted = jax.tree.map(lambda o: (o != 0).astype(jnp.float32), own_tree)
            opt_states = jax.vmap(opt.mask)(opt_states, transmitted)

        metrics = {"loss": jnp.mean(losses)}
        if measure:
            # client 0's transmitted ΔW*, for host-side wire metering
            metrics["own_client0"] = jax.tree.map(lambda o: o[0], own_tree)
            if device_pack:
                # every client's upload: packed words, exact per-(client,
                # shard, row) bits, per-row μ (the host can decode it)
                metrics["packed_words"] = packed[0]
                metrics["packed_nbits"] = packed[1]
                metrics["packed_mu"] = packed[2]
        return (
            {"params": new_params, "opt": opt_states, "residual": new_residual},
            metrics,
        )

    def wrapped(state, batch):
        b_axes = ("data",) if cfg.client_mode == "pod" else None
        with hints.activation_sharding(
            mesh, batch_axes=b_axes, seq_axis="model",
            expert_axis="data" if cfg.moe_dispatch == "flat_ep" else None,
            seq_every=2 if "seq_every2" in opts else 1,
            lean_moe="lean_moe" in opts,
        ):
            return train_step(state, batch)

    jitted = jax.jit(
        wrapped,
        in_shardings=(state_shardings, None),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )

    residual_to_tree = None
    if space is not None:
        # host-side view of the flat sharded residual as the per-leaf
        # stacked pytree the legacy path stores (tests / checkpoints)
        p_treedef = jax.tree.structure(a_params)

        def _unf(res):
            return tuple(b[None] for b in space.unflatten_local(res[0, 0]))

        unf_jit = jax.jit(shard_map(
            _unf, mesh=mesh, in_specs=(res_spec,),
            out_specs=tuple(flat_r_specs),
        ))

        def residual_to_tree(flat_res):
            return jax.tree.unflatten(p_treedef, unf_jit(flat_res))

    return DistTrainFns(
        jitted, init_state, state_shardings, batch_shardings, a_state,
        bits_per_client=bits.per_client,
        bits_dense=bits.dense,
        flat_space=space,
        residual_to_tree=residual_to_tree,
        channel=channel,
    )


# --------------------------------------------------------------- serve side


def cache_specs(cfg: ModelConfig, mesh: Mesh, a_caches: PyTree) -> PyTree:
    """Shardings for decode caches.

    k/v (B, L, Hkv, hd): batch over ('pod','data') when divisible; kv heads
    over 'model' when divisible, else the cache *sequence* dim over 'model'
    (flash-decoding style — DESIGN.md §4).  SSM states: channels over 'model'.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m = sizes.get("model", 1)
    b_axes = tuple(a for a in ("pod", "data") if a in sizes)
    b_total = math.prod(sizes[a] for a in b_axes) if b_axes else 1
    b_spec = _lead_spec(b_axes)

    def spec_for(path: str, leaf) -> P:
        shape = leaf.shape
        off = 1 if path.startswith("scan/") else 0
        dims: list[Any] = [None] * len(shape)
        name = path.split("/")[-1]
        if name in ("k", "v", "cross_k", "cross_v"):
            B, L, H = shape[off], shape[off + 1], shape[off + 2]
            if b_axes and B % b_total == 0:
                dims[off] = b_spec
            if H % m == 0:
                dims[off + 2] = "model"
            elif L % m == 0:
                dims[off + 1] = "model"
        elif name == "h":  # mamba (B, di, N)
            B, di = shape[off], shape[off + 1]
            if b_axes and B % b_total == 0:
                dims[off] = b_spec
            if di % m == 0:
                dims[off + 1] = "model"
        elif name in ("conv", "tm_prev", "cm_prev"):  # (B, w, ch)
            B, ch = shape[off], shape[-1]
            if b_axes and B % b_total == 0:
                dims[off] = b_spec
            if ch % m == 0:
                dims[-1] = "model"
        elif name == "s":  # rwkv (B, H, hs, hs)
            B, H = shape[off], shape[off + 1]
            if b_axes and B % b_total == 0:
                dims[off] = b_spec
            if H % m == 0:
                dims[off + 1] = "model"
        return P(*dims)

    flat, treedef = jax.tree_util.tree_flatten_with_path(a_caches)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(k.key if hasattr(k, "key") else str(k) for k in path)
        specs.append(spec_for(pstr, leaf))
    return jax.tree_util.tree_unflatten(treedef, specs)


class DistServeFns(NamedTuple):
    serve_step: Callable
    param_shardings: Any
    cache_shardings: Any
    abstract_caches: Any


def make_dist_serve(
    cfg: ModelConfig, mesh: Mesh, *, batch: int, seq_len: int,
    model: Optional[Model] = None,
) -> DistServeFns:
    """One-token decode step against a ``seq_len``-deep sharded KV/SSM cache."""
    model = model or build_model(cfg)
    a_params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_specs = model.param_specs(a_params, mesh)
    ns = lambda s: NamedSharding(mesh, s)
    p_shard = jax.tree.map(ns, p_specs, is_leaf=lambda s: isinstance(s, P))

    a_caches = jax.eval_shape(lambda: model.init_caches(None, batch, seq_len))
    c_shard = jax.tree.map(
        ns, cache_specs(cfg, mesh, a_caches), is_leaf=lambda s: isinstance(s, P)
    )

    def step(params, tokens, caches, pos):
        with hints.activation_sharding(mesh, batch_axes=None, seq_axis=None):
            return model.decode_step(params, tokens, caches, pos)

    jitted = jax.jit(
        step,
        in_shardings=(p_shard, ns(P(None, None)), c_shard, ns(P())),
        out_shardings=(None, c_shard),
        donate_argnums=(2,),
    )
    return DistServeFns(jitted, p_shard, c_shard, a_caches)


class DistPrefillFns(NamedTuple):
    prefill: Callable
    param_shardings: Any
    batch_shardings: Callable


def make_dist_prefill(
    cfg: ModelConfig, mesh: Mesh, *, model: Optional[Model] = None
) -> DistPrefillFns:
    """Full-sequence prefill returning (hidden, caches) — the prefill_32k unit."""
    model = model or build_model(cfg)
    a_params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_specs = model.param_specs(a_params, mesh)
    ns = lambda s: NamedSharding(mesh, s)
    p_shard = jax.tree.map(ns, p_specs, is_leaf=lambda s: isinstance(s, P))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    b_axes = tuple(a for a in ("pod", "data") if a in sizes)
    b_total = math.prod(sizes[a] for a in b_axes) if b_axes else 1
    lead = _lead_spec(b_axes)

    def pre(params, batch):
        with hints.activation_sharding(mesh, batch_axes=b_axes, seq_axis="model"):
            return model.prefill(params, batch)

    def batch_shardings(batch_tree):
        def one(x):
            head = lead if x.shape[0] % b_total == 0 else None
            return ns(P(head, *([None] * (x.ndim - 1))))

        return jax.tree.map(one, batch_tree)

    jitted = jax.jit(pre, in_shardings=(p_shard, None))
    return DistPrefillFns(jitted, p_shard, batch_shardings)


# -------------------------------------------------------------- launcher


def build_parser():
    """Thin parser over the shared RunSpec surface, pinned to gspmd."""
    import argparse

    from repro.run.flags import add_run_flags

    ap = argparse.ArgumentParser(
        description="GSPMD sharded DSGD launcher (one client per mesh "
        "data coordinate; run under XLA_FLAGS=--xla_force_host_platform_"
        "device_count=N to fan out on CPU)"
    )
    add_run_flags(ap, backend="gspmd", preset="tiny", rounds=10, log_every=5)
    return ap


def main(argv=None):
    from repro.paths import use_compile_cache
    from repro.run.build import build_run
    from repro.run.flags import spec_from_args

    use_compile_cache()
    args = build_parser().parse_args(argv)
    spec = spec_from_args(args, backend="gspmd")
    run = build_run(spec)
    print(
        f"gspmd: {run.n_clients} clients over {run.mesh.devices.size} "
        f"device(s), p={spec.sparsity}, fast={spec.fast}, "
        f"bits/client/round={run.fns.bits_per_client:.3e} "
        f"(dense {run.fns.bits_dense:.3e})"
    )
    state, hist = run.run(log_every=args.log_every)
    print(f"loss {hist['loss'][0]:.4f} → {hist['loss'][-1]:.4f}  "
          f"compression ×{hist['compression_rate']:.0f}")
    if spec.measure_wire:
        run.ledger.reconcile(rel=0.1)
        t = run.ledger.totals()
        print(
            f"wire: up {t['up_bytes']/1e3:.1f} kB (measured/analytic "
            f"×{t['up_bits_measured']/max(t['up_bits_analytic'],1):.3f})"
        )
    if spec.telemetry:
        from repro.obs import finish_run

        finish_run(
            run.telemetry, trace=args.trace, metrics_out=args.metrics_out,
            meta={"backend": "gspmd", "preset": spec.preset,
                  "rounds": spec.rounds},
        )
    if args.history:
        import json
        import os

        os.makedirs(os.path.dirname(os.path.abspath(args.history)), exist_ok=True)
        with open(args.history, "w") as f:
            json.dump(hist, f, default=float)
    return hist


if __name__ == "__main__":
    main()
