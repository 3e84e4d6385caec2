"""DSGD trainer — paper Alg. 1 with pluggable compression (Alg. 2 + baselines).

One *communication round* (the jit unit):

  1. every client syncs to the master weights W                    (l.7-9)
  2. runs ``n_delay`` local optimizer steps on its own microbatches (l.10,
     ``SGD_n``; n_delay > 1 = Federated-Averaging-style communication delay)
  3. ΔW_i = R_i + (W_i' − W);  ΔW*_i = compress(ΔW_i);  R_i ← ΔW_i − ΔW*_i
     (l.10-12 — residual add + error feedback live in the policy engine,
     :meth:`repro.core.policy.ResolvedPolicy.compress`)
  4. exchange: ΔW ← mean_i ΔW*_i;  W ← W + ΔW                      (l.17-19)
  5. momentum masking (supplement A): client momentum zeroed at transmitted
     coordinates.

Steps 3-4 plus all bit accounting are one
:class:`~repro.core.channel.LocalVmapChannel` call (``round_exchange``,
DESIGN.md §12): clients are a leading vmap axis, so per-client
weight-updates exist as real tensors *before* any reduction — the thing
that makes per-client compression expressible at all (DESIGN.md §4).

``DSGDTrainer`` itself is the **legacy entry point** for this backend: it
predates the declarative run surface and survives as a documented shim —
``repro.run.build_run(RunSpec(backend="local", ...))`` constructs the same
trainer (bit-identical states; ``tests/test_legacy_api.py`` holds it to
that) and adds the uniform ledger/checkpoint surface on top.  Direct
construction emits a :class:`DeprecationWarning` pointing there.

Bit accounting: ``metrics['bits_per_client']`` is the analytic wire size
(Eq. 1 with Golomb position bits for SBC) of one client's upload this round;
``bits_dense`` is the 32-bit dense equivalent, so compression rate =
``delay · bits_dense / bits_per_client`` cumulated over rounds.  With
``fit(..., measure_wire=True)`` client 0's update is additionally packed to
real bytes every round (:mod:`repro.core.wire`), the *measured* sizes are
recorded next to the analytic ones, and the channel's
:class:`~repro.core.ledger.BandwidthLedger` gets one row per round.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.api import Compressor, CompressorState
from repro.core.channel import LocalVmapChannel
from repro.core.policy import CompressionPolicy, ResolvedPolicy
from repro.models.model import Model
from repro.optim.optimizers import Optimizer

PyTree = Any


class TrainState(NamedTuple):
    params: PyTree  # master weights W (shared by all clients)
    opt_states: PyTree  # per-client local optimizer state (leading C axis)
    comp_state: CompressorState  # per-client compressor state (leading C axis)
    round: jax.Array  # communication-round counter


@dataclasses.dataclass(eq=False)  # id-hash → usable as a jit static arg
class DSGDTrainer:
    model: Model
    compressor: Union[Compressor, CompressionPolicy]
    optimizer: Optimizer
    n_clients: int
    lr: Callable[[jax.Array], jax.Array]  # lr(iteration) schedule
    residual_dtype: Any = jnp.float32
    # None → keep the policy's own flag; True/False → force the flat-buffer
    # fast path (core/flat.py §10) on or off.  With the fast path active the
    # per-client error-feedback residual is stored as ONE flat f32 buffer
    # per client instead of a per-leaf pytree.
    fast: Optional[bool] = None
    # construction provenance: repro.run builds this trainer internally and
    # suppresses the legacy-surface warning
    _from_run: dataclasses.InitVar[bool] = False

    def __post_init__(self, _from_run: bool = False) -> None:
        if not _from_run:
            warnings.warn(
                "constructing DSGDTrainer directly is the legacy local-"
                "backend surface; build it declaratively via "
                "repro.run.build_run(RunSpec(backend='local', ...)) "
                "(bit-identical states, uniform ledger/checkpoint API)",
                DeprecationWarning,
                stacklevel=2,
            )
        if isinstance(self.compressor, CompressionPolicy):
            self.compressor = Compressor.from_policy(
                self.compressor.name, self.compressor
            )
        if self.fast is not None and self.fast != self.compressor.policy.fast:
            self.compressor = Compressor.from_policy(
                self.compressor.name,
                dataclasses.replace(self.compressor.policy, fast=self.fast),
            )
        self.channel = LocalVmapChannel(
            compressor=self.compressor,
            n_clients=self.n_clients,
            residual_dtype=self.residual_dtype,
        )

    @property
    def ledger(self):
        """The channel's bandwidth ledger (rows recorded by
        ``fit(measure_wire=True)`` / the run API)."""
        return self.channel.ledger

    def resolved(self, params: PyTree) -> ResolvedPolicy:
        """The compressor's policy bound to this model's param structure."""
        return self.channel.resolved(params)

    # ------------------------------------------------------------------ init

    def init(self, rng: jax.Array) -> TrainState:
        p_rng, c_rng = jax.random.split(rng)
        params = self.model.init(p_rng)

        def stack_c(tree):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (self.n_clients,) + x.shape).copy(), tree
            )

        opt_states = stack_c(self.optimizer.init(params))
        comp_state = self.channel.init_state(params, c_rng)
        return TrainState(params, opt_states, comp_state, jnp.zeros((), jnp.int32))

    # ------------------------------------------------------------- one round

    def _round_step(
        self,
        state: TrainState,
        batch: PyTree,  # (clients, n_delay, per_client_batch, ...)
        *,
        n_delay: int,
        sparsity: Union[float, Tuple[float, ...]],  # global rate | per-leaf rates
        return_compressed: bool = False,
    ) -> tuple:
        params = state.params
        iteration = state.round * n_delay  # forward-backward passes so far

        def local_update(opt_state, client_batch):
            """n_delay local steps from the master weights (Alg. 1 l.10)."""

            def one(carry, micro):
                p, os, it = carry
                loss, g = jax.value_and_grad(self.model.loss_fn)(p, micro)
                p2, os2 = self.optimizer.apply(os, g, p, self.lr(it), it)
                return (p2, os2, it + 1), loss

            (p_new, os_new, _), losses = jax.lax.scan(
                one, (params, opt_state, iteration), client_batch
            )
            delta = jax.tree.map(
                lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32)).astype(
                    self.residual_dtype
                ),
                p_new,
                params,
            )
            return delta, os_new, jnp.mean(losses)

        deltas, opt_states, losses = jax.vmap(local_update)(state.opt_states, batch)

        # ---- per-client compression + exchange (Alg. 1 l.11-17), one
        # channel call (compress with error feedback, mean over clients,
        # Eq. 1 accounting — DESIGN.md §12)
        ex = self.channel.round_exchange(
            deltas, state.comp_state, sparsity,
            return_compressed=return_compressed,
        )
        new_params = jax.tree.map(
            lambda p, d: (p.astype(jnp.float32) + d.astype(jnp.float32)).astype(p.dtype),
            params,
            ex.mean_delta,
        )

        # ---- momentum masking at transmitted coordinates (supplement A)
        transmitted = jax.tree.map(
            lambda d: (d != 0).astype(jnp.float32), ex.transmitted
        )
        opt_states = jax.vmap(self.optimizer.mask)(opt_states, transmitted)

        n_params = sum(x.size for x in jax.tree.leaves(params))
        metrics = {
            "loss": jnp.mean(losses),
            "bits_per_client": ex.bits_per_client,
            "bits_dense": jnp.asarray(32.0 * n_params * n_delay, jnp.float32),
            "update_norm": _tree_norm(ex.mean_delta),
        }
        new_state = TrainState(new_params, opt_states, ex.state, state.round + 1)
        if return_compressed:
            # client 0's compressed tree, for host-side wire measurement
            return new_state, metrics, ex.compressed0
        return new_state, metrics

    # the round consumes ``state``: its buffers are reused for the new
    # state, so a round holds one copy of the per-client optimizer and
    # residual state, not two.  A caller that still needs the old state
    # copies it before stepping.
    round_step = partial(
        jax.jit,
        static_argnames=("self", "n_delay", "sparsity", "return_compressed"),
        donate_argnames=("state",),
    )(_round_step)

    # --------------------------------------------------------------- fitting

    def fit(
        self,
        rng: jax.Array,
        batch_fn: Callable[[int], PyTree],  # round -> (C, n_delay, B, ...) batch
        *,
        n_rounds: int,
        n_delay: int,
        sparsity: float,
        eval_fn: Optional[Callable[[PyTree], dict]] = None,
        eval_every: int = 0,
        log_every: int = 0,
        measure_wire: bool = False,
    ) -> tuple:
        """Run ``n_rounds`` communication rounds; returns (state, history)."""
        state = self.init(rng)
        resolved = self.resolved(state.params)
        hist: dict = {"round": [], "loss": [], "bits_per_client": [], "eval": []}
        if measure_wire:
            hist["measured_bits_per_client"] = []
        total_bits = 0.0
        for r in range(n_rounds):
            rates = resolved.rates(sparsity, r)
            step_out = self.round_step(
                state, batch_fn(r), n_delay=n_delay, sparsity=rates,
                return_compressed=measure_wire,
            )
            if measure_wire:
                state, m, comp0 = step_out
                measured = self.channel.record_round(
                    r, params=state.params, compressed0=comp0, rate=sparsity,
                    bits_analytic_per_client=float(m["bits_per_client"]),
                )
                hist["measured_bits_per_client"].append(measured)
            else:
                state, m = step_out
            total_bits += float(m["bits_per_client"])
            hist["round"].append(r)
            hist["loss"].append(float(m["loss"]))
            hist["bits_per_client"].append(float(m["bits_per_client"]))
            if eval_fn and eval_every and (r + 1) % eval_every == 0:
                hist["eval"].append((r, eval_fn(state.params)))
            if log_every and (r + 1) % log_every == 0:
                print(
                    f"round {r+1:5d}  loss {float(m['loss']):.4f}  "
                    f"bits/client {float(m['bits_per_client']):.3e}"
                )
        hist["total_upload_bits"] = total_bits
        n_params = sum(x.size for x in jax.tree.leaves(state.params))
        hist["dense_total_bits"] = 32.0 * n_params * n_rounds * n_delay
        hist["compression_rate"] = hist["dense_total_bits"] / max(total_bits, 1.0)
        if measure_wire and hist["measured_bits_per_client"]:
            hist["measured_total_bits"] = sum(hist["measured_bits_per_client"])
        return state, hist


def _tree_norm(tree: PyTree) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))
    )
