"""``build_run(spec) -> Run``: one declarative spec drives any backend.

The Run object is the uniform driver surface (DESIGN.md §12):

  ``init``        allocate the backend's full training state
  ``step``        one communication round (state, metrics)
  ``evaluate``    held-out loss of the current master weights
  ``checkpoint``  persist the state via :mod:`repro.checkpoint`
  ``run``         the init+step loop with the backend's native history
  ``channel``     the :class:`~repro.core.channel.CommChannel` underneath
                  (its ``ledger`` carries the measured-vs-Eq.1/Eq.5 rows)

Backends:

  local   :class:`~repro.train.trainer.DSGDTrainer` over a
          :class:`~repro.core.channel.LocalVmapChannel` (clients = vmap axis)
  gspmd   :func:`~repro.launch.dist.build_dist_train` over a
          :class:`~repro.core.channel.ShardedGspmdChannel` (clients = mesh
          axes; this builder places one "data" axis over all local devices)
  fed     :class:`~repro.fed.scheduler.RoundScheduler` over a
          :class:`~repro.core.channel.FedWireChannel` (real SBW1 bytes)

Every backend constructs its compression policy through the SAME
:func:`policy_from_spec`, so a (policy, backend) point is one field away
from any other — the API redesign the paper's sparsity-vs-topology
trade-off needs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import (
    CompressionPolicy,
    Compressor,
    PolicyRule,
    make_compressor,
)
from repro.obs import NULL_TELEMETRY, Telemetry, make_telemetry
from repro.run.spec import RunSpec

PyTree = Any


# ------------------------------------------------------------ shared pieces


def policy_from_spec(spec: RunSpec) -> Union[Compressor, CompressionPolicy]:
    """The spec's compression policy — compressor + path-regex rules + fast
    flag, identical composition to the legacy launchers (skip rules first,
    then dense fallbacks, then the compressor's own rules)."""
    comp = make_compressor(spec.compressor)
    rules: Tuple[PolicyRule, ...] = ()
    if spec.skip_pattern:
        rules += (PolicyRule(spec.skip_pattern, codec="skip"),)
    if spec.dense_pattern:
        rules += (PolicyRule(spec.dense_pattern, codec="dense32"),)
    if rules:
        return CompressionPolicy(
            default=comp.codec,
            rules=rules + comp.policy.rules,
            name=spec.compressor + "+rules",
            fast=spec.fast,
        )
    # fast=True opts in; False keeps the compressor's own flag (the legacy
    # launchers' `fast=True if args.fast else None` semantics)
    if spec.fast and not comp.policy.fast:
        return Compressor.from_policy(
            comp.name, dataclasses.replace(comp.policy, fast=True)
        )
    return comp


def as_policy(thing: Union[Compressor, CompressionPolicy]) -> CompressionPolicy:
    return thing.policy if isinstance(thing, Compressor) else thing


def lr_schedule(base_lr: float, decay_at: tuple[int, ...] = (), factor: float = 0.1):
    def lr(it):
        mult = 1.0
        for d in decay_at:
            mult = jnp.where(it >= d, mult * factor, mult)
        return base_lr * mult

    return lr


def _preset_for(spec: RunSpec):
    from repro.run.presets import build_preset

    return build_preset(spec.preset, batch=spec.batch, seq_len=spec.seq_len,
                        seed=spec.seed)


# ---------------------------------------------------------------- Run base


@dataclasses.dataclass(eq=False)
class Run:
    """A built backend: the init/step/eval/checkpoint driver surface."""

    spec: RunSpec
    cfg: Any
    model: Any
    task: Any
    channel: Any = None  # set by the backend builder
    telemetry: Telemetry = NULL_TELEMETRY  # enabled iff spec.telemetry

    # ------------------------------------------------------------ protocol

    def init(self, rng: Optional[jax.Array] = None):
        raise NotImplementedError

    def step(self, state, round_idx: int) -> tuple:
        raise NotImplementedError

    def evaluate(self, state) -> dict:
        """Held-out loss: a batch stream no training client consumes.

        Uses the backend's REAL client count (gspmd derives it from the
        mesh, not from spec.clients), so the held-out stream is genuinely
        untouched by training.
        """
        params = self.params_of(state)
        n_training = getattr(self, "n_clients", 0) or self.spec.clients
        batch = self.task.sample(0, n_training + 1)
        return {"loss": float(self.model.loss_fn(params, batch))}

    def checkpoint(self, state, path: str) -> None:
        raise NotImplementedError

    def params_of(self, state) -> PyTree:
        raise NotImplementedError

    @property
    def ledger(self):
        return self.channel.ledger

    def run(self, n_rounds: Optional[int] = None, log_every: int = 0) -> tuple:
        """init + step loop with the backend's native history dict."""
        raise NotImplementedError

    # ----------------------------------------------------------- telemetry

    def _init_for_run(self):
        """The state the traced loop starts from (fed reuses a live
        scheduler instead of rebuilding)."""
        return self.init()

    def _leaf_table(self, state) -> list:
        """Per-leaf static compression plan rows ``(path, n, k, rate)``
        for the leaf/* gauges; backends override (None-k = dense/skip)."""
        return []

    def _residual_of(self, state) -> Optional[PyTree]:
        """The error-feedback residual in pytree/flat form, or None when
        this backend doesn't expose one."""
        return None

    def _finalize_hist(self, hist: dict, n_rounds: int) -> dict:
        """Backend-specific derived history fields (compression totals)."""
        return hist

    def _record_static_gauges(self, state) -> None:
        from repro.core.golomb import expected_position_bits

        metrics = self.telemetry.metrics
        for path, n, k, rate in self._leaf_table(state):
            metrics.gauge("leaf/n", n, leaf=path)
            metrics.gauge("leaf/rate", rate, leaf=path)
            if k is not None:
                metrics.gauge("leaf/k", k, leaf=path)
                if 0.0 < rate < 1.0:
                    metrics.gauge(
                        "leaf/golomb_bits_pos", expected_position_bits(rate),
                        leaf=path,
                    )

    def _traced_run(self, n_rounds: Optional[int] = None,
                    log_every: int = 0) -> tuple:
        """The telemetry-instrumented init+step loop: one ``round`` span
        per round (stage spans open inside the backends/channels), the
        train/* gauges, and a final bit-exact ledger ingest.

        Replaces the backends' native ``run`` loops when
        ``spec.telemetry`` is on — same step semantics (it drives the
        same :meth:`step`), plus :meth:`_finalize_hist` reconstructs each
        backend's derived history fields.
        """
        import time

        tel = self.telemetry
        n_rounds = self.spec.rounds if n_rounds is None else n_rounds
        state = self._init_for_run()
        self._record_static_gauges(state)
        hist: dict = {"round": [], "loss": [], "bits_per_client": []}
        for r in range(n_rounds):
            t0 = time.perf_counter()
            with tel.span("round", round=r):
                state, m = self.step(state, r)
                tel.fence(self.params_of(state))
            step_ms = (time.perf_counter() - t0) * 1e3
            tel.metrics.gauge("train/step_ms", step_ms, round=r,
                              phase="compile" if r == 0 else "steady")
            tel.metrics.gauge("train/loss", float(m["loss"]), round=r)
            if "bits_per_client" in m:
                tel.metrics.gauge("train/bits_per_client",
                                  float(m["bits_per_client"]), round=r)
            res = self._residual_of(state)
            if res is not None:
                norm = float(jnp.sqrt(sum(
                    jnp.sum(jnp.square(x.astype(jnp.float32)))
                    for x in jax.tree.leaves(res)
                )))
                tel.metrics.gauge("train/residual_norm", norm, round=r)
            hist["round"].append(r)
            hist["loss"].append(float(m["loss"]))
            hist["bits_per_client"].append(float(m.get("bits_per_client", 0.0)))
            if "measured_bits_per_client" in m:
                hist.setdefault("measured_bits_per_client", []).append(
                    float(m["measured_bits_per_client"])
                )
            if log_every and (r + 1) % log_every == 0:
                print(f"round {r+1:5d}  loss {float(m['loss']):.4f}  "
                      f"step {step_ms:.1f} ms")
        tel.metrics.ingest_ledger(self.ledger)
        return state, self._finalize_hist(hist, n_rounds)


# ------------------------------------------------------------ local backend


@dataclasses.dataclass(eq=False)
class LocalRun(Run):
    trainer: Any = None
    batch_fn: Callable = None

    def init(self, rng: Optional[jax.Array] = None):
        if rng is None:
            rng = jax.random.PRNGKey(self.spec.seed)
        return self.trainer.init(rng)

    def step(self, state, round_idx: int) -> tuple:
        resolved = self.trainer.resolved(state.params)
        rates = resolved.rates(self.spec.sparsity, round_idx)
        # local select/quantize/exchange/apply fuse into ONE jitted round
        # (docs/observability.md) — honestly traced as one fused exchange
        with self.telemetry.span("exchange", round=round_idx, fused=True):
            out = self.trainer.round_step(
                state, self.batch_fn(round_idx), n_delay=self.spec.delay,
                sparsity=rates, return_compressed=self.spec.measure_wire,
            )
            self.telemetry.fence(out[0].params)
        if self.spec.measure_wire:
            state, m, comp0 = out
            m = dict(m)
            m["measured_bits_per_client"] = self.channel.record_round(
                round_idx, params=state.params, compressed0=comp0,
                rate=self.spec.sparsity,
                bits_analytic_per_client=float(m["bits_per_client"]),
            )
        else:
            state, m = out
        return state, {k: v for k, v in m.items()}

    def checkpoint(self, state, path: str) -> None:
        from repro.checkpoint.io import save_train_state

        save_train_state(path, state)

    def params_of(self, state) -> PyTree:
        return state.params

    def _leaf_table(self, state) -> list:
        from repro.core.stages import k_for

        resolved = self.trainer.resolved(state.params)
        rates = resolved.rates(self.spec.sparsity, 0)
        rows = []
        for plan, leaf, p in zip(
            resolved.plans, resolved._leaves_of(state.params), rates
        ):
            n = int(np.prod(np.shape(leaf)) or 1)
            sparse = not (plan.codec.skip or plan.codec.selector.dense)
            rows.append((plan.path, n, k_for(n, p) if sparse else None,
                         float(p)))
        return rows

    def _residual_of(self, state) -> Optional[PyTree]:
        return state.comp_state.residual

    def _finalize_hist(self, hist: dict, n_rounds: int) -> dict:
        total_bits = sum(hist["bits_per_client"])
        hist["total_upload_bits"] = total_bits
        n_params = sum(
            x.size for x in jax.tree.leaves(
                jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
            )
        )
        hist["dense_total_bits"] = 32.0 * n_params * n_rounds * self.spec.delay
        hist["compression_rate"] = hist["dense_total_bits"] / max(total_bits, 1.0)
        if hist.get("measured_bits_per_client"):
            hist["measured_total_bits"] = sum(hist["measured_bits_per_client"])
        return hist

    def run(self, n_rounds: Optional[int] = None, log_every: int = 0) -> tuple:
        if self.telemetry.enabled:
            return self._traced_run(n_rounds, log_every)
        return self.trainer.fit(
            jax.random.PRNGKey(self.spec.seed),
            self.batch_fn,
            n_rounds=self.spec.rounds if n_rounds is None else n_rounds,
            n_delay=self.spec.delay,
            sparsity=self.spec.sparsity,
            log_every=log_every,
            measure_wire=self.spec.measure_wire,
        )


def _build_local(spec: RunSpec) -> LocalRun:
    from repro.data import client_batches
    from repro.models.model import build_model
    from repro.optim import get_optimizer
    from repro.train import DSGDTrainer

    cfg, task = _preset_for(spec)
    model = build_model(cfg)
    lr = spec.lr if spec.lr is not None else cfg.base_lr
    trainer = DSGDTrainer(
        model=model,
        compressor=policy_from_spec(spec),
        optimizer=get_optimizer(cfg.local_opt),
        n_clients=spec.clients,
        lr=lr_schedule(lr),
        _from_run=True,
    )
    return LocalRun(
        spec=spec, cfg=cfg, model=model, task=task,
        channel=trainer.channel,
        trainer=trainer,
        batch_fn=client_batches(task, spec.clients, spec.delay),
    )


# ------------------------------------------------------------ gspmd backend


@dataclasses.dataclass(eq=False)
class GspmdRun(Run):
    mesh: Any = None
    fns: Any = None  # DistTrainFns
    n_clients: int = 0

    def init(self, rng: Optional[jax.Array] = None):
        if rng is None:
            rng = jax.random.PRNGKey(self.spec.seed)
        # placed as the step expects it, so the first round compiles the
        # same program as every later one
        return jax.device_put(self.fns.init_state(rng), self.fns.state_shardings)

    def _batch(self, round_idx: int) -> PyTree:
        ids = np.arange(self.n_clients)
        if self.task.sample_many is not None:
            return self.task.sample_many(
                np.full((self.n_clients,), round_idx), ids
            )
        per = [self.task.sample(round_idx, int(c)) for c in ids]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per)

    def step(self, state, round_idx: int) -> tuple:
        # the shard_map round (compress + collective + apply) is one jitted
        # fused call — traced as one exchange span (docs/observability.md)
        with self.telemetry.span("exchange", round=round_idx, fused=True):
            state, m = self.fns.train_step(state, self._batch(round_idx))
            self.telemetry.fence(state["params"])
        m = dict(m)
        if self.spec.measure_wire:
            own_client0 = m.pop("own_client0")
            packed_nbits = m.pop("packed_nbits", None)
            m.pop("packed_words", None)
            m.pop("packed_mu", None)
            m["measured_bits_per_client"] = self.channel.record_round(
                round_idx, own_client0=own_client0, packed_nbits=packed_nbits
            )
        m["bits_per_client"] = self.fns.bits_per_client
        m["bits_dense"] = self.fns.bits_dense
        return state, m

    def checkpoint(self, state, path: str) -> None:
        from repro.checkpoint.io import save_pytree

        save_pytree(path, state)

    def params_of(self, state) -> PyTree:
        return state["params"]

    def _leaf_table(self, state) -> list:
        rows = []
        for gl in self.channel.leaves:
            n = int(np.prod(gl.global_shape) or 1)
            if gl.mode == "sparse":
                L = (gl.global_shape[0]
                     if gl.scanned and len(gl.global_shape) > 1 else 1)
                n_loc = max(1, n // (L * gl.n_shards))
                k_loc = max(1, min(n_loc, int(round(gl.rate * n_loc))))
                k = L * gl.n_shards * k_loc
            else:
                k = None
            rows.append((gl.path, n, k, float(gl.rate)))
        return rows

    def _residual_of(self, state) -> Optional[PyTree]:
        return state.get("residual")

    def _finalize_hist(self, hist: dict, n_rounds: int) -> dict:
        hist["total_upload_bits"] = float(self.fns.bits_per_client) * n_rounds
        hist["dense_total_bits"] = float(self.fns.bits_dense) * n_rounds
        hist["compression_rate"] = hist["dense_total_bits"] / max(
            hist["total_upload_bits"], 1.0
        )
        return hist

    def run(self, n_rounds: Optional[int] = None, log_every: int = 0) -> tuple:
        if self.telemetry.enabled:
            return self._traced_run(n_rounds, log_every)
        n_rounds = self.spec.rounds if n_rounds is None else n_rounds
        state = self.init()
        hist: dict = {"round": [], "loss": [], "bits_per_client": []}
        for r in range(n_rounds):
            state, m = self.step(state, r)
            hist["round"].append(r)
            hist["loss"].append(float(m["loss"]))
            hist["bits_per_client"].append(float(m["bits_per_client"]))
            if log_every and (r + 1) % log_every == 0:
                print(f"round {r+1:5d}  loss {float(m['loss']):.4f}")
        hist["total_upload_bits"] = float(self.fns.bits_per_client) * n_rounds
        hist["dense_total_bits"] = float(self.fns.bits_dense) * n_rounds
        hist["compression_rate"] = hist["dense_total_bits"] / max(
            hist["total_upload_bits"], 1.0
        )
        return state, hist


def _build_gspmd(spec: RunSpec, mesh=None) -> GspmdRun:
    from jax.sharding import Mesh

    from repro.launch.dist import build_dist_train, client_topology
    from repro.models.model import build_model

    cfg, task = _preset_for(spec)
    if mesh is None:
        # one "data" client axis over every local device (plus a size-1
        # "model" axis for the sharding hints) — the in-process topology;
        # production meshes come from repro.launch.mesh and enter through
        # the ``mesh=`` override
        mesh = Mesh(np.asarray(jax.devices()).reshape(-1, 1), ("data", "model"))
    model = build_model(cfg)
    policy = policy_from_spec(spec)
    fns = build_dist_train(
        cfg, mesh,
        compressor=spec.compressor,
        sparsity=spec.sparsity,
        policy=as_policy(policy) if not isinstance(policy, Compressor) else None,
        model=model,
        fast=True if spec.fast else None,
        flat_engine=spec.flat_engine,
        measure=spec.measure_wire,
        device_pack=spec.device_pack,
    )
    n_clients, _ = client_topology(cfg, mesh)
    return GspmdRun(
        spec=spec, cfg=cfg, model=model, task=task,
        channel=fns.channel, mesh=mesh, fns=fns, n_clients=n_clients,
    )


# -------------------------------------------------------------- fed backend


@dataclasses.dataclass(eq=False)
class FedRun(Run):
    scheduler: Any = None  # the stateful RoundScheduler IS the run state

    def init(self, rng: Optional[jax.Array] = None):
        from repro.fed import ClientPool, ParameterServer, RoundScheduler
        from repro.optim import get_optimizer
        from repro.run.flags import profiles_from_spec

        spec = self.spec
        params = self.model.init(
            rng if rng is not None else jax.random.PRNGKey(spec.seed)
        )
        policy = as_policy(policy_from_spec(spec))
        agg = spec.agg or ("staleness" if spec.async_rounds else "mean")
        lr = spec.lr if spec.lr is not None else self.cfg.base_lr
        server = ParameterServer(
            params=params, up_policy=policy, down_sparsity=spec.down_sparsity,
            aggregator=agg, staleness_beta=spec.staleness_beta,
            delta_horizon=spec.delta_horizon if spec.broadcast_log else None,
        )
        pool = ClientPool(
            model=self.model, optimizer=get_optimizer(self.cfg.local_opt),
            policy=policy, task=self.task, n_clients=spec.clients,
            lr=lambda it: lr, profiles=profiles_from_spec(spec),
            seed=spec.seed,
            cohort_tile=spec.cohort_tile, store=spec.client_store,
        )
        faults = None
        if spec.faults:
            from repro.fed.faults import FaultSchedule

            faults = FaultSchedule.parse(spec.faults)
        self.scheduler = RoundScheduler(
            server=server, pool=pool,
            cohort_size=spec.cohort or spec.clients,
            mode="async" if spec.async_rounds else "sync",
            max_staleness=spec.max_staleness, seed=spec.seed,
            straggler_timeout=spec.straggler_timeout, faults=faults,
        )
        self.channel = self.scheduler.channel
        # thread the telemetry handle to the wire endpoints (stage spans:
        # select_quantize/encode in the channel, decode/apply/encode in
        # the server)
        self.channel.telemetry = self.telemetry
        server.telemetry = self.telemetry
        return self.scheduler

    def step(self, state, round_idx: int) -> tuple:
        return state, state.step(round_idx)

    def checkpoint(self, state, path: str,
                   rounds_done: Optional[int] = None) -> None:
        """Full-federation snapshot (server + pool + channel + DeltaLog):
        ``repro.fed.checkpoint`` makes a restored run continue
        bit-identically, mid-round included."""
        from repro.fed.checkpoint import save_fed_state

        save_fed_state(path, state, rounds_done=rounds_done)

    def restore(self, path: str) -> dict:
        """Restore a :meth:`checkpoint` file into a freshly-initialized
        scheduler; returns the checkpoint meta (``rounds_done`` etc.)."""
        from repro.fed.checkpoint import restore_fed_state

        state = self.init() if self.scheduler is None else self.scheduler
        return restore_fed_state(path, state)

    def params_of(self, state) -> PyTree:
        return state.server.params

    def _init_for_run(self):
        return self.init() if self.scheduler is None else self.scheduler

    def _leaf_table(self, state) -> list:
        from repro.core.stages import k_for

        resolved = state.server._up_resolved
        params = state.server.params
        rates = resolved.rates(self.spec.sparsity, 0)
        rows = []
        for plan, leaf, p in zip(
            resolved.plans, resolved._leaves_of(params), rates
        ):
            n = int(np.prod(np.shape(leaf)) or 1)
            sparse = not (plan.codec.skip or plan.codec.selector.dense)
            rows.append((plan.path, n, k_for(n, p) if sparse else None,
                         float(p)))
        return rows

    def _finalize_hist(self, hist: dict, n_rounds: int) -> dict:
        hist.update({f"wire_{k}": v for k, v in self.ledger.history().items()})
        hist.update(self.ledger.totals())
        return hist

    def run(self, n_rounds: Optional[int] = None, log_every: int = 0) -> tuple:
        if self.telemetry.enabled:
            return self._traced_run(n_rounds, log_every)
        state = self.init() if self.scheduler is None else self.scheduler
        hist = state.run(
            self.spec.rounds if n_rounds is None else n_rounds,
            log_every=log_every,
        )
        return state, hist


def _build_fed(spec: RunSpec) -> FedRun:
    from repro.data import make_non_iid_lm_task
    from repro.models.model import build_model

    cfg, task = _preset_for(spec)
    if spec.non_iid:
        if cfg.family not in ("decoder",):
            raise ValueError(
                f"non_iid needs an LM preset; {spec.preset!r} is {cfg.family}"
            )
        task = make_non_iid_lm_task(
            vocab=cfg.vocab_size, batch=spec.batch, seq_len=spec.seq_len,
            n_clients=spec.clients, skew=spec.skew, temperature=0.5,
            seed=spec.seed,
        )
    model = build_model(cfg)
    return FedRun(spec=spec, cfg=cfg, model=model, task=task)


# ------------------------------------------------------------- entry point

_BUILDERS = {
    "local": _build_local,
    "gspmd": _build_gspmd,
    "fed": _build_fed,
}


def build_run(spec: RunSpec, **backend_kw) -> Run:
    """Construct the backend a spec names.  ``backend_kw`` carries the few
    non-declarative objects a backend can accept (e.g. ``mesh=`` for
    gspmd).

    ``spec.telemetry`` attaches one enabled :class:`~repro.obs.Telemetry`
    bundle to the run AND its channel (disabled runs keep the shared
    no-op ``NULL_TELEMETRY`` — zero overhead by construction).
    """
    run = _BUILDERS[spec.backend](spec, **backend_kw)
    if spec.telemetry:
        run.telemetry = make_telemetry()
        if run.channel is not None:  # fed attaches at init() time
            run.channel.telemetry = run.telemetry
    return run
