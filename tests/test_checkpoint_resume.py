"""Checkpoint/resume of a DSGDTrainer mid-run (ISSUE 4 satellite).

A fast=True trainer's per-client error-feedback residual is ONE flat f32
buffer per client (core/flat.py §10).  Saving the full TrainState —
params, per-client optimizer state, the flat residual, RNG keys, round
counter — through checkpoint/io.py and restoring it must continue the
run BIT-identically to an uninterrupted one: error feedback means a
lossy checkpoint would silently change every later update.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import restore_train_state, save_train_state
from repro.core.api import get_compressor
from repro.core.policy import (
    DENSE_SMALL_PATTERN,
    CompressionPolicy,
    PolicyRule,
)
from repro.data import client_batches
from repro.optim import get_optimizer
from repro.train import DSGDTrainer

N_CLIENTS = 2
SPARSITY = 0.02


def make_trainer(lm_setup):
    cfg, model, task = lm_setup
    policy = CompressionPolicy(
        default=get_compressor("sbc").codec,
        rules=(PolicyRule(DENSE_SMALL_PATTERN, codec="dense32"),),
        name="sbc+dense-small",
        fast=True,
    )
    trainer = DSGDTrainer(
        model=model,
        compressor=policy,
        optimizer=get_optimizer("momentum"),
        n_clients=N_CLIENTS,
        lr=lambda it: 0.1,
    )
    return trainer, client_batches(task, N_CLIENTS, 1)


def run_rounds(trainer, batch_fn, state, rates, start, n):
    for r in range(start, start + n):
        state, _ = trainer.round_step(
            state, batch_fn(r), n_delay=1, sparsity=rates
        )
    return state


def assert_state_bitwise(a, b):
    la = jax.tree.leaves(a._asdict())
    lb = jax.tree.leaves(b._asdict())
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        na, nb = np.asarray(xa), np.asarray(xb)
        assert na.dtype == nb.dtype and na.shape == nb.shape
        assert na.tobytes() == nb.tobytes()


def test_resume_mid_run_is_bit_identical(tmp_path, lm_setup):
    trainer, batch_fn = make_trainer(lm_setup)
    state = trainer.init(jax.random.PRNGKey(0))
    rates = trainer.resolved(state.params).rates(SPARSITY, 0)

    # the fast path stores the residual FLAT: (clients, n_pad) f32
    assert state.comp_state.residual.ndim == 2
    assert state.comp_state.residual.shape[0] == N_CLIENTS
    assert state.comp_state.residual.dtype == jnp.float32

    # 2 rounds → checkpoint → 2 more rounds, against 4 straight rounds
    mid = run_rounds(trainer, batch_fn, state, rates, 0, 2)
    path = str(tmp_path / "mid.npz")
    save_train_state(path, mid)

    like = trainer.init(jax.random.PRNGKey(7))  # template only
    restored = restore_train_state(path, like)
    assert_state_bitwise(restored, mid)  # the checkpoint itself is lossless
    assert int(restored.round) == 2
    # each round donates its state, so mid is stepped only after the check
    uninterrupted = run_rounds(trainer, batch_fn, mid, rates, 2, 2)
    resumed = run_rounds(trainer, batch_fn, restored, rates, 2, 2)

    assert_state_bitwise(resumed, uninterrupted)


def test_restore_rejects_mismatched_structure(tmp_path, lm_setup):
    import pytest

    trainer, batch_fn = make_trainer(lm_setup)
    state = trainer.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "state.npz")
    save_train_state(path, state)

    wrong = DSGDTrainer(
        model=trainer.model,
        compressor=CompressionPolicy.single(
            get_compressor("sbc").codec, name="sbc", fast=True
        ),
        optimizer=get_optimizer("momentum"),
        n_clients=N_CLIENTS + 1,
        lr=lambda it: 0.1,
    )
    with pytest.raises(ValueError):
        restore_train_state(path, wrong.init(jax.random.PRNGKey(0)))


# ------------------- federated backend (ISSUE 8 satellite) -------------------
#
# The fed checkpoint covers MUCH more than a TrainState: master weights W,
# the replica Ŵ, the server's downstream residual, every client's pooled
# optimizer/compressor rows, the async snapshot ring, the DeltaLog horizon,
# the bandwidth ledger, and a mid-round pending half-round.  Same contract
# as above, federation-wide: restore must continue bit-identically.

from faults import (  # noqa: E402
    FaultSchedule,
    ServerKilled,
    assert_trees_bitwise,
    capture_state,
    make_federation,
)
from faults import run_rounds as run_fed_rounds  # noqa: E402
from repro.fed.checkpoint import restore_fed_state, save_fed_state  # noqa: E402


def _log_state(sched):
    return sched.server.delta_log.state_dict()


def assert_federation_bitwise(a, b):
    """Full-federation equality: state arrays, ledger rows, DeltaLog."""
    assert_trees_bitwise(capture_state(a), capture_state(b), "federation")
    assert a.ledger.totals() == b.ledger.totals()
    assert [vars(r) for r in a.ledger.records] == \
           [vars(r) for r in b.ledger.records]
    la, lb = _log_state(a), _log_state(b)
    assert la["head"] == lb["head"] and la["entries"] == lb["entries"]
    assert_trees_bitwise(la["replica"], lb["replica"], "DeltaLog replica")


def test_fed_resume_at_round_boundary_is_bit_identical(tmp_path):
    sched = make_federation(delta_horizon=4)
    run_fed_rounds(sched, 2)
    path = str(tmp_path / "fed.npz")
    save_fed_state(path, sched, rounds_done=2)
    run_fed_rounds(sched, 4, start=2)  # sched becomes the 4-round reference

    fresh = make_federation(delta_horizon=4)
    meta = restore_fed_state(path, fresh)
    assert meta["rounds_done"] == 2
    run_fed_rounds(fresh, 4, start=2)
    assert_federation_bitwise(fresh, sched)


def test_fed_resume_mid_round_is_bit_identical(tmp_path):
    """Kill the server AFTER partial aggregation of a dropout round, restore
    the checkpoint into a freshly built federation, finish the parked
    half-round, continue — and land on the bytes of a never-killed run."""
    import pytest

    faulted = FaultSchedule(drops=((1, 2),), kill_server=((2, "post_aggregate"),))
    sched = make_federation(faults=faulted, delta_horizon=4)
    run_fed_rounds(sched, 2)
    with pytest.raises(ServerKilled):
        sched.step(2)
    path = str(tmp_path / "fed-mid.npz")
    save_fed_state(path, sched, rounds_done=2)

    fresh = make_federation(faults=faulted, delta_horizon=4)
    meta = restore_fed_state(path, fresh)
    assert meta["rounds_done"] == 2
    # the fired kill is in the checkpoint: the resumed run sails past it
    assert (2, "post_aggregate") in fresh._kills_fired
    m = fresh.resume_pending()
    assert m is not None and m["round"] == 2
    run_fed_rounds(fresh, 5, start=3)

    # reference: the SAME faults minus the kill, never interrupted
    ref = make_federation(faults=FaultSchedule(drops=((1, 2),)), delta_horizon=4)
    run_fed_rounds(ref, 5)
    assert_federation_bitwise(fresh, ref)
    fresh.ledger.reconcile(rel=0.12)


def test_fed_restore_rejects_mismatched_federation(tmp_path):
    import pytest

    sched = make_federation(delta_horizon=4)
    run_fed_rounds(sched, 1)
    path = str(tmp_path / "fed.npz")
    save_fed_state(path, sched)
    with pytest.raises(ValueError, match="shape mismatch"):
        restore_fed_state(path, make_federation(n_clients=6, delta_horizon=4))
    with pytest.raises(ValueError, match="delta_horizon"):
        restore_fed_state(path, make_federation())
