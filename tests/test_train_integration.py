"""DSGD trainer integration: convergence, equivalence and bit accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import get_compressor
from repro.core.golomb import expected_position_bits
from repro.data import client_batches, make_lm_task
from repro.optim import get_optimizer
from repro.train import DSGDTrainer



def _trainer(model, compressor="sbc", opt="momentum", clients=4, lr=0.05):
    return DSGDTrainer(
        model=model, compressor=get_compressor(compressor),
        optimizer=get_optimizer(opt), n_clients=clients, lr=lambda it: lr,
    )


# lm_setup is the session-scoped (cfg, model, task) fixture from conftest —
# shared with test_codec_pipeline so the tiny decoder compiles once.


class TestConvergence:
    def test_sbc_learns(self, lm_setup, rng):
        _, model, task = lm_setup
        tr = _trainer(model, "sbc")
        _, hist = tr.fit(rng, client_batches(task, 4, 1), n_rounds=22,
                         n_delay=1, sparsity=0.01)
        assert hist["loss"][-1] < hist["loss"][0] - 0.8

    def test_delay_matches_budget(self, lm_setup, rng):
        """SBC(2)-style delayed training also converges (Fig. 5/6 claim:
        delay does not significantly slow convergence per iteration)."""
        _, model, task = lm_setup
        tr = _trainer(model, "sbc")
        _, hist = tr.fit(rng, client_batches(task, 4, 5), n_rounds=6,
                         n_delay=5, sparsity=0.01)
        assert hist["loss"][-1] < hist["loss"][0] - 0.8

    def test_compression_rate_matches_theory(self, lm_setup, rng):
        _, model, task = lm_setup
        p, delay = 0.01, 2
        tr = _trainer(model, "sbc")
        _, hist = tr.fit(rng, client_batches(task, 4, delay), n_rounds=3,
                         n_delay=delay, sparsity=p)
        # expected: delay × 32 / (p · (b̄_pos + 0)) up to per-tensor overheads
        expect = delay * 32.0 / (p * expected_position_bits(p))
        assert 0.7 * expect < hist["compression_rate"] < 1.3 * expect

    def test_dense_equals_plain_sgd(self, lm_setup, rng):
        """compressor='none', 1 client, delay 1 == vanilla training."""
        cfg, model, _ = lm_setup
        task = make_lm_task(vocab=cfg.vocab_size, batch=8, seq_len=32)
        tr = _trainer(model, "none", opt="sgd", clients=1, lr=0.1)
        state = tr.init(rng)
        params0 = jax.tree.map(jnp.copy, state.params)  # the round donates state
        batch = client_batches(task, 1, 1)(0)
        new_state, m = tr.round_step(state, batch, n_delay=1, sparsity=1.0)

        # manual SGD step
        loss, g = jax.value_and_grad(model.loss_fn)(
            params0, jax.tree.map(lambda x: x[0, 0], batch)
        )
        manual = jax.tree.map(lambda p, gg: p - 0.1 * gg, params0, g)
        for a, b in zip(jax.tree.leaves(new_state.params), jax.tree.leaves(manual)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                       atol=2e-6)

    def test_momentum_masking_applied(self, lm_setup, rng):
        _, model, task = lm_setup
        tr = _trainer(model, "sbc", opt="momentum")
        state = tr.init(rng)
        state, _ = tr.round_step(state, client_batches(task, 4, 1)(0),
                                 n_delay=1, sparsity=0.05)
        # momentum must be exactly zero at ≥ the sparsity fraction of coords
        mom = jnp.concatenate([x.reshape(-1) for x in jax.tree.leaves(state.opt_states)])
        frac_zero = float(jnp.mean(mom == 0.0))
        assert frac_zero >= 0.04  # ~5% transmitted → zeroed


class TestBaselineCompressorsTrain:
    @pytest.mark.parametrize("name,p", [
        ("topk", 0.01), ("signsgd", 1.0), ("terngrad", 1.0), ("qsgd", 1.0),
        ("randomk", 0.01), ("onebit", 1.0), ("fedavg", 1.0),
    ])
    def test_each_baseline_learns(self, lm_setup, rng, name, p):
        _, model, task = lm_setup
        # sign updates and random-k's unbiased 1% picks move slower
        rounds = 22 if name in ("signsgd", "randomk") else 14
        tr = _trainer(model, name, lr=0.05)
        _, hist = tr.fit(rng, client_batches(task, 4, 1), n_rounds=rounds,
                         n_delay=1, sparsity=p)
        assert hist["loss"][-1] < hist["loss"][0] - 0.35, name


class TestClientSemantics:
    def test_clients_see_distinct_data(self, lm_setup):
        _, _, task = lm_setup
        b = client_batches(task, 4, 1)(0)
        toks = b["tokens"]
        assert toks.shape[0] == 4
        assert not bool(jnp.all(toks[0] == toks[1]))

    def test_round_deterministic(self, lm_setup, rng):
        _, model, task = lm_setup
        tr = _trainer(model, "sbc")
        s1 = tr.init(rng)
        s2 = tr.init(rng)
        batch = client_batches(task, 4, 1)(0)
        o1, m1 = tr.round_step(s1, batch, n_delay=1, sparsity=0.01)
        o2, m2 = tr.round_step(s2, batch, n_delay=1, sparsity=0.01)
        assert float(m1["loss"]) == float(m2["loss"])
        for a, b in zip(jax.tree.leaves(o1.params), jax.tree.leaves(o2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
