"""Serving engine + checkpoint + data-pipeline tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_pytree, save_pytree
from repro.configs.base import INPUT_SHAPES, get_config, input_specs
from repro.data import make_classification_task, make_lm_task, split_among_clients
from repro.models.model import build_model
from repro.serve import ServeEngine

from conftest import tiny_decoder


class TestServeEngine:
    def test_greedy_deterministic(self, rng):
        cfg = tiny_decoder()
        model = build_model(cfg)
        params = model.init(rng)
        engine = ServeEngine(model)
        batch = {"tokens": jax.random.randint(rng, (2, 16), 0, cfg.vocab_size)}
        out1 = engine.generate(params, batch, max_new_tokens=8)
        out2 = engine.generate(params, batch, max_new_tokens=8)
        assert out1.shape == (2, 8)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    def test_generation_consistent_with_rerun(self, rng):
        """Greedy decode == iterated argmax over full re-forwards."""
        cfg = tiny_decoder()
        model = build_model(cfg)
        params = model.init(rng)
        engine = ServeEngine(model)
        toks = jax.random.randint(rng, (1, 12), 0, cfg.vocab_size)
        out = engine.generate(params, {"tokens": toks}, max_new_tokens=4)

        from repro.models import transformer

        cur = toks
        ref = []
        for _ in range(4):
            hidden, _ = transformer.decoder_hidden(params, cur, cfg)
            emb = transformer.output_embedding(params, cfg)
            logits = hidden[:, -1, :].astype(jnp.float32) @ emb.T.astype(jnp.float32)
            nxt = jnp.argmax(logits, -1)
            ref.append(int(nxt[0]))
            cur = jnp.concatenate([cur, nxt[:, None].astype(jnp.int32)], axis=1)
        assert np.asarray(out)[0].tolist() == ref

    def test_temperature_sampling_runs(self, rng):
        cfg = tiny_decoder()
        model = build_model(cfg)
        params = model.init(rng)
        engine = ServeEngine(model)
        batch = {"tokens": jnp.ones((3, 8), jnp.int32)}
        out = engine.generate(params, batch, max_new_tokens=5, temperature=1.0, rng=rng)
        assert out.shape == (3, 5)
        assert bool(jnp.all((out >= 0) & (out < cfg.vocab_size)))

    def test_first_token_uses_split_key(self, rng):
        """The first sample must consume a SPLIT of the caller's key, not
        the key itself — reusing it raw would correlate the first decode
        step with any other use of the same key."""
        cfg = tiny_decoder()
        model = build_model(cfg)
        params = model.init(rng)
        engine = ServeEngine(model)
        batch = {"tokens": jnp.ones((4, 8), jnp.int32)}
        key = jax.random.PRNGKey(123)
        out = engine.generate(
            params, batch, max_new_tokens=1, temperature=1.0, rng=key
        )
        logits, _ = engine.prefill(params, batch)
        _, r = jax.random.split(key)
        want = jax.random.categorical(r, logits[:, -1, :] / 1.0)
        np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(want))
        raw = jax.random.categorical(key, logits[:, -1, :] / 1.0)
        assert not np.array_equal(np.asarray(out[:, 0]), np.asarray(raw))


class TestCheckpoint:
    def test_roundtrip_with_bf16(self, tmp_path, rng):
        tree = {
            "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "nest": {"b": jnp.ones((5,), jnp.bfloat16) * 1.5,
                     "c": jnp.array([1, 2, 3], jnp.int32)},
        }
        path = os.path.join(tmp_path, "ckpt.npz")
        save_pytree(path, tree)
        back = load_pytree(path, like=tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_structure_mismatch_raises(self, tmp_path):
        path = os.path.join(tmp_path, "c.npz")
        save_pytree(path, {"a": jnp.zeros((2,))})
        with pytest.raises(ValueError, match="mismatch"):
            load_pytree(path, like={"b": jnp.zeros((2,))})
        with pytest.raises(ValueError, match="shape"):
            load_pytree(path, like={"a": jnp.zeros((3,))})

    def test_model_params_roundtrip(self, tmp_path, rng):
        cfg = tiny_decoder()
        model = build_model(cfg)
        params = model.init(rng)
        path = os.path.join(tmp_path, "m.npz")
        save_pytree(path, params)
        back = load_pytree(path, like=params)
        batch = {"tokens": jnp.ones((1, 8), jnp.int32),
                 "labels": jnp.ones((1, 8), jnp.int32)}
        np.testing.assert_allclose(float(model.loss_fn(params, batch)),
                                   float(model.loss_fn(back, batch)), rtol=1e-6)


class TestData:
    def test_markov_task_determinism_and_floor(self):
        task = make_lm_task(vocab=50, batch=4, seq_len=16, temperature=0.3, seed=7)
        b1 = task.sample(3, 1)
        b2 = task.sample(3, 1)
        np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
        assert 0.0 < task.entropy_floor < np.log(50)
        # labels are next tokens
        np.testing.assert_array_equal(np.asarray(b1["labels"][:, :-1]),
                                      np.asarray(b1["tokens"][:, 1:]))

    def test_markov_task_large_vocab_draws_rows_lazily(self):
        """Above the dense-table size the chain's rows come from per-token
        keys: still one fixed chain (deterministic, in range, batched
        sampling identical to per-pair sampling), no V×V table."""
        from repro.data.synthetic import DENSE_CHAIN_MAX_VOCAB

        vocab = DENSE_CHAIN_MAX_VOCAB + 1
        task = make_lm_task(vocab=vocab, batch=2, seq_len=8, temperature=0.5)
        b1, b2 = task.sample(3, 1), task.sample(3, 1)
        np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                      np.asarray(b2["tokens"]))
        toks = np.asarray(b1["tokens"])
        assert toks.min() >= 0 and toks.max() < vocab
        assert 0.0 < task.entropy_floor < np.log(vocab)
        many = task.sample_many([3, 0], [1, 2])
        np.testing.assert_array_equal(np.asarray(many["tokens"][0]), toks)

    def test_affine_task_is_deterministic_sequence(self):
        task = make_lm_task(vocab=97, batch=2, seq_len=8, kind="affine")
        b = task.sample(0, 0)
        t, l = np.asarray(b["tokens"]), np.asarray(b["labels"])
        np.testing.assert_array_equal((3 * t[:, 0] + 7) % 97, l[:, 0])

    def test_classification_blobs_separable(self):
        task = make_classification_task(n_classes=4, img_size=8, channels=1,
                                        batch=64, noise=0.05)
        b = task.sample(0, 0)
        assert b["images"].shape == (64, 8, 8, 1)
        assert set(np.unique(np.asarray(b["labels"]))) <= set(range(4))

    def test_client_split_disjoint_streams(self):
        task = make_lm_task(vocab=50, batch=2, seq_len=8)
        bf = split_among_clients(task, 3)
        b = bf(0)
        assert b["tokens"].shape[0] == 3
        assert not np.array_equal(np.asarray(b["tokens"][0]),
                                  np.asarray(b["tokens"][1]))


class TestInputSpecs:
    @pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
    def test_specs_have_expected_fields(self, shape_name):
        cfg = get_config("qwen1.5-4b")
        if cfg.skip_reason(shape_name):
            pytest.skip("documented skip")
        specs = input_specs(cfg, shape_name, n_clients=4)
        kind = INPUT_SHAPES[shape_name]["kind"]
        if kind == "train":
            assert specs["tokens"].shape[0] == 4
            assert specs["tokens"].shape[-1] == INPUT_SHAPES[shape_name]["seq_len"]
        elif kind == "prefill":
            assert specs["tokens"].shape == (
                INPUT_SHAPES[shape_name]["global_batch"],
                INPUT_SHAPES[shape_name]["seq_len"],
            )

    def test_modality_stub_fields(self):
        seam = get_config("seamless-m4t-medium")
        s = input_specs(seam, "train_4k", n_clients=2)
        assert "enc_frames" in s and s["enc_frames"].shape[-1] == seam.d_model
        phi = get_config("phi-3-vision-4.2b")
        s = input_specs(phi, "train_4k", n_clients=2)
        assert "prefix" in s and s["prefix"].shape[-2] == phi.n_prefix
