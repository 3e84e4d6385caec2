"""Per-architecture smoke tests (deliverable (f)).

Each assigned architecture instantiates a REDUCED variant of the same family
(≤2 superblock periods, d_model ≤ 256, ≤4 experts) and runs one forward +
one train step on CPU, asserting output shapes and absence of NaNs.  The
FULL configs are exercised only by the dry-run (ShapeDtypeStructs).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ASSIGNED_ARCHS, PAPER_ARCHS
from repro.core.api import get_compressor
from repro.optim import get_optimizer
from repro.train import DSGDTrainer

from conftest import arch_setup

SEQ = 32
BATCH = 2


def _batch_for(cfg, rng):
    if cfg.family == "cnn":
        return {
            "images": jax.random.normal(rng, (BATCH, cfg.img_size, cfg.img_size,
                                               cfg.img_channels)),
            "labels": jnp.zeros((BATCH,), jnp.int32),
        }
    b = {
        "tokens": jax.random.randint(rng, (BATCH, SEQ), 0, cfg.vocab_size),
        "labels": jax.random.randint(rng, (BATCH, SEQ), 0, cfg.vocab_size),
    }
    if cfg.family == "encdec":
        if cfg.modality == "audio":
            b["enc_frames"] = 0.1 * jax.random.normal(rng, (BATCH, SEQ, cfg.d_model))
        else:
            b["enc_tokens"] = b["tokens"]
    elif cfg.modality == "vision":
        b["prefix"] = 0.1 * jax.random.normal(rng, (BATCH, cfg.n_prefix, cfg.d_model))
    return b


def _no_nan(tree) -> bool:
    return not any(bool(jnp.any(jnp.isnan(x))) for x in jax.tree.leaves(tree)
                   if jnp.issubdtype(x.dtype, jnp.floating))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS + PAPER_ARCHS)
class TestArchSmoke:
    def test_forward_and_train_step(self, arch, rng):
        cfg, model, params = arch_setup(arch)
        batch = _batch_for(cfg, rng)

        loss = model.loss_fn(params, batch)
        assert loss.shape == ()
        assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"

        grads = jax.grad(model.loss_fn)(params, batch)
        assert _no_nan(grads), f"{arch}: NaN grads"
        assert jax.tree.structure(grads) == jax.tree.structure(params)

    def test_one_dsgd_round(self, arch, rng):
        """One SBC communication round updates weights and stays finite."""
        cfg, model, _ = arch_setup(arch)
        trainer = DSGDTrainer(
            model=model, compressor=get_compressor("sbc"),
            optimizer=get_optimizer("sgd"), n_clients=2, lr=lambda it: 0.05,
        )
        state = trainer.init(rng)
        batch = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (2, 1) + x.shape),
            _batch_for(cfg, rng),
        )
        params0 = jax.tree.map(jnp.copy, state.params)  # the round donates state
        new_state, m = trainer.round_step(state, batch, n_delay=1, sparsity=0.05)
        assert bool(jnp.isfinite(m["loss"]))
        assert float(m["bits_per_client"]) < float(m["bits_dense"])
        assert _no_nan(new_state.params)
        # weights actually moved
        moved = any(
            bool(jnp.any(a != b))
            for a, b in zip(jax.tree.leaves(new_state.params),
                            jax.tree.leaves(params0))
        )
        assert moved, f"{arch}: no parameter moved after a round"


DECODE_ARCHS = [a for a in ASSIGNED_ARCHS]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_prefill(arch, rng):
    """Prefill-then-decode logits ≈ one-shot forward logits at the next
    position (exercises KV-cache / SSM-state correctness per arch)."""
    cfg, model, params = arch_setup(arch)
    batch = _batch_for(cfg, rng)

    hidden, caches = model.prefill(params, batch)
    next_tok = jnp.ones((BATCH, 1), jnp.int32)
    logits, _ = model.decode_step(params, next_tok, caches, jnp.asarray(SEQ))
    assert logits.shape == (BATCH, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))

    # reference: run the full sequence + the new token through prefill again
    batch2 = dict(batch)
    batch2["tokens"] = jnp.concatenate([batch["tokens"], next_tok], axis=1)
    if "enc_frames" in batch2:
        pass  # encoder input unchanged
    hidden2, _ = model.prefill(params, batch2)
    from repro.models import transformer

    emb = transformer.output_embedding(params, cfg)
    ref = hidden2[:, -1:, :].astype(jnp.float32) @ emb.T.astype(jnp.float32)
    # SSM decode paths accumulate fp differences over the state; tolerance
    # is loose but catches index/slot bugs (which produce wildly different
    # logits, not 1e-2 drift)
    err = float(jnp.max(jnp.abs(logits - ref)))
    scale = float(jnp.max(jnp.abs(ref))) + 1e-6
    assert err / scale < 0.05, f"{arch}: decode/prefill mismatch {err/scale:.3f}"
