"""Deterministic synthetic data pipelines with per-client sharding.

The paper's experiments split a dataset into M equal shards, one per client
(homogeneous/IID split).  This container is offline, so every reproduction
task uses a *synthetic but genuinely learnable* stand-in with the same
interface, seeded deterministically:

  * LM task ("markov"): a fixed random first-order Markov chain over the
    vocabulary with temperature-controlled entropy.  A model that learns the
    transition table reaches the chain's entropy floor; an untrained model
    sits at ln(V).  This gives convergence curves with real headroom, which
    is what the Table II / Fig. 5-6 analogues need.
  * LM task ("affine"): x_{t+1} = (a·x_t + b) mod V — near-zero achievable
    loss, used by fast smoke/integration tests.
  * Classification ("blobs"): Gaussian class blobs in pixel space (LeNet /
    ResNet shapes) — fixed class means with additive noise.

Batches are generated on the fly from a counter-based PRNG (jax.random.fold_in)
so the pipeline is stateless, reproducible, and infinite.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

PyTree = dict

# Largest vocabulary whose Markov chain is kept as a dense V×V table (64 MiB
# of f32).  Above it the table would not fit beside the model (32k vocab:
# 4 GB, captured as a constant in every jitted sampler), so each row's
# logits are drawn from the row's own key when that token is sampled.
DENSE_CHAIN_MAX_VOCAB = 4096


@dataclasses.dataclass(frozen=True)
class Task:
    """A data source: ``sample(step, client) -> dict`` plus metadata.

    ``sample_many(steps, clients)``, when present, generates the batches of
    many (step, client) pairs in ONE jitted dispatch with a leading pair
    axis — byte-identical streams to per-pair ``sample`` calls (same
    fold-in key construction), but without O(pairs) Python dispatch
    overhead.  The federated cohort runner and ``client_batches`` prefer it.
    """

    name: str
    sample: Callable[[int, int], PyTree]  # (step, client) -> batch dict
    vocab_size: int = 0
    n_classes: int = 0
    entropy_floor: float = 0.0  # achievable loss (nats/token) for LM tasks
    sample_many: Optional[Callable] = None  # (steps[N], clients[N]) -> dict


# ------------------------------------------------------------------ LM tasks


def make_lm_task(
    *,
    vocab: int,
    batch: int,
    seq_len: int,
    kind: str = "markov",
    temperature: float = 1.0,
    seed: int = 0,
    extra_fields: Optional[Callable[[jax.Array], PyTree]] = None,
) -> Task:
    """Next-token prediction: ``labels[t] = tokens[t+1]`` at every position."""
    base = jax.random.PRNGKey(seed)
    floor = 0.0

    if kind == "markov":
        chain_key = jax.random.fold_in(base, 17)
        t = max(temperature, 1e-3)
        if vocab <= DENSE_CHAIN_MAX_VOCAB:
            logits = jax.random.normal(chain_key, (vocab, vocab)) / t
            probs = jax.nn.softmax(logits, axis=-1)
            log_probs = jnp.log(probs)
            next_logits = log_probs.__getitem__
        else:
            def row_logits(tok):
                key = jax.random.fold_in(chain_key, tok)
                return jax.random.normal(key, (vocab,)) / t

            next_logits = jax.vmap(row_logits)
            probs = jax.nn.softmax(next_logits(jnp.arange(64)), axis=-1)
        # entropy floor ≈ mean row entropy (stationary dist of a dense random
        # chain is near-uniform; 64 sampled rows above the dense-table size)
        row_ent = -jnp.sum(probs * jnp.log(probs + 1e-12), axis=-1)
        floor = float(jnp.mean(row_ent))

        def gen_tokens(rng: jax.Array) -> jax.Array:
            def step(tok, r):
                nxt = jax.random.categorical(r, next_logits(tok))
                return nxt, nxt

            r0, rs = jax.random.split(rng)
            start = jax.random.randint(r0, (batch,), 0, vocab)
            keys = jax.random.split(rs, seq_len)
            _, toks = jax.lax.scan(step, start, keys)  # (S, B)
            return jnp.concatenate([start[None], toks], axis=0).T  # (B, S+1)

    elif kind == "affine":
        a, b = 3, 7

        def gen_tokens(rng: jax.Array) -> jax.Array:
            x0 = jax.random.randint(rng, (batch,), 0, vocab)

            def step(x, _):
                nxt = (a * x + b) % vocab
                return nxt, nxt

            _, xs = jax.lax.scan(step, x0, None, length=seq_len)  # (S, B)
            return jnp.concatenate([x0[None], xs], axis=0).T  # (B, S+1)

    else:
        raise ValueError(f"unknown LM task kind {kind!r}")

    gen_tokens = jax.jit(gen_tokens)

    def _key(step, client):
        return jax.random.fold_in(jax.random.fold_in(base, 1000 + client), step)

    def sample(step: int, client: int) -> PyTree:
        rng = _key(step, client)
        toks = gen_tokens(rng)  # (B, S+1)
        out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if extra_fields is not None:
            out.update(extra_fields(rng))
        return out

    @jax.jit
    def _many(steps: jax.Array, clients: jax.Array) -> PyTree:
        rngs = jax.vmap(_key)(steps, clients)
        toks = jax.vmap(gen_tokens)(rngs)  # (N, B, S+1)
        out = {"tokens": toks[:, :, :-1], "labels": toks[:, :, 1:]}
        if extra_fields is not None:
            out.update(jax.vmap(extra_fields)(rngs))
        return out

    def sample_many(steps, clients) -> PyTree:
        return _many(jnp.asarray(steps, jnp.int32), jnp.asarray(clients, jnp.int32))

    return Task(name=f"lm_{kind}", sample=sample, vocab_size=vocab,
                entropy_floor=floor, sample_many=sample_many)


# ----------------------------------------------------------- non-IID shards


def make_non_iid_lm_task(
    *,
    vocab: int,
    batch: int,
    seq_len: int,
    n_clients: int,
    skew: float = 2.0,
    temperature: float = 1.0,
    seed: int = 0,
) -> Task:
    """Non-IID client shards for federated runs (DESIGN.md §9).

    Client ``c`` samples from its OWN first-order Markov chain, an
    interpolation between one shared global chain and a client-private
    chain:  ``logits_c = (1−λ)·global + λ·private_c`` with
    ``λ = skew / (1 + skew)``.  ``skew=0`` degenerates to the IID split of
    :func:`make_lm_task`; larger skew pushes clients toward disjoint
    transition structure, the pathological-FL setting where naive averaging
    and sparse updates interact worst.

    The stacked transition table is ``(n_clients, V, V)`` f32 — intended
    for the small-vocab federated presets, not 32k-vocab LMs.
    """
    base = jax.random.PRNGKey(seed)
    lam = float(skew) / (1.0 + float(skew))
    g = jax.random.normal(jax.random.fold_in(base, 17), (vocab, vocab))
    priv = jax.random.normal(
        jax.random.fold_in(base, 29), (n_clients, vocab, vocab)
    )
    logits = ((1.0 - lam) * g[None] + lam * priv) / max(temperature, 1e-3)
    probs = jax.nn.softmax(logits, axis=-1)
    row_ent = -jnp.sum(probs * jnp.log(probs + 1e-12), axis=-1)
    floor = float(jnp.mean(row_ent))
    log_probs = jnp.log(probs)  # (C, V, V)

    @jax.jit
    def gen_tokens(rng: jax.Array, client: jax.Array) -> jax.Array:
        table = log_probs[client]

        def step(tok, r):
            nxt = jax.random.categorical(r, table[tok])
            return nxt, nxt

        r0, rs = jax.random.split(rng)
        start = jax.random.randint(r0, (batch,), 0, vocab)
        keys = jax.random.split(rs, seq_len)
        _, toks = jax.lax.scan(step, start, keys)  # (S, B)
        return jnp.concatenate([start[None], toks], axis=0).T  # (B, S+1)

    def _key(step, client):
        return jax.random.fold_in(jax.random.fold_in(base, 3000 + client), step)

    def sample(step: int, client: int) -> PyTree:
        toks = gen_tokens(_key(step, client),
                          jnp.asarray(client % n_clients, jnp.int32))
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    @jax.jit
    def _many(steps: jax.Array, clients: jax.Array) -> PyTree:
        rngs = jax.vmap(_key)(steps, clients)
        toks = jax.vmap(gen_tokens)(rngs, clients % n_clients)
        return {"tokens": toks[:, :, :-1], "labels": toks[:, :, 1:]}

    def sample_many(steps, clients) -> PyTree:
        return _many(jnp.asarray(steps, jnp.int32), jnp.asarray(clients, jnp.int32))

    return Task(
        name=f"lm_markov_noniid{n_clients}", sample=sample, vocab_size=vocab,
        entropy_floor=floor, sample_many=sample_many,
    )


# --------------------------------------------------------- classification


def make_classification_task(
    *,
    n_classes: int,
    img_size: int,
    channels: int,
    batch: int,
    noise: float = 0.35,
    seed: int = 0,
) -> Task:
    """Gaussian class-blob images: class c has a fixed mean image; samples
    add isotropic noise."""
    base = jax.random.PRNGKey(seed)
    means = (
        jax.random.normal(jax.random.fold_in(base, 23), (n_classes, img_size, img_size, channels))
        * 0.5
    )

    @jax.jit
    def gen(rng: jax.Array) -> tuple[jax.Array, jax.Array]:
        r1, r2 = jax.random.split(rng)
        labels = jax.random.randint(r1, (batch,), 0, n_classes)
        imgs = means[labels] + noise * jax.random.normal(
            r2, (batch, img_size, img_size, channels)
        )
        return imgs, labels

    def _key(step, client):
        return jax.random.fold_in(jax.random.fold_in(base, 2000 + client), step)

    def sample(step: int, client: int) -> PyTree:
        imgs, labels = gen(_key(step, client))
        return {"images": imgs, "labels": labels}

    @jax.jit
    def _many(steps: jax.Array, clients: jax.Array) -> PyTree:
        imgs, labels = jax.vmap(lambda s, c: gen(_key(s, c)))(steps, clients)
        return {"images": imgs, "labels": labels}

    def sample_many(steps, clients) -> PyTree:
        return _many(jnp.asarray(steps, jnp.int32), jnp.asarray(clients, jnp.int32))

    return Task(name="blobs", sample=sample, n_classes=n_classes,
                sample_many=sample_many)


# ------------------------------------------------------- client-sharded view


def split_among_clients(task: Task, n_clients: int) -> Callable[[int], PyTree]:
    """``batch_fn(round) -> dict`` with a leading client axis.

    Each client sees a disjoint stream (folded-in client id), mirroring the
    paper's balanced IID shard split.
    """

    def batch_fn(round_idx: int) -> PyTree:
        per = [task.sample(round_idx, c) for c in range(n_clients)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per)

    return batch_fn


def client_batches(task: Task, n_clients: int, n_delay: int) -> Callable[[int], PyTree]:
    """Like :func:`split_among_clients` but with a local-step (delay) axis:
    returns (clients, n_delay, batch, ...) — one microbatch per local step.

    When the task exposes ``sample_many`` the whole (clients × delay) grid
    is generated in one jitted dispatch (identical streams, see Task)."""
    import numpy as np

    def batch_fn(round_idx: int) -> PyTree:
        if task.sample_many is not None:
            clients = np.repeat(np.arange(n_clients), n_delay)
            micro = np.tile(round_idx * n_delay + np.arange(n_delay), n_clients)
            flat = task.sample_many(micro, clients)  # (C·D, B, ...)
            return jax.tree.map(
                lambda x: x.reshape((n_clients, n_delay) + x.shape[1:]), flat
            )
        steps = []
        for d in range(n_delay):
            per = [task.sample(round_idx * n_delay + d, c) for c in range(n_clients)]
            steps.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
        return jax.tree.map(lambda *xs: jnp.stack(xs, axis=1), *steps)

    return batch_fn
