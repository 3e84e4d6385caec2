"""Plain reference of the paper's CharLSTM: embedding (vocab x hidden) ->
``n_layers`` LSTM layers of ``hidden`` units -> untied output head (hidden x
vocab, no bias), mean softmax cross-entropy of the next character.

An LSTM layer's gates are x W_x + h W_h + b, split in the order input,
forget, cell, output; c' = sigmoid(f + forget_bias) c + sigmoid(i) tanh(g),
h' = sigmoid(o) tanh(c'), from h = c = 0.  Written from that description in
``jax.numpy``; it imports nothing of the program.  The parameter names are
the program's layout (``embed/embedding``, ``cell<i>/wx``, ``wh``, ``b``,
``head/w``).
"""
import math

import jax
import jax.numpy as jnp


def init_params(cfg, key):
    """Normal kernels scaled by 1/sqrt(hidden) and zero biases from ``key``."""
    v, d, n = cfg["vocab_size"], cfg["hidden"], cfg["n_layers"]
    ks = jax.random.split(key, 2 * n + 2)
    scale = 1.0 / math.sqrt(d)

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * scale

    params = {"embed": {"embedding": normal(ks[0], (v, d))},
              "head": {"w": normal(ks[1], (d, v))}}
    for i in range(n):
        params[f"cell{i}"] = {"wx": normal(ks[2 + 2 * i], (d, 4 * d)),
                              "wh": normal(ks[3 + 2 * i], (d, 4 * d)),
                              "b": jnp.zeros((4 * d,), jnp.float32)}
    return params


def loss(cfg, params, batch):
    """Mean next-character cross-entropy of ``batch = {tokens, labels}``."""
    n, fb = cfg["n_layers"], cfg["forget_bias"]
    x = params["embed"]["embedding"][batch["tokens"]]  # (B, S, d)
    b, _, d = x.shape
    zeros = jnp.zeros((b, d), x.dtype)

    def step(carry, xt):
        out = []
        for i, (h, c) in enumerate(carry):
            p = params[f"cell{i}"]
            i_g, f_g, g_g, o_g = jnp.split(xt @ p["wx"] + h @ p["wh"] + p["b"], 4, axis=-1)
            c = jax.nn.sigmoid(f_g + fb) * c + jax.nn.sigmoid(i_g) * jnp.tanh(g_g)
            h = jax.nn.sigmoid(o_g) * jnp.tanh(c)
            out.append((h, c))
            xt = h
        return tuple(out), xt

    _, hs = jax.lax.scan(step, ((zeros, zeros),) * n, jnp.swapaxes(x, 0, 1))
    logits = (jnp.swapaxes(hs, 0, 1) @ params["head"]["w"]).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def tensor_rows(cfg, path):
    return 1


def flops_per_sample(cfg, traffic):
    """Operations that the forward and backward passes of one sequence of
    ``seq_len`` characters require: each product 2 per multiply-add, once
    forward and twice backward; the embedding's gather counts 0."""
    d, v = cfg["hidden"], cfg["vocab_size"]
    macs = cfg["n_layers"] * 2 * d * 4 * d + d * v
    return float(2 * 3 * macs * traffic["seq_len"])
