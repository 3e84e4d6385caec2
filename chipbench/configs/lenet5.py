"""Plain reference of LeNet5-Caffe: conv 5x5 (20) -> max-pool 2 ->
conv 5x5 (50) -> max-pool 2 -> dense 500 + ReLU -> dense 10, softmax
cross-entropy.  Convolutions keep the spatial size ('SAME'), images are
NHWC and the flatten is in NHWC order.

Written from the architecture's description in ``jax.numpy``/``lax``; it
imports nothing of the program.  The parameter names are the program's
checkpoint layout (``c1``, ``c2``, ``f1``, ``f1b``, ``f2``, ``f2b``).
"""
import math

import jax
import jax.numpy as jnp


def _sizes(cfg):
    kh1, kw1, c1 = cfg["conv1"]
    kh2, kw2, c2 = cfg["conv2"]
    flat = (cfg["img_size"] // 4) ** 2 * c2
    return kh1, kw1, c1, kh2, kw2, c2, flat


def init_params(cfg, key):
    """He-normal kernels and zero biases from ``key``."""
    kh1, kw1, c1, kh2, kw2, c2, flat = _sizes(cfg)
    cin, fc1, ncls = cfg["img_channels"], cfg["fc1"], cfg["n_classes"]
    ks = jax.random.split(key, 4)

    def he(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * math.sqrt(2.0 / fan_in)

    return {
        "c1": he(ks[0], (kh1, kw1, cin, c1), kh1 * kw1 * cin),
        "c2": he(ks[1], (kh2, kw2, c1, c2), kh2 * kw2 * c1),
        "f1": he(ks[2], (flat, fc1), flat),
        "f1b": jnp.zeros((fc1,), jnp.float32),
        "f2": he(ks[3], (fc1, ncls), fc1),
        "f2b": jnp.zeros((ncls,), jnp.float32),
    }


def _conv(x, w):
    """'SAME' stride-1 convolution as one matrix product over the image's
    kh x kw patches (no convolution primitive: the TPU compiler takes
    minutes and tens of GiB of host memory for a float32 convolution at
    ``highest`` precision in a scanned training step)."""
    kh, kw, cin, cout = w.shape
    b, h, wd, _ = x.shape
    xp = jnp.pad(x, ((0, 0), ((kh - 1) // 2, kh // 2), ((kw - 1) // 2, kw // 2), (0, 0)))
    patches = jnp.concatenate(
        [xp[:, i:i + h, j:j + wd, :] for i in range(kh) for j in range(kw)], axis=-1)
    return patches @ w.reshape(kh * kw * cin, cout)


def _pool(x):
    """2 x 2 max-pool, stride 2."""
    b, h, w, c = x.shape
    return jnp.max(x.reshape(b, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def loss(cfg, params, batch):
    """Mean softmax cross-entropy of ``batch = {images, labels}``."""
    x = batch["images"].astype(params["c1"].dtype)
    x = _pool(_conv(x, params["c1"]))
    x = _pool(_conv(x, params["c2"]))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["f1"] + params["f1b"])
    logits = x @ params["f2"] + params["f2b"]
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold).astype(jnp.float32)


def tensor_rows(cfg, path):
    return 1


def flops_per_sample(cfg, traffic):
    """Operations that the forward and backward passes of one image require.

    Each convolution and dense product counts 2 operations per
    multiply-add: once forward, once for the weight gradient, and once for
    the input gradient except in the first layer, whose input is the image.
    Pooling, biases, ReLU and softmax count 0."""
    kh1, kw1, c1, kh2, kw2, c2, flat = _sizes(cfg)
    hw = cfg["img_size"] ** 2
    macs = [
        hw * c1 * kh1 * kw1 * cfg["img_channels"],  # conv1 at full resolution
        (hw // 4) * c2 * kh2 * kw2 * c1,  # conv2 after the first pool
        flat * cfg["fc1"],
        cfg["fc1"] * cfg["n_classes"],
    ]
    return float(2 * (3 * sum(macs) - macs[0]))
