"""Input kind ``images``: Gaussian class blobs.

Fixed class means (0.5 N(0,1)) plus the mix's ``noise`` N(0,1) (0.35 by
default), labels uniform over the configuration's ``n_classes``; images
of ``img_size`` x ``img_size`` x ``img_channels``, NHWC.
"""
import jax
import jax.numpy as jnp

TRAFFIC_KEYS = ()  # what the mix must state beyond its shape


def make(cfg, traffic, key, lead):
    size, ch, ncls = cfg["img_size"], cfg["img_channels"], cfg["n_classes"]
    k_means, k_lab, k_noise = jax.random.split(key, 3)
    means = 0.5 * jax.random.normal(k_means, (ncls, size, size, ch))
    labels = jax.random.randint(k_lab, lead, 0, ncls)
    noise = traffic.get("noise", 0.35) * jax.random.normal(
        k_noise, lead + (size, size, ch))
    return {"images": means[labels] + noise, "labels": labels.astype(jnp.int32)}
