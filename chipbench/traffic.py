"""The one generator of training inputs, driven by a cell's traffic file.

Everything is drawn from the seed, on the device, in one jitted call per
cell: a pool of ``POOL_ROUNDS`` rounds of batches, each with a leading
``(clients, delay)`` axis.  Round ``r`` of a run reads pool entry
``r % POOL_ROUNDS``, so the first rounds (which the correctness check
replays) all have distinct rows, and the timed window drives the program
with no input generation in it.

Input kinds, named by the configuration's ``inputs`` key:

  images  Gaussian class blobs: fixed class means (0.5 N(0,1)) plus
          ``noise`` N(0,1), labels uniform over the classes.
"""
import jax
import jax.numpy as jnp

POOL_ROUNDS = 8


def pool_shape(cfg, traffic):
    """Leading axes of one round's batch: (clients, delay, batch)."""
    return (traffic["clients"], traffic["delay"], traffic["batch"])


def _images(cfg, traffic, key, lead):
    size, ch, ncls = cfg["img_size"], cfg["img_channels"], cfg["n_classes"]
    k_means, k_lab, k_noise = jax.random.split(key, 3)
    means = 0.5 * jax.random.normal(k_means, (ncls, size, size, ch))
    labels = jax.random.randint(k_lab, lead, 0, ncls)
    noise = traffic.get("noise", 0.35) * jax.random.normal(
        k_noise, lead + (size, size, ch))
    return {"images": means[labels] + noise, "labels": labels.astype(jnp.int32)}


KINDS = {"images": _images}


def make_pool(cfg, traffic, key):
    """``POOL_ROUNDS`` rounds of batches, leading axes
    ``(POOL_ROUNDS, clients, delay, batch)``, in one jitted call."""
    lead = (POOL_ROUNDS,) + pool_shape(cfg, traffic)
    make = KINDS[cfg["inputs"]]
    return jax.jit(lambda k: make(cfg, traffic, k, lead))(key)
