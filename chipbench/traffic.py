"""The one generator of training inputs, driven by a cell's traffic file.

Everything is drawn from the seed, on the device, in one jitted call per
cell: a pool of ``POOL_ROUNDS`` rounds of batches, each with a leading
``(clients, delay)`` axis.  Round ``r`` of a run reads pool entry
``r % POOL_ROUNDS``, so the first rounds (which the correctness check
replays) all have distinct rows, and the timed window drives the program
with no input generation in it.

What a batch holds is the input kind that the configuration's ``inputs``
key names (``inputs/<kind>.py``, loaded by ``harness.input_kind``): its
``make(cfg, traffic, key, lead)`` returns the batch with leading axes
``lead``.  A new kind is a new file.
"""
import jax

POOL_ROUNDS = 8


def pool_shape(cfg, traffic):
    """Leading axes of one round's batch: (clients, delay, batch)."""
    return (traffic["clients"], traffic["delay"], traffic["batch"])


def make_pool(make, cfg, traffic, key):
    """``POOL_ROUNDS`` rounds of the input kind ``make``'s batches, leading
    axes ``(POOL_ROUNDS, clients, delay, batch)``, in one jitted call."""
    lead = (POOL_ROUNDS,) + pool_shape(cfg, traffic)
    return jax.jit(lambda k: make(cfg, traffic, k, lead))(key)
