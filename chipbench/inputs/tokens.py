"""Input kind ``tokens``: next-token batches whose ids follow Zipf's law.

Each row draws ``seq_len + 1`` ids.  Id i (0 .. V-1, V the
configuration's ``vocab_size``) has probability proportional to
(i + 1)^-ZIPF_S, sampled by inverse CDF; ``tokens`` holds a row's first
``seq_len`` ids and ``labels`` its last ``seq_len`` (labels[t] =
tokens[t + 1], the program's LM batch layout), both int32 of shape
``lead + (seq_len,)``.

Why Zipf: text is unigram-skewed, and skewed ids route unevenly over
experts, which uniform ids would hide.  ZIPF_S is Zipf's law for word
frequencies, exponent about 1 (G. K. Zipf, Human Behavior and the
Principle of Least Effort, 1949; S. T. Piantadosi, Psychon. Bull. Rev.
21:1112, 2014).  Rows are not packed documents: there is no segment mask.

The inverse CDF runs on 32-bit words: uniform uint32 draws against the
law's cumulative thresholds, worked out in float64 and scaled by 2^32.  A
float32 uniform is a multiple of 2^-23 (1.2e-7), coarser than one tail
id's share at a large vocabulary (about 5e-7 at 163,840 ids); a 32-bit
word resolves 2.3e-10.
"""
import jax
import jax.numpy as jnp
import numpy as np

ZIPF_S = 1.0
TRAFFIC_KEYS = ("seq_len",)  # what the mix must state beyond its shape


def thresholds(vocab):
    """uint32 t[0 .. V-2], increasing: the word w draws id #{t <= w}."""
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -ZIPF_S
    cdf = np.cumsum(w) / w.sum()
    return np.minimum(np.round(cdf[:-1] * 2.0**32), 2**32 - 1).astype(np.uint32)


def make(cfg, traffic, key, lead):
    seq = traffic["seq_len"]
    words = jax.random.bits(key, lead + (seq + 1,), jnp.uint32)
    ids = jnp.searchsorted(jnp.asarray(thresholds(cfg["vocab_size"])), words,
                           side="right").astype(jnp.int32)
    return {"tokens": ids[..., :seq], "labels": ids[..., 1:]}
