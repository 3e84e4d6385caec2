"""Plain reference of distributed SGD with Sparse Binary Compression.

Follows the paper's Alg. 1 and Alg. 2 with the configuration's Adam, one
client after another, in plain ``jax.numpy``.  It imports nothing of the
program and takes nothing that the program made: the weights and batches
come from the benchmark's own seeded generators.

One round, for every client c:

  1. W_c <- W, then ``delay`` local Adam steps on the client's
     microbatches (m, v persist per client; the bias correction counts
     every local step of the run);
  2. A_c = R_c + (W_c - W);
  3. SBC per tensor (a stacked leaf holds one tensor per layer): the k =
     max(1, min(n, round(p n))) largest and the k smallest entries, the
     side with the larger mean magnitude wins, its positions carry the
     signed mean mu; ``none`` sends A_c whole;
  4. R_c <- A_c - S_c; the optimizer's momentum is zeroed where S_c != 0.

Then W <- W + (1/C) sum_c S_c.  The reference works leaf by leaf so
that it fits beside a model that fills the chip: the device holds W, the
client's W_c, one step's gradient and the running mean (16 bytes a
float32 parameter), and one leaf's Adam update in flight (its W_c, m and
v in and out); every client's m, v and R_c, and the initial weights,
live on the host, each leaf moved to the device for its own update only.

``dtype`` sets the type of every array (the control runs the same code
in bfloat16); ``fault`` plants one of the faults the calibration reads
(see ``FAULTS``): half of every batch left out, the exchange left out
(client 0 applies its own update), or the first leaf's update doubled in
every round where the round produces it.
"""
import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("half_batch", "no_exchange", "scaled_update")


def leaf_paths(tree):
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append("/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path))
    return out


def k_for(n, p):
    return max(1, min(n, int(round(p * n))))


def _sbc_rows(x, p):
    """x: (rows, n) -> S with mu at the winning side's k positions per row."""
    rows, n = x.shape
    k = k_for(n, p)
    vp, ip = jax.lax.top_k(x, k)
    vn, ineg = jax.lax.top_k(-x, k)
    mp, mn = jnp.mean(vp, axis=1), jnp.mean(vn, axis=1)
    pos = mp > mn
    idx = jnp.where(pos[:, None], ip, ineg)
    mu = jnp.where(pos, mp, -mn)
    return jnp.zeros_like(x).at[jnp.arange(rows)[:, None], idx].set(
        jnp.broadcast_to(mu[:, None], idx.shape))


class Reference:
    def __init__(self, mod, cfg, traffic, *, dtype=jnp.float32, fault=None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
        self.mod, self.cfg, self.traffic = mod, cfg, traffic
        self.dtype, self.fault = dtype, fault
        self.opt = cfg["local_opt"]
        if self.opt["name"] != "adam":
            raise ValueError(f"the reference knows Adam, not {self.opt['name']!r}")
        self.clients, self.delay = traffic["clients"], traffic["delay"]
        self._grad = jax.jit(jax.value_and_grad(self._loss))
        self._adam = jax.jit(self._adam_leaf)
        self._compress = jax.jit(self._compress_leaf, static_argnums=1)

    # ------------------------------------------------------------ one client

    def _loss(self, params, batch):
        if self.fault == "half_batch":
            batch = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
        return self.mod.loss(self.cfg, params, batch)

    def _adam_leaf(self, w, m, v, g, t):
        """One Adam step of one leaf; ``t`` counts the optimizer's steps
        with this one."""
        o, dt = self.opt, self.dtype
        f32 = jnp.float32
        # scalars stay float32 (0.999 is 1 in bfloat16); every array is
        # rounded back to ``dt`` after each update
        lr = jnp.asarray(o["lr"], f32)
        cast = lambda x: x.astype(dt)  # noqa: E731
        b1, b2 = jnp.asarray(o["b1"], f32), jnp.asarray(o["b2"], f32)
        m = cast(b1 * m + (1 - b1) * g)
        v = cast(b2 * v + (1 - b2) * g * g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        w = cast(w - lr * (m / c1) / (jnp.sqrt(v / c2) + o["eps"]))
        return w, m, v

    def _compress_leaf(self, x, rows):
        if self.traffic["compressor"] == "none":
            return x
        return _sbc_rows(x.reshape(rows, -1), self.traffic["sparsity"]).reshape(x.shape)

    def _client(self, w, treedef, rows, host, batch, step0):
        """One client's round from the weights ``w`` (device leaves).
        ``host`` = (m, v, r), the client's Adam moments and residual as
        host leaves; each leaf visits the device only for its own update.
        Returns the client's S_c (device leaves), its mean loss over the
        local steps and its new (m, v, r) on the host."""
        dev = next(iter(w[0].devices()))
        m, v, r = (list(x) for x in host)
        wc, losses = list(w), []
        for j in range(self.delay):
            step = jax.tree.map(lambda x: x[j], batch)
            loss, g = self._grad(jax.tree.unflatten(treedef, wc), step)
            losses.append(loss)
            t = jnp.asarray(step0 + j + 1, jnp.float32)
            for i, gi in enumerate(jax.tree.leaves(g)):
                wc[i], mi, vi = self._adam(wc[i], jax.device_put(m[i], dev),
                                           jax.device_put(v[i], dev), gi, t)
                m[i], v[i] = jax.device_get((mi, vi))
                del mi, vi  # else held on the device through the next update
            del g
        s = []
        for i, n_rows in enumerate(rows):
            acc = jax.device_put(r[i], dev) + (wc[i] - w[i])
            wc[i] = None
            s.append(self._compress(acc, n_rows))
            r[i] = jax.device_get(acc - s[i])
            del acc
            m[i] = jax.device_get(jnp.where(s[i] != 0, 0, jax.device_put(m[i], dev)))
        return s, float(jnp.mean(jnp.stack(losses))), (m, v, r)

    # ----------------------------------------------------------------- rounds

    def run(self, params0, batches, rounds=3, device=None):
        """``rounds`` rounds from ``params0`` (host arrays) on ``device``;
        ``batches[r]`` leads with (clients, delay).  Returns the evidence
        the comparison reads: per-round losses, per-leaf gradient evidence
        after round 1 and per-leaf norms of the parameters' change after
        ``rounds``."""
        dt, device = self.dtype, device or jax.devices()[0]
        paths = leaf_paths(params0)
        w, treedef = jax.tree.flatten(
            jax.tree.map(lambda x: jax.device_put(x, device).astype(dt), params0))
        rows = [self.mod.tensor_rows(self.cfg, path) for path in paths]
        zeros = lambda: [np.zeros(x.shape, x.dtype) for x in w]  # noqa: E731
        state = [(zeros(), zeros(), zeros()) for _ in range(self.clients)]
        losses, grad_evidence = [], None
        for r in range(rounds):
            round_losses, mean = [], [None] * len(w)
            for c in range(self.clients):
                batch = jax.tree.map(lambda x: x[c], batches[r])
                if "images" in batch:
                    batch["images"] = batch["images"].astype(dt)
                s, loss, state[c] = self._client(w, treedef, rows, state[c], batch,
                                                 r * self.delay)
                round_losses.append(loss)
                if self.fault == "no_exchange":  # client 0 applies its own
                    mean = s if c == 0 else mean
                    continue
                for i in range(len(s)):  # clients added in order, as sum_c S_c / C
                    part, s[i] = s[i] / self.clients, None
                    mean[i] = part if c == 0 else mean[i] + part
            if self.fault == "scaled_update":
                mean[0] = mean[0] * 2
            w = [a + b for a, b in zip(w, mean)]
            del mean
            losses.append(float(np.mean(round_losses)))
            if r == 0:
                grad_evidence = adam_evidence(
                    [jax.tree.unflatten(treedef, v) for _, v, _ in state])
        change, support = np.asarray(change_evidence(jax.tree.unflatten(treedef, w),
                                                     params0))
        return {"losses": losses, "grad": grad_evidence, "change": change,
                "support": support, "paths": paths}


@jax.jit
def change_evidence(a, b):
    """Per leaf, the norm of a - b and the number of entries that differ
    (as float32 both sides hold them)."""
    pairs = [(x.astype(jnp.float32), y.astype(jnp.float32))
             for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    return jnp.stack([
        jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x - y))) for x, y in pairs]),
        jnp.stack([jnp.sum(x != y).astype(jnp.float32) for x, y in pairs])])


@jax.jit
def leaf_sums(tree):
    return jnp.stack([jnp.sum(x.astype(jnp.float32)) for x in jax.tree.leaves(tree)])


def adam_evidence(vs):
    """Per-leaf evidence of the first gradient in Adam's state, summed over
    the given client trees (or one tree with a leading client axis):
    sqrt(sum v), as v = (1 - b2) g^2 after one step and is never masked."""
    return np.sqrt(sum(np.asarray(leaf_sums(v), np.float64) for v in vs))
