"""Plain reference of distributed SGD with Sparse Binary Compression.

Follows the paper's Alg. 1 and Alg. 2 with the configuration's Adam, one client after another, in plain ``jax.numpy``.  It
imports nothing of the program and takes nothing that the program made:
the weights and batches come from the benchmark's own seeded generators.

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

Then W <- W + (1/C) sum_c S_c.  ``dtype`` sets the type of every array
(the control runs the same code in bfloat16); ``fault`` plants one of the
faults the calibration reads (see ``FAULTS``): half of every batch left
out, the exchange left out (client 0 applies its own update), or the
first leaf's update doubled in every round where the round produces it.
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
        self._local = jax.jit(self._local_steps)
        self._compress = jax.jit(self._compress_tree)

    # ------------------------------------------------------------ one client

    def _loss(self, params, batch):
        if self.fault == "half_batch":
            batch = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
        return self.mod.loss(self.cfg, params, batch)

    def _local_steps(self, w, m, v, batches, t0):
        """``delay`` optimizer steps from w; batches lead with the delay
        axis; ``t0`` is the optimizer's step count before the first."""
        o, dt = self.opt, self.dtype
        f32 = jnp.float32
        # scalars stay float32 (0.999 is 1 in bfloat16); every array is
        # rounded back to ``dt`` after each update
        lr = jnp.asarray(o["lr"], f32)
        cast = lambda x: x.astype(dt)  # noqa: E731

        def one(carry, batch):
            w, m, v, t = carry
            loss, g = jax.value_and_grad(self._loss)(w, batch)
            b1, b2 = jnp.asarray(o["b1"], f32), jnp.asarray(o["b2"], f32)
            t = t + 1.0
            m = jax.tree.map(lambda m, g: cast(b1 * m + (1 - b1) * g), m, g)
            v = jax.tree.map(lambda v, g: cast(b2 * v + (1 - b2) * g * g), v, g)
            c1, c2 = 1 - b1 ** t, 1 - b2 ** t
            w = jax.tree.map(
                lambda w, m, v: cast(w - lr * (m / c1) / (jnp.sqrt(v / c2) + o["eps"])),
                w, m, v)
            return (w, m, v, t), loss

        (w, m, v, _), losses = jax.lax.scan(one, (w, m, v, t0), batches)
        return w, m, v, jnp.mean(losses)

    def _compress_tree(self, acc):
        if self.traffic["compressor"] == "none":
            return acc
        p = self.traffic["sparsity"]
        paths = leaf_paths(acc)
        leaves, treedef = jax.tree.flatten(acc)
        out = []
        for path, x in zip(paths, leaves):
            rows = self.mod.tensor_rows(self.cfg, path)
            out.append(_sbc_rows(x.reshape(rows, -1), p).reshape(x.shape))
        return jax.tree.unflatten(treedef, out)

    # ----------------------------------------------------------------- rounds

    def run(self, params0, batches, rounds=3):
        """``rounds`` rounds from ``params0``; ``batches[r]`` leads with
        (clients, delay).  Returns the evidence the comparison reads:
        per-round losses, per-leaf gradient evidence after round 1 and
        per-leaf norms of the parameters' change after ``rounds``."""
        dt = self.dtype
        cast = lambda t: jax.tree.map(lambda x: x.astype(dt), t)
        zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
        w = cast(params0)
        ms = [zeros(w) for _ in range(self.clients)]
        vs = [zeros(w) for _ in range(self.clients)]
        rs = [zeros(w) for _ in range(self.clients)]
        losses, grad_evidence = [], None
        for r in range(rounds):
            step0 = r * self.delay
            round_losses, mean = [], None
            for c in range(self.clients):
                batch = jax.tree.map(lambda x: x[c], batches[r])
                if "images" in batch:
                    batch["images"] = batch["images"].astype(dt)
                wc, ms[c], vs[c], loss = self._local(
                    w, ms[c], vs[c], batch, jnp.asarray(step0, jnp.float32))
                acc = jax.tree.map(lambda a, b, res: res + (a - b), wc, w, rs[c])
                del wc
                s = self._compress(acc)
                rs[c] = jax.tree.map(lambda a, b: a - b, acc, s)
                del acc
                ms[c] = jax.tree.map(lambda m, x: jnp.where(x != 0, 0, m), ms[c], s)
                round_losses.append(float(loss))
                if self.fault == "no_exchange":  # client 0 applies its own
                    mean = s if c == 0 else mean
                else:  # clients added in order, as sum_c S_c / C
                    part = jax.tree.map(lambda x: x / self.clients, s)
                    mean = part if mean is None else jax.tree.map(jnp.add, mean, part)
                del s
            if self.fault == "scaled_update":
                leaves, treedef = jax.tree.flatten(mean)
                mean = jax.tree.unflatten(treedef, [leaves[0] * 2] + leaves[1:])
            w = jax.tree.map(lambda a, b: a + b, w, mean)
            del mean
            losses.append(float(np.mean(round_losses)))
            if r == 0:
                grad_evidence = adam_evidence(vs)
        change, support = np.asarray(change_evidence(w, params0))
        return {"losses": losses, "grad": grad_evidence, "change": change,
                "support": support, "paths": leaf_paths(params0)}


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
