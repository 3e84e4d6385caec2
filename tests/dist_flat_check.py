"""Executed by test_dist_flat.py in a subprocess with 8 fake CPU devices.

Parity of the §11 sharded flat dist exchange against the per-leaf
shard_map path on a (2, 2, 2) ('pod', 'data', 'model') mesh — the ISSUE 4
acceptance matrix:

  * both client modes ('data': 4 clients, 'pod': 2 clients),
  * aggregated params BIT-IDENTICAL per step,
  * the flat sharded residual, viewed as a pytree, BIT-IDENTICAL to the
    per-leaf residual,
  * momentum state bit-identical (exercises the own/ΔW*_i masking path),
  * static Eq. 1/Eq. 5 bit accounting exactly equal,
  * a mixed per-leaf policy (sparse + dense-small + skip) rides the same
    flat buffer,
  * the Pallas hist engine ('flat_engine="hist"') executes inside
    shard_map (loss finite, params move; approximate by design).

Prints CHECK lines; the pytest wrapper asserts on them.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # forced devices are CPU-only

import jax
import jax.numpy as jnp
import numpy as np

from repro.paths import use_compile_cache

use_compile_cache()  # the suite's cache (conftest.py does the same)

from repro.configs.base import ModelConfig
from repro.core.codec import make_codec
from repro.core.policy import DENSE_SMALL_PATTERN, CompressionPolicy, PolicyRule
from repro.launch.dist import client_topology, make_dist_train
from repro.launch.mesh import make_mesh
from repro.models.model import build_model

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))


def tiny(client_mode):
    return ModelConfig(
        name="tiny", family="decoder", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=96, dtype=jnp.float32,
        client_mode=client_mode, local_opt="momentum", base_lr=0.05,
        scan_layers=True,
    )


def mixed_policy(fast):
    return CompressionPolicy(
        default=make_codec("sbc"),
        rules=(PolicyRule(r"(^|/)wv(/|$)", codec="skip"),
               PolicyRule(DENSE_SMALL_PATTERN, codec="dense32")),
        name="sbc+rules",
        fast=fast,
    )


def tree_bytes_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb)
    )


def make_batch(cfg, n_clients, seed=1):
    rng = jax.random.PRNGKey(seed)
    per = max(8 // n_clients, 2)
    return {
        "tokens": jax.random.randint(rng, (n_clients, per, 16), 0, 96),
        "labels": jax.random.randint(rng, (n_clients, per, 16), 0, 96),
    }


def run_parity(client_mode, policy_fn=None, tag=""):
    cfg = tiny(client_mode)
    model = build_model(cfg)
    kw = {}
    if policy_fn is not None:
        kw["policy"] = policy_fn(False)
    slow = make_dist_train(cfg, mesh, sparsity=0.05, model=model, **kw)
    if policy_fn is not None:
        kw["policy"] = policy_fn(True)
    fast = make_dist_train(cfg, mesh, sparsity=0.05, model=model, fast=True, **kw)
    assert fast.flat_space is not None, "sharded flat fast path did not engage"
    n_clients, _ = client_topology(cfg, mesh)

    bits_ok = (slow.bits_per_client == fast.bits_per_client
               and slow.bits_dense == fast.bits_dense)
    batch = make_batch(cfg, n_clients)
    states = {}
    for name, fns in (("slow", slow), ("fast", fast)):
        state = jax.device_put(
            fns.init_state(jax.random.PRNGKey(0)), fns.state_shardings
        )
        b = jax.device_put(batch, fns.batch_shardings(batch))
        for _ in range(3):
            state, metrics = fns.train_step(state, b)
        states[name] = (state, metrics)

    s_state, s_metrics = states["slow"]
    f_state, f_metrics = states["fast"]
    params_ok = tree_bytes_equal(s_state["params"], f_state["params"])
    opt_ok = tree_bytes_equal(s_state["opt"], f_state["opt"])
    res_ok = tree_bytes_equal(
        s_state["residual"], fast.residual_to_tree(f_state["residual"])
    )
    loss_ok = float(s_metrics["loss"]) == float(f_metrics["loss"])
    label = tag or client_mode
    print(f"CHECK {label} params_identical={params_ok} "
          f"residual_identical={res_ok} opt_identical={opt_ok} "
          f"bits_identical={bits_ok} loss_identical={loss_ok} "
          f"bits={fast.bits_per_client:.6e}")
    return params_ok and res_ok and opt_ok and bits_ok and loss_ok


def run_hist_smoke():
    cfg = tiny("data")
    model = build_model(cfg)
    fns = make_dist_train(cfg, mesh, sparsity=0.05, model=model, fast=True,
                          flat_engine="hist")
    n_clients, _ = client_topology(cfg, mesh)
    batch = make_batch(cfg, n_clients)
    state = jax.device_put(
        fns.init_state(jax.random.PRNGKey(0)), fns.state_shardings
    )
    b = jax.device_put(batch, fns.batch_shardings(batch))
    p0 = jax.tree.map(lambda x: x.copy(), state["params"])
    state, metrics = fns.train_step(state, b)
    finite = bool(jnp.isfinite(metrics["loss"]))
    moved = any(
        bool(jnp.any(a != c))
        for a, c in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(p0))
    )
    print(f"CHECK hist loss_finite={finite} moved={moved}")
    return finite and moved


if __name__ == "__main__":
    ok = run_parity("data")
    ok &= run_parity("pod")
    ok &= run_parity("data", policy_fn=mixed_policy, tag="data+policy")
    ok &= run_hist_smoke()
    print(f"CHECK all_parity_ok={bool(ok)}")
