"""CPU-only count and parity check: sharded flat exchange vs per-leaf
shard_map on 8 forced host devices.

This runs on XLA's CPU backend (forced host devices exist only there, so
the child pins ``JAX_PLATFORMS=cpu`` even on an accelerator host).  Its
byte counts and parity flags are exact; its times are CPU-backend wall
times, never a device metric — chip timing belongs to the chip benchmark.

Runs one full DSGD train_step — local steps, residual add, per-shard
SBC compression, cross-client exchange, momentum masking — on a forced
8-device host mesh ((2, 2, 2) 'pod'/'data'/'model'), two ways:

  per-leaf    the PR 2 shard_map exchange: one lax.scan of top-k per leaf
              and 2 all_gathers per leaf per client axis.
  flat        the §11 ``ShardedFlatParamSpace`` exchange: every device
              compresses its shard of ONE block-padded flat buffer, one
              fused scatter, one packed (positions, μ) all_gather per
              client axis, flat sharded residual state.

It then measures one WIRE ROUND — a communication round where the Golomb
bitstream is the cohort transport, end to end through to the aggregated
mean — two ways:

  per-leaf + host wire    exchange over raw index arrays, then the host
                          produces every client's transport bytes
                          (``golomb.encode_positions_packed`` per row) and
                          the server decodes every stream back to
                          positions (``golomb.decode_positions``, the
                          parameter-server hot path).
  flat + device pack      the §11 fused select→pack kernels: the exchange
                          all_gathers PACKED uint32 words (the transport
                          itself), decodes them on-device, and the wire
                          bytes are a truncating copy of the word buffer.

Both step paths must produce bit-identical parameters, and both wire
paths byte-identical streams (asserted here; the full parity matrix
lives in tests/dist_flat_check.py and tests/test_channel_parity.py).
Because forcing host devices needs XLA_FLAGS before jax initializes, the
measurement runs in a subprocess; ``--child`` is that entry point.

  PYTHONPATH=src python -m benchmarks.dist_flat            # quick
  PYTHONPATH=src python -m benchmarks.dist_flat --smoke    # CI-sized
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MARK = "DIST_FLAT_JSON "
N_DEVICES = 8
MIN_WIRE_SPEEDUP = 1.15


def _bench_child(repeats: int) -> dict:
    """Runs under 8 forced host devices (see main): the actual timing."""
    import statistics
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import ModelConfig
    from repro.core import golomb
    from repro.core.channel import _iter_shard_blocks
    from repro.launch.dist import _lead_spec, build_dist_train, client_topology
    from repro.launch.mesh import make_mesh
    from repro.models.model import build_model, make_param_specs

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = ModelConfig(
        name="bench", family="decoder", n_layers=4, d_model=128, n_heads=4,
        n_kv_heads=2, d_ff=256, vocab_size=256, dtype=jnp.float32,
        client_mode="data", local_opt="momentum", base_lr=0.05,
        scan_layers=True,
    )
    model = build_model(cfg)
    n_clients, client_axes = client_topology(cfg, mesh)
    sparsity = 0.01
    per_leaf = build_dist_train(cfg, mesh, sparsity=sparsity, model=model)
    flat = build_dist_train(cfg, mesh, sparsity=sparsity, model=model, fast=True)
    packed = build_dist_train(
        cfg, mesh, sparsity=sparsity, model=model, fast=True, device_pack=True
    )
    assert flat.flat_space is not None

    rng = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(rng, (n_clients, 2, 64), 0, 256),
        "labels": jax.random.randint(rng, (n_clients, 2, 64), 0, 256),
    }

    states, batches = {}, {}
    for name, fns in (("per_leaf", per_leaf), ("flat", flat)):
        states[name] = jax.device_put(
            fns.init_state(jax.random.PRNGKey(0)), fns.state_shardings
        )
        batches[name] = jax.device_put(batch, fns.batch_shardings(batch))

    # correctness anchor: one step from identical inits, identical params
    # (also the compile call — the flat path lowers O(1) collectives
    # instead of O(leaves), which shows up as compile time on every mesh)
    t0 = time.perf_counter()
    s_pl, m = per_leaf.train_step(states["per_leaf"], batches["per_leaf"])
    jax.block_until_ready(m["loss"])
    compile_pl = time.perf_counter() - t0
    t0 = time.perf_counter()
    s_fl, m = flat.train_step(states["flat"], batches["flat"])
    jax.block_until_ready(m["loss"])
    compile_fl = time.perf_counter() - t0
    parity = all(
        np.asarray(a).tobytes() == np.asarray(b).tobytes()
        for a, b in zip(
            jax.tree.leaves(s_pl["params"]), jax.tree.leaves(s_fl["params"])
        )
    )
    # device-pack path: same step from the same init must land on the
    # same parameters (the packed words ride along, they never perturb)
    s_pk, m = packed.train_step(
        jax.device_put(
            packed.init_state(jax.random.PRNGKey(0)), packed.state_shardings
        ),
        jax.device_put(batch, packed.batch_shardings(batch)),
    )
    jax.block_until_ready(m["loss"])
    pack_parity = all(
        np.asarray(a).tobytes() == np.asarray(b).tobytes()
        for a, b in zip(
            jax.tree.leaves(s_fl["params"]), jax.tree.leaves(s_pk["params"])
        )
    )
    states = {"per_leaf": s_pl, "flat": s_fl}

    # snapshot the 1-step residuals for the wire round now — the timing
    # loop below donates s_pl's buffers, and the wire paths must see
    # IDENTICAL residual content (one local step from the same init, where
    # parity holds) or their byte totals drift apart
    res_pl = jax.tree.map(jnp.copy, s_pl["residual"])
    res_pk = s_pk["residual"]

    # interleaved timing so ambient load hits both paths alike
    fns_by = {"per_leaf": per_leaf, "flat": flat}
    samples: dict = {"per_leaf": [], "flat": []}
    for _ in range(repeats):
        for name in samples:
            t0 = time.perf_counter()
            states[name], m = fns_by[name].train_step(
                states[name], batches[name]
            )
            jax.block_until_ready(m["loss"])
            samples[name].append(time.perf_counter() - t0)
    t_pl = statistics.median(samples["per_leaf"])
    t_fl = statistics.median(samples["flat"])

    # ---------------------------------------------------------- wire round
    # Time the exchange as a TRANSPORT round: compressed bytes in, mean
    # out, for the whole cohort.  The per-leaf path exchanges raw index
    # arrays, so the host must still produce every client's bitstream and
    # the server must decode every stream; the device-pack exchange
    # gathers the packed words themselves and decodes on-device, so its
    # wire bytes are a truncating copy.
    ch_pl, ch_pk = per_leaf.channel, packed.channel
    a_params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_specs = make_param_specs(
        a_params, mesh, fsdp=cfg.fsdp, expert_parallel=False
    )
    flat_specs = tuple(
        jax.tree.leaves(p_specs, is_leaf=lambda s: isinstance(s, P))
    )
    lead = _lead_spec(client_axes)
    round_specs = tuple(P(lead, *s) for s in flat_specs)
    shard_axes = tuple(a for a in mesh.axis_names if a not in client_axes)
    res_spec = P(lead, _lead_spec(shard_axes), None)

    deltas = jax.tree.map(
        lambda p: 0.01 * jax.random.normal(
            jax.random.PRNGKey(2), (n_clients,) + p.shape, jnp.float32
        ),
        states["per_leaf"]["params"],
    )
    deltas = jax.device_put(
        deltas,
        jax.tree.unflatten(
            jax.tree.structure(deltas),
            [NamedSharding(mesh, s) for s in round_specs],
        ),
    )
    ex_pl = jax.jit(lambda res, d: ch_pl.round_exchange(
        res, d, mesh=mesh, in_specs=round_specs, res_spec=res_spec,
        need_own=True,
    ))
    ex_pk = jax.jit(lambda res, d: ch_pk.round_exchange(
        res, d, mesh=mesh, in_specs=round_specs, res_spec=res_spec,
        need_own=True,
    ))
    space = ch_pk.flat_space
    dense_bytes = sum(
        4 * int(np.prod(gl.global_shape) or 1)
        for gl in ch_pl.leaves if gl.mode == "dense"
    )

    def wire_round_pl() -> int:
        mean, _, own = ex_pl(res_pl, deltas)
        jax.block_until_ready(jax.tree.leaves(mean)[0])
        nbytes = n_clients * dense_bytes
        for c in range(n_clients):
            own_c = jax.tree.map(lambda o: np.asarray(o[c]), own)
            for gl, leaf in zip(ch_pl.leaves, jax.tree.leaves(own_c)):
                if gl.mode != "sparse":
                    continue
                for block in _iter_shard_blocks(np.asarray(leaf), gl.shard_grid):
                    L = block.shape[0] if gl.scanned and block.ndim > 1 else 1
                    for row in block.reshape(L, -1):
                        pos = np.flatnonzero(row)
                        blob, nb = golomb.encode_positions_packed(pos, gl.rate)
                        nbytes += len(blob) + 4  # +32-bit μ
                        bits = np.unpackbits(np.frombuffer(blob, np.uint8))[:nb]
                        golomb.decode_positions(bits, gl.rate)
        return nbytes

    def wire_round_pk() -> int:
        mean, _, own, (words, nbits, _) = ex_pk(res_pk, deltas)
        jax.block_until_ready(jax.tree.leaves(mean)[0])
        w_all = np.asarray(jax.device_get(words))
        nb_all = np.asarray(jax.device_get(nbits))
        n_dev = w_all.shape[1]
        nbytes = n_clients * dense_bytes
        for c in range(n_clients):
            for s_ in range(n_dev):
                mi = 0
                for seg, (_, w, off) in zip(space._sparse, space._pack_info):
                    reps = n_dev // seg.n_shards
                    for r in range(seg.rows):
                        if s_ % reps == 0:  # distinct shard replicas only
                            blob = golomb.packed_words_to_bytes(
                                w_all[c, s_, off + r * w: off + (r + 1) * w],
                                int(nb_all[c, s_, mi]),
                            )
                            nbytes += len(blob) + 4
                        mi += 1
        return nbytes

    wire_bytes_pl = wire_round_pl()  # compile + 1st
    wire_bytes_pk = wire_round_pk()
    wire_samples: dict = {"pl": [], "pk": []}
    for _ in range(repeats):
        t0 = time.perf_counter()
        wire_round_pl()
        wire_samples["pl"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        wire_round_pk()
        wire_samples["pk"].append(time.perf_counter() - t0)
    t_wire_pl = statistics.median(wire_samples["pl"])
    t_wire_pk = statistics.median(wire_samples["pk"])

    n_params = sum(
        x.size for x in jax.tree.leaves(states["flat"]["params"])
    )
    return {
        "n_devices": N_DEVICES,
        "mesh": "2x2x2 pod/data/model",
        "client_mode": cfg.client_mode,
        "n_clients": n_clients,
        "n_params": n_params,
        "sparsity": sparsity,
        "repeats": repeats,
        "per_leaf_step_ms": 1e3 * t_pl,
        "flat_step_ms": 1e3 * t_fl,
        "speedup": t_pl / t_fl,
        "per_leaf_compile_s": compile_pl,
        "flat_compile_s": compile_fl,
        "compile_speedup": compile_pl / compile_fl,
        "per_leaf_wire_ms": 1e3 * t_wire_pl,
        "device_pack_wire_ms": 1e3 * t_wire_pk,
        "wire_speedup": t_wire_pl / t_wire_pk,
        "wire_bytes": wire_bytes_pk,
        "wire_bytes_equal": wire_bytes_pl == wire_bytes_pk,
        "bits_per_client": flat.bits_per_client,
        "bits_equal": per_leaf.bits_per_client == flat.bits_per_client,
        "parity": bool(parity),
        "pack_parity": bool(pack_parity),
    }


def run(quick: bool = True) -> dict:
    """Spawn the 8-device child, collect and persist its measurement."""
    from benchmarks.common import save_json

    repeats = 5 if quick else 15
    env = dict(os.environ)
    # forced host devices only exist on the CPU backend — pin it so the
    # child's 8-device mesh builds on GPU/TPU dev boxes too
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={N_DEVICES} "
        + env.get("XLA_FLAGS", "")
    )
    env["PYTHONPATH"] = (
        os.path.join(ROOT, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "benchmarks.dist_flat",
            "--child",
            "--repeats",
            str(repeats),
        ],
        capture_output=True,
        text=True,
        timeout=1800,
        env=env,
        cwd=ROOT,
    )
    out = proc.stdout + proc.stderr
    if proc.returncode != 0:
        raise RuntimeError(f"dist_flat child failed:\n{out[-3000:]}")
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith(MARK):
            payload = json.loads(line[len(MARK):])
    assert payload is not None, out[-3000:]
    assert payload["parity"], "flat and per-leaf params diverged"
    assert payload["pack_parity"], "device-pack and flat params diverged"
    assert payload["bits_equal"], "Eq. 1 bit accounting diverged"
    assert payload["wire_bytes_equal"], "wire byte totals diverged"
    assert payload["wire_speedup"] >= MIN_WIRE_SPEEDUP, (
        f"device-pack wire round speedup {payload['wire_speedup']:.2f} "
        f"< {MIN_WIRE_SPEEDUP}"
    )
    print(
        f"{payload['n_devices']} devices, {payload['n_clients']} clients, "
        f"{payload['n_params']} params, p={payload['sparsity']}"
    )
    print(
        f"per-leaf {payload['per_leaf_step_ms']:.1f} ms/step   "
        f"flat {payload['flat_step_ms']:.1f} ms/step   "
        f"x{payload['speedup']:.2f}  (parity={payload['parity']})"
    )
    print(
        f"compile: per-leaf {payload['per_leaf_compile_s']:.1f} s   "
        f"flat {payload['flat_compile_s']:.1f} s   "
        f"x{payload['compile_speedup']:.2f}"
    )
    print(
        f"wire round: host {payload['per_leaf_wire_ms']:.1f} ms   "
        f"device-pack {payload['device_pack_wire_ms']:.1f} ms   "
        f"x{payload['wire_speedup']:.2f}  "
        f"({payload['wire_bytes']} bytes, equal={payload['wire_bytes_equal']})"
    )
    path = save_json("dist_flat", payload)
    print(f"wrote {path}")
    return payload


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="CI-sized run (default size)")
    ap.add_argument("--full", action="store_true", help="more timing repeats")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--repeats", type=int, default=5)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.child:
        payload = _bench_child(args.repeats)
        print(MARK + json.dumps(payload))
        return
    run(quick=not args.full)


if __name__ == "__main__":
    main()
