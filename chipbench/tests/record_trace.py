"""Record the small chip trace that ``test_trace_reduce`` reads.

    python3 chipbench/tests/record_trace.py   # on a host with TPU chips

Three steps of a jitted matmul (followed, with more than one chip, by an
all-gather over up to four of them), each step inside the benchmark's
own ``bench.step`` / ``bench.wait`` annotations, traced by the JAX
profiler.  Writes ``data/v5e_trace.xplane.pb.gz`` and, from the profiler's
own Perfetto export of the same trace, ``data/v5e_trace.json``: per device the
busy time and the collective time that no other op covers, worked out
from that export with plain ``json`` — the second witness the test holds
the reduction to.
"""
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
COLLECTIVE = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
              "all-to-all")


def union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def uncovered(a, b):
    """Length of the intervals ``a`` outside the intervals ``b``, by a
    sweep over both sets' edges."""
    edges = sorted({x for iv in a + b for x in iv})
    inside = lambda iv, t: any(s <= t < e for s, e in iv)  # noqa: E731
    return sum(hi - lo for lo, hi in zip(edges, edges[1:])
               if inside(a, lo) and not inside(b, lo))


def from_perfetto(path):
    """Per device: busy ns (``X`` events on the device's ``XLA Ops``
    thread) and exposed collective ns (collectives on that thread or on
    ``Async XLA Ops``, outside every other ``XLA Ops`` event), from the
    Perfetto export, whose times are in us."""
    trace = json.loads(gzip.open(path).read())
    events = trace["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    ops = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        dev = procs.get(e["pid"], "")
        line = threads.get((e["pid"], e["tid"]))
        if not dev.startswith("/device:TPU:") or line not in ("XLA Ops", "Async XLA Ops"):
            continue
        s, d = float(e["ts"]) * 1e3, float(e.get("dur", 0)) * 1e3
        own = e["name"].split(" = ", 1)[0].lower()
        ops.setdefault(dev, []).append(
            (s, s + d, any(w in own for w in COLLECTIVE), line == "XLA Ops"))
    busy = {d: sum(e - s for s, e in union([(s, e) for s, e, _, sync in v if sync]))
            for d, v in ops.items()}
    exposed = {d: uncovered(union([(s, e) for s, e, c, _ in v if c]),
                            union([(s, e) for s, e, c, sync in v if sync and not c]))
               for d, v in ops.items()}
    return sorted(ops), busy, exposed


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    n = min(4, len(devs))
    mesh = Mesh(np.asarray(devs[:n]), ("d",))
    x = jax.device_put(jnp.ones((n * 512, 512), jnp.float32), NamedSharding(mesh, P("d")))

    @jax.jit
    def step(x):
        y = jnp.tanh(x @ x[:512].T)
        return jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P()))  # all-gather

    step(x).block_until_ready()
    out = tempfile.mkdtemp(prefix="record_trace_")
    with jax.profiler.trace(out):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                y = step(x)
            with jax.profiler.TraceAnnotation("bench.wait"):
                y.block_until_ready()
    pb = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    pf = glob.glob(os.path.join(out, "**", "*.trace.json.gz"), recursive=True)[0]
    devices, busy, exposed = from_perfetto(pf)
    data = os.path.join(HERE, "data")
    os.makedirs(data, exist_ok=True)
    with open(pb, "rb") as src, gzip.open(os.path.join(data, "v5e_trace.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(data, "v5e_trace.json"), "w") as f:
        json.dump({"devices": devices, "busy_ns": busy, "exposed_ns": exposed, "steps": 3,
                   "source": f"TPU v5 lite x{n}, jax {jax.__version__}"}, f, indent=1)
    shutil.rmtree(out, ignore_errors=True)
    print(json.dumps({"devices": devices, "busy_ns": busy, "exposed_ns": exposed}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
