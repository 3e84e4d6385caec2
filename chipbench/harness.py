"""One run of one benchmark cell, driven by the cell's files.

``workloads/<cell>.json`` names a configuration and a traffic mix and
holds the cell's limits; ``traffic_mixes/<mix>.json`` gives the traffic;
``configs/<config>.json`` holds the sizes and ``configs/<config>.py`` the
plain reference beside them; ``inputs/<kind>.py`` makes the batches of
the kind the configuration's ``inputs`` names.  Nothing here names a
cell, a configuration, an input kind or a per-layer metric:
``BENCHMARK.json`` lists the metrics, and each per-layer metric is read
by ``layer_metrics/<metric>.py``.  The mix's ``seq_len``, where it has
one, is the program's sequence length.

A run:

  1. builds the program's ``RunSpec`` and ``repro.run.build_run(spec)``
     on the local backend (every client of the round vmapped on one chip);
  2. makes the whole training state in one jitted call from the seed (the
     weights from the configuration's reference, every other leaf zero, as
     the program's own init makes them), and a pool of input rounds in one
     more (``traffic.py``), and hands the pool to the run as its data;
  3. drives ``Run.step`` through rounds 0-2 — these compile the step and
     are the rounds the correctness check replays — reading the losses, the
     optimizer state after round 0, the program's counters of round 0 and
     the weights after round 2;
  4. times ``Run.step`` round after round, each ended by
     ``block_until_ready`` on the new weights, for ``seconds`` (with
     ``trace``, the first rounds of the window are profiled);
  5. reads the peak device memory, frees the program, and runs the
     reference over rounds 0-2.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import statistics
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SETUP_ROUNDS = 3
TRACE_MIN_S = 1.5  # the traced part of the window: at least this long...
TRACE_MIN_ROUNDS = 3  # ...and at least this many rounds
COUNTERS = ("measured_bits_per_client",)  # read from the program's round 0


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    traffic: dict
    cfg: dict
    mod: object  # the configuration's plain reference
    inputs: object  # its input kind, ``inputs/<kind>.py``

    @property
    def chips(self):
        return int(self.workload["chips"])

    @property
    def samples_per_round(self):
        t = self.traffic
        return t["clients"] * t["delay"] * t["batch"]


def input_kind(name, bench=BENCH):
    """The module of input kind ``name``: ``inputs/<name>.py``, whose
    ``make(cfg, traffic, key, lead)`` returns a batch with leading axes
    ``lead`` and whose ``TRAFFIC_KEYS`` names what the mix must state."""
    path = Path(bench) / "inputs" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no input kind {name!r}: {path} does not exist")
    return load_module(path, "chipbench_inputs_" + name.replace(".", "_").replace("-", "_"))


def validate(workload, t, cfg, kind):
    """Refuse a cell that the harness cannot run as its files state: another
    backend than the local one, more chips, a compressor the reference does
    not know, or a mix that lacks what the input kind ``kind`` needs."""
    if t["backend"] != "local":
        why = ("runs one local step per round whatever delay says"
               if t["delay"] > 1 else "restarts Adam's bias correction every round")
        raise ValueError(
            f"{workload['name']}: the harness drives the local backend only; the "
            f"{t['backend']} backend {why}, so its cell would measure another job "
            "under this one's name")
    if workload["chips"] != 1:
        raise ValueError(f"{workload['name']}: the local backend runs on one chip")
    if t["compressor"] not in ("sbc", "none"):
        raise ValueError(f"{workload['name']}: the reference knows sbc and none")
    missing = [k for k in kind.TRAFFIC_KEYS if k not in t]
    if missing:
        raise ValueError(f"{workload['name']}: the {cfg['inputs']} inputs of "
                         f"{cfg['name']} need {missing} in the traffic mix")


def load_cell(name, bench=BENCH):
    bench = Path(bench)
    workload = json.loads((bench / "workloads" / f"{name}.json").read_text())
    traffic = json.loads((bench / "traffic_mixes" / f"{workload['traffic']}.json").read_text())
    cfg = json.loads((bench / "configs" / f"{workload['config']}.json").read_text())
    inputs = input_kind(cfg["inputs"], bench)
    validate(workload, traffic, cfg, inputs)
    mod = load_module(bench / "configs" / cfg["reference"],
                      "chipbench_ref_" + cfg["name"].replace("-", "_").replace(".", "_"))
    return Cell(name=name, workload=workload, traffic=traffic, cfg=cfg, mod=mod,
                inputs=inputs)


def seed_key(seed):
    """A PRNG key from any non-negative seed (more than 32 bits fold in)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def chip_devices(chips, require_tpu=True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def _program_on_path():
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))


def use_compile_cache():
    """The program's persistent compile cache (a fixed path in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), with every program kept,
    so that only a checkout's first run compiles."""
    import jax

    _program_on_path()
    from repro.paths import use_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------- program


class Program:
    """The system under test: ``build_run(spec)`` on the local backend and
    its state, fed by the benchmark."""

    def __init__(self, cell, seed):
        import jax

        _program_on_path()
        from repro.run import RunSpec, build_run

        t, cfg = cell.traffic, cell.cfg
        self.cell = cell
        self.spec = RunSpec(
            preset=cfg["preset"], backend="local", compressor=t["compressor"],
            sparsity=t.get("sparsity", 0.001), delay=t["delay"], clients=t["clients"],
            batch=t["batch"], measure_wire=t.get("measure_wire", False), telemetry=False,
            seed=seed & 0x7FFFFFFF, **({"seq_len": t["seq_len"]} if "seq_len" in t else {}))
        self.run = build_run(self.spec)
        self.abstract = jax.eval_shape(self.run.init, jax.random.PRNGKey(0))
        self._check_sizes()

        def make_state(key):
            zeros = jax.tree.map(lambda a: jax.numpy.zeros(a.shape, a.dtype),
                                 self.abstract)
            return zeros._replace(params=cell.mod.init_params(cell.cfg, key))

        self.make_state = jax.jit(make_state)
        self.make_params = jax.jit(lambda k: cell.mod.init_params(cell.cfg, k))

    def _check_sizes(self):
        """The configuration file states the sizes the program runs."""
        import jax

        want = jax.eval_shape(lambda: self.cell.mod.init_params(
            self.cell.cfg, jax.random.PRNGKey(0)))
        have = self.abstract.params
        if jax.tree.structure(want) != jax.tree.structure(have) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
            raise ValueError(f"{self.cell.cfg['name']}: the configuration's "
                             "parameters differ from the program's")
        n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(have))
        if n != self.cell.cfg["n_params"]:
            raise ValueError(f"program has {n} parameters, config states "
                             f"{self.cell.cfg['n_params']}")

    def feed(self, pool, n_rounds):
        """Round r of the run reads pool entry r % n_rounds."""
        import jax

        entries = [jax.tree.map(lambda x: x[r], pool) for r in range(n_rounds)]
        jax.block_until_ready(entries)
        self.run.batch_fn = lambda r: entries[r % len(entries)]


# ------------------------------------------------------------ measurement


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _device_info(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class StallLog:
    """Where the window's slow rounds went: per round its start, the time
    in ``Run.step`` (dispatch and the host's own work), the wait for the
    device, and the CPU time of this thread; and every collection of the
    garbage collector.  A round whose wall time its thread did not spend
    on the CPU, in no wait and no collection, was held by the host."""

    def __init__(self):
        self.rounds, self.gcs, self._gc0 = [], [], None

    def on_gc(self, phase, info):
        if phase == "start":
            self._gc0 = time.perf_counter()
        elif self._gc0 is not None:
            self.gcs.append((self._gc0, time.perf_counter() - self._gc0,
                             info["generation"]))

    def summary(self, over, top=6):
        slow = sorted((x for x in self.rounds if x[1] + x[2] > over),
                      key=lambda x: -(x[1] + x[2]))[:top]
        rows = []
        for a, step, wait, cpu in slow:
            gc_s = sum(d for t, d, _ in self.gcs if a <= t <= a + step + wait)
            rows.append(f"{1e3 * (step + wait):.1f} ms = step {1e3 * step:.1f} + wait "
                        f"{1e3 * wait:.1f}, thread cpu {1e3 * cpu:.1f}, gc {1e3 * gc_s:.1f}")
        gen2 = [d for _, d, g in self.gcs if g == 2]
        return (f"{rows}; {len(self.gcs)} collections ({len(gen2)} of generation 2, "
                f"{1e3 * sum(d for _, d, _ in self.gcs):.1f} ms in all)")


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader (``layer_metrics/<name>.py``) gets:
    the reduced trace and its window, the cell, the peaks, the traced
    window's rate and the program's counters of round 0."""

    reduced: object
    window: tuple
    devices: list
    rounds: int
    chips: int
    traffic: dict
    cfg: dict
    peaks: dict
    samples_per_s: float
    flops_per_sample: float
    busy_s: float
    window_s: float
    counters: dict


def read_layer_metrics(names, ctx, bench=BENCH):
    out = {}
    for name, unit in names:
        mod = load_module(bench / "layer_metrics" / f"{name}.py",
                          "chipbench_metric_" + name.replace(".", "_").replace("-", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit}
    return out


def cell_metrics(bench_json, cell_name, section):
    """(name, unit) of the metrics ``section`` lists for this cell."""
    return [(m["name"], m["unit"]) for m in bench_json[section]
            if "workloads" not in m or cell_name in m["workloads"]]


# -------------------------------------------------------------- the run


@dataclasses.dataclass
class SetUp:
    """What rounds 0-2 leave for the check."""

    state: object
    evidence: dict  # losses, grad, change: what the reference is held to
    counters: dict  # the program's own counters of round 0
    capture_s: float  # time spent copying the evidence out, not set-up


def keys_for(seed):
    import jax

    return jax.random.split(seed_key(seed))  # weights, data


def setup_rounds(prog, seed):
    """Seed the state and the data, then drive rounds 0-2 through the
    window's own ``Run.step``; compiles on the first round."""
    from chipbench import traffic as traffic_mod
    from chipbench.check.reference import adam_evidence, change_evidence

    cell, run = prog.cell, prog.run
    k_weights, k_data = keys_for(seed)
    prog.feed(traffic_mod.make_pool(cell.inputs.make, cell.cfg, cell.traffic, k_data),
              traffic_mod.POOL_ROUNDS)
    state = prog.make_state(k_weights)
    losses, grad, counters, capture_s = [], None, {}, 0.0
    for r in range(SETUP_ROUNDS):
        state, m = run.step(state, r)
        losses.append(float(m["loss"]))
        if r == 0:
            t0 = time.perf_counter()
            # every client's Adam state, on the leading axis
            grad = adam_evidence([state.opt_states.v])
            counters = {k: float(m[k]) for k in COUNTERS if k in m}
            capture_s += time.perf_counter() - t0
    t0 = time.perf_counter()
    change, support = np.asarray(change_evidence(state.params, prog.make_params(k_weights)))
    capture_s += time.perf_counter() - t0
    return SetUp(state, {"losses": losses, "grad": grad, "change": change,
                         "support": support}, counters, capture_s)


def reference_evidence(cell, seed, device, dtype=None, fault=None):
    """The plain reference over rounds 0-2 of this seed's weights and data,
    at ``highest`` matmul precision on ``device``."""
    import jax
    import jax.numpy as jnp

    from chipbench import traffic as traffic_mod
    from chipbench.check.reference import Reference

    k_weights, k_data = keys_for(seed)
    pool = traffic_mod.make_pool(cell.inputs.make, cell.cfg, cell.traffic, k_data)
    batches = [jax.tree.map(lambda x: np.asarray(x[i]), pool) for i in range(SETUP_ROUNDS)]
    del pool
    params0 = jax.device_get(jax.jit(lambda k: cell.mod.init_params(cell.cfg, k))(k_weights))
    ref = Reference(cell.mod, cell.cfg, cell.traffic,
                    dtype=dtype or jnp.float32, fault=fault)
    with jax.default_matmul_precision("highest"):
        return ref.run(params0, batches, SETUP_ROUNDS, device)


def evidence_json(prog, ref):
    """The per-round losses and per-leaf norms that the numbers are made
    from, for the log."""
    def side(ev):
        return {k: [float(x) for x in np.asarray(ev[k]).reshape(-1)]
                for k in ("losses", "grad", "change", "support")}

    return {"paths": ref["paths"], "program": side(prog), "reference": side(ref)}


def check_numbers(cell, su, ref, log=print):
    """The numbers compared for ``correct``."""
    from chipbench.check import compare

    values, note = compare.numbers(su.evidence, ref)
    log(f"[{cell.name}] {note}")
    return values


def run_cell(name, seed, seconds, trace, *, bench=BENCH, bench_json=None,
             require_tpu=True, t_start=None, log=print, patch=None):
    """One run; returns the result dict (the JSON line).  ``patch`` (tests
    only) is called with the built :class:`Program` before set-up."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    from chipbench.check import compare
    from chipbench.peaks import peaks_for

    bench = Path(bench)
    bench_json = bench_json or json.loads((bench.parent / "BENCHMARK.json").read_text())
    cell = load_cell(name, bench)
    devices = chip_devices(cell.chips, require_tpu)
    peaks = peaks_for(devices[0].device_kind) if require_tpu else None
    use_compile_cache()
    t = cell.traffic

    t_chip = time.perf_counter()
    prog = Program(cell, seed)
    if patch is not None:
        patch(prog)
    run = prog.run
    t_built = time.perf_counter()
    su = setup_rounds(prog, seed)
    state = su.state
    su.state = None
    # the harness's and the set-up's objects stay out of every collection
    # in the window, so the collector scans only what the rounds allocate
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start - su.capture_s
    log(f"[{name}] set-up {setup_s:.1f} s (check capture {su.capture_s:.1f} s "
        f"apart): {t_chip - t_start:.1f} s to the chip, {t_built - t_chip:.1f} s to "
        f"build the program, the rest for state, inputs and rounds 0-2; losses "
        f"{su.evidence['losses']}, counters {su.counters}")

    # ---- the window
    times, r = [], SETUP_ROUNDS
    stalls = StallLog()
    trace_dir, traced = None, None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(trace_dir)
    gc.callbacks.append(stalls.on_gc)
    w0 = time.perf_counter()
    while True:
        a, cpu = time.perf_counter(), time.thread_time()
        with _annotate("bench.step"):
            state, _ = run.step(state, r)
        s = time.perf_counter()
        with _annotate("bench.wait"):
            jax.block_until_ready(state.params)
        b = time.perf_counter()
        times.append(b - a)
        stalls.rounds.append((a, s - a, b - s, time.thread_time() - cpu))
        r += 1
        if trace_dir and traced is None and (
                b - w0 >= TRACE_MIN_S and len(times) >= TRACE_MIN_ROUNDS):
            jax.profiler.stop_trace()
            traced = len(times)
        if b - w0 >= seconds:
            break
    window_s = time.perf_counter() - w0
    gc.callbacks.remove(stalls.on_gc)
    if trace_dir and traced is None:
        jax.profiler.stop_trace()
        traced = len(times)
    n_rounds = len(times)
    samples_per_s = cell.samples_per_round * n_rounds / window_s
    device = _device_info(devices)
    med = statistics.median(times)
    slow = [x for x in times if x > 1.5 * med]
    log(f"[{name}] {n_rounds} rounds in {window_s:.2f} s, median round "
        f"{1e3 * med:.2f} ms, max {1e3 * max(times):.1f} ms, {len(slow)} rounds over "
        f"1.5x the median ({sum(slow) - med * len(slow):.3f} s beyond it) at "
        f"{[i for i, x in enumerate(times) if x > 1.5 * med][:10]}, peak memory "
        f"{device['memory_peak_bytes'] / 2**30:.3f} GiB")
    log(f"[{name}] slow rounds: {stalls.summary(1.5 * med)}")

    del state, run, prog
    gc.unfreeze()
    gc.collect()

    # ---- metrics
    result_metrics, breakdown = {}, None
    if trace:
        from chipbench import trace_reduce as tr

        red = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        win = red.window()
        devs = red.devices
        busy = sum(tr.total(red.busy(d, win)) for d in devs) / max(len(devs), 1) * 1e-9
        win_s = (win[1] - win[0]) * 1e-9
        rounds = sum(1 for _, _, n in red.host if n == "bench.step")
        device["busy_s"], device["window_s"] = busy, win_s
        ctx = LayerContext(
            reduced=red, window=win, devices=devs, rounds=rounds, chips=cell.chips,
            traffic=t, cfg=cell.cfg, peaks=peaks,
            flops_per_sample=cell.mod.flops_per_sample(cell.cfg, t),
            samples_per_s=cell.samples_per_round * rounds / win_s,
            busy_s=busy, window_s=win_s, counters=su.counters)
        result_metrics = read_layer_metrics(
            cell_metrics(bench_json, name, "per_layer"), ctx, bench)
        n_dev = max(len(devs), 1)
        breakdown = {"device_ops": [[n, s / n_dev] for n, s in tr.top_ops(red, win)],
                     "idle_gaps": [list(g) for g in tr.idle_gaps(red, devs[0], win)]
                     if devs else []}

    # ---- correctness: the reference replays rounds 0-2
    before = _device_info(devices)["memory_peak_bytes"]
    ref = reference_evidence(cell, seed, devices[0])
    log(f"[{name}] peak memory {device['memory_peak_bytes']} B after the window, "
        f"{before} B before the reference, "
        f"{_device_info(devices)['memory_peak_bytes']} B after it")
    values = check_numbers(cell, su, ref, log)
    log(f"[{name}] evidence {json.dumps(evidence_json(su.evidence, ref))}")
    if not trace:
        wanted = dict(cell_metrics(bench_json, name, "end_to_end"))
        have = {"samples_per_s": samples_per_s, "setup_s": setup_s,
                "peak_hbm_gib": device["memory_peak_bytes"] / 2**30}
        result_metrics = {k: {"value": have[k], "unit": u}
                          for k, u in wanted.items() if k in have}
    ok, rows = compare.verdict(values, cell.workload["limits"])
    return {
        "correct": bool(ok),
        "attempted": SETUP_ROUNDS + n_rounds,
        "failed": 0 if ok else SETUP_ROUNDS,
        "metrics": result_metrics,
        "device": device,
        **({"breakdown": breakdown} if breakdown else {}),
        "checks": {k: {"value": v, "limit": lim} for k, v, lim in rows},
    }
