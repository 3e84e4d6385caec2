"""``correct`` on the CPU at small sizes: the program passes against the
reference, and comes out false with the control in its place and with
each fault a cell can have planted underneath the timed path.  The limits
are the real cells' (``conftest.CELLS``)."""
import pytest

from chipbench.tests.conftest import run_tiny

pytestmark = pytest.mark.usefixtures("cpu_only")
CELLS = ["lenet-local", "lenet-dense"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_program_is_correct(tiny_bench, cell):
    res = run_tiny(tiny_bench, cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    root, _ = tiny_bench
    from chipbench import harness

    assert set(res["checks"]) == set(harness.load_cell(cell, root).workload["limits"])
    assert res["metrics"]["samples_per_s"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_is_not_correct(tiny_bench, cell):
    import jax
    import jax.numpy as jnp

    from chipbench import harness
    from chipbench.check import compare

    root, _ = tiny_bench
    c = harness.load_cell(cell, root)
    seed = 11
    ref = harness.reference_evidence(c, seed, jax.devices()[0])
    control = harness.reference_evidence(c, seed, jax.devices()[0], jnp.bfloat16)
    values, _ = compare.numbers(control, ref)
    ok, rows = compare.verdict(values, c.workload["limits"])
    assert not ok, rows


def _unchanged(prog):
    import jax
    import jax.numpy as jnp

    run, step = prog.run, prog.run.step

    def keep_state(state, r):
        kept = jax.tree.map(jnp.copy, state)
        _, m = step(state, r)
        return kept, m

    run.step = keep_state


def _half_batch(prog):
    import jax

    feed = prog.feed
    prog.feed = lambda pool, n: feed(
        jax.tree.map(lambda x: x[:, :, :, : x.shape[3] // 2], pool), n)


def _no_exchange(prog):
    import jax

    channel = prog.run.channel
    exchange = channel.round_exchange

    def own_only(*a, **kw):
        ex = exchange(*a, **kw)
        return ex._replace(mean_delta=jax.tree.map(lambda t: t[0], ex.transmitted))

    channel.round_exchange = own_only


def _altered(prog):
    import jax
    import jax.numpy as jnp

    run, step = prog.run, prog.run.step

    def doubled_first_leaf(state, r):
        before = jnp.copy(jax.tree.leaves(state.params)[0])
        new, m = step(state, r)
        leaves, treedef = jax.tree.flatten(new.params)
        leaves[0] = before + 2 * (leaves[0] - before)
        return new._replace(params=jax.tree.unflatten(treedef, leaves)), m

    run.step = doubled_first_leaf


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "no_exchange": _no_exchange, "altered": _altered}


@pytest.mark.parametrize("cell,fault", [
    ("lenet-dense", "unchanged"), ("lenet-dense", "half_batch"),
    ("lenet-dense", "no_exchange"), ("lenet-dense", "altered"),
    # half a batch is not among them: under SBC2's top-k no number read
    # parts it from sound seeds on the chip (PERF.md)
    ("lenet-local", "unchanged"), ("lenet-local", "no_exchange"),
    ("lenet-local", "altered"),
])
def test_planted_fault_is_not_correct(tiny_bench, cell, fault):
    res = run_tiny(tiny_bench, cell, patch=FAULTS[fault])
    assert not res["correct"], res["checks"]


def test_upload_bits_are_the_programs_own_count(tiny_bench):
    """The SBC cell's round-0 counter reaches the per-layer reader; the
    dense cell meters nothing, so its reader has nothing to read."""
    import json

    from chipbench import harness

    root, _ = tiny_bench
    reader = harness.load_module(root / "layer_metrics" / "up_bits_per_step.py", "ub")
    for cell, want in (("lenet-local", True), ("lenet-dense", False)):
        c = harness.load_cell(cell, root)
        prog = harness.Program(c, 7)
        su = harness.setup_rounds(prog, 7)
        ctx = harness.LayerContext(
            reduced=None, window=None, devices=[], rounds=0, chips=1,
            traffic=c.traffic, cfg=c.cfg, peaks=None, samples_per_s=0.0,
            flops_per_sample=0.0, busy_s=0.0, window_s=0.0, counters=su.counters)
        bits = reader.read(ctx)
        if not want:
            assert bits is None
            continue
        # k = 1% of each leaf's entries, each position costing a few bits
        n = c.cfg["n_params"]
        assert 0.01 * n < bits * c.traffic["delay"] < 0.01 * n * 16, json.dumps(su.counters)
