"""The plain reference runs leaf by leaf: every client's Adam moments and
residual stay on the host between rounds, what it holds on the device
stays within about 16 bytes a parameter, and its evidence is the one
that the reference held whole on the device gave, to the last digit on
the CPU."""
import gc
import sys

import jax
import numpy as np
import pytest

from chipbench import harness, traffic
from chipbench.check import reference

pytestmark = pytest.mark.usefixtures("cpu_only")
SEED = 2**33 + 5

# The evidence of the reference that kept every client's state on the
# device as whole trees, on the tiny bench's cells at SEED (CPU)
WHOLE = {
    "lenet-local": {
        "losses": [8.40973711013794, 4.976719260215759, 3.6791577339172363],
        "grad": [0.17614861882517208, 1.4129483169787544, 3.1627536656870534,
                 0.04023723146566969, 2.2459434923416097, 0.04667698491225592],
        "change": [0.011685959994792938, 0.0817982405424118, 0.5372485518455505,
                   0.009634832851588726, 0.031141389161348343, 0.0031024322379380465],
        "support": [29.0, 1455.0, 69334.0, 30.0, 292.0, 5.0],
    },
    "lenet-dense": {
        "losses": [3.5799379348754883, 7.429579257965088, 6.7027060985565186],
        "grad": [0.0939825125103051, 0.6838444495303652, 1.9506003274918697,
                 0.024843185995892948, 0.9604588844111615, 0.027411272665904644],
        "change": [0.022360721603035927, 0.14902308583259583, 0.7234286665916443,
                   0.013974380679428577, 0.03964686393737793, 0.001624228316359222],
        "support": [500.0, 25000.0, 889802.0, 367.0, 3503.0, 10.0],
    },
}


@pytest.mark.parametrize("cell", sorted(WHOLE))
def test_evidence_equals_the_whole_tree_reference(tiny_bench, cell):
    root, _ = tiny_bench
    ev = harness.reference_evidence(harness.load_cell(cell, root), SEED, jax.devices()[0])
    for key, want in WHOLE[cell].items():
        np.testing.assert_allclose(np.asarray(ev[key], np.float64).reshape(-1), want,
                                   rtol=1e-6, atol=0, err_msg=key)


def test_client_state_stays_on_the_host(tiny_bench, monkeypatch):
    root, _ = tiny_bench
    cell = harness.load_cell("lenet-local", root)
    client, seen = reference.Reference._client, []

    def watched(self, *args):
        s, loss, host = client(self, *args)
        seen.append((list(s), host))
        return s, loss, host

    monkeypatch.setattr(reference.Reference, "_client", watched)
    harness.reference_evidence(cell, SEED, jax.devices()[0])
    assert len(seen) == harness.SETUP_ROUNDS * cell.traffic["clients"]
    n_leaves = len(seen[0][1][0])
    for s, host in seen:
        assert all(isinstance(x, jax.Array) for x in s)  # S_c goes to the mean
        for part in host:  # m, v, residual
            assert len(part) == n_leaves
            assert all(isinstance(x, np.ndarray) and not isinstance(x, jax.Array)
                       for x in part)


def _peak_device_bytes(fn, monkeypatch):
    """``fn()`` and the most bytes that it held on the device, above what
    was live before, at the end of each call of a function that
    ``check/reference.py`` jits: the live jax arrays, the call's arguments
    and results among them, and the host arrays that the call takes and
    so moves to the device for it.  On the CPU a fetched array aliases the
    device buffer; here each fetch is a copy, as from an accelerator."""
    jit, device_get, peak = jax.jit, jax.device_get, [0]
    monkeypatch.setattr(jax, "device_get", lambda x: jax.tree.map(
        lambda a: np.array(a, copy=True), device_get(x)))

    def live():
        return sum(a.nbytes for a in jax.live_arrays())

    def sampled(f):
        def call(*args):
            out = jax.block_until_ready(f(*args))
            moved = sum(x.nbytes for x in jax.tree.leaves(args) if isinstance(x, np.ndarray))
            peak[0] = max(peak[0], live() + moved - base)
            return out
        return call

    def jit_in_reference(f, *a, **k):
        g = jit(f, *a, **k)
        return sampled(g) if sys._getframe(1).f_code.co_filename == reference.__file__ else g

    monkeypatch.setattr(jax, "jit", jit_in_reference)
    for name in ("change_evidence", "leaf_sums"):  # jitted at import
        monkeypatch.setattr(reference, name, sampled(getattr(reference, name)))
    gc.collect()
    base = live()
    fn()
    return peak[0]


@pytest.mark.parametrize("bench,cell", [
    ("tiny_bench", "lenet-local"), ("tiny_bench", "lenet-dense"),
    ("token_bench", "charlstm-tok"),
])
def test_device_bytes_stay_near_16_a_parameter(request, monkeypatch, bench, cell):
    """W, W_c, one step's gradient and the running mean (four float32
    trees), one leaf's Adam update in flight (its old W_c, m and v in, its
    new W_c, m and v out: five of the largest leaf), the rounds' batches
    and a few scalars; the reference that held every client's state whole
    on the device held more."""
    root, _ = request.getfixturevalue(bench)
    c = harness.load_cell(cell, root)
    key = jax.random.PRNGKey(0)
    leaves = [x.size * 4 for x in jax.tree.leaves(
        jax.eval_shape(lambda k: c.mod.init_params(c.cfg, k), key))]
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(jax.eval_shape(
        lambda k: traffic.make_pool(c.inputs.make, c.cfg, c.traffic, k), key)))
    budget = (4 * sum(leaves) + 5 * max(leaves)
              + pool * harness.SETUP_ROUNDS // traffic.POOL_ROUNDS
              + 1024)  # the seed's keys, a client's losses and step count
    peak = _peak_device_bytes(
        lambda: harness.reference_evidence(c, SEED, jax.devices()[0]), monkeypatch)
    assert 2 * sum(leaves) < peak <= budget, (peak, budget, sum(leaves))
