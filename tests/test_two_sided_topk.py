"""The exact two-sided top-k (core/select.py) against ``lax.top_k``, byte
for byte: values, indices and their order, on both sides of the
crossover, under vmap and inside lax.scan."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import select
from repro.core.select import CROSSOVER, from_key, order_key, two_sided_topk


def _ref(x, k):
    return jax.lax.top_k(x, k), jax.lax.top_k(-x, k)


def _assert_bytes_equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(
            np.asarray(g).view(np.uint8), np.asarray(w).view(np.uint8))


def _row(kind, n, seed=0):
    rng = np.random.default_rng(seed)
    if kind == "gauss":
        return rng.standard_normal(n)
    if kind == "zero":
        return np.zeros(n)
    if kind == "pm0":
        return np.where(rng.random(n) < 0.5, 0.0, -0.0)
    if kind == "equal":
        return np.full(n, -1.25)
    if kind == "ties":  # a few values, so ties straddle the k-th place
        return rng.integers(-3, 4, n) * 0.5
    if kind == "nan":
        x = rng.standard_normal(n)
        x[rng.random(n) < 0.01] = np.nan
        x[::97] = -np.nan
        x[::89] = np.inf
        x[::83] = -np.inf
        return x
    raise ValueError(kind)


KINDS = ["gauss", "zero", "pm0", "equal", "ties", "nan"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", [CROSSOVER - 1, CROSSOVER + 1000])
@pytest.mark.parametrize("k_of", ["one", "p01", "third", "all"])
def test_matches_lax_top_k(kind, n, k_of):
    k = {"one": 1, "p01": n // 100, "third": n // 3, "all": n}[k_of]
    x = jnp.asarray(_row(kind, n).astype(np.float32))
    got = jax.jit(two_sided_topk, static_argnums=1)(x, k)
    _assert_bytes_equal(got, _ref(x, k))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", [7, 128, 1000, 4097])
def test_threshold_path_at_small_rows(kind, n):
    """The threshold path itself (the dispatch keeps lax.top_k there),
    at row lengths around one compaction block."""
    x = jnp.asarray(_row(kind, n, seed=n).astype(np.float32))
    for k in sorted({1, min(3, n), max(1, n // 2), n}):
        got = jax.jit(select._threshold_topk, static_argnums=1)(x, k)
        _assert_bytes_equal(got, _ref(x, k))


def test_signed_zero_order():
    x = jnp.asarray([0.0, -0.0, 0.0, -0.0, 1.0, 1.0, -1.0], jnp.float32)
    (_, idx), _ = select._threshold_topk(x, 4)
    assert np.asarray(idx).tolist() == [4, 5, 0, 2]


def test_key_is_a_bijection_that_orders_like_top_k():
    bits = np.array([0, 1, 0x7F800000, 0x7FC00000, 0x80000000, 0x80000001,
                     0xFF800000, 0xFFC00000, 0x3F800000, 0xBF800000],
                    np.uint32)
    x = jnp.asarray(bits.view(np.float32))
    back = np.asarray(from_key(order_key(x))).view(np.uint32)
    np.testing.assert_array_equal(back, bits)
    np.testing.assert_array_equal(np.asarray(order_key(-x)),
                                  ~np.asarray(order_key(x)))


def test_vmapped_at_the_cells_shape():
    """LeNet5's fc1 update, four clients vmapped: f32[4, 1225000], k 12250."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal((4, 1_225_000)).astype(np.float32)
    x[1, ::3] = 0.0  # ties at zero on one client
    x = jnp.asarray(x)
    f = lambda r: two_sided_topk(r, 12_250)  # noqa: E731
    _assert_bytes_equal(jax.jit(jax.vmap(f))(x),
                        jax.jit(jax.vmap(lambda r: _ref(r, 12_250)))(x))


def test_inside_scan_as_the_gspmd_round_runs_it():
    rng = np.random.default_rng(5)
    rows = jnp.asarray(rng.integers(-50, 50, (3, CROSSOVER + 333))
                       .astype(np.float32) * 0.25)
    k = 700

    def scan_with(fn):
        def body(_, row):
            return None, fn(row, k)
        return jax.jit(lambda r: jax.lax.scan(body, None, r)[1])(rows)

    _assert_bytes_equal(scan_with(two_sided_topk), scan_with(_ref))


def test_dispatch_by_row_length():
    assert not select.uses_threshold(CROSSOVER - 1)
    assert select.uses_threshold(CROSSOVER)
    # below the crossover the compiled selection is lax.top_k's sort
    x = jax.ShapeDtypeStruct((CROSSOVER - 1,), jnp.float32)
    hlo = jax.jit(two_sided_topk, static_argnums=1).lower(x, 10).as_text()
    assert "top_k" in hlo or "sort" in hlo
    x = jax.ShapeDtypeStruct((CROSSOVER,), jnp.float32)
    hlo = jax.jit(two_sided_topk, static_argnums=1).lower(x, 10).as_text()
    assert "top_k" not in hlo


@pytest.mark.parametrize("crossover,share", [
    # LeNet5's leaves hold 500, 25,000, 1,225,000, 500, 5,000 and 10
    (CROSSOVER, (25_000 + 1_225_000 + 5_000) / 1_256_010),
    (1_225_001, 0.0),  # every sparse leaf below the crossover
])
def test_threshold_share_gauge(monkeypatch, crossover, share):
    from repro.run import RunSpec, build_run

    monkeypatch.setattr(select, "CROSSOVER", crossover)
    run = build_run(RunSpec(preset="lenet5", backend="local", compressor="sbc",
                            sparsity=0.01, clients=2, batch=4, seq_len=16,
                            telemetry=True))
    params = run.init().params
    run.channel.resolved(params)
    run.channel.resolved(params)  # once per resolve, not per call
    got = run.telemetry.metrics.series("select/threshold_share")
    assert len(got) == 1
    assert got[0]["value"] == pytest.approx(share, rel=1e-12)
