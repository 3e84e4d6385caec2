"""The main-path Pallas kernels compile for a TPU v5e at lm-100m size.

Interpret mode on CPU cannot show what Mosaic refuses (unaligned blocks,
vector layouts it has no lowering for, VMEM overruns).  These tests
compile each kernel with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology — no chip attached — at the flat-buffer shapes the
``lm-100m`` preset produces (one segment per leaf, ``bm=8``,
``lanes=128``), and check the compiled HLO holds the Mosaic custom call.

The topology is described inside a fixture, never at import, so that
test workers that are not given this file do not load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.flat import ShardedFlatParamSpace
from repro.kernels.flat import (
    seg_binarize_apply,
    seg_hist2side,
    seg_moments,
    seg_tier_counts,
)
from repro.kernels.ops import seg_sbc_hist
from repro.kernels.pack import seg_packbits
from repro.models.model import build_model
from repro.run.presets import lm_100m_config

SPARSITY = 0.001


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip cannot be read back on this host, so
    keep them out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def lm100m_space():
    """The gspmd flat layout of lm-100m on one chip: one segment per leaf,
    scanned leaves one row per layer (shapes only, nothing allocated)."""
    model = build_model(lm_100m_config())
    flat = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))
    )[0]
    entries = []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        scanned = "'scan'" in name and leaf.ndim > 1
        entries.append(dict(
            path=name, shape=leaf.shape, rows=leaf.shape[0] if scanned else 1,
            kind="sparse", rate=SPARSITY, n_shards=1, global_size=leaf.size,
        ))
    return ShardedFlatParamSpace.build(
        entries, client_axes=(), shard_axes=(), n_clients=1,
        shards_per_client=1,
    )


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _flat_args(space, one_chip, ncols):
    per_block = space.bm * space.lanes
    starts = tuple(s.offset // per_block for s in space.segments)
    x = jax.ShapeDtypeStruct((space.n_blocks * space.bm, space.lanes),
                             jnp.float32, sharding=one_chip)
    params = jax.ShapeDtypeStruct((len(starts), ncols), jnp.float32,
                                  sharding=one_chip)
    return starts, x, params


def test_lm100m_layout_is_full_size(lm100m_space):
    assert len(lm100m_space.segments) == 11
    assert lm100m_space.n_total == 137_841_408
    assert lm100m_space.n_pad % (8 * 128) == 0


@pytest.mark.parametrize("kernel,ncols,picks", [
    (seg_hist2side, 4, None), (seg_tier_counts, 4, None),
    (seg_moments, 8, (4,)), (seg_binarize_apply, 10, (2,)),
])
def test_seg_pass_compiles_for_v5e(kernel, ncols, picks, lm100m_space,
                                   one_chip, no_persistent_cache):
    starts, x, params = _flat_args(lm100m_space, one_chip, ncols)
    args = [x, params]
    if picks is not None:  # per-block (j0, lim) of the kept ties
        args.append(jax.ShapeDtypeStruct((lm100m_space.n_blocks, *picks),
                                         jnp.float32, sharding=one_chip))
    text = _compile_text(
        lambda *a: kernel(*a, blk_starts=starts, interpret=False), *args
    )
    assert "tpu_custom_call" in text


def test_hist_pipeline_compiles_for_v5e(lm100m_space, one_chip,
                                        no_persistent_cache):
    """The whole exact-k hist pipeline (5 launches) at lm-100m size."""
    space = lm100m_space
    bounds = [(s.offset, s.rows * s.n_loc) for s in space.segments]
    ks = [s.rows * s.k for s in space.segments]
    acc = jax.ShapeDtypeStruct((space.n_pad,), jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda a: seg_sbc_hist(a, bounds, ks, [SPARSITY] * len(ks),
                               bm=space.bm, lanes=space.lanes,
                               interpret=False)[:2],
        acc,
    )
    assert text.count("tpu_custom_call") == 5


def test_seg_packbits_compiles_for_v5e(lm100m_space, one_chip,
                                       no_persistent_cache):
    nwords = lm100m_space.n_pack_words
    assert nwords > 0
    planes = jax.ShapeDtypeStruct((32, nwords), jnp.uint32, sharding=one_chip)
    text = _compile_text(lambda b: seg_packbits(b, interpret=False), planes)
    assert "tpu_custom_call" in text


def test_interpret_resolver():
    """One place decides: interpret off a TPU, compile on one, and an
    explicit interpret=True on a TPU is refused."""
    from repro import kernels

    assert kernels.resolve_interpret(None) is (jax.default_backend() != "tpu")
    assert kernels.resolve_interpret(False) is False
    real = kernels.on_tpu
    try:
        kernels.on_tpu = lambda: True
        assert kernels.resolve_interpret(None) is False
        with pytest.raises(ValueError, match="interpret=True"):
            kernels.resolve_interpret(True)
    finally:
        kernels.on_tpu = real
    x = jnp.asarray(np.arange(32 * 128, dtype=np.uint32) % 2).reshape(32, 128)
    np.testing.assert_array_equal(
        np.asarray(seg_packbits(x)), np.asarray(seg_packbits(x, interpret=True))
    )
