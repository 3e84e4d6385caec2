"""Input kinds (``chipbench/inputs/<kind>.py``): the ``images`` pool is
the one PR 14's generator drew, bit for bit; the ``tokens`` kind draws
next-token rows whose ids follow Zipf's law; a kind is found by its file
in the bench, and a mix that lacks what its kind needs is refused."""
import hashlib
import json

import numpy as np
import pytest

from chipbench import harness, traffic
from chipbench.tests.conftest import BENCH, MIXES

LENET5 = json.loads((BENCH / "configs" / "lenet5.json").read_text())


def _pool(cfg, mix, seed):
    _, k_data = harness.keys_for(seed)
    return traffic.make_pool(harness.input_kind(cfg["inputs"]).make, cfg, mix, k_data)


# sha256 over the pool's arrays in name order, taken before input kinds
# became files
@pytest.mark.parametrize("mix,seed,digest", [
    ("lenet-l2", 7, "e10a7e8ed7b486400713e560b70d6a7a150bcf548b145929830de37d49f29b9e"),
    ("lenet-l2", 2**33 + 5, "d7f70030b62988150a93d00029688c0e91c22c746d57b0f6d4496aa613cd2578"),
    ("lenet-d2", 7, "0a65d948be87e48c7795fa36273ce3224f1a71e02005337c38d4f9514dc0e061"),
    ("lenet-d2", 2**33 + 5, "7597748cbe2e6d8bfda8ad893968aa407af0ab47799d2362eaa6c65e2c3d4e9f"),
])
def test_images_pool_is_unchanged(mix, seed, digest):
    pool = _pool(LENET5, {"name": mix, **MIXES[mix]}, seed)
    h = hashlib.sha256()
    for name in sorted(pool):
        h.update(np.asarray(pool[name]).tobytes())
    assert h.hexdigest() == digest


TOKENS_CFG = {"name": "t", "inputs": "tokens", "vocab_size": 4096}
TOKENS_MIX = {"clients": 2, "delay": 3, "batch": 4, "seq_len": 33}


def test_tokens_rows_are_next_token_pairs():
    pool = _pool(TOKENS_CFG, TOKENS_MIX, 2**33 + 5)
    lead = (traffic.POOL_ROUNDS, 2, 3, 4)
    assert set(pool) == {"tokens", "labels"}
    for x in pool.values():
        assert x.shape == lead + (33,) and x.dtype == np.int32
    tokens, labels = np.asarray(pool["tokens"]), np.asarray(pool["labels"])
    np.testing.assert_array_equal(labels[..., :-1], tokens[..., 1:])
    assert tokens.min() >= 0 and max(tokens.max(), labels.max()) < 4096
    # the rows of the pool differ, so every round replayed reads new data
    flat = tokens.reshape(-1, 33)
    assert len({r.tobytes() for r in flat}) == len(flat)


@pytest.mark.parametrize("vocab", [4096, 163840, 98])
def test_tokens_follow_the_zipf_law(vocab):
    """On 2^20 ids, log count against log (id + 1) over the 256 most
    frequent ids falls with slope -ZIPF_S, and the counts fall with the id."""
    cfg = dict(TOKENS_CFG, vocab_size=vocab)
    out = harness.input_kind("tokens").make(cfg, {"seq_len": 1023}, harness.seed_key(5),
                                            (1024,))
    ids = np.concatenate([np.asarray(out["tokens"]), np.asarray(out["labels"])[:, -1:]], 1)
    assert ids.size == 2**20 and ids.min() >= 0 and ids.max() < vocab
    counts = np.bincount(ids.ravel(), minlength=vocab)
    top = min(256, vocab)
    slope = np.polyfit(np.log(np.arange(1, top + 1)), np.log(counts[:top]), 1)[0]
    assert abs(slope + harness.input_kind("tokens").ZIPF_S) <= 0.1, slope
    assert counts[0] > counts[9] > counts[top - 1]


def test_unknown_input_kind_names_its_file(tmp_path):
    with pytest.raises(ValueError, match=r"inputs/audio\.py"):
        harness.input_kind("audio")
    (tmp_path / "inputs").mkdir()
    (tmp_path / "inputs" / "audio.py").write_text("TRAFFIC_KEYS = ()\n")
    assert harness.input_kind("audio", tmp_path).TRAFFIC_KEYS == ()  # found in its bench


def test_token_mix_must_state_its_sequence_length():
    w = {"name": "x", "chips": 1}
    mix = dict(TOKENS_MIX, backend="local", compressor="sbc")
    kind = harness.input_kind("tokens")
    harness.validate(w, mix, TOKENS_CFG, kind)
    short = {k: v for k, v in mix.items() if k != "seq_len"}
    harness.validate(w, short, LENET5, harness.input_kind("images"))
    with pytest.raises(ValueError, match="seq_len"):
        harness.validate(w, short, TOKENS_CFG, kind)


# sha256 of RunSpec.to_json() for seed 4700000001, taken before the mix
# could set the sequence length: an image mix states none, so the spec
# keeps the default
@pytest.mark.parametrize("cell,digest", [
    ("lenet5-sbc2-local4", "3c6b9d928adfd64c0128cf0919b39bf18e258e6058bafc6629a6e8e785d75824"),
    ("lenet5-dense-local4", "e50322f0403d84c0d708110e9b024272597b7e7adf3ad0051108bc01399391a2"),
])
def test_lenet5_run_spec_is_unchanged(cell, digest):
    spec = harness.Program(harness.load_cell(cell), 4700000001).spec
    assert hashlib.sha256(spec.to_json().encode()).hexdigest() == digest
