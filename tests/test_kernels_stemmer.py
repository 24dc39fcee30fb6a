"""Pallas kernel tests: interpret-mode vs pure-jnp oracles, shape sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import corpus, pyref, stemmer
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels import stem_datapath as sdp
from repro.kernels import stem_match as sm


@pytest.fixture(scope="module")
def dicts():
    d = corpus.build_dictionary(n_tri=800, n_quad=100, seed=7)
    return d, stemmer.RootDictArrays.from_rootdict(d)


# ---------------------------------------------------------------------------
# dict_match kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 5, 128, 300, 1024])
@pytest.mark.parametrize("r", [1, 64, 500, 2048])
def test_dict_match_shapes(n, r):
    rng = np.random.default_rng(n * 1000 + r)
    dict_keys = jnp.asarray(np.unique(rng.integers(0, 2**24, size=r)).astype(np.int32))
    keys = jnp.asarray(rng.integers(0, 2**24, size=n).astype(np.int32))
    got = sm.dict_match_pallas(keys, dict_keys, interpret=True)
    want = kref.dict_match_ref(keys, dict_keys)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block_n,block_r", [(1, 1), (2, 8), (4, 2)])
def test_dict_match_block_shapes(block_n, block_r):
    rng = np.random.default_rng(0)
    dict_keys = jnp.asarray(np.sort(rng.integers(0, 2**24, 700)).astype(np.int32))
    keys = jnp.asarray(rng.integers(0, 2**24, 513).astype(np.int32))
    # force hits
    keys = keys.at[:100].set(dict_keys[:100])
    got = sm.dict_match_pallas(
        keys, dict_keys, block_n=block_n, block_r=block_r, interpret=True
    )
    want = kref.dict_match_ref(keys, dict_keys)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(1, 260),
    r=st.integers(1, 600),
    seed=st.integers(0, 2**16),
)
def test_dict_match_property(n, r, seed):
    rng = np.random.default_rng(seed)
    dict_keys = jnp.asarray(np.unique(rng.integers(0, 2**24, r)).astype(np.int32))
    keys = jnp.asarray(rng.integers(0, 2**24, n).astype(np.int32))
    got = sm.dict_match_pallas(keys, dict_keys, interpret=True)
    want = kref.dict_match_ref(keys, dict_keys)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# stem_datapath kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b", [1, 7, 64, 256, 500])
def test_datapath_matches_ref(b):
    words, _, _ = corpus.build_corpus(n_words=b, seed=b)
    enc = jnp.asarray(corpus.encode_corpus(words))
    keys, valid = sdp.stem_datapath_pallas(enc, block_b=64, interpret=True)
    rkeys, rvalid = kref.stem_datapath_ref(enc)
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(rvalid))
    # keys only compared where valid (invalid slots may hold garbage chars)
    mask = np.asarray(rvalid) > 0
    np.testing.assert_array_equal(np.asarray(keys)[mask], np.asarray(rkeys)[mask])


@pytest.mark.parametrize("block_b", [8, 32, 256])
def test_datapath_block_sweep(block_b):
    words, _, _ = corpus.build_corpus(n_words=100, seed=1)
    enc = jnp.asarray(corpus.encode_corpus(words))
    keys, valid = sdp.stem_datapath_pallas(enc, block_b=block_b, interpret=True)
    rkeys, rvalid = kref.stem_datapath_ref(enc)
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(rvalid))
    mask = np.asarray(rvalid) > 0
    np.testing.assert_array_equal(np.asarray(keys)[mask], np.asarray(rkeys)[mask])


# ---------------------------------------------------------------------------
# fused kernel pipeline == core stemmer == pyref
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("infix", [True, False])
def test_fused_pipeline_matches_core(dicts, infix):
    d, da = dicts
    words, _, _ = corpus.build_corpus(n_words=300, seed=11)
    enc = jnp.asarray(corpus.encode_corpus(words))
    r1, s1 = ops.extract_roots_fused(enc, da, infix=infix, interpret=True)
    r2, s2 = stemmer.stem_batch(enc, da, infix=infix)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_fused_pipeline_matches_pyref(dicts):
    d, da = dicts
    words = ["أفاستسقيناكموها", "سيلعبون", "قال", "كاتب", "درس", "فتزحزحت"]
    enc = jnp.asarray(corpus.encode_corpus(words))
    roots, srcs = ops.extract_roots_fused(enc, da, interpret=True)
    for i, w in enumerate(words):
        want_root, want_src = pyref.extract_root(enc[i], d)
        got = tuple(int(c) for c in np.asarray(roots)[i] if c)
        assert got == want_root, w
        assert int(srcs[i]) == want_src, w
