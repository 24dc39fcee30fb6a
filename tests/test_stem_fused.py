"""Megakernel (stages 1-5 in one pallas_call) parity + launch-count tests.

No hypothesis dependency: this module must always collect, so the
single-launch stemmer keeps kernel-level coverage even on minimal
dev environments.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import corpus, pyref, stemmer
from repro.data import pipeline as data_pipeline
from repro.kernels import ops
from repro.kernels import stem_fused as sf
from repro.kernels import stem_match as sm

MATCHES = ("bank", "bsearch")


@pytest.fixture(scope="module")
def dicts():
    d = corpus.build_dictionary(n_tri=800, n_quad=100, seed=7)
    return d, stemmer.RootDictArrays.from_rootdict(d)


@pytest.fixture(scope="module")
def corpus_enc():
    words, _, _ = corpus.build_corpus(n_words=512, seed=11)
    return words, jnp.asarray(corpus.encode_corpus(words))


# ---------------------------------------------------------------------------
# parity: megakernel == core jnp == pyref, both match strategies
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("match", MATCHES)
@pytest.mark.parametrize("infix", [True, False])
def test_megakernel_matches_core(dicts, corpus_enc, infix, match):
    _, da = dicts
    _, enc = corpus_enc
    r1, s1 = ops.extract_roots_fused(enc, da, infix=infix, match=match,
                                     interpret=True)
    r2, s2 = stemmer.stem_batch(enc, da, infix=infix)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


@pytest.mark.parametrize("match", MATCHES)
@pytest.mark.parametrize("infix", [True, False])
def test_megakernel_matches_pyref(dicts, corpus_enc, infix, match):
    d, da = dicts
    words, enc = corpus_enc
    roots, srcs = ops.extract_roots_fused(enc, da, infix=infix, match=match,
                                          interpret=True)
    roots, srcs = np.asarray(roots), np.asarray(srcs)
    for i, w in enumerate(words[:128]):
        want_root, want_src = pyref.extract_root(np.asarray(enc[i]), d,
                                                 infix=infix)
        got = tuple(int(c) for c in roots[i] if c)
        assert got == want_root, w
        assert int(srcs[i]) == want_src, w


@pytest.mark.parametrize("block_b", [64, 128, 512])
def test_megakernel_block_sweep(dicts, corpus_enc, block_b):
    _, da = dicts
    _, enc = corpus_enc
    r1, s1 = ops.extract_roots_fused(enc, da, block_b=block_b, interpret=True)
    r2, s2 = stemmer.stem_batch(enc, da)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


# ---------------------------------------------------------------------------
# single-launch property
# ---------------------------------------------------------------------------
def test_megakernel_is_single_launch(dicts, monkeypatch):
    """The infix path must trace exactly ONE pallas_call."""
    _, da = dicts
    calls = []
    real = sf.pl.pallas_call

    def counting(*a, **kw):
        calls.append(kw.get("grid"))
        return real(*a, **kw)

    monkeypatch.setattr(sf.pl, "pallas_call", counting)
    # unique batch size -> fresh trace under jit, so the counter fires
    words, _, _ = corpus.build_corpus(n_words=97, seed=23)
    enc = jnp.asarray(corpus.encode_corpus(words))
    ops.extract_roots_fused(enc, da, infix=True, block_b=64, interpret=True)
    assert len(calls) == 1, calls


# ---------------------------------------------------------------------------
# in-kernel sorted search building block
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,r", [(1, 1), (5, 64), (300, 500), (1024, 2048)])
def test_dict_match_bsearch_shapes(n, r):
    rng = np.random.default_rng(n * 1000 + r)
    dict_keys = jnp.asarray(
        np.unique(rng.integers(0, 2**24, size=r)).astype(np.int32))
    keys = jnp.asarray(rng.integers(0, 2**24, size=n).astype(np.int32))
    keys = keys.at[: n // 2].set(dict_keys[: max(1, min(n // 2, r))][: n // 2])
    got = sm.dict_match_bsearch_pallas(keys, dict_keys, interpret=True)
    want = stemmer.match_dense(keys, dict_keys)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bsearch_hit_boundaries():
    """First/last/absent keys around the sentinel padding."""
    d = jnp.asarray(np.array([3, 9, 11, 200, 2**24 - 1], np.int32))
    flat = sm.pad_dict_sorted(d).reshape(-1)
    keys = jnp.asarray(np.array([0, 3, 4, 9, 199, 200, 2**24 - 1, 2**24 - 2],
                                np.int32))
    got = np.asarray(sm.bsearch_hit(flat, keys))
    np.testing.assert_array_equal(
        got, [False, True, False, True, False, True, True, False])


@pytest.mark.parametrize("r", [1, 127, 128, 1025, 5000])
def test_sorted_member_matches_bisection(r):
    """The in-kernel two-level search (row-max count + one-hot MXU row
    fetch) answers exactly what the bisection answers — first and last
    keys, keys between rows, keys past the sentinel padding, and keys
    whose high byte is set."""
    rng = np.random.default_rng(r)
    d = np.unique(rng.integers(1, 2**24, size=r)).astype(np.int32)
    probes = np.concatenate([
        d, d - 1, d + 1, [0, 1, 2**24 - 1, sm.DICT_SENTINEL - 1],
        rng.integers(0, 2**24, size=300)]).astype(np.int32)
    rows = sm.pad_dict_tiles(jnp.asarray(d), 8)
    got = sm.sorted_member(sm.sorted_tables(rows),
                           jnp.asarray(probes)[:, None])[:, 0]
    want = sm.bsearch_hit(sm.pad_dict_sorted(jnp.asarray(d)).reshape(-1),
                          jnp.asarray(probes))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), np.isin(probes, d))


# ---------------------------------------------------------------------------
# fused backend through the public APIs
# ---------------------------------------------------------------------------
def test_fused_backend_in_core_stemmer(dicts, corpus_enc):
    _, da = dicts
    _, enc = corpus_enc
    r1, s1 = stemmer.stem_batch(enc, da, backend="fused")
    r2, s2 = stemmer.stem_batch(enc, da, backend="sorted")
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_fused_backend_in_stem_pipelined(dicts, corpus_enc):
    _, da = dicts
    _, enc = corpus_enc
    r1, s1 = stemmer.stem_pipelined(enc, da, backend="fused", microbatch=128)
    r2, s2 = stemmer.stem_batch(enc, da)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_fused_backend_in_morph_preprocessor():
    words = ["سيلعبون", "يدرسون", "قال", "فتزحزحت"]
    pre_s = data_pipeline.MorphPreprocessor(n_tri=500, n_quad=60)
    pre_f = data_pipeline.MorphPreprocessor(n_tri=500, n_quad=60,
                                            backend="fused")
    toks_s, ids_s = pre_s(words)
    toks_f, ids_f = pre_f(words)
    np.testing.assert_array_equal(toks_s, toks_f)
    np.testing.assert_array_equal(ids_s, ids_f)
    assert (ids_f > 0).all()


def test_empty_batch(dicts):
    _, da = dicts
    root, src = ops.extract_roots_fused(
        jnp.zeros((0, 16), jnp.int32), da, interpret=True)
    assert root.shape == (0, 4) and src.shape == (0,)


def test_unknown_match_strategy_raises(dicts, corpus_enc):
    _, da = dicts
    _, enc = corpus_enc
    with pytest.raises(ValueError, match="match strategy"):
        ops.extract_roots_fused(enc, da, match="nope", interpret=True)


def test_autotune_returns_valid_config(dicts):
    _, da = dicts
    words, _, _ = corpus.build_corpus(n_words=256, seed=3)
    enc = jnp.asarray(corpus.encode_corpus(words))
    cfg = ops.autotune_stem_fused(enc, da, block_bs=(64, 128),
                                  matches=("bsearch",), iters=1,
                                  interpret=True)
    assert cfg["block_b"] in (64, 128) and cfg["match"] == "bsearch"
    assert all(t > 0 for t in cfg["timings"].values())


_LOWER_SCRIPT = """
import hashlib
import jax, jax.numpy as jnp
from repro.core import corpus, stemmer
from repro.kernels import stem_fused as sf
d = stemmer.RootDictArrays.from_rootdict(
    corpus.build_dictionary(n_tri=60, n_quad=12, seed=0))
w = jax.ShapeDtypeStruct((32, 16), jnp.int32)
text = jax.jit(lambda w, r: sf.stem_fused_pallas(
    w, r, block_b=32, residency="resident", interpret=True)).lower(
    w, d).as_text()
print(hashlib.sha256(text.encode()).hexdigest())
"""


def test_megakernel_lowers_identically_across_processes():
    """The persistent compilation cache keys on the lowered program, so
    the kernel must trace in the same order in every process — no
    iteration over a set of strings, whose order follows the
    per-process hash seed."""
    import os
    import subprocess
    import sys

    digests = set()
    for seed in ("1", "2", "3"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu")
        proc = subprocess.run([sys.executable, "-c", _LOWER_SCRIPT],
                              env=env, capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        digests.add(proc.stdout.strip().splitlines()[-1])
    assert len(digests) == 1
