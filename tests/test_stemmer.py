"""Stemmer unit tests: paper worked examples + JAX-vs-pyref equivalence."""
import numpy as np
import pytest

from repro.core import alphabet as ab
from repro.core import corpus, pyref, stemmer


@pytest.fixture(scope="module")
def dicts():
    d = corpus.build_dictionary(n_tri=800, n_quad=100, seed=7)
    return d, stemmer.RootDictArrays.from_rootdict(d)


def test_pack_unpack_keys_exhaustive_grid():
    """Batched JAX pack_keys/unpack_keys round-trip every valid 6-bit
    char code in every key position, plus the key-space corners, and
    agree with the scalar alphabet.pack_key reference. (A randomized
    hypothesis variant lives in test_properties.py; this grid keeps
    coverage on hosts without hypothesis.)"""
    import jax.numpy as jnp

    from repro.kernels import ops

    grid = np.zeros((4 * 64, 4), np.int32)
    for p in range(4):
        grid[p * 64:(p + 1) * 64, p] = np.arange(64)
    corners = np.array([[0, 0, 0, 0], [63, 63, 63, 63], [63, 0, 63, 0],
                        [0, 63, 0, 63], [1, 2, 3, 4]], np.int32)
    codes = np.concatenate([grid, corners])
    keys = np.asarray(stemmer.pack_keys(jnp.asarray(codes)))
    assert ((keys >= 0) & (keys < 2**24)).all()
    assert len(np.unique(keys[:4 * 64])) == 4 * 64 - 3  # all-zero row x4
    np.testing.assert_array_equal(
        np.asarray(ops.unpack_keys(jnp.asarray(keys))), codes)
    for row, key in zip(codes.tolist(), keys.tolist()):
        assert ab.pack_key(row) == key


# ---------------------------------------------------------------------------
# Paper worked examples (§3.1, §6.1)
# ---------------------------------------------------------------------------
def test_paper_example_afastasqaynakumuha(dicts):
    d, _ = dicts
    root, src = pyref.stem_word("أفاستسقيناكموها", d)
    assert root == "سقي"
    assert src == pyref.SRC_TRI


def test_paper_example_sayalaboon(dicts):
    d, _ = dicts
    root, src = pyref.stem_word("سيلعبون", d)
    assert root == "لعب"
    assert src == pyref.SRC_TRI


def test_paper_example_quadrilateral(dicts):
    d, _ = dicts
    # Fig 14: quadrilateral extraction with فت proclitics + ت suffix.
    root, src = pyref.stem_word("فتزحزحت", d)
    assert root == "زحزح"
    assert src == pyref.SRC_QUAD


def test_prefix_mask_stops_after_yeh():
    # سيلعبون: the ل after سي is a prefix letter but the person marker ي
    # terminates the run (paper Table 3 masks it). p options: -1, 0, 1 only.
    word = [int(c) for c in ab.encode_word("سيلعبون") if c]
    pp, ps = pyref.check_and_produce(word)
    assert pp == [True, True, False, False, False]
    tri, quad = pyref.generate_stems(word)
    enc = lambda w: tuple(int(c) for c in ab.encode_word(w) if c)
    assert enc("لعب") in tri
    assert enc("يلعب") in quad and enc("لعبو") in quad
    assert enc("عبو") not in tri  # p=2 masked


def test_suffix_mask_interrupted_run():
    # يكتبون: the ب breaks the suffix run; only و ن survive (paper §4.1).
    word = [int(c) for c in ab.encode_word("يكتبون") if c]
    _, ps = pyref.check_and_produce(word)
    assert ps == [False, False, False, False, True, True]


def test_infix_restore_hollow(dicts):
    d, _ = dicts
    root, src = pyref.stem_word("قال", d)
    assert root == "قول"
    assert src == pyref.SRC_RESTORED
    root, src = pyref.stem_word("قال", d, infix=False)
    assert src == pyref.SRC_NONE


def test_infix_remove_form3(dicts):
    d, _ = dicts
    root, src = pyref.stem_word("كاتب", d)
    assert root == "كتب"
    assert src == pyref.SRC_DEINFIX_TRI


def test_infix_remove_bilateral():
    d = pyref.RootDict.from_words(bi=["مد"])
    root, src = pyref.stem_word("ماد", d)
    assert root == "مد"
    assert src == pyref.SRC_DEINFIX_BI


def test_word_equal_to_root(dicts):
    d, _ = dicts
    assert pyref.stem_word("درس", d) == ("درس", pyref.SRC_TRI)
    assert pyref.stem_word("دحرج", d) == ("دحرج", pyref.SRC_QUAD)


# ---------------------------------------------------------------------------
# JAX implementation == pure-Python oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["dense", "sorted"])
@pytest.mark.parametrize("infix", [True, False])
def test_jax_matches_pyref_on_corpus(dicts, backend, infix):
    d, da = dicts
    words, _, _ = corpus.build_corpus(n_words=500, seed=3)
    enc = corpus.encode_corpus(words)
    roots_jax, src_jax = stemmer.stem_batch(enc, da, infix=infix, backend=backend)
    roots_jax, src_jax = np.asarray(roots_jax), np.asarray(src_jax)
    for i, w in enumerate(words):
        ref_root, ref_src = pyref.extract_root(enc[i], d, infix=infix)
        got = tuple(int(c) for c in roots_jax[i] if c)
        assert got == ref_root, (w, got, ref_root)
        assert int(src_jax[i]) == ref_src, (w, int(src_jax[i]), ref_src)


def test_sequential_equals_batch(dicts):
    _, da = dicts
    words, _, _ = corpus.build_corpus(n_words=64, seed=5)
    enc = corpus.encode_corpus(words)
    r1, s1 = stemmer.stem_batch(enc, da)
    r2, s2 = stemmer.stem_sequential(enc, da)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_pipelined_equals_batch(dicts):
    _, da = dicts
    words, _, _ = corpus.build_corpus(n_words=300, seed=6)
    enc = corpus.encode_corpus(words)
    r1, s1 = stemmer.stem_batch(enc, da)
    r2, s2 = stemmer.stem_pipelined(enc, da, microbatch=128)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_sorted_equals_dense_backend(dicts):
    _, da = dicts
    words, _, _ = corpus.build_corpus(n_words=400, seed=9)
    enc = corpus.encode_corpus(words)
    r1, s1 = stemmer.stem_batch(enc, da, backend="dense")
    r2, s2 = stemmer.stem_batch(enc, da, backend="sorted")
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


@pytest.mark.parametrize("backend", ["pallas", "bank"])
def test_unknown_backend_raises(dicts, backend):
    """Only "dense", "sorted" and "fused" name a Compare backend."""
    _, da = dicts
    enc = corpus.encode_corpus(["كتب"])
    with pytest.raises(ValueError, match="unknown match backend"):
        stemmer.stem_batch(enc, da, backend=backend)


# ---------------------------------------------------------------------------
# Encoding
# ---------------------------------------------------------------------------
def test_encode_decode_roundtrip():
    for w in ["درس", "أفاستسقيناكموها", "سيلعبون", "قال"]:
        enc = ab.encode_word(w)
        assert ab.decode_word(enc) == ab.normalise(w)


def test_normalise_strips_diacritics():
    assert ab.normalise("دَرَسَ") == "درس"
    assert ab.normalise("أَدْرِسُ") == "ادرس"


def test_pack_unpack_key():
    for codes in [[1, 2, 3], [5, 6, 7, 8], [33, 1]]:
        k = ab.pack_key(codes)
        assert 0 <= k < 2**24
        padded = list(codes) + [0] * (4 - len(codes))
        assert ab.unpack_key(k) == padded


# ---------------------------------------------------------------------------
# Extended rule pool (beyond-paper; paper §7 future work)
# ---------------------------------------------------------------------------
def test_extended_defective_final(dicts):
    d, da = dicts
    # سقى (defective past of سقي) unrecoverable with paper rules...
    root, src = pyref.stem_word("سقى", d)
    assert src == pyref.SRC_NONE
    # ...recovered with the extended pool
    root, src = pyref.stem_word("سقى", d, extended=True)
    assert root == "سقي" and src == pyref.SRC_EXT_DEFECTIVE


def test_extended_hollow_yeh(dicts):
    d, da = dicts
    root, src = pyref.stem_word("باع", d, extended=True)
    assert root == "بيع" and src == pyref.SRC_EXT_HOLLOW_Y


def test_extended_jax_matches_pyref(dicts):
    d, da = dicts
    words, _, _ = corpus.build_corpus(n_words=400, seed=17)
    enc = corpus.encode_corpus(words)
    roots_jax, src_jax = stemmer.stem_batch(enc, da, extended=True)
    roots_jax, src_jax = np.asarray(roots_jax), np.asarray(src_jax)
    for i, w in enumerate(words):
        ref_root, ref_src = pyref.extract_root(enc[i], d, extended=True)
        got = tuple(int(c) for c in roots_jax[i] if c)
        assert got == ref_root, w
        assert int(src_jax[i]) == ref_src, w


def test_extended_improves_accuracy():
    from repro.core import accuracy
    words, truths, _ = corpus.build_corpus(n_words=2500, seed=19)
    d = corpus.build_dictionary()
    base = accuracy.evaluate(words, truths, d, infix=True)
    ext = accuracy.evaluate(words, truths, d, infix=True, extended=True)
    assert ext.accuracy > base.accuracy  # defective pasts now recovered
