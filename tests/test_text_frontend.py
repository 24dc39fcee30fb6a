"""Text front-end kernel: segmentation properties and fused-chain parity.

The satellite property tests run twice: once hypothesis-driven (skipped
when hypothesis is absent — it is not in the pinned image) and once as
an exhaustive small-grid sweep that needs no extra dependency: every
string over a 6-symbol alphabet up to length 4, coalesced into ONE tile
and pushed through the kernel in a single launch, compared per-document
against the host reference."""
import itertools

import numpy as np
import pytest

from repro.core import corpus, stemmer
from repro.core import textnorm as tn
from repro.kernels import ops
from repro.kernels import text_frontend as tf


def _pad(chars: np.ndarray, block: int = 128) -> np.ndarray:
    t = max(block, -(-chars.shape[0] // block) * block)
    tile = np.zeros(t, np.int32)
    tile[:chars.shape[0]] = chars
    return tile


def _expected(docs):
    """Host reference over coalesced docs: concatenated word rows plus
    tile-absolute byte spans."""
    _, _, byte_off = tn.coalesce_docs(docs)
    rows, spans = [], []
    for off, doc in zip(byte_off, docs):
        w, s = tn.analyze_text_py(doc)
        rows.append(w)
        spans.append(s + off)
    return (np.concatenate(rows) if rows else np.zeros((0, 16), np.int32),
            np.concatenate(spans) if spans else np.zeros((0, 2), np.int64))


def _run_tile(tile, block_w=128):
    words_j, geo = tn.frontend_reference(tile, block_w=block_w)
    words_k = tf.text_frontend_pallas(tile, geo.starts, geo.lens,
                                      block_w=block_w, interpret=True)
    np.testing.assert_array_equal(np.asarray(words_k), np.asarray(words_j))
    n = int(geo.n_words)
    return np.asarray(words_j)[:n], np.asarray(geo.spans)[:n]


# ---------------------------------------------------------------------------
# exhaustive small-grid sweep (the hypothesis-free fallback)
# ---------------------------------------------------------------------------
def test_exhaustive_small_grid_one_launch():
    # letters, a separator, a combining mark, Arabic punctuation
    symbols = ("ا", "ب", "ك", " ", "ّ", "،")
    docs = ["".join(p) for n in range(5)
            for p in itertools.product(symbols, repeat=n)]
    assert len(docs) == 1 + 6 + 36 + 216 + 1296
    chars, _, _ = tn.coalesce_docs(docs)
    got_w, got_s = _run_tile(_pad(chars))
    want_w, want_s = _expected(docs)
    np.testing.assert_array_equal(got_w, want_w)
    np.testing.assert_array_equal(got_s, want_s)


# ---------------------------------------------------------------------------
# hypothesis-driven variant (skipped when the package is absent)
# ---------------------------------------------------------------------------
def test_hypothesis_random_documents():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    alphabet = st.sampled_from(
        list("ابكلموسدرهن فق،.x1َّـةأٱ"))
    texts = st.lists(st.text(alphabet, max_size=40), min_size=1, max_size=6)

    @hyp.given(texts)
    @hyp.settings(max_examples=25, deadline=None)
    def prop(docs):
        chars, _, _ = tn.coalesce_docs(docs)
        got_w, got_s = _run_tile(_pad(chars))
        want_w, want_s = _expected(docs)
        np.testing.assert_array_equal(got_w, want_w)
        np.testing.assert_array_equal(got_s, want_s)

    prop()


# ---------------------------------------------------------------------------
# named segmentation properties
# ---------------------------------------------------------------------------
def test_byte_spans_round_trip():
    from repro.launch.serve import build_documents

    docs = build_documents(4, 40) + ["ٱلرَّحْمَٰنِ الرَّحِيمِ", "x قلمٌ y"]
    chars, _, _ = tn.coalesce_docs(docs)
    raw = "\0".join(docs).encode("utf-8")
    got_w, got_s = _run_tile(_pad(chars))
    want_w, want_s = _expected(docs)
    np.testing.assert_array_equal(got_w, want_w)
    np.testing.assert_array_equal(got_s, want_s)
    prev = 0
    for row, (b0, b1) in zip(got_w, got_s):
        # spans are increasing, non-overlapping, valid utf-8 slices...
        assert prev <= b0 < b1 <= len(raw)
        surface = raw[b0:b1].decode("utf-8")
        prev = b1
        # ...and re-analysing the surface alone reproduces the word row:
        # the span covers exactly the raw run that produced the row
        again, _ = tn.analyze_text_py(surface)
        assert again.shape[0] == 1
        np.testing.assert_array_equal(again[0], row)


def test_words_longer_than_16_truncate_identically():
    long_words = ["ب" * n for n in (16, 17, 20, 25, 31)]
    # marks inflate the raw window past MAX_RAW=32 without adding letters
    long_words.append("كَ" * 20)          # 40 raw cps, 20 letters
    long_words.append("د" + "ّ" * 40 + "رس")
    doc = " ".join(long_words)
    got_w, got_s = _run_tile(_pad(tn.coalesce_docs([doc])[0]))
    want_w, want_s = _expected([doc])
    np.testing.assert_array_equal(got_w, want_w)
    np.testing.assert_array_equal(got_s, want_s)
    # truncation keeps at most 15 letters and the pad column stays zero
    assert got_w.shape[0] == len(long_words)
    assert (got_w[:, 15] == 0).all()
    assert ((got_w != 0).sum(axis=1) <= 15).all()
    # spans still cover the whole (untruncated) surface run
    raw = doc.encode("utf-8")
    for (b0, b1), w in zip(got_s, long_words):
        assert raw[b0:b1].decode("utf-8") == w


def test_empty_whitespace_and_punctuation_docs():
    docs = ["", "   ", "،؟!", "\n\t ", ".,;:", "ًّ", "قلم"]
    chars, _, _ = tn.coalesce_docs(docs)
    got_w, got_s = _run_tile(_pad(chars))
    want_w, want_s = _expected(docs)
    # a marks-only run is still a token (maximal non-separator run): it
    # keeps its byte span but carries an all-zero letter row, which the
    # stemmer maps to SRC_NONE — plus the one real word
    assert want_w.shape[0] == 2
    assert not want_w[0].any() and want_w[1].any()
    np.testing.assert_array_equal(got_w, want_w)
    np.testing.assert_array_equal(got_s, want_s)
    # an all-separator tile segments to zero words
    chars2, _, _ = tn.coalesce_docs(["", " ،؟ ", "  .. "])
    w2, s2 = _run_tile(_pad(chars2))
    assert w2.shape[0] == 0 and s2.shape[0] == 0


def test_segment_geometry_rejects_empty_tile():
    with pytest.raises(ValueError, match="non-empty"):
        tn.segment_geometry(np.zeros(0, np.int32))


def test_block_w_invariance_and_alignment_guard():
    docs = ["والعلم نور", "كتبها في مدرسة"]
    tile = _pad(tn.coalesce_docs(docs)[0])
    w64, s64 = _run_tile(tile, block_w=64)
    w128, s128 = _run_tile(tile, block_w=128)
    np.testing.assert_array_equal(w64, w128)
    np.testing.assert_array_equal(s64, s128)
    geo = tn.segment_geometry(tile, block_w=128)
    with pytest.raises(ValueError, match="block_w"):
        tf.text_frontend_pallas(tile, geo.starts, geo.lens,
                                block_w=96, interpret=True)


# ---------------------------------------------------------------------------
# fused chain: bytes -> roots with no host round-trip
# ---------------------------------------------------------------------------
def test_ops_text_to_words_matches_host_and_counts_one_dispatch():
    from repro.launch.serve import build_documents

    docs = build_documents(3, 32, seed=5)
    tile = _pad(tn.coalesce_docs(docs)[0])
    ops.reset_dispatch_count()
    words, spans, n_words = ops.text_to_words(tile)
    assert ops.dispatch_count() == 1
    n = int(n_words)
    want_w, want_s = _expected(docs)
    assert n == want_w.shape[0]
    np.testing.assert_array_equal(np.asarray(words)[:n], want_w)
    np.testing.assert_array_equal(np.asarray(spans)[:n], want_s)
    assert not np.asarray(words)[n:].any()


def test_clitic_stripping_matches_host_on_cliticised_corpus():
    """4,000 corpus words, each wrapped in a rotating proclitic and
    enclitic and joined into one document: at least 99.9% of the word
    rows the front-end kernel strips are identical to the host
    reference's."""
    words, _, _ = corpus.build_corpus(n_words=4000, seed=11)
    pro = ("", "وال", "ب", "ف", "لل", "ك", "و")
    enc = ("", "ها", "هم", "كم", "ه", "نا", "هما")
    doc = " ".join(pro[i % len(pro)] + w + enc[i % len(enc)]
                   for i, w in enumerate(words))
    want, _ = tn.analyze_text_py(doc)
    got, _, n_words = ops.text_to_words(_pad(tn.coalesce_docs([doc])[0]))
    got = np.asarray(got)[:int(n_words)]
    assert got.shape == want.shape
    assert (got == want).all(axis=1).mean() >= 0.999


@pytest.mark.parametrize("residency", ["resident", "streamed"])
def test_extract_roots_text_bit_identical(residency):
    import jax.numpy as jnp

    from repro.launch.serve import build_documents

    d = corpus.build_dictionary(n_tri=300, n_quad=40, seed=0)
    arrays = stemmer.RootDictArrays.from_rootdict(d)
    if residency == "streamed":
        arrays = corpus.grow_root_arrays(arrays, 1 << 14, seed=3)
    docs = build_documents(3, 24, seed=7)
    tile = _pad(tn.coalesce_docs(docs)[0])
    roots, sources, spans, n_words = ops.extract_roots_text(
        tile, arrays, residency=residency)
    n = int(n_words)
    want_w, want_s = _expected(docs)
    assert n == want_w.shape[0]
    np.testing.assert_array_equal(np.asarray(spans)[:n], want_s)
    want_r, want_src = stemmer.stem_batch(jnp.asarray(want_w), arrays)
    np.testing.assert_array_equal(np.asarray(roots)[:n],
                                  np.asarray(want_r))
    np.testing.assert_array_equal(np.asarray(sources)[:n],
                                  np.asarray(want_src))
