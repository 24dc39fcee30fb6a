"""The benchmark's generators and references: seeded, fixed in size, and
pinned.

The lexicon builder, the alphabet, the text rules and the reference
stemmer were copied from the program and checked equal to its originals
when they were copied. Here they, and the traffic drawn from them, are
held to digests recorded in ``bench/testdata/golden.json``, so that the
yardstick cannot move unseen, whatever the program later does with its
own copies.
"""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench.gen import alphabet as ab
from bench.gen import corpus as gen
from bench.gen import textrules as tr
from bench.ref import index as ref_index
from bench.ref import stemmer as ref

BIG_SEED = 2**31 + 12345      # seeds run past 32 signed bits
GOLDEN = Path(__file__).resolve().parent / "testdata" / "golden.json"


@pytest.fixture(scope="module")
def lexicon():
    return gen.build_dictionary(300, 40, 5)


@pytest.fixture(scope="module")
def table(lexicon):
    return gen.build_token_table(lexicon)


def _docs(seed, table):
    return gen.documents(seed, table, count=12, words_median=40,
                         words_sigma=0.6, words_min=5, words_max=120,
                         names_share=0.1, comma_every=8)


def _chapters(seed, lexicon):
    return gen.chapters(seed, lexicon, count=20, total_words=3000,
                        words_min=10, words_max=800, zipf_a=1.3)


def _corpus(seed, table):
    return gen.corpus_chunks(seed, table, chunks=2, chunk_words=2048,
                             words_per_doc=300)


@pytest.mark.parametrize("kind", ["lexicon", "documents", "queries",
                                  "chapters", "corpus"])
def test_same_seed_same_traffic_other_seed_other(kind, table, lexicon):
    def make(seed):
        if kind == "lexicon":
            return gen.packed_tables(gen.build_dictionary(300, 40, seed))
        if kind == "documents":
            return _docs(seed, table)
        if kind == "queries":
            return gen.queries(seed, table, count=50, words_min=1, words_max=5)
        if kind == "chapters":
            return _chapters(seed, lexicon)
        return [c.words for c in _corpus(seed, table)]

    def flat(x):
        if isinstance(x, dict):
            return [x[k] for k in sorted(x)]
        return x

    a, b, c = flat(make(BIG_SEED)), flat(make(BIG_SEED)), flat(make(7))
    assert len(a) == len(b) == len(c)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))
    assert not all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, c))


def test_every_seed_gets_the_same_sizes(table, lexicon):
    """The seed orders the sizes and picks the words; the work is fixed."""
    for make, size in ((lambda s: _chapters(s, lexicon), len),
                       (lambda s: gen.queries(s, table, count=50, words_min=1,
                                              words_max=5),
                        lambda q: len(q.split()))):
        sizes = [sorted(size(x) for x in make(s)) for s in (1, BIG_SEED)]
        assert sizes[0] == sizes[1]
    g1, g2 = gen.arrival_gaps(100, 5.0), gen.arrival_gaps(100, 5.0)
    assert np.array_equal(g1, g2) and abs(g1.sum() - 5.0) < 1e-9


def test_chapter_lengths_meet_range_and_total():
    n = gen.chapter_lengths(114, total=77430, lo=10, hi=6100)
    assert len(n) == 114 and n.sum() == 77430
    assert n.min() == 10 and 6000 <= n.max() <= 6200


def test_documents_carry_commas_and_names(table):
    docs = _docs(3, table)
    lengths = [len(d.split()) for d in docs]
    assert min(lengths) >= 5 and max(lengths) <= 120
    text = "".join(docs)
    assert not any(ord(ch) in ab.DIACRITICS for ch in text)
    assert gen.COMMA in text
    words = text.split()
    assert 0 < sum(w.rstrip(gen.COMMA) in gen.NAMES for w in words) < len(words) / 4


def test_words_reach_the_whole_lexicon(lexicon, table):
    """Words are drawn over every root of the lexicon, its seeded
    pseudo-roots too, and a share of them has no root at all."""
    rows = np.concatenate(_chapters(BIG_SEED, lexicon))
    roots, sources = ref.stem_rows(rows, lexicon)
    real = {tuple(int(c) for c in ab.encode_word(r) if c)
            for r in gen.REAL_TRI_ROOTS + gen.REAL_QUAD_ROOTS}
    found = {tuple(int(c) for c in r if c) for r in roots[sources > 0]}
    assert found - real                      # pseudo-roots are hit
    assert 0 < (sources == 0).mean() < 0.5   # some words have no root
    assert len(table.texts) > 24 * len(lexicon.tri | lexicon.quad)


def test_templated_forms_equal_the_conjugator():
    from bench.gen import conjugator

    strong = [r for r in gen.REAL_TRI_ROOTS + gen.REAL_QUAD_ROOTS
              if gen._templated(r)]
    assert len(strong) > 50
    for r in strong:
        want = tuple(dict.fromkeys(w for w, _ in conjugator.conjugate(r)))
        assert gen.root_forms(r) == want
        assert gen.root_form(r, len(want) - 1) == want[-1]


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        if isinstance(a, str):
            h.update(a.encode())
        else:
            a = np.ascontiguousarray(a)
            h.update(str(a.shape).encode() + a.astype(np.int64).tobytes())
    return h.hexdigest()[:16]


def _pinned(table, lexicon) -> dict:
    """What the digests cover: the copies and the traffic drawn from them."""
    lex = gen.build_dictionary(600, 60, 5)
    tables = gen.packed_tables(lex)
    words = [w for r in gen.REAL_TRI_ROOTS[:40] for w in gen.root_forms(r, 12)]
    enc = ab.encode_batch(words)
    roots, sources = ref.stem_rows(enc, lex)
    docs = _docs(9, table)
    text = [tr.analyze_text_py(d) for d in docs]
    return {
        "lexicon_tables": _digest(*(tables[k] for k in sorted(tables))),
        "encoded_words": _digest(enc),
        "reference_roots": _digest(roots, sources),
        "token_table": _digest("\n".join(table.texts)),
        "documents": _digest("\n".join(docs)),
        "front_end_rows": _digest(*(a for w, sp in text for a in (w, sp))),
        "queries": _digest("\n".join(gen.queries(9, table, count=40, words_min=1,
                                                  words_max=5, names_share=0.1))),
        "chapters": _digest(*_chapters(9, lexicon)),
        "corpus": _digest(*(a for c in _corpus(9, table)
                            for a in (c.words, c.doc_ids, c.positions))),
    }


def test_copies_and_traffic_match_their_digests(table, lexicon):
    assert _pinned(table, lexicon) == json.loads(GOLDEN.read_text())


def test_unique_rows_and_reference_index():
    rows = np.array([[1, 2, 3] + [0] * 13, [1, 2, 3] + [0] * 13,
                     [0] * 15 + [5], [4] * 16], np.int32)
    uniq, inv = ref.unique_rows(rows)
    assert len(uniq) == 3 and np.array_equal(uniq[inv], rows)
    lex = gen.build_dictionary(200, 20, 1)
    tables = gen.packed_tables(lex)
    keys = ref_index.vocab(tables)
    words = ab.encode_batch(["كتب", "يكتبون", "qq", "علم"])
    counts, docs, pos = ref_index.build(
        words, np.array([0, 0, 1, 1]), np.array([0, 1, 0, 1], np.int32),
        lex, keys)
    assert counts.sum() == 3 and len(docs) == 3
    k = keys.searchsorted(ab.pack_key(ab.encode_word("كتب")[:3]))
    at = int(counts[:k].sum())
    assert counts[k] == 2
    assert docs[at:at + 2].tolist() == [0, 0]
    assert pos[at:at + 2].tolist() == [0, 1]
