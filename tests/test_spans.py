"""The program's profiler spans (serve/spans.py), read back from a real
profile: each span opens where the table in serve/spans.py says, nests
under the engine call that causes it, the stemmer's per-launch phases
never overlap, one launch span opens per launch attempt, and taking a
profile changes no result."""
import jax
import numpy as np
import pytest

from repro.core import corpus, stemmer
from repro.serve import (DictStore, Engine, StemmerWorkload,
                         TextAnalysisWorkload, spans)

HOST_PLANE = "/host:CPU"
STEM_LEAVES = (spans.STEM_COALESCE, spans.STEM_STAGE, spans.STEM_LAUNCH,
               spans.STEM_FETCH, spans.STEM_VERIFY, spans.STEM_SCATTER)
SIZES = (37, 64, 5, 50)          # words per request, not block_b-aligned


@pytest.fixture(scope="module")
def lexicon():
    d = corpus.build_dictionary(n_tri=400, n_quad=60, seed=0)
    return stemmer.RootDictArrays.from_rootdict(d)


@pytest.fixture(scope="module")
def word_batches():
    words, _, _ = corpus.build_corpus(n_words=sum(SIZES), seed=1)
    enc = corpus.encode_corpus(words)
    cuts = np.cumsum((0,) + SIZES)
    return [enc[a:b] for a, b in zip(cuts[:-1], cuts[1:])]


@pytest.fixture(scope="module")
def documents():
    from repro.launch.serve import build_documents

    docs = build_documents(5, 32, seed=2)
    return [docs[:2], [docs[2]], docs[3:]]


def _serve(workload, payloads):
    eng = Engine(workload)
    rids = [eng.submit(p) for p in payloads]
    assert eng.run_until_drained().drained
    return [eng.result(r) for r in rids]


def _profiled(tmp_path, fn):
    """-> (fn's result, every ``repro.*`` host span of the profile as
    (name, thread line, start ns, end ns), in start order)."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    found = []
    path = next(tmp_path.rglob("*.xplane.pb"))
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            found += [(ev.name, line.name, ev.start_ns,
                       ev.start_ns + ev.duration_ns)
                      for ev in line.events if ev.name.startswith("repro.")]
    return out, sorted(found, key=lambda s: s[2])


def _named(found, *names):
    return [s for s in found if s[0] in names]


def _assert_nested(found, child, parent):
    parents = _named(found, parent)
    for _name, line, start, end in _named(found, child):
        assert any(p[1] == line and p[2] <= start and end <= p[3]
                   for p in parents), (child, "outside", parent)


def _assert_stem_tree(found):
    for leaf in STEM_LEAVES:
        _assert_nested(found, leaf, spans.ENGINE_STEP)
    leaves = _named(found, *STEM_LEAVES)
    for a, b in zip(leaves, leaves[1:]):
        assert a[3] <= b[2], ("overlap", a[0], b[0])


def _same_results(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.failure is None and y.failure is None
        for field in ("words", "roots", "sources", "dict_versions"):
            np.testing.assert_array_equal(getattr(x, field),
                                          getattr(y, field))


def test_stemmer_spans(tmp_path, lexicon, word_batches):
    store = DictStore(lexicon)
    plain = _serve(StemmerWorkload(store, block_b=32, megabatch_tiles=1),
                   word_batches)
    work = StemmerWorkload(store, block_b=32, megabatch_tiles=1)
    traced, found = _profiled(tmp_path, lambda: _serve(work, word_batches))
    _same_results(plain, traced)
    assert {s[0] for s in found} == {spans.ENGINE_SUBMIT, spans.ENGINE_STEP,
                                     *STEM_LEAVES}
    assert len(_named(found, spans.ENGINE_SUBMIT)) == len(SIZES)
    _assert_stem_tree(found)
    launches = len(_named(found, spans.STEM_LAUNCH))
    assert launches == work.ticks_launched > 1
    for per_launch in (spans.STEM_STAGE, spans.STEM_FETCH,
                       spans.STEM_VERIFY, spans.STEM_SCATTER):
        assert len(_named(found, per_launch)) == launches


def test_text_frontend_spans(tmp_path, lexicon, documents):
    store = DictStore(lexicon)

    def workload():
        return TextAnalysisWorkload(store, block_b=32, char_block=256,
                                    frontend="kernel")

    plain = _serve(workload(), documents)
    traced, found = _profiled(tmp_path,
                              lambda: _serve(workload(), documents))
    _same_results(plain, traced)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a.spans, b.spans)
        np.testing.assert_array_equal(a.doc_ids, b.doc_ids)
    assert {s[0] for s in found} == set(spans.ALL)
    for name in (spans.ENGINE_SUBMIT, spans.TEXT_FRONTEND, spans.TEXT_FETCH):
        assert len(_named(found, name)) == len(documents)
    _assert_nested(found, spans.TEXT_FRONTEND, spans.ENGINE_SUBMIT)
    _assert_nested(found, spans.TEXT_FETCH, spans.TEXT_FRONTEND)
    _assert_stem_tree(found)


def test_no_verify_span_without_checksum(tmp_path, lexicon, word_batches):
    work = StemmerWorkload(DictStore(lexicon), block_b=32, checksum=False)
    _, found = _profiled(tmp_path, lambda: _serve(work, word_batches))
    assert not _named(found, spans.STEM_VERIFY)
    assert len(_named(found, spans.STEM_FETCH)) == work.ticks_launched
    _assert_stem_tree(found)


def test_a_failed_launch_closes_its_span(tmp_path, lexicon, word_batches,
                                         monkeypatch):
    """A launch that raises goes to the retry machinery; its span still
    closes, so launch spans count launches plus failed attempts."""
    from repro.kernels import ops

    real, boom = ops.extract_roots_fused, {"armed": True}

    def flaky(*a, **kw):
        if boom.pop("armed", False):
            raise RuntimeError("transient device failure")
        return real(*a, **kw)

    monkeypatch.setattr(ops, "extract_roots_fused", flaky)
    work = StemmerWorkload(DictStore(lexicon), block_b=32)
    _, found = _profiled(tmp_path, lambda: _serve(work, word_batches))
    assert work.retries_total == 1
    assert len(_named(found, spans.STEM_LAUNCH)) == work.ticks_launched + 1
    _assert_stem_tree(found)
