"""TextAnalysisWorkload: raw documents through the unchanged Engine
machinery, bit-identical to the host normalise -> segment -> stem_batch
pipeline across every front end, resident/streamed dictionaries,
megabatch on/off, the persistent descriptor-ring kernel, and a hot swap
landing mid-stream. Multi-device (data_devices=4) text coverage lives
in test_serve_sharded.py under forced host devices."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import corpus, stemmer
from repro.core import textnorm as tn
from repro.serve import (DictStore, Engine, StemmerWorkload,
                         TextAnalysisWorkload, TextRequest, Workload)


@pytest.fixture(scope="module")
def arrays():
    d = corpus.build_dictionary(n_tri=400, n_quad=60, seed=0)
    return stemmer.RootDictArrays.from_rootdict(d)


@pytest.fixture(scope="module")
def docs():
    from repro.launch.serve import build_documents

    return build_documents(6, 32, seed=2)


def _oracle(doc_batch, use):
    """Host pipeline for one request's documents."""
    words, spans, ids = [], [], []
    for i, d in enumerate(doc_batch):
        w, s = tn.analyze_text_py(d)
        words.append(w)
        spans.append(s)
        ids.append(np.full(w.shape[0], i, np.int32))
    w = np.concatenate(words) if words else np.zeros((0, 16), np.int32)
    r, src = stemmer.stem_batch(jnp.asarray(w), use)
    return (w, np.concatenate(spans) if spans else np.zeros((0, 2)),
            np.concatenate(ids) if ids else np.zeros(0, np.int32),
            np.asarray(r), np.asarray(src))


def _check(req, doc_batch, use):
    assert req.done
    w, s, ids, r, src = _oracle(doc_batch, use)
    assert req.n_words == w.shape[0]
    np.testing.assert_array_equal(req.words, w)
    np.testing.assert_array_equal(req.spans, s)
    np.testing.assert_array_equal(req.doc_ids, ids)
    np.testing.assert_array_equal(req.roots, r)
    np.testing.assert_array_equal(req.sources, src)
    assert req.n_bytes == sum(len(d.encode("utf-8")) for d in doc_batch)


def _requests(docs):
    # multi-doc, single-doc list, bare string, and a batch with an empty
    # + punctuation-only doc in the middle
    return [docs[:3], [docs[3]], docs[4], [docs[5], "", "،؟ !", docs[0]]]


def _serve(workload, payloads):
    eng = Engine(workload)
    rids = [eng.submit(p) for p in payloads]
    rep = eng.run_until_drained()
    assert rep.drained
    return eng, rids


@pytest.mark.parametrize("frontend", ["kernel", "reference", "host"])
def test_text_serve_parity_all_frontends(arrays, docs, frontend):
    store = DictStore(arrays)
    eng, rids = _serve(
        TextAnalysisWorkload(store, block_b=32, char_block=256,
                             frontend=frontend),
        _requests(docs))
    for rid, payload in zip(rids, _requests(docs)):
        batch = [payload] if isinstance(payload, str) else list(payload)
        _check(eng.result(rid), batch, arrays)


@pytest.mark.parametrize("residency", ["resident", "streamed"])
@pytest.mark.parametrize("megabatch_tiles", [1, 2])
def test_text_serve_residency_x_megabatch(arrays, docs, residency,
                                          megabatch_tiles):
    use = (corpus.grow_root_arrays(arrays, 1 << 14, seed=3)
           if residency == "streamed" else arrays)
    store = DictStore(use, residency=residency)
    eng, rids = _serve(
        TextAnalysisWorkload(store, block_b=32, char_block=256,
                             megabatch_tiles=megabatch_tiles),
        _requests(docs))
    for rid, payload in zip(rids, _requests(docs)):
        batch = [payload] if isinstance(payload, str) else list(payload)
        _check(eng.result(rid), batch, use)


def test_text_serve_persistent(arrays, docs):
    store = DictStore(arrays, residency="resident")
    eng, rids = _serve(
        TextAnalysisWorkload(store, block_b=32, char_block=256,
                             persistent=True, megabatch_tiles=2),
        [docs[:2], docs[2:4]])
    for rid, payload in zip(rids, [docs[:2], docs[2:4]]):
        _check(eng.result(rid), list(payload), arrays)


def test_text_hot_swap_mid_stream(arrays, docs):
    grown = corpus.grow_root_arrays(arrays, 2048, seed=7)
    store = DictStore(arrays)
    eng = Engine(TextAnalysisWorkload(store, block_b=16, char_block=256,
                                      max_inflight=2, megabatch_tiles=1))
    rids = [eng.submit([d]) for d in docs]
    for _ in range(2):
        eng.step()
    store.publish(grown)
    rep = eng.run_until_drained()
    assert rep.drained
    versions = np.concatenate([eng.result(r).dict_versions for r in rids])
    assert set(versions.tolist()) == {0, 1}   # the swap landed mid-stream
    for rid, d in zip(rids, docs):
        req = eng.result(rid)
        w, s = tn.analyze_text_py(d)
        np.testing.assert_array_equal(req.words, w)
        np.testing.assert_array_equal(req.spans, s)
        # every word's roots must match the dictionary version that
        # actually served it
        for use, ver in ((arrays, 0), (grown, 1)):
            sel = req.dict_versions == ver
            if not sel.any():
                continue
            r, src = stemmer.stem_batch(jnp.asarray(w[sel]), use)
            np.testing.assert_array_equal(req.roots[sel], np.asarray(r))
            np.testing.assert_array_equal(req.sources[sel], np.asarray(src))


def test_text_analyses_scatter_per_document(arrays, docs):
    store = DictStore(arrays)
    batch = [docs[0], "", docs[1]]
    eng, rids = _serve(TextAnalysisWorkload(store, block_b=32,
                                            char_block=256), [batch])
    req = eng.result(rids[0])
    per_doc = req.analyses()
    assert len(per_doc) == 3 and per_doc[1] == []
    for i, d in enumerate(batch):
        w, s = tn.analyze_text_py(d)
        assert len(per_doc[i]) == w.shape[0]
        r, _ = stemmer.stem_batch(jnp.asarray(w), arrays)
        from repro.core import alphabet as ab

        for (root, _src, span), want_r, want_s in zip(per_doc[i],
                                                      np.asarray(r), s):
            assert root == ab.decode_word(want_r)
            assert span == (int(want_s[0]), int(want_s[1]))


def test_text_char_bucketing_bounds_tiles(arrays):
    w = TextAnalysisWorkload(DictStore(arrays), char_block=256)
    assert w._char_bucket(1) == 256
    assert w._char_bucket(256) == 256
    assert w._char_bucket(257) == 512
    assert w._char_bucket(5000) == 8192


def test_text_workload_satisfies_protocol(arrays):
    w = TextAnalysisWorkload(DictStore(arrays))
    assert isinstance(w, (Workload, StemmerWorkload))
    assert isinstance(w.make_request(0, "قلم"), TextRequest)


def test_text_validation_errors(arrays):
    store = DictStore(arrays)
    with pytest.raises(ValueError, match="frontend"):
        TextAnalysisWorkload(store, frontend="gpu")
    with pytest.raises(ValueError, match="char_block"):
        TextAnalysisWorkload(store, char_block=64)
    w = TextAnalysisWorkload(store)
    with pytest.raises(ValueError, match="str documents"):
        w.make_request(0, [b"bytes not str"])
    with pytest.raises(ValueError, match="unknown text request options"):
        w.make_request(0, ["قلم"], max_new=4)


def test_text_empty_request_completes(arrays):
    store = DictStore(arrays)
    eng, rids = _serve(TextAnalysisWorkload(store, block_b=16), [[], ""])
    for rid in rids:
        req = eng.result(rid)
        assert req.done and req.n_words == 0
        assert req.analyses() == ([] if req.docs == [] else [[]])
