"""TextAnalysisWorkload: raw documents through the unchanged Engine
machinery, bit-identical to the host normalise -> segment -> stem_batch
pipeline across every front end, resident/streamed dictionaries,
megabatch on/off, the persistent descriptor-ring kernel, and a hot swap
landing mid-stream. The front end runs once a tick over every queued
document, one launch a tile of at most ``char_cap`` codepoints; a front
end that keeps failing on a document quarantines that document's
request and serves the rest, and one that fails in strict mode leaves
its requests pending, never served. Multi-device (data_devices=4) text
coverage lives in test_serve_sharded.py under forced host devices."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import corpus, stemmer
from repro.core import textnorm as tn
from repro.kernels import ops
from repro.serve import (DictStore, Engine, EngineUndrained,
                         StemmerWorkload, TextAnalysisWorkload, TextRequest,
                         Workload)
from repro.serve.text import FRONTENDS


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


def _sized(docs, n_chars: int) -> str:
    """A document of exactly ``n_chars`` codepoints cut from ``docs``."""
    text = " ".join(docs)
    while len(text) < n_chars:
        text += " " + text
    return text[:n_chars]


def _batch(payload):
    return [payload] if isinstance(payload, str) else list(payload)


@pytest.mark.parametrize("frontend", FRONTENDS)
def test_text_serve_parity_all_frontends(arrays, docs, frontend):
    """Every request queued before a step is segmented in that step,
    bit-identical per request: multi-document, empty and punctuation
    requests, and a document over the cap, which takes a tile of its
    own between the documents before and after it."""
    payloads = _requests(docs) + [[], [docs[1], _sized(docs, 5000),
                                       docs[2]]]
    w = TextAnalysisWorkload(DictStore(arrays), block_b=32, char_block=256,
                             frontend=frontend)
    assert 5000 > w.char_cap
    eng = Engine(w)
    rids = [eng.submit(p) for p in payloads]
    eng.step()
    assert w.frontend_launches == 3
    assert not any(r.awaiting_frontend for r in w.inflight)
    assert eng.run_until_drained().drained
    assert w.frontend_launches == 3
    for rid, payload in zip(rids, payloads):
        _check(eng.result(rid), _batch(payload), arrays)


def test_queued_requests_share_one_frontend_launch(arrays, docs):
    """N short requests submitted before one step make one front-end
    launch; a lone request later makes one of its own. Warm launches
    count in neither counter."""
    w = TextAnalysisWorkload(DictStore(arrays), block_b=32, char_block=256)
    eng = Engine(w)
    rids = [eng.submit(d) for d in docs]
    eng.step()
    assert w.frontend_launches == 1
    assert eng.run_until_drained().drained
    rids.append(eng.submit([docs[0], docs[1]]))
    assert eng.run_until_drained().drained
    assert w.frontend_launches == 2
    assert ops.dispatch_count() == 2 + w.ticks_launched
    for rid, payload in zip(rids, list(docs) + [[docs[0], docs[1]]]):
        _check(eng.result(rid), _batch(payload), arrays)


@pytest.mark.parametrize("n_docs", [17, 18, 52])
def test_a_batch_over_the_cap_takes_one_launch_a_cap(arrays, docs, n_docs):
    """Documents of 240 codepoints, three a request: 17 with their
    separators fill the 4,096-codepoint cap exactly, so a batch of
    ``chars`` codepoints takes ceil(chars / cap) launches, requests
    straddling tiles."""
    w = TextAnalysisWorkload(DictStore(arrays), block_b=32, char_block=256)
    assert w.char_cap == 4096 == 17 * 241 - 1
    base = _sized(docs, 240 * n_docs)
    batch = [base[i * 240:(i + 1) * 240] for i in range(n_docs)]
    payloads = [batch[i:i + 3] for i in range(0, n_docs, 3)]
    eng = Engine(w)
    rids = [eng.submit(p) for p in payloads]
    assert eng.run_until_drained().drained
    chars = 240 * n_docs + n_docs - 1
    assert w.frontend_launches == -(-chars // w.char_cap)
    for rid, payload in zip(rids, payloads):
        _check(eng.result(rid), payload, arrays)


def test_every_frontend_bucket_compiled_by_the_first_launch(
        arrays, docs, compile_counter):
    """After the first front-end launch, a tick of either shape (one
    char_block, or the cap) traces, lowers and compiles nothing."""
    w = TextAnalysisWorkload(DictStore(arrays), block_b=32, char_block=256)
    eng = Engine(w)
    eng.submit(docs[0])
    assert eng.run_until_drained().drained
    sizes = (100, 256, 300, 700, 1500, 3000, 4096)   # a tile each
    compile_counter.on = True
    rids = []
    for n in sizes:
        rids.append(eng.submit(_sized(docs, n)))
        assert eng.run_until_drained().drained
    compile_counter.on = False
    assert (compile_counter.compiles, compile_counter.lowerings) == (0, 0)
    assert w.frontend_launches == 1 + len(sizes)
    for rid, n in zip(rids, sizes):
        _check(eng.result(rid), [_sized(docs, n)], arrays)


def _break_frontend(monkeypatch, times: int | None = None):
    """ops.text_to_words raises its next ``times`` calls (None: all)."""
    real, left = ops.text_to_words, [times]

    def broken(*a, **kw):
        if left[0] is None or left[0] > 0:
            if left[0] is not None:
                left[0] -= 1
            raise RuntimeError("front end down")
        return real(*a, **kw)

    monkeypatch.setattr(ops, "text_to_words", broken)


def test_a_frontend_error_leaves_the_batch_pending(arrays, docs,
                                                   monkeypatch):
    """Strict mode (max_retries=0): a front end that raises propagates
    out of step with every request of the tick still awaiting it, none
    finished; the next step segments and serves them bit-identically."""
    _break_frontend(monkeypatch, times=1)
    w = TextAnalysisWorkload(DictStore(arrays), block_b=32, char_block=256,
                             max_retries=0)
    eng = Engine(w)
    rids = [eng.submit(d) for d in docs[:3]]
    with pytest.raises(RuntimeError, match="front end down"):
        eng.step()
    assert not eng.finished and w.pending_rids() == rids
    assert all(r.awaiting_frontend and r.n_words == 0 for r in w.inflight)
    assert eng.run_until_drained().drained
    for rid, d in zip(rids, docs[:3]):
        _check(eng.result(rid), [d], arrays)


def test_a_transient_frontend_error_is_retried_in_the_step(arrays, docs,
                                                           monkeypatch):
    """A front end that fails once is retried within the same step: every
    request is served bit-identically, and one retry is on record."""
    _break_frontend(monkeypatch, times=1)
    w = TextAnalysisWorkload(DictStore(arrays), block_b=32, char_block=256)
    eng = Engine(w)
    rids = [eng.submit(d) for d in docs[:3]]
    eng.step()
    assert not any(r.awaiting_frontend for r in w.inflight)
    assert eng.run_until_drained().drained
    assert [e.kind for e in eng.events()].count("retry") == 1
    assert w.frontend_launches == 1 and w.quarantined == 0
    for rid, d in zip(rids, docs[:3]):
        _check(eng.result(rid), [d], arrays)


@pytest.mark.parametrize("error", [ValueError, RuntimeError])
@pytest.mark.parametrize("frontend", FRONTENDS)
def test_a_document_the_frontend_cannot_process_is_quarantined(
        arrays, docs, frontend, error, poison_frontend):
    """A request whose document makes the front end raise every time is
    quarantined in the tick it is segmented in, and the other requests
    of that tick are served bit-identically. A failure to trace or
    compile (ValueError) is not retried; a runtime error is retried
    ``max_retries`` times, on the whole tick and then on the request
    alone."""
    w = TextAnalysisWorkload(DictStore(arrays), block_b=32, char_block=256,
                             frontend=frontend)
    marker = poison_frontend(w, error)
    eng = Engine(w)
    payloads = [docs[0], [docs[1], "كتب " + marker], docs[2]]
    rids = [eng.submit(p) for p in payloads]
    eng.step()
    bad = eng.result(rids[1])
    retries = 0 if error is ValueError else w.max_retries
    assert bad.failure.code == "quarantined"
    assert bad.failure.retries == retries
    assert "poison document" in bad.failure.detail
    assert w.quarantined == 1
    assert [e.kind for e in eng.events()].count("retry") == 2 * retries
    assert eng.run_until_drained().drained
    assert w.frontend_launches == 2   # each good request, alone
    for i in (0, 2):
        _check(eng.result(rids[i]), _batch(payloads[i]), arrays)


def test_a_request_awaiting_the_frontend_expires_or_is_cancelled(
        arrays, docs, monkeypatch):
    """Requests still awaiting the front end finish with their
    FailureInfo: a deadline that passes while queued or while the front
    end is down, and the cancellation of an undrained engine."""
    _break_frontend(monkeypatch)
    w = TextAnalysisWorkload(DictStore(arrays), block_b=32, char_block=256,
                             max_retries=0)
    eng = Engine(w)
    queued = eng.submit(docs[0], deadline_s=0.01)
    time.sleep(0.02)
    admitted = eng.submit(docs[1], deadline_s=0.05)
    stuck = eng.submit(docs[2])
    with pytest.raises(RuntimeError, match="front end down"):
        eng.step()
    assert eng.result(queued).failure.code == "deadline"
    assert w.pending_rids() == [admitted, stuck]
    time.sleep(0.06)
    with pytest.raises(RuntimeError, match="front end down"):
        eng.step()
    assert eng.result(admitted).failure.code == "deadline"
    with pytest.raises(EngineUndrained):
        eng.run_until_drained(max_ticks=0)
    assert eng.result(stuck).failure.code == "cancelled"
    assert w.frontend_launches == 0 and w.active == 0
    for rid in (queued, admitted, stuck):
        with pytest.raises(RuntimeError, match="no analyses"):
            eng.result(rid).analyses()


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
    assert w._char_bucket(257) == w.char_cap == 4096   # two shapes
    assert w._char_bucket(4096) == 4096
    assert w._char_bucket(4097) == 8192        # a document over the cap
    assert w._char_bucket(5000) == 8192
    assert w._char_bucket(9000) == 16384


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
