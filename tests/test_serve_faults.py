"""Fault-tolerance tests: the deterministic fault-injection harness
(serve/faults.py) and every recovery path it drives — ring-slot retry
with backoff under injected dispatch failures, poison-pill bisection
quarantine, per-request deadlines, retire-side checksum verification
of corrupted device results, queue-cap admission control
(shed/raise/block), validated two-phase DictStore publishes with
rollback, and torn-checkpoint recovery in the corpus-index builder.
The recovery invariant throughout: every request that survives a fault
returns bit-identical results to a fault-free run."""
import itertools
import json
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import corpus, stemmer
from repro.index import builder
from repro.kernels import ops
from repro.serve import (DictStore, DictValidationError, Engine,
                         EngineUndrained, FailureInfo, FaultInjector,
                         FaultPlan, FaultSpec, InjectedFault, QueueFull,
                         StemmerWorkload, TextAnalysisWorkload,
                         validate_handle)


@pytest.fixture(scope="module")
def dict_and_words():
    d = corpus.build_dictionary(n_tri=400, n_quad=60, seed=0)
    arrays = stemmer.RootDictArrays.from_rootdict(d)
    words, _, _ = corpus.build_corpus(n_words=256, seed=1)
    return arrays, corpus.encode_corpus(words)


@pytest.fixture(scope="module")
def baseline(dict_and_words):
    """Fault-free per-request roots for 8 x 32-word requests."""
    arrays, enc = dict_and_words
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32,
                                 max_inflight=2))
    rids = [eng.submit(enc[i * 32:(i + 1) * 32]) for i in range(8)]
    assert eng.run_until_drained().drained
    return [np.array(eng.result(r).roots) for r in rids]


def _drain_8(arrays, enc, *, injector=None, **kw):
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32,
                                 max_inflight=2, injector=injector, **kw))
    rids = [eng.submit(enc[i * 32:(i + 1) * 32]) for i in range(8)]
    assert eng.run_until_drained().drained
    return eng, rids


# ---------------------------------------------------------------------------
# the injector itself
# ---------------------------------------------------------------------------
def test_fault_spec_validation():
    with pytest.raises(ValueError, match="site"):
        FaultSpec("gpu")
    with pytest.raises(ValueError, match="kind"):
        FaultSpec("dispatch", kind="corrupt")   # corrupt is retire-only
    with pytest.raises(ValueError, match="at"):
        FaultSpec("dispatch", at=-1)
    with pytest.raises(ValueError, match="count"):
        FaultSpec("retire", count=0)
    s = FaultSpec("dispatch", at=2, count=3)
    assert s.kind == "fail"                     # site default
    assert not s.covers(1) and s.covers(2) and s.covers(4)
    assert not s.covers(5)


def test_injector_is_deterministic(dict_and_words):
    """Same plan + same event sequence -> identical fired log and
    identical corruption (the retire rng is seeded per event)."""
    arrays, _ = dict_and_words
    plan = FaultPlan(specs=(FaultSpec("retire", at=0),), seed=42)
    outs = []
    for _ in range(2):
        inj = FaultInjector(plan)
        roots = np.arange(128, dtype=np.int32).reshape(32, 4)
        srcs = np.zeros(32, np.int32)
        r2, s2 = inj.on_retire(roots, srcs)
        outs.append((np.array(r2), inj.fired[:]))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    assert outs[0][1] == outs[1][1] == [("retire", "corrupt", 0)]
    assert not np.array_equal(outs[0][0],
                              np.arange(128, dtype=np.int32).reshape(32, 4))


# ---------------------------------------------------------------------------
# dispatch faults: retry, backoff, bisection quarantine
# ---------------------------------------------------------------------------
def test_dispatch_fault_mid_ring_bit_identical(dict_and_words, baseline):
    """An injected launch failure with max_inflight=2 is retried and the
    full drain stays bit-identical to the fault-free run."""
    arrays, enc = dict_and_words
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("dispatch", at=1),)))
    eng, rids = _drain_8(arrays, enc, injector=inj, megabatch_tiles=1)
    assert inj.fired == [("dispatch", "fail", 1)]
    assert eng.workload.retries_total == 1
    for rid, want in zip(rids, baseline):
        req = eng.result(rid)
        assert req.failure is None
        np.testing.assert_array_equal(req.roots, want)


def test_repeated_dispatch_faults_with_backoff(dict_and_words, baseline):
    """Several injected failures in a row are absorbed while backoff is
    in effect; results stay bit-identical."""
    arrays, enc = dict_and_words
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("dispatch", count=2),)))
    eng, rids = _drain_8(arrays, enc, injector=inj, max_retries=3,
                         retry_backoff_s=0.01)
    assert eng.workload.retries_total == 2
    for rid, want in zip(rids, baseline):
        np.testing.assert_array_equal(eng.result(rid).roots, want)


def test_poison_pill_bisection_quarantine(dict_and_words, baseline):
    """Four requests coalesce into one tile; the one poisoned request is
    isolated by bisection and quarantined with a structured FailureInfo
    while the other three complete bit-identically."""
    arrays, enc = dict_and_words
    inj = FaultInjector(FaultPlan(poison_rids=frozenset({2})))
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=128,
                                 max_inflight=1, max_retries=1,
                                 injector=inj))
    rids = [eng.submit(enc[i * 32:(i + 1) * 32]) for i in range(4)]
    assert eng.run_until_drained().drained
    w = eng.workload
    assert w.bisections >= 1 and w.quarantined == 1
    for i, rid in enumerate(rids):
        req = eng.result(rid)
        if i == 2:
            assert isinstance(req.failure, FailureInfo)
            assert req.failure.code == "quarantined"
            assert req.failure.rid == rid and req.failure.retries > 0
        else:
            assert req.failure is None
            np.testing.assert_array_equal(req.roots, baseline[i])


def test_strict_mode_propagates_first_failure(dict_and_words):
    """max_retries=0 restores the fail-fast contract: the injected
    launch failure reaches the caller, claims are unwound, and the
    engine still drains on retry."""
    arrays, enc = dict_and_words
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("dispatch", at=0),)))
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32,
                                 max_retries=0, injector=inj))
    eng.submit(enc[:32])
    with pytest.raises(InjectedFault):
        eng.step()
    assert all(r.dispatched == 0 for r in eng.workload.inflight)
    assert eng.run_until_drained().drained


# ---------------------------------------------------------------------------
# retire faults: checksum catches corrupted results
# ---------------------------------------------------------------------------
def test_tile_checksum_host_device_parity(dict_and_words):
    arrays, enc = dict_and_words
    roots, sources = stemmer.stem_batch(jnp.asarray(enc[:64]), arrays)
    dev = np.asarray(ops.tile_checksum(roots, sources, block_b=32))
    host = ops.tile_checksum_host(np.asarray(roots), np.asarray(sources),
                                  block_b=32)
    assert dev.shape == (2,)
    np.testing.assert_array_equal(dev, host)
    # a single flipped element changes the row checksum
    bad = np.array(roots)
    bad[5, 1] ^= 0x5A
    assert ops.tile_checksum_host(bad, np.asarray(sources),
                                  block_b=32)[0] != host[0]


def test_retire_corruption_detected_and_retried(dict_and_words, baseline):
    """An injected device-result corruption is caught by the retire-side
    checksum, the tile redispatches, and the drain is bit-identical."""
    arrays, enc = dict_and_words
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("retire", at=0),)))
    eng, rids = _drain_8(arrays, enc, injector=inj)
    assert eng.workload.checksum_failures == 1
    assert eng.workload.retries_total == 1
    for rid, want in zip(rids, baseline):
        req = eng.result(rid)
        assert req.failure is None
        np.testing.assert_array_equal(req.roots, want)


def test_retire_corruption_strict_mode_raises(dict_and_words):
    arrays, enc = dict_and_words
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("retire", at=0),)))
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32,
                                 max_retries=0, injector=inj))
    eng.submit(enc[:32])
    with pytest.raises(RuntimeError, match="checksum"):
        eng.run_until_drained()


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------
def test_deadline_expired_request_fails_later_succeed(dict_and_words,
                                                      baseline):
    arrays, enc = dict_and_words
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32))
    rid_dead = eng.submit(enc[:32], deadline_s=0.001)
    time.sleep(0.01)
    rid_live = eng.submit(enc[32:64])
    assert eng.run_until_drained().drained
    dead = eng.result(rid_dead)
    assert dead.failure is not None and dead.failure.code == "deadline"
    live = eng.result(rid_live)
    assert live.failure is None
    np.testing.assert_array_equal(live.roots, baseline[1])


def test_deadline_far_future_never_fires(dict_and_words, baseline):
    arrays, enc = dict_and_words
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32))
    rid = eng.submit(enc[:32], deadline_s=3600.0)
    assert eng.run_until_drained().drained
    assert eng.result(rid).failure is None
    np.testing.assert_array_equal(eng.result(rid).roots, baseline[0])


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------
def test_queue_cap_validation(dict_and_words):
    arrays, _ = dict_and_words
    w = StemmerWorkload(DictStore(arrays), block_b=32)
    with pytest.raises(ValueError, match="on_full"):
        Engine(w, queue_cap=2, on_full="explode")
    with pytest.raises(ValueError, match="queue_cap"):
        Engine(w, queue_cap=0)
    with pytest.raises(ValueError, match="queue_cap"):
        Engine(w, on_full="shed")   # a cap-less queue is never full


def test_queue_cap_raise(dict_and_words):
    arrays, enc = dict_and_words
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32),
                 queue_cap=1, on_full="raise")
    eng.submit(enc[:32])
    with pytest.raises(QueueFull):
        eng.submit(enc[:32])
    assert eng.run_until_drained().drained      # admitted work unaffected


def test_queue_cap_shed(dict_and_words, baseline):
    arrays, enc = dict_and_words
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32),
                 queue_cap=2, on_full="shed")
    rids = [eng.submit(enc[:32]) for _ in range(5)]
    shed = [r for r in rids if eng.result(r) is not None
            and eng.result(r).failure is not None]
    assert len(shed) == 3 and eng.shed == 3
    for r in shed:
        assert eng.result(r).failure.code == "shed"
    assert eng.run_until_drained().drained
    served = [r for r in rids if r not in shed]
    for r in served:
        np.testing.assert_array_equal(eng.result(r).roots, baseline[0])


def test_queue_cap_block(dict_and_words, baseline):
    """on_full="block" ticks the engine inside submit until the request
    fits; every submission is eventually served."""
    arrays, enc = dict_and_words
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32),
                 queue_cap=1, on_full="block")
    rids = [eng.submit(enc[i * 32:(i + 1) * 32]) for i in range(4)]
    assert eng.run_until_drained().drained and eng.shed == 0
    for rid, want in zip(rids, baseline):
        np.testing.assert_array_equal(eng.result(rid).roots, want)


def test_undrained_raise_cancels_and_engine_reusable(dict_and_words,
                                                     baseline):
    """A poisoned request that would never drain is cancelled by
    on_undrained="raise" and the engine serves fresh work afterwards."""
    arrays, enc = dict_and_words
    inj = FaultInjector(FaultPlan(poison_rids=frozenset({0})))
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32,
                                 max_retries=50, retry_backoff_s=0.01,
                                 injector=inj))
    eng.submit(enc[:32])
    with pytest.raises(EngineUndrained) as exc:
        eng.run_until_drained(max_ticks=3)
    assert exc.value.report.cancelled == [0]
    assert eng.result(0).failure.code == "cancelled"
    assert not eng.queue and eng.workload.active == 0
    rid = eng.submit(enc[32:64])
    assert eng.run_until_drained().drained
    np.testing.assert_array_equal(eng.result(rid).roots, baseline[1])


# ---------------------------------------------------------------------------
# text workload inherits the whole fault path
# ---------------------------------------------------------------------------
def test_text_workload_dispatch_fault_and_failed_read(dict_and_words):
    arrays, _ = dict_and_words
    docs = ["كتب الولد درسا", "ذهب الرجل الى السوق"]
    ref = Engine(TextAnalysisWorkload(DictStore(arrays), block_b=32,
                                      frontend="host"))
    ref_rids = [ref.submit(d) for d in docs]
    assert ref.run_until_drained().drained
    want = [ref.result(r).analyses() for r in ref_rids]

    inj = FaultInjector(FaultPlan(specs=(FaultSpec("dispatch", at=0),)))
    eng = Engine(TextAnalysisWorkload(DictStore(arrays), block_b=32,
                                      frontend="host", injector=inj))
    rids = [eng.submit(d) for d in docs]
    assert eng.run_until_drained().drained
    assert eng.workload.retries_total == 1
    assert [eng.result(r).analyses() for r in rids] == want

    # a quarantined text request refuses to hand out garbage analyses
    inj2 = FaultInjector(FaultPlan(poison_rids=frozenset({0})))
    eng2 = Engine(TextAnalysisWorkload(DictStore(arrays), block_b=32,
                                       frontend="host", max_retries=1,
                                       injector=inj2))
    rid = eng2.submit(docs[0])
    assert eng2.run_until_drained().drained
    req = eng2.result(rid)
    assert req.failure.code == "quarantined"
    with pytest.raises(RuntimeError, match="quarantined"):
        req.analyses()


# ---------------------------------------------------------------------------
# DictStore: two-phase publish, injected rejection, rollback
# ---------------------------------------------------------------------------
def test_publish_validation_rejects_bad_tables(dict_and_words):
    arrays, _ = dict_and_words
    store = DictStore(arrays)
    v0 = store.version
    bad = stemmer.RootDictArrays(
        tri=np.array([5, 3, 1], np.int32),          # unsorted
        quad=np.asarray(arrays.quad), bi=np.asarray(arrays.bi))
    with pytest.raises(DictValidationError, match="sorted"):
        store.publish(bad)
    assert store.version == v0                      # phase 2 never ran
    dup = stemmer.RootDictArrays(
        tri=np.array([3, 3], np.int32),
        quad=np.asarray(arrays.quad), bi=np.asarray(arrays.bi))
    with pytest.raises(DictValidationError):
        store.publish(dup)
    neg = stemmer.RootDictArrays(
        tri=np.array([-7, 3], np.int32),
        quad=np.asarray(arrays.quad), bi=np.asarray(arrays.bi))
    with pytest.raises(DictValidationError, match="negative"):
        store.publish(neg)
    validate_handle(store.acquire().handle)         # current is valid


def test_publish_injected_rejection_and_rollback(dict_and_words):
    arrays, _ = dict_and_words
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("publish", at=0),)))
    store = DictStore(arrays, keep_history=True, injector=inj)
    v0 = store.acquire().version
    d2 = corpus.build_dictionary(n_tri=150, n_quad=20, seed=7)
    a2 = stemmer.RootDictArrays.from_rootdict(d2)
    with pytest.raises(InjectedFault):
        store.publish(a2)
    assert store.acquire().version == v0            # still serving v0
    v1 = store.publish(a2)                          # next publish lands
    assert v1 > v0
    v2 = store.rollback(v0)
    assert v2 > v1                                  # versions stay monotone
    np.testing.assert_array_equal(
        np.asarray(store.acquire().handle.arrays.tri),
        np.asarray(store.get(v0).handle.arrays.tri))


def test_rollback_requires_history(dict_and_words):
    arrays, _ = dict_and_words
    store = DictStore(arrays, keep_history=False)
    d2 = corpus.build_dictionary(n_tri=150, n_quad=20, seed=7)
    store.publish(stemmer.RootDictArrays.from_rootdict(d2))
    with pytest.raises(KeyError):
        store.rollback(0)


# ---------------------------------------------------------------------------
# index builder: torn checkpoints, chunk retry
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def index_setup():
    table = corpus.build_token_table(forms_per_root=6)
    d = corpus.build_dictionary(n_tri=300, n_quad=40, seed=0)
    arrays = stemmer.RootDictArrays.from_rootdict(d)

    def stream():
        return corpus.stream_corpus_words(9000, seed=3, chunk_words=4096,
                                          table=table)

    ref = builder.build_corpus_index(stream(), arrays, block_b=512,
                                     block_w=512)
    return arrays, stream, ref


def _assert_same_index(got, want):
    np.testing.assert_array_equal(np.asarray(got.counts),
                                  np.asarray(want.counts))
    np.testing.assert_array_equal(np.asarray(got.docs),
                                  np.asarray(want.docs))
    np.testing.assert_array_equal(np.asarray(got.positions),
                                  np.asarray(want.positions))


def test_build_under_checkpoint_and_compute_faults(index_setup, tmp_path):
    """A torn checkpoint write and a failed chunk compute are both
    retried in-build; the result is bit-identical and the manifest
    records a content hash per chunk."""
    arrays, stream, ref = index_setup
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("checkpoint", at=1),
                                         FaultSpec("dispatch", at=1))))
    idx = builder.build_corpus_index(stream(), arrays,
                                     checkpoint_dir=str(tmp_path),
                                     block_b=512, block_w=512,
                                     injector=inj)
    assert len(inj.fired) == 2
    _assert_same_index(idx, ref)
    man = json.loads((tmp_path / "manifest.json").read_text())
    assert man["schema"] == builder.MANIFEST_SCHEMA
    for rec in man["chunks"]:
        assert isinstance(rec["sha"], str) and len(rec["sha"]) == 16


def test_torn_partial_on_resume_recomputed(index_setup, tmp_path):
    """A partial torn on disk between runs fails its manifest hash check
    and is transparently recomputed on resume — bit-identical result."""
    arrays, stream, ref = index_setup
    ckpt = str(tmp_path / "ckpt")
    builder.build_corpus_index(itertools.islice(stream(), 2), arrays,
                               checkpoint_dir=ckpt, block_b=512,
                               block_w=512)
    parts = sorted(p for p in os.listdir(ckpt) if p.endswith(".npz"))
    assert len(parts) == 2
    torn = os.path.join(ckpt, parts[1])
    with open(torn, "r+b") as f:
        f.truncate(os.path.getsize(torn) // 2)
    resumed = builder.build_corpus_index(stream(), arrays,
                                         checkpoint_dir=ckpt, resume=True,
                                         block_b=512, block_w=512)
    _assert_same_index(resumed, ref)
    # and the manifest now carries the recomputed chunk's fresh hash
    man = json.loads((tmp_path / "ckpt" / "manifest.json").read_text())
    assert man["chunks"][1]["sha"] == builder._file_sha(torn)


def test_chunk_compute_fault_exhaustion_raises(index_setup, tmp_path):
    arrays, stream, _ = index_setup
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("dispatch", count=99),)))
    with pytest.raises(RuntimeError):
        builder.build_corpus_index(stream(), arrays,
                                   checkpoint_dir=str(tmp_path),
                                   block_b=512, block_w=512,
                                   injector=inj, chunk_retries=1)
