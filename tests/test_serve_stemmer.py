"""Serving-core tests: the workload-agnostic Engine, StemmerWorkload
tile coalescing + bit-exact parity across dispatch ring depths
(including across a dictionary hot swap, and one landing while tiles
are in flight), the dispatch/retire pipeline's tick accounting,
DictStore versioning + sorted-merge delta publishes, resolved-dict
re-trace avoidance, and the drain report / undrained-work surfacing.
Multi-device (sharded super-tile) coverage lives in
test_serve_sharded.py under forced host devices."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import corpus, pyref, stemmer
from repro.kernels import stem_fused as sf
from repro.serve import (DictStore, DrainReport, Engine, EngineUndrained,
                         StemmerWorkload, Workload)


@pytest.fixture(scope="module")
def dict_and_words():
    d = corpus.build_dictionary(n_tri=400, n_quad=60, seed=0)
    arrays = stemmer.RootDictArrays.from_rootdict(d)
    words, _, _ = corpus.build_corpus(n_words=200, seed=1)
    return arrays, corpus.encode_corpus(words)


def _serve(store, enc, sizes, *, block_b=32, steps_before_swap=None,
           swap_to=None, max_inflight=2, max_requests=None, **kw):
    """Submit word batches of the given sizes, optionally hot-swap, drain."""
    eng = Engine(StemmerWorkload(store, block_b=block_b,
                                 max_inflight=max_inflight,
                                 max_requests=max_requests, **kw))
    off, rids = 0, []
    for n in sizes:
        rids.append(eng.submit(enc[off:off + n]))
        off += n
    if steps_before_swap is not None:
        for _ in range(steps_before_swap):
            eng.step()
        store.publish(swap_to)
    rep = eng.run_until_drained()
    assert rep.drained
    return eng, rids, rep


# ---------------------------------------------------------------------------
# StemmerWorkload parity + coalescing
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("max_inflight", [1, 2, 4])
def test_serve_parity_bit_identical(dict_and_words, max_inflight):
    """Bit-exact at every dispatch ring depth: 1 (synchronous tick,
    overlap off) through deep overlapped rings."""
    arrays, enc = dict_and_words
    store = DictStore(arrays)
    sizes = (37, 64, 5, 50)  # deliberately not block_b-aligned
    eng, rids, rep = _serve(store, enc, sizes, block_b=32,
                            max_inflight=max_inflight)

    want_r, want_s = stemmer.stem_batch(jnp.asarray(enc[:sum(sizes)]), arrays)
    want_r, want_s = np.asarray(want_r), np.asarray(want_s)
    off = 0
    for rid, n in zip(rids, sizes):
        req = eng.result(rid)
        assert req.done and req.n_words == n
        np.testing.assert_array_equal(req.roots, want_r[off:off + n])
        np.testing.assert_array_equal(req.sources, want_s[off:off + n])
        assert (req.dict_versions == 0).all()
        assert req.dict_version == 0
        off += n


def test_serve_coalesces_across_requests(dict_and_words):
    """Many small requests share tiles: ticks == ceil(total / block_b),
    not one tick per request."""
    arrays, enc = dict_and_words
    store = DictStore(arrays)
    sizes = (10,) * 13  # 130 words
    eng, rids, rep = _serve(store, enc, sizes, block_b=32, megabatch_tiles=1)
    assert eng.workload.ticks_launched == -(-130 // 32)  # 5 tiles
    assert all(eng.result(r).done for r in rids)


def test_serve_empty_request_completes(dict_and_words):
    arrays, enc = dict_and_words
    store = DictStore(arrays)
    eng = Engine(StemmerWorkload(store, block_b=16))
    rid_empty = eng.submit(np.zeros((0, 16), np.int32))
    rid_real = eng.submit(enc[:8])
    rep = eng.run_until_drained()
    assert rep.drained
    req = eng.result(rid_empty)
    assert req.done and req.n_words == 0 and req.dict_version is None
    assert eng.result(rid_real).done


def test_serve_accepts_raw_strings(dict_and_words):
    arrays, _ = dict_and_words
    store = DictStore(arrays)
    eng = Engine(StemmerWorkload(store, block_b=16))
    words, _, _ = corpus.build_corpus(n_words=10, seed=3)
    rid = eng.submit(words)  # list[str] encodes through alphabet
    eng.run_until_drained()
    req = eng.result(rid)
    want_r, _ = stemmer.stem_batch(
        jnp.asarray(corpus.encode_corpus(words)), arrays)
    np.testing.assert_array_equal(req.roots, np.asarray(want_r))


def test_stemmer_workload_satisfies_protocol(dict_and_words):
    arrays, _ = dict_and_words
    assert isinstance(StemmerWorkload(DictStore(arrays)), Workload)


# ---------------------------------------------------------------------------
# dispatch/retire ring (overlapped serving)
# ---------------------------------------------------------------------------
def test_tick_dispatches_until_ring_full(dict_and_words):
    """One engine tick must keep launching tiles until max_inflight
    launches are outstanding — not one tile per tick (the pre-async
    coalescing bug)."""
    arrays, enc = dict_and_words
    store = DictStore(arrays)
    eng = Engine(StemmerWorkload(store, block_b=16, max_inflight=4,
                                 megabatch_tiles=1))
    for i in range(10):
        eng.submit(enc[i * 16:(i + 1) * 16])   # 10 tiles pending
    eng.step()
    w = eng.workload
    assert w.ticks_launched == 4               # ring filled in ONE tick
    assert len(w.ring) + len(w._free_slots) == 4


def test_ticks_to_drain_shrink_with_ring_depth(dict_and_words):
    """Deeper rings drain the same workload in fewer engine ticks, with
    the launch count invariant (regression for the one-tile-per-tick
    coalescing)."""
    arrays, enc = dict_and_words
    store = DictStore(arrays)
    ticks, launches = {}, {}
    for depth in (1, 4):
        eng = Engine(StemmerWorkload(store, block_b=16, max_inflight=depth,
                                     megabatch_tiles=1))
        for i in range(10):                    # 160 words -> 10 tiles
            eng.submit(enc[i * 16:(i + 1) * 16])
        rep = eng.run_until_drained()
        assert rep.drained
        ticks[depth] = rep.ticks
        launches[depth] = eng.workload.ticks_launched
    assert launches[1] == launches[4] == 10
    assert ticks[4] < ticks[1]


def test_staging_buffers_reused_across_ticks(dict_and_words):
    """Dispatch fills a preallocated per-slot staging buffer; no per-tick
    tile allocation."""
    arrays, enc = dict_and_words
    store = DictStore(arrays)
    w = StemmerWorkload(store, block_b=16, max_inflight=2)
    eng = Engine(w)
    buffers = {id(b) for b in w._staging}
    assert len(buffers) == 2
    for i in range(8):
        eng.submit(enc[i * 16:(i + 1) * 16])
    eng.run_until_drained()
    assert {id(b) for b in w._staging} == buffers  # same arrays throughout
    assert w._free_slots and len(w._free_slots) == 2  # all slots returned


def test_trickle_feed_keeps_launches_in_flight(dict_and_words):
    """A tick that dispatched (or retired) something never hard-syncs
    the ring: a server alternating submit()/step() keeps overlap even
    though the queue empties between requests."""
    arrays, enc = dict_and_words
    store = DictStore(arrays)
    eng = Engine(StemmerWorkload(store, block_b=16, max_inflight=2))
    w = eng.workload
    for i in range(3):                  # one tile per request, trickled
        eng.submit(enc[i * 16:(i + 1) * 16])
        eng.step()
        # the just-dispatched launch stays in flight — no drain sync
        assert w.ring, f"step {i}: ring drained despite fresh dispatch"
    rep = eng.run_until_drained()
    assert rep.drained and w.ticks_launched == 3
    want_r, _ = stemmer.stem_batch(jnp.asarray(enc[:48]), arrays)
    got_r = np.concatenate([eng.result(r).roots for r in range(3)])
    np.testing.assert_array_equal(got_r, np.asarray(want_r))


def test_failed_launch_leaves_engine_recoverable(dict_and_words,
                                                 monkeypatch):
    """A kernel launch that raises must not wedge the engine. In strict
    mode (max_retries=0) the exception propagates but the staging slot
    returns to the ring and the words stay undispatched, so the next
    tick retries and the engine still drains; with retries enabled
    (the default) the same failure is absorbed entirely."""
    from repro.kernels import ops

    arrays, enc = dict_and_words
    store = DictStore(arrays)
    eng = Engine(StemmerWorkload(store, block_b=16, max_inflight=2,
                                 max_retries=0))
    rids = [eng.submit(enc[i * 16:(i + 1) * 16]) for i in range(3)]

    real = ops.extract_roots_fused
    boom = {"armed": True}

    def flaky(*a, **kw):
        if boom.pop("armed", False):
            raise RuntimeError("transient device failure")
        return real(*a, **kw)

    monkeypatch.setattr(ops, "extract_roots_fused", flaky)
    with pytest.raises(RuntimeError, match="transient"):
        eng.step()
    w = eng.workload
    assert len(w._free_slots) == 2          # slot returned
    assert all(r.dispatched == 0 for r in w.inflight)  # nothing stranded
    rep = eng.run_until_drained()           # retry succeeds
    assert rep.drained
    want_r, _ = stemmer.stem_batch(jnp.asarray(enc[:48]), arrays)
    got_r = np.concatenate([eng.result(r).roots for r in rids])
    np.testing.assert_array_equal(got_r, np.asarray(want_r))

    # default mode: the retry machinery absorbs the same transient
    # failure — no exception reaches the caller, results bit-identical
    eng2 = Engine(StemmerWorkload(store, block_b=16, max_inflight=2))
    rids2 = [eng2.submit(enc[i * 16:(i + 1) * 16]) for i in range(3)]
    boom["armed"] = True
    rep2 = eng2.run_until_drained()
    assert rep2.drained and eng2.workload.retries_total == 1
    got2 = np.concatenate([eng2.result(r).roots for r in rids2])
    np.testing.assert_array_equal(got2, np.asarray(want_r))


def _trace_error(*a, **kw):
    return jnp.dot(jnp.ones((2, 3)), jnp.ones((4, 5)))   # TypeError


def _lowering_error(*a, **kw):
    raise NotImplementedError("Only 2D gather is supported")


def _compile_error(*a, **kw):
    import jax

    raise jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: unsupported shape"
        " cast")


@pytest.mark.parametrize("broken", [_trace_error, _lowering_error,
                                    _compile_error])
def test_launch_that_cannot_compile_propagates_unretried(dict_and_words,
                                                         monkeypatch, broken):
    """A launch that fails to trace, lower or compile fails the same way
    on every attempt: with retries enabled it still propagates out of the
    engine on the first attempt — no retry, no quarantine — with the
    slot returned and the words unclaimed, so the engine drains once the
    launch is fixed."""
    from repro.kernels import ops

    arrays, enc = dict_and_words
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=16,
                                 max_inflight=2))
    rids = [eng.submit(enc[i * 16:(i + 1) * 16]) for i in range(2)]
    monkeypatch.setattr(ops, "extract_roots_fused", broken)
    with pytest.raises((TypeError, NotImplementedError, RuntimeError)):
        eng.step()
    w = eng.workload
    assert w.retries_total == 0
    assert not [ev for ev in eng.events() if ev.kind in ("retry", "failure")]
    assert len(w._free_slots) == 2
    assert all(r.dispatched == 0 for r in w.inflight)
    monkeypatch.undo()
    assert eng.run_until_drained().drained
    want_r, _ = stemmer.stem_batch(jnp.asarray(enc[:32]), arrays)
    got_r = np.concatenate([eng.result(r).roots for r in rids])
    np.testing.assert_array_equal(got_r, np.asarray(want_r))


def test_overlap_parity_with_sync(dict_and_words):
    """Depth-4 overlapped serving returns exactly what the synchronous
    tick returns, request by request."""
    arrays, enc = dict_and_words
    store = DictStore(arrays)
    sizes = (37, 64, 5, 50, 20)
    sync_eng, sync_rids, _ = _serve(store, enc, sizes, max_inflight=1)
    over_eng, over_rids, _ = _serve(store, enc, sizes, max_inflight=4)
    for rs, ro in zip(sync_rids, over_rids):
        a, b = sync_eng.result(rs), over_eng.result(ro)
        np.testing.assert_array_equal(a.roots, b.roots)
        np.testing.assert_array_equal(a.sources, b.sources)
        np.testing.assert_array_equal(a.dict_versions, b.dict_versions)


# ---------------------------------------------------------------------------
# dictionary hot swap
# ---------------------------------------------------------------------------
def test_hot_swap_mid_stream_bit_identical(dict_and_words):
    """A publish() between ticks is picked up by the next tile launch;
    responses carry the version that served each word, and every word is
    bit-identical to stem_batch under that version's arrays."""
    arrays, enc = dict_and_words
    store = DictStore(arrays)
    grown = corpus.grow_root_arrays(arrays, 2048, seed=7)
    sizes = (30, 30, 30, 30, 30)
    eng, rids, _ = _serve(store, enc, sizes, block_b=32,
                          steps_before_swap=2, swap_to=grown,
                          megabatch_tiles=1)

    versions = np.concatenate([eng.result(r).dict_versions for r in rids])
    assert set(versions.tolist()) == {0, 1}  # swap landed mid-stream
    all_words = enc[:sum(sizes)]
    got_r = np.concatenate([eng.result(r).roots for r in rids])
    got_s = np.concatenate([eng.result(r).sources for r in rids])
    for v in (0, 1):
        mask = versions == v
        want_r, want_s = stemmer.stem_batch(jnp.asarray(all_words[mask]),
                                            store.get(v).arrays)
        np.testing.assert_array_equal(got_r[mask], np.asarray(want_r))
        np.testing.assert_array_equal(got_s[mask], np.asarray(want_s))
    # a request straddling the swap reports the version of its last word
    straddlers = [eng.result(r) for r in rids
                  if len(set(eng.result(r).dict_versions.tolist())) > 1]
    assert straddlers
    for req in straddlers:
        assert req.dict_version == int(req.dict_versions[-1]) == 1


def test_same_shape_swap_replays_jit_trace(dict_and_words):
    """A hot swap whose arrays keep their shapes must not re-trace the
    megakernel: the DictStore's pre-resolved handle pins the static
    config, so the jit cache is hit."""
    arrays, enc = dict_and_words
    store = DictStore(arrays)
    _serve(store, enc, (40,), block_b=32)
    before = sf.stem_fused_pallas._cache_size()

    shifted = stemmer.RootDictArrays(tri=arrays.tri + 1, quad=arrays.quad + 1,
                                     bi=arrays.bi + 1)  # same shapes, sorted
    store.publish(shifted)
    eng, rids, _ = _serve(store, enc, (40,), block_b=32)
    assert sf.stem_fused_pallas._cache_size() == before
    # and the swapped dictionary really was used
    want_r, _ = stemmer.stem_batch(jnp.asarray(enc[:40]), shifted)
    np.testing.assert_array_equal(eng.result(rids[0]).roots,
                                  np.asarray(want_r))


def test_swap_while_tile_in_flight_pins_dispatch_version(dict_and_words):
    """A publish() landing between a tile's dispatch and its retire must
    not relabel (or re-serve) that tile: every word records the version
    acquired at dispatch, exactly."""
    arrays, enc = dict_and_words
    store = DictStore(arrays)
    grown = corpus.grow_root_arrays(arrays, 2048, seed=9)
    eng = Engine(StemmerWorkload(store, block_b=16, max_inflight=4,
                                 megabatch_tiles=1))
    rids = [eng.submit(enc[i * 16:(i + 1) * 16]) for i in range(8)]
    eng.step()                      # fills the ring: 4 tiles in flight
    w = eng.workload
    assert w.ticks_launched == 4 and len(w.ring) + len(w._free_slots) == 4
    in_flight_words = sum(r.dispatched for r in w.inflight)
    served_words = sum(r.served for r in w.inflight)
    assert in_flight_words == 64    # dispatched under v0 ...
    assert served_words < 64        # ... not yet all retired
    v1 = store.publish(grown)
    rep = eng.run_until_drained()
    assert rep.drained and v1 == 1

    versions = np.concatenate([eng.result(r).dict_versions for r in rids])
    # tiles in flight at publish time keep the version they dispatched
    # under; only post-swap dispatches see v1
    np.testing.assert_array_equal(versions[:64], 0)
    np.testing.assert_array_equal(versions[64:], 1)
    # and each half is bit-identical to stem_batch under its own version
    got_r = np.concatenate([eng.result(r).roots for r in rids])
    for v, sl in ((0, slice(0, 64)), (1, slice(64, 128))):
        want_r, _ = stemmer.stem_batch(jnp.asarray(enc[sl]),
                                       store.get(v).arrays)
        np.testing.assert_array_equal(got_r[sl], np.asarray(want_r))


# ---------------------------------------------------------------------------
# DictStore
# ---------------------------------------------------------------------------
def test_dict_store_versioning(dict_and_words):
    arrays, _ = dict_and_words
    store = DictStore(arrays)
    assert store.version == 0
    assert store.acquire().version == 0
    assert store.acquire().handle.residency in ("resident", "streamed")

    snapshot = store.acquire()  # held across a publish -> unchanged
    grown = corpus.grow_root_arrays(arrays, 2048, seed=5)
    assert store.publish(grown) == 1
    assert store.version == 1
    assert snapshot.version == 0
    assert store.get(0).n_keys == arrays.n_keys
    assert store.get(1).n_keys > store.get(0).n_keys
    with pytest.raises(KeyError, match="version 9"):
        store.get(9)

    # raw pyref.RootDict publishes pack through from_rootdict
    d = corpus.build_dictionary(n_tri=50, n_quad=10, seed=2)
    assert isinstance(d, pyref.RootDict)
    assert store.publish(d) == 2
    assert store.get(2).arrays.tri.shape[0] > 0

    no_hist = DictStore(arrays, keep_history=False)
    no_hist.publish(grown)
    with pytest.raises(KeyError):
        no_hist.get(0)


def test_publish_delta_sorted_merge(dict_and_words):
    """publish_delta merges insert/remove key lists against the current
    version: equivalent to a from-scratch publish of the merged table,
    with untouched tables sharing the current version's device arrays."""
    arrays, enc = dict_and_words
    store = DictStore(arrays)
    tri0 = np.asarray(arrays.tri)
    removed = tri0[[0, 3, 11]].tolist()
    inserted = [int(tri0.max() + d) for d in (2, 7, 5)]
    v1 = store.publish_delta(insert={"tri": inserted + [int(tri0[1])]},
                             remove={"tri": removed})
    assert v1 == 1
    a1 = store.get(1).arrays
    want_tri = np.union1d(np.setdiff1d(tri0, removed),
                          np.asarray(inserted, np.int32))
    np.testing.assert_array_equal(np.asarray(a1.tri), want_tri)
    # untouched tables are the same device buffers, not re-uploads
    assert a1.quad is arrays.quad and a1.bi is arrays.bi

    # served output equals a from-scratch publish of the merged arrays
    scratch = stemmer.RootDictArrays(tri=jnp.asarray(want_tri),
                                     quad=arrays.quad, bi=arrays.bi)
    want_r, want_s = stemmer.stem_batch(jnp.asarray(enc[:64]), scratch)
    eng, rids, _ = _serve(store, enc, (64,))
    np.testing.assert_array_equal(eng.result(rids[0]).roots,
                                  np.asarray(want_r))
    np.testing.assert_array_equal(eng.result(rids[0]).sources,
                                  np.asarray(want_s))
    assert eng.result(rids[0]).dict_version == 1


def test_publish_delta_validates(dict_and_words):
    arrays, _ = dict_and_words
    store = DictStore(arrays)
    with pytest.raises(ValueError, match="absent"):
        store.publish_delta(remove={"tri": [1 << 23]})
    with pytest.raises(ValueError, match="both"):
        store.publish_delta(insert={"tri": [7]}, remove={"tri": [7]})
    with pytest.raises(ValueError, match="unknown dictionary tables"):
        store.publish_delta(insert={"pent": [7]})
    assert store.version == 0       # failed deltas publish nothing

    # raw root strings encode + pack through the alphabet
    from repro.core import alphabet as ab
    root = "كتب"
    key = ab.pack_key(ab.encode_word(root))
    v_str = store.publish_delta(insert={"tri": [root]})
    assert key in np.asarray(store.get(v_str).arrays.tri)

    # removing every bi key leaves the empty-table sentinel, and the
    # table can be refilled later
    bi0 = np.asarray(arrays.bi)
    bi0 = bi0[bi0 >= 0]
    v = store.publish_delta(remove={"bi": bi0.tolist()})
    np.testing.assert_array_equal(np.asarray(store.get(v).arrays.bi), [-1])
    v2 = store.publish_delta(insert={"bi": bi0[:3].tolist()})
    np.testing.assert_array_equal(np.asarray(store.get(v2).arrays.bi),
                                  np.sort(bi0[:3]))


# ---------------------------------------------------------------------------
# drain reporting (Engine-level, workload-independent)
# ---------------------------------------------------------------------------
def test_run_until_drained_surfaces_unfinished(dict_and_words):
    arrays, enc = dict_and_words
    store = DictStore(arrays)

    # "return" policy hands back the report and leaves the engine resumable
    eng = Engine(StemmerWorkload(store, block_b=16))
    rids = [eng.submit(enc[:40]), eng.submit(enc[40:80])]
    partial = eng.run_until_drained(max_ticks=1,  # 80 words need 5 ticks
                                    on_undrained="return")
    assert isinstance(partial, DrainReport) and not partial.drained
    assert partial.ticks == 1 and partial.pending
    final = eng.run_until_drained()
    assert final.drained and final.pending == []
    assert all(eng.result(r).done and eng.result(r).failure is None
               for r in rids)
    with pytest.raises(ValueError, match="on_undrained"):
        eng.run_until_drained(on_undrained="ignore")

    # "raise" policy cancels the stranded requests — each lands in the
    # finished table with FailureInfo("cancelled") — so the engine is
    # empty and reusable afterwards, not wedged mid-drain
    eng2 = Engine(StemmerWorkload(store, block_b=16))
    rids2 = [eng2.submit(enc[:40]), eng2.submit(enc[40:80])]
    with pytest.raises(EngineUndrained) as exc:
        eng2.run_until_drained(max_ticks=1)
    report = exc.value.report
    assert not report.drained and report.ticks == 1
    assert set(report.pending) == set(rids2)
    assert set(report.cancelled) == set(rids2)
    for r in rids2:
        req = eng2.result(r)
        assert req.done and req.failure.code == "cancelled"
    assert not eng2.queue and eng2.workload.active == 0
    rid3 = eng2.submit(enc[:16])            # fresh work still serves
    assert eng2.run_until_drained().drained
    want_r, _ = stemmer.stem_batch(jnp.asarray(enc[:16]), arrays)
    np.testing.assert_array_equal(eng2.result(rid3).roots,
                                  np.asarray(want_r))
