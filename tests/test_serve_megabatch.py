"""Grid-over-queue megabatch + persistent-kernel serving tests.

Megabatch coalescing (one launch retires many queued tiles), bit-exact
parity against the synchronous per-tile tick — including ragged final
megabatches and a DictStore hot swap landing while a megabatch is in
flight — the persistent descriptor-ring kernel's parity and completion
flags, the scalar-prefetch visit-table chunking that keeps megabatch
SMEM tables within budget, and the dispatch accounting
(ops.dispatch_count / stem_fused.planned_launches) that proves one
``pallas_call`` retires >= 4 queue tiles. Sharded-megabatch coverage
lives in test_serve_sharded.py under forced host devices.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import corpus, stemmer
from repro.kernels import ops
from repro.kernels import stem_fused as sf
from repro.serve import (DegradationPolicy, DictStore, Engine,
                         StemmerWorkload, TextAnalysisWorkload)
from repro.serve.engine import MEGABATCH_TILES

MATCHES = ("bank", "bsearch")


@pytest.fixture(scope="module")
def dicts():
    d = corpus.build_dictionary(n_tri=400, n_quad=60, seed=0)
    return stemmer.RootDictArrays.from_rootdict(d)


@pytest.fixture(scope="module")
def enc():
    words, _, _ = corpus.build_corpus(n_words=600, seed=1)
    return corpus.encode_corpus(words)


def _serve(store, enc, sizes, *, block_b=32, megabatch_tiles=1,
           persistent=False, max_inflight=2, steps_before_swap=None,
           swap_to=None):
    eng = Engine(StemmerWorkload(store, block_b=block_b,
                                 megabatch_tiles=megabatch_tiles,
                                 persistent=persistent,
                                 max_inflight=max_inflight))
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
    return eng, rids


def _gather(eng, rids):
    reqs = [eng.result(r) for r in rids]
    assert all(r.done for r in reqs)
    return (np.concatenate([r.roots for r in reqs]),
            np.concatenate([r.sources for r in reqs]),
            np.concatenate([r.dict_versions for r in reqs]))


# ---------------------------------------------------------------------------
# persistent kernel: descriptor-ring parity + completion flags
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("match", MATCHES)
@pytest.mark.parametrize("infix", [True, False])
def test_persistent_resident_parity(dicts, enc, infix, match):
    ref_r, ref_s = stemmer.stem_batch(jnp.asarray(enc), dicts, infix=infix)
    r, s, fl = ops.extract_roots_persistent(
        jnp.asarray(enc), dicts, infix=infix, match=match, block_b=128,
        residency="resident", version_slot=5, interpret=True)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(ref_r))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(ref_s))
    # 600 words / block_b=128 -> 5 descriptors, each flagged 1 + slot
    assert np.asarray(fl).shape == (5,)
    assert (np.asarray(fl) == 6).all()


@pytest.mark.parametrize("match", MATCHES)
def test_persistent_streamed_parity(dicts, enc, match):
    ref_r, ref_s = stemmer.stem_batch(jnp.asarray(enc), dicts)
    r, s, fl = ops.extract_roots_persistent(
        jnp.asarray(enc), dicts, match=match, block_b=128,
        residency="streamed", dict_block_r=2, version_slot=0,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(ref_r))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(ref_s))
    assert (np.asarray(fl) == 1).all()


def test_persistent_ragged_batch(dicts, enc):
    """A batch that is not a multiple of block_b pads its final
    descriptor; the padded words never leak into the sliced output."""
    ref_r, ref_s = stemmer.stem_batch(jnp.asarray(enc[:77]), dicts)
    r, s, fl = ops.extract_roots_persistent(
        jnp.asarray(enc[:77]), dicts, block_b=32, residency="streamed",
        dict_block_r=2, interpret=True)
    assert r.shape == (77, 4) and s.shape == (77,)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(ref_r))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(ref_s))
    assert np.asarray(fl).shape == (3,)  # ceil(77 / 32) descriptors


def test_persistent_empty_batch(dicts):
    r, s, fl = ops.extract_roots_persistent(
        jnp.zeros((0, 16), jnp.int32), dicts, interpret=True)
    assert r.shape == (0, 4) and s.shape == (0,) and fl.shape == (0,)


# ---------------------------------------------------------------------------
# visit-table chunking: megabatch SMEM tables stay within budget
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("persistent", [False, True])
def test_visit_budget_chunking_parity(dicts, enc, persistent):
    """A visit budget smaller than the megabatch's table forces the
    streamed path to chunk along the batch axis — output stays
    bit-identical and planned_launches mirrors the actual chunk count."""
    ref_r, ref_s = stemmer.stem_batch(jnp.asarray(enc), dicts)
    n_tiles = sf.dict_tile_count(dicts, 2)
    budget = 2 * n_tiles  # two batch tiles of table per chunk
    kw = dict(block_b=64, residency="streamed", dict_block_r=2,
              visit_budget=budget, interpret=True)
    fn = ops.extract_roots_persistent if persistent else ops.extract_roots_fused
    ops.reset_dispatch_count()
    out = fn(jnp.asarray(enc), dicts, **kw)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref_r))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(ref_s))
    want = sf.planned_launches(len(enc), dicts, block_b=64,
                               residency="streamed", dict_block_r=2,
                               persistent=persistent, visit_budget=budget)
    # 600 words / 64 = 10 batch tiles, 2 per chunk -> 5 pallas_calls
    assert want == 5
    assert ops.dispatch_count() == want
    if persistent:
        assert np.asarray(out[2]).shape == (10,)
        assert (np.asarray(out[2]) == 1).all()


def test_planned_launches_counts(dicts):
    assert sf.planned_launches(0, dicts) == 0
    assert sf.planned_launches(512, dicts, residency="resident") == 1
    # default budget comfortably fits this dictionary: one launch
    assert sf.planned_launches(512, dicts, block_b=64,
                               residency="streamed", dict_block_r=2) == 1
    # budget below one batch tile's table still launches (1 tile/chunk)
    n_tiles = sf.dict_tile_count(dicts, 2)
    assert sf.planned_launches(512, dicts, block_b=64,
                               residency="streamed", dict_block_r=2,
                               visit_budget=n_tiles - 1) == 8


# ---------------------------------------------------------------------------
# megabatch serving: one dispatch retires many queued tiles
# ---------------------------------------------------------------------------
def test_megabatch_single_launch_retires_four_tiles(dicts, enc):
    """The acceptance criterion: ONE pallas_call dispatch retires >= 4
    queued tiles, bit-identical to the per-tile path."""
    sizes = (37, 64, 5, 22)  # 128 words = 4 tiles of 32
    store = DictStore(dicts)
    ops.reset_dispatch_count()
    eng, rids = _serve(store, enc, sizes, block_b=32, megabatch_tiles=4,
                       max_inflight=1)
    assert eng.workload.ticks_launched == 1
    assert ops.dispatch_count() == 1
    got_r, got_s, _ = _gather(eng, rids)

    store2 = DictStore(dicts)
    eng2, rids2 = _serve(store2, enc, sizes, block_b=32, max_inflight=1)
    assert eng2.workload.ticks_launched == 4  # the per-tile baseline
    ref_r, ref_s, _ = _gather(eng2, rids2)
    np.testing.assert_array_equal(got_r, ref_r)
    np.testing.assert_array_equal(got_s, ref_s)


@pytest.mark.parametrize("megabatch_tiles,persistent",
                         [(4, False), (8, False), (1, True), (4, True)])
def test_megabatch_parity_vs_sync_tick(dicts, enc, megabatch_tiles,
                                       persistent):
    """Bit-identity against the max_inflight=1 synchronous per-tile tick,
    including the ragged final megabatch (sizes don't fill the last
    launch)."""
    sizes = (37, 120, 5, 50, 99)  # 311 words: ragged at every tile size
    ref_eng, ref_rids = _serve(DictStore(dicts), enc, sizes, max_inflight=1)
    ref = _gather(ref_eng, ref_rids)
    eng, rids = _serve(DictStore(dicts), enc, sizes,
                       megabatch_tiles=megabatch_tiles,
                       persistent=persistent)
    got = _gather(eng, rids)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g, r)
    if megabatch_tiles > 1:
        assert eng.workload.ticks_launched < ref_eng.workload.ticks_launched


@pytest.mark.parametrize("persistent", [False, True])
def test_megabatch_parity_across_midflight_swap(dicts, enc, persistent):
    """A DictStore publish landing while a megabatch is in flight never
    relabels (or re-serves) its words: each word records the version its
    launch pinned, and words served after the swap match the new dict."""
    d2 = corpus.build_dictionary(n_tri=500, n_quad=80, seed=5)
    arrays2 = stemmer.RootDictArrays.from_rootdict(d2)
    sizes = (100, 100, 100)
    store = DictStore(dicts)
    eng, rids = _serve(store, enc, sizes, megabatch_tiles=2,
                       persistent=persistent, max_inflight=2,
                       steps_before_swap=1, swap_to=arrays2)
    got_r, got_s, got_v = _gather(eng, rids)
    assert store.version == 1
    assert got_v.min() == 0 and got_v.max() == 1  # swap landed mid-stream
    # every word must match the dictionary version that served it
    for v, arrays in ((0, dicts), (1, arrays2)):
        idx = np.nonzero(got_v == v)[0]
        want_r, want_s = stemmer.stem_batch(jnp.asarray(enc[:300][idx]),
                                            arrays)
        np.testing.assert_array_equal(got_r[idx], np.asarray(want_r))
        np.testing.assert_array_equal(got_s[idx], np.asarray(want_s))


def test_persistent_serve_flags_checked(dicts, enc):
    """The persistent retire verifies completion flags against the
    pinned version — a launch whose flags disagree is a hard error."""
    store = DictStore(dicts)
    eng = Engine(StemmerWorkload(store, block_b=32, persistent=True,
                                 max_inflight=1))
    eng.submit(enc[:64])
    eng.run_until_drained()  # healthy path: no raise, versions stamped
    req = eng.result(0)
    assert (req.dict_versions == 0).all()


# ---------------------------------------------------------------------------
# the default: launches follow the queue depth, both shapes warmed
# ---------------------------------------------------------------------------
class CompileCounter:
    """XLA compiles and lowerings while ``on``, by jax.monitoring."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.on = False
        self.compiles = self.lowerings = 0

    def __call__(self, event, _secs, **_kw):
        if self.on:
            self.compiles += event == self.COMPILE
            self.lowerings += event == self.LOWER


@pytest.fixture
def compile_counter():
    import jax

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    yield counter
    jax.monitoring.unregister_event_duration_listener(counter)


def _launch_rows(workload):
    """Record the staging rows of every launch ``workload`` makes, warm
    launches included: -> the list it appends to."""
    rows, real = [], workload._launch

    def launch(tile, *a, **kw):
        rows.append(tile.shape[0])
        return real(tile, *a, **kw)

    workload._launch = launch
    return rows


def test_default_deep_queue_launches_at_cap(dicts, enc):
    """A deep queue under the default workload launches
    ceil(words / (cap * block_b)) times, bit-identical to one tile a
    launch."""
    sizes = (37, 120, 5, 50, 99, 250, 39)       # 600 words
    eng = Engine(StemmerWorkload(DictStore(dicts), block_b=32))
    ops.reset_dispatch_count()
    rids = [eng.submit(enc[a:b]) for a, b in
            zip(np.cumsum((0,) + sizes[:-1]), np.cumsum(sizes))]
    assert eng.run_until_drained().drained
    want = -(-sum(sizes) // (MEGABATCH_TILES * 32))
    assert eng.workload.megabatch_tiles == MEGABATCH_TILES
    assert (TextAnalysisWorkload(DictStore(dicts)).megabatch_tiles
            == MEGABATCH_TILES)
    assert eng.workload.ticks_launched == ops.dispatch_count() == want
    ref_eng, ref_rids = _serve(DictStore(dicts), enc, sizes, block_b=32)
    assert ref_eng.workload.ticks_launched == -(-sum(sizes) // 32)
    for g, r in zip(_gather(eng, rids), _gather(ref_eng, ref_rids)):
        np.testing.assert_array_equal(g, r)


@pytest.mark.parametrize("n_words", [1, 20, 32])
def test_lone_request_launches_one_tile(dicts, enc, n_words):
    """A lone request of one tile or less launches one block_b tile
    under the default cap."""
    w = StemmerWorkload(DictStore(dicts), block_b=32)
    rows = _launch_rows(w)
    eng = Engine(w)
    rid = eng.submit(enc[:n_words])
    assert eng.run_until_drained().drained
    assert w.ticks_launched == 1 and rows[-1] == 32
    want_r, want_s = stemmer.stem_batch(jnp.asarray(enc[:n_words]), dicts)
    np.testing.assert_array_equal(eng.result(rid).roots, np.asarray(want_r))
    np.testing.assert_array_equal(eng.result(rid).sources,
                                  np.asarray(want_s))


def test_every_bucket_compiled_by_the_first_dispatch(dicts, enc,
                                                     compile_counter):
    """After the first dispatch under a handle, a launch at every bucket
    of the ladder traces, lowers and compiles nothing; more than one
    tile of words launches the whole megabatch."""
    w = StemmerWorkload(DictStore(dicts), block_b=32)
    eng = Engine(w)
    eng.submit(enc[:5])
    assert eng.run_until_drained().drained
    rows = _launch_rows(w)
    compile_counter.on = True
    rids, sizes = [], (32, 33, 300, 512)
    for n in sizes:                   # one request a launch
        rids.append(eng.submit(enc[:n]))
        assert eng.run_until_drained().drained
    compile_counter.on = False
    assert rows == [32, 512, 512, 512]
    assert (compile_counter.compiles, compile_counter.lowerings) == (0, 0)
    for rid, n in zip(rids, sizes):
        want_r, _ = stemmer.stem_batch(jnp.asarray(enc[:n]), dicts)
        np.testing.assert_array_equal(eng.result(rid).roots,
                                      np.asarray(want_r))


def test_warm_launches_are_not_counted(dicts, enc):
    """The warm launch of the first dispatch leaves ticks_launched and
    ops.dispatch_count() as they were; a hot swap to a handle of the
    same shapes warms nothing again."""
    store = DictStore(dicts)
    w = StemmerWorkload(store, block_b=32)
    rows = _launch_rows(w)
    eng = Engine(w)
    ops.reset_dispatch_count()
    eng.submit(enc[:40])                        # two tiles: the cap
    assert eng.run_until_drained().drained
    assert rows == [32, 512]                    # the warm, then the launch
    assert w.ticks_launched == ops.dispatch_count() == 1

    shifted = stemmer.RootDictArrays(tri=dicts.tri + 1, quad=dicts.quad + 1,
                                     bi=dicts.bi + 1)   # same shapes
    store.publish(shifted)
    rows.clear()
    rid = eng.submit(enc[:40])
    assert eng.run_until_drained().drained
    assert rows == [512]
    assert w.ticks_launched == ops.dispatch_count() == 2
    want_r, _ = stemmer.stem_batch(jnp.asarray(enc[:40]), shifted)
    np.testing.assert_array_equal(eng.result(rid).roots, np.asarray(want_r))
    assert (eng.result(rid).dict_versions == 1).all()


def test_default_ladder_keeps_per_tile_rung(dicts):
    """The degradation ladder of a default workload steps down from the
    capped megabatch to one tile a launch."""
    pol = DegradationPolicy()
    Engine(StemmerWorkload(DictStore(dicts)), policy=pol)
    assert [(r.label, r.megabatch_tiles) for r in pol.rungs[:2]] == [
        (f"megabatch x{MEGABATCH_TILES}", MEGABATCH_TILES), ("per-tile", 1)]


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------
def test_megabatch_tiles_validation(dicts):
    with pytest.raises(ValueError, match="megabatch_tiles"):
        StemmerWorkload(DictStore(dicts), megabatch_tiles=0)


def test_persistent_sharded_rejected(dicts):
    with pytest.raises(ValueError, match="persistent"):
        StemmerWorkload(DictStore(dicts), persistent=True, data_devices=2)
