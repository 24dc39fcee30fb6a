"""Pipelined streamed-Compare tests (DESIGN.md §5.3).

The streamed megakernel path is an explicitly pipelined sweep: a
scalar-prefetched per-batch-tile tile-visit index (only dictionary tiles
a live candidate key can land in are visited) feeding a num_buffers-deep
make_async_copy DMA ladder. This suite pins:

  - bit-identity with residency="resident" and the core jnp stemmer
    across num_buffers x match x infix x dictionary sizes straddling the
    64K-key VMEM ceiling;
  - adversarial key distributions: every dictionary key in one tile,
    matching keys sitting exactly on tile boundaries, and dictionaries
    no candidate key can land in (empty visit lists);
  - the visit index itself (strictly fewer visits than the full sweep on
    big dictionaries; zero visits when nothing can match; full sweep
    when skip_index=False);
  - the publish-time DictTileSet plumbing (prebuilt tile stream +
    boundary tables through ResolvedRootDict / DictStore) and the
    serving workload's num_buffers / skip_index knobs.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import corpus, pyref, stemmer
from repro.kernels import ops
from repro.kernels import stem_datapath as sdp
from repro.kernels import stem_fused as sf
from repro.kernels import stem_match as sm

MATCHES = ("bank", "bsearch")


def _assert_parity(got, ref):
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))


@pytest.fixture(scope="module")
def small():
    d = corpus.build_dictionary(n_tri=600, n_quad=80, seed=9)
    return stemmer.RootDictArrays.from_rootdict(d)


@pytest.fixture(scope="module")
def big(small):
    da = corpus.grow_root_arrays(small, 100_000, seed=2)
    assert sf._loaded_keys(da, True) > sf.MAX_RESIDENT_KEYS
    return da


@pytest.fixture(scope="module")
def enc():
    words, _, _ = corpus.build_corpus(n_words=384, seed=13)
    return jnp.asarray(corpus.encode_corpus(words))


# ---------------------------------------------------------------------------
# parity: ladder depth x match x infix, straddling the VMEM ceiling
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("num_buffers", [1, 2, 3, 4])
def test_ladder_depth_parity_small(small, enc, num_buffers):
    """Every ladder depth is bit-identical to the resident layout and the
    core stemmer (which is pyref-pinned by test_stemmer.py)."""
    ref = stemmer.stem_batch(enc, small)
    res = ops.extract_roots_fused(enc, small, residency="resident",
                                  interpret=True)
    got = ops.extract_roots_fused(enc, small, residency="streamed",
                                  block_b=128, dict_block_r=2,
                                  num_buffers=num_buffers, interpret=True)
    _assert_parity(res, ref)
    _assert_parity(got, ref)


@pytest.mark.parametrize("match", MATCHES)
@pytest.mark.parametrize("infix", [True, False])
def test_pipeline_matches_core_past_ceiling(big, enc, infix, match):
    ref = stemmer.stem_batch(enc, big, infix=infix)
    got = ops.extract_roots_fused(enc, big, infix=infix, match=match,
                                  residency="streamed", block_b=128,
                                  num_buffers=2, interpret=True)
    _assert_parity(got, ref)


@pytest.mark.parametrize("match", MATCHES)
@pytest.mark.parametrize("skip_index", [True, False])
def test_skip_index_polarity_parity(small, enc, match, skip_index):
    """skip_index=False (full sweep) and =True run the same ladder kernel
    and must agree bit-for-bit with the resident layout."""
    ref = ops.extract_roots_fused(enc, small, match=match,
                                  residency="resident", interpret=True)
    got = ops.extract_roots_fused(enc, small, match=match,
                                  residency="streamed", block_b=128,
                                  dict_block_r=2, skip_index=skip_index,
                                  num_buffers=3, interpret=True)
    _assert_parity(got, ref)


def test_pipeline_through_public_api_256k(small):
    da = corpus.grow_root_arrays(small, 262_144, seed=5)
    words, _, _ = corpus.build_corpus(n_words=192, seed=17)
    e = jnp.asarray(corpus.encode_corpus(words))
    r1, s1 = stemmer.extract_roots(e, da, backend="fused", num_buffers=4)
    r2, s2 = stemmer.extract_roots(e, da, backend="sorted")
    _assert_parity((r1, s1), (r2, s2))
    assert (np.asarray(s1) != pyref.SRC_NONE).any()


# ---------------------------------------------------------------------------
# the visit index
# ---------------------------------------------------------------------------
def _assert_skip_index_prunes(enc, da):
    on = sf.tile_visit_stats(enc, da, block_b=128, dict_block_r=8,
                             skip_index=True)
    off = sf.tile_visit_stats(enc, da, block_b=128, dict_block_r=8,
                              skip_index=False)
    assert off["visited"] == off["full_sweep"]
    assert on["visited"] < off["visited"]          # the acceptance bar
    assert on["full_sweep"] == off["full_sweep"]


def test_skip_index_visits_fewer_tiles_on_big_dict(big, enc):
    _assert_skip_index_prunes(enc, big)


def test_skip_index_visits_fewer_tiles_at_128k_keys(small, enc):
    da = corpus.grow_root_arrays(small, 131_072, seed=2)
    assert da.n_keys >= 131_072
    _assert_skip_index_prunes(enc, da)


def test_visit_stats_excludes_bi_without_infix(big, enc):
    on = sf.tile_visit_stats(enc, big, infix=True, block_b=128)
    off = sf.tile_visit_stats(enc, big, infix=False, block_b=128)
    assert off["dict_tiles"] < on["dict_tiles"]    # bi tiles not swept


# ---------------------------------------------------------------------------
# adversarial key distributions
# ---------------------------------------------------------------------------
def _arrays(tri=(), quad=(), bi=()):
    def pack(keys):
        return jnp.asarray(sorted(keys) or [-1], jnp.int32)

    return stemmer.RootDictArrays(tri=pack(tri), quad=pack(quad), bi=pack(bi))


def test_all_keys_in_one_tile(small, enc):
    """A dictionary clustered into a single tile: the visit index floors
    at one tile per dictionary and stays bit-identical."""
    # every real tri key, dict_block_r large enough for one tile each
    tri = np.asarray(small.tri).tolist()
    da = _arrays(tri=tri)
    dr = (len(tri) + sm.LANE - 1) // sm.LANE       # one tile holds them all
    st = sf.tile_visit_stats(enc, da, block_b=128, dict_block_r=dr)
    bt = st["batch_tiles"]
    assert st["dict_tiles"] == 3                   # one tile per dictionary
    # at most the tri tile + the quad/bi placeholder tiles per batch tile
    assert bt <= st["visited"] <= 3 * bt
    ref = stemmer.stem_batch(enc, da)
    for nb in (1, 2, 4):
        got = ops.extract_roots_fused(enc, da, residency="streamed",
                                      block_b=128, dict_block_r=dr,
                                      num_buffers=nb, interpret=True)
        _assert_parity(got, ref)
    assert (np.asarray(ref[1]) != pyref.SRC_NONE).any()  # real hits occurred


def test_keys_at_tile_boundaries(enc):
    """Every candidate-producible key IS a dictionary key, with
    dict_block_r=1 so matches sit on every tile's first/last element."""
    kc, vc = sdp.candidate_columns(enc)
    keys = np.asarray(jnp.stack(kc[:6], axis=1))      # tri-group candidates
    valid = np.asarray(jnp.stack(vc[:6], axis=1)) > 0
    tri = sorted(set(keys[valid].tolist()))
    assert len(tri) > sm.LANE                      # spans multiple tiles
    da = _arrays(tri=tri)
    ref = stemmer.stem_batch(enc, da)
    got = ops.extract_roots_fused(enc, da, residency="streamed",
                                  block_b=64, dict_block_r=1,
                                  num_buffers=2, interpret=True)
    _assert_parity(got, ref)
    # every word with a valid tri candidate found a root
    assert (np.asarray(ref[1]) == pyref.SRC_TRI).sum() == valid.any(1).sum()


def test_empty_visit_lists(enc):
    """Dictionary keys beyond any candidate key: zero tiles visited, and
    the kernel still writes clean no-hit outputs for every batch tile."""
    hi = 50 * 64 ** 3                              # above any packed letter
    da = _arrays(tri=[hi, hi + 1], quad=[hi + 2], bi=[hi + 3])
    st = sf.tile_visit_stats(enc, da, block_b=128, dict_block_r=2)
    assert st["visited"] == 0
    for nb in (1, 4):
        root, src = ops.extract_roots_fused(enc, da, residency="streamed",
                                            block_b=128, dict_block_r=2,
                                            num_buffers=nb, interpret=True)
        assert (np.asarray(src) == pyref.SRC_NONE).all()
        assert (np.asarray(root) == 0).all()


def test_num_buffers_validation(small, enc):
    with pytest.raises(ValueError, match="num_buffers"):
        ops.extract_roots_fused(enc, small, residency="streamed",
                                num_buffers=0, interpret=True)
    with pytest.raises(ValueError, match="num_buffers"):
        ops.extract_roots_fused(enc, small, residency="streamed",
                                num_buffers=5, interpret=True)


# ---------------------------------------------------------------------------
# residency budget scoping (the choose_residency infix fix)
# ---------------------------------------------------------------------------
def test_residency_budget_ignores_unloaded_bi(small, big):
    """A dictionary whose tri+quad fit the VMEM budget must stay resident
    for infix=False even when a huge bi table would blow it."""
    da = stemmer.RootDictArrays(tri=small.tri, quad=small.quad,
                                bi=big.quad)        # any big sorted table
    assert sf._loaded_keys(da, True) > sf.MAX_RESIDENT_KEYS
    assert sf._loaded_keys(da, False) <= sf.MAX_RESIDENT_KEYS
    assert sf.choose_residency(da, "auto", infix=True) == "streamed"
    assert sf.choose_residency(da, "auto", infix=False) == "resident"
    # and the resident launch itself accepts it with infix=False
    words, _, _ = corpus.build_corpus(n_words=96, seed=21)
    e = jnp.asarray(corpus.encode_corpus(words))
    ref = stemmer.stem_batch(e, da, infix=False)
    got = ops.extract_roots_fused(e, da, infix=False, residency="resident",
                                  interpret=True)
    _assert_parity(got, ref)
    with pytest.raises(ValueError, match="VMEM residency"):
        ops.extract_roots_fused(e, da, infix=True, residency="resident",
                                interpret=True)


# ---------------------------------------------------------------------------
# prebuilt tile stream (publish-time boundary tables) + serving knobs
# ---------------------------------------------------------------------------
def test_resolve_dict_prebuilds_tiles(big):
    h = stemmer.resolve_dict(big, dict_block_r=8)
    assert h.residency == "streamed" and h.tiles is not None
    assert h.tiles.dict_block_r == 8
    assert h.tiles.n_tiles == sum(h.tiles.counts)
    # boundary tables are per-tile first/last elements of the stream
    flat = np.asarray(h.tiles.stream).reshape(h.tiles.n_tiles, -1)
    np.testing.assert_array_equal(np.asarray(h.tiles.mins), flat[:, 0])
    np.testing.assert_array_equal(np.asarray(h.tiles.maxs), flat[:, -1])


def test_resolve_dict_upgrades_bare_handle(big):
    """Re-resolving an already-resolved handle with dict_block_r must
    build the tiles it lacks (publish must not silently skip the
    prebuild), and an already-matching handle passes through unchanged."""
    bare = stemmer.resolve_dict(big)                 # no tiles
    assert bare.tiles is None
    h = stemmer.resolve_dict(bare, dict_block_r=8)
    assert h.tiles is not None and h.tiles.dict_block_r == 8
    assert h.residency == bare.residency
    assert stemmer.resolve_dict(h, dict_block_r=8) is h   # no rebuild
    h2 = stemmer.resolve_dict(h, dict_block_r=4)          # height change
    assert h2.tiles.dict_block_r == 4


def test_prebuilt_tiles_bit_identical(big, enc):
    h = stemmer.resolve_dict(big, dict_block_r=8)
    ref = ops.extract_roots_fused(enc, big, residency="streamed",
                                  block_b=128, dict_block_r=8,
                                  interpret=True)
    got = ops.extract_roots_fused(enc, h, block_b=128, dict_block_r=8,
                                  interpret=True)
    _assert_parity(got, ref)


def test_mismatched_tile_height_rebuilds(big, enc):
    """A handle pinned at one dict_block_r still serves a call at another
    (the kernel rebuilds in-trace rather than mis-tiling)."""
    h = stemmer.resolve_dict(big, dict_block_r=4)
    ref = stemmer.stem_batch(enc, big)
    got = ops.extract_roots_fused(enc, h, block_b=128, dict_block_r=8,
                                  interpret=True)
    _assert_parity(got, ref)


def test_dict_store_publishes_tiles_and_keeps_trace(big, small, enc):
    from repro.serve import DictStore

    store = DictStore(big, dict_block_r=8)
    h = store.acquire().handle
    assert h.tiles is not None and h.residency == "streamed"
    ref = stemmer.stem_batch(enc, big)
    got = ops.extract_roots_fused(enc, h, block_b=128, interpret=True)
    _assert_parity(got, ref)
    # a shape-matched delta publish keeps the cached trace (tiles and all)
    before = sf.stem_fused_pallas._cache_size()
    k_new = 40 * 64 ** 3 + 7 * 64 ** 2 + 7 * 64
    k_old = int(np.asarray(big.tri)[0])
    store.publish_delta(insert={"tri": [k_new]}, remove={"tri": [k_old]})
    h2 = store.acquire().handle
    assert h2.tiles is not None
    ops.extract_roots_fused(enc, h2, block_b=128, interpret=True)
    assert sf.stem_fused_pallas._cache_size() == before
    # small dict resolves resident: no tile stream is built
    store_small = DictStore(small, dict_block_r=8)
    assert store_small.acquire().handle.tiles is None


def test_workload_pipeline_knobs_serve_parity(big):
    from repro.serve import DictStore, Engine, StemmerWorkload

    words, _, _ = corpus.build_corpus(n_words=150, seed=23)
    e = corpus.encode_corpus(words)
    store = DictStore(big, dict_block_r=4)
    eng = Engine(StemmerWorkload(store, block_b=64, dict_block_r=4,
                                 num_buffers=3, skip_index=True,
                                 max_inflight=2, interpret=True))
    rid = eng.submit(e)
    eng.run_until_drained()
    req = eng.result(rid)
    ref = stemmer.stem_batch(jnp.asarray(e), big)
    np.testing.assert_array_equal(req.roots, np.asarray(ref[0]))
    np.testing.assert_array_equal(req.sources, np.asarray(ref[1]))
