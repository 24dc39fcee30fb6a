"""Public jit'd wrappers for the Pallas kernels.

On CPU hosts (this container) kernels run with ``interpret=True`` — the
kernel body executes in Python with numpy semantics, validating the exact
code that pallas_call lowers for TPU. On TPU backends interpret=False.
"""
from __future__ import annotations

import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pyref
from repro.core import stemmer as core_stemmer
from repro.kernels import stem_datapath as sdp
from repro.kernels import stem_fused as sf
from repro.kernels import stem_match as sm


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# -- dispatch accounting -----------------------------------------------------
# ``pallas_call`` dispatches issued through the extract_roots_* wrappers
# since the last reset. A Python counter inside a jitted function would
# only tick at trace time, so each wrapper adds what its configuration is
# *known* to launch (stem_fused.planned_launches mirrors the kernel's
# chunking exactly). The benchmark's launch counts and the launch-count
# tests read this.
_dispatches = 0


def reset_dispatch_count() -> None:
    """Zero the pallas_call dispatch counter."""
    global _dispatches
    _dispatches = 0


def dispatch_count() -> int:
    """pallas_call dispatches issued through extract_roots_fused /
    extract_roots_persistent / extract_roots_sharded since the last
    :func:`reset_dispatch_count`."""
    return _dispatches


def _count_dispatches(n: int) -> None:
    global _dispatches
    _dispatches += n


@contextlib.contextmanager
def uncounted_dispatches():
    """Leave :func:`dispatch_count` as it was across the block: for
    launches that serve no words (a serving workload compiling its
    launch shapes ahead of traffic)."""
    global _dispatches
    before = _dispatches
    try:
        yield
    finally:
        _dispatches = before


def dict_match(keys: jnp.ndarray, dict_keys: jnp.ndarray, *,
               strategy: str = "bank", **kw) -> jnp.ndarray:
    """Membership of packed stem keys in a packed root dictionary.

    strategy="bank"    tiled all-pairs compare (the paper's comparator
                       banks; dict streamed tile-by-tile over the grid)
    strategy="bsearch" in-kernel unrolled binary search over the sorted
                       dictionary (the paper's §7 tree-search upgrade;
                       dict VMEM-resident)
    """
    kw.setdefault("interpret", _interpret_default())
    if strategy == "bank":
        return sm.dict_match_pallas(keys, dict_keys, **kw)
    if strategy == "bsearch":
        kw.pop("block_r", None)  # bsearch holds the whole dict resident
        return sm.dict_match_bsearch_pallas(keys, dict_keys, **kw)
    raise ValueError(f"unknown match strategy: {strategy}")


def stem_candidates(words: jnp.ndarray, **kw):
    """Fused stages 1-4: words[B,16] -> (keys[B,32], valid[B,32])."""
    kw.setdefault("interpret", _interpret_default())
    return sdp.stem_datapath_pallas(words, **kw)


def unpack_keys(keys: jnp.ndarray) -> jnp.ndarray:
    """int32[...] packed keys -> int32[..., 4] char codes."""
    return jnp.stack(
        [(keys >> 18) & 63, (keys >> 12) & 63, (keys >> 6) & 63, keys & 63],
        axis=-1,
    )


@functools.partial(jax.jit,
                   static_argnames=("block_w", "max_words", "interpret"))
def _text_to_words_jit(chars, *, block_w, max_words, interpret):
    from repro.core import textnorm as tn
    from repro.kernels import text_frontend as tf

    geo = tn.segment_geometry(chars, block_w=block_w, max_words=max_words)
    words = tf.text_frontend_pallas(chars, geo.starts, geo.lens,
                                    block_w=block_w, interpret=interpret)
    return words, geo.spans, geo.n_words


def text_to_words(chars, *, block_w: int = 128,
                  max_words: int | None = None,
                  interpret: bool | None = None):
    """Text front-end launch: codepoint tile int32[T] (0-padded) ->
    (words int32[Wp, 16], spans int32[Wp, 2], n_words int32).

    One pallas_call (kernels/text_frontend.py) preceded by the jnp
    segmentation-geometry pre-pass in the same jit scope — the visit-index
    pattern: word starts/lengths/byte spans come from XLA scatters, the
    dense per-word normalise/strip/pack work runs in the kernel. Rows at
    and past ``n_words`` are zero; bit-identical to
    ``textnorm.analyze_text_py`` on the decoded text.
    """
    if interpret is None:
        interpret = _interpret_default()
    _count_dispatches(1)
    return _text_to_words_jit(jnp.asarray(chars, jnp.int32),
                              block_w=block_w, max_words=max_words,
                              interpret=interpret)


def extract_roots_text(chars, roots, *, block_w: int = 128,
                       max_words: int | None = None, infix: bool = True,
                       match: str = "bsearch", block_b: int | None = None,
                       residency: str = "auto", dict_block_r: int = 8,
                       num_buffers: int = 2, skip_index: bool = True,
                       visit_budget: int | None = None,
                       interpret: bool | None = None):
    """Bytes in, roots out: codepoint tile -> (roots int32[Wp, 4],
    sources int32[Wp], spans int32[Wp, 2], n_words int32).

    Chains the text front-end kernel straight into the stemmer megakernel
    — the word tiles stay on device between the two launches (and the
    visit-index pre-pass consumes them there), so there is no host
    round-trip at the text/stemmer boundary. block_b defaults to block_w
    so the front end's padded word rows feed the megakernel without
    re-tiling. Rows past ``n_words`` come from all-zero words and carry
    SRC_NONE.
    """
    words, spans, n_words = text_to_words(chars, block_w=block_w,
                                          max_words=max_words,
                                          interpret=interpret)
    root, source = extract_roots_fused(
        words, roots, infix=infix, match=match,
        block_b=block_b or block_w, residency=residency,
        dict_block_r=dict_block_r, num_buffers=num_buffers,
        skip_index=skip_index, visit_budget=visit_budget,
        interpret=interpret)
    return root, source, spans, n_words


def extract_roots_fused(words, roots, *, infix: bool = True,
                        match: str = "bsearch", block_b: int = 256,
                        residency: str = "auto", dict_block_r: int = 8,
                        num_buffers: int = 2, skip_index: bool = True,
                        visit_budget: int | None = None,
                        with_checksum: bool = False,
                        interpret: bool | None = None):
    """Megabatch megakernel: all five stages, the grid's batch axis
    spanning every [block_b, 16] tile of the (arbitrarily deep) batch, in
    ONE pallas_call (stem_fused.py). Same contract as
    repro.core.stemmer.extract_roots; bit-identical output.

    residency: "resident" keeps the packed dictionaries in VMEM across
    the batch sweep, "streamed" sweeps a scalar-prefetched visit list of
    (dict_block_r x 128) dictionary tiles through an explicit
    ``num_buffers``-deep DMA ladder (unbounded dictionary size; with
    ``skip_index`` only tiles a live candidate key can land in are
    visited), "auto" (default) streams only past
    stem_fused.MAX_RESIDENT_KEYS. Streamed megabatches whose
    scalar-prefetch visit table would exceed ``visit_budget`` (default
    stem_fused.VISIT_SMEM_BUDGET int32 entries) chunk along the batch
    axis into several pallas_calls — ``dispatch_count()`` reflects the
    actual launch count either way.

    roots accepts plain RootDictArrays or a pre-resolved
    core.stemmer.ResolvedRootDict handle (serving path): the handle's
    pinned residency overrides the residency argument and its prebuilt
    tile stream skips the per-call pad/concat, so dictionary hot swaps
    with matching shapes never re-trace.

    ``with_checksum=True`` returns ``(root, source, checksums)`` with the
    per-tile integrity row of :func:`tile_checksum` computed in the SAME
    jit scope as the launch (rows must be a multiple of block_b) — the
    serving path's retire-side verification pays no extra XLA dispatch.
    """
    if interpret is None:
        interpret = _interpret_default()
    _count_dispatches(sf.planned_launches(
        words.shape[0], roots, infix=infix, block_b=block_b,
        residency=residency, dict_block_r=dict_block_r,
        visit_budget=visit_budget))
    if with_checksum:
        return _stem_cs_call(words, roots, 0, infix=infix, match=match,
                             block_b=block_b, residency=residency,
                             dict_block_r=dict_block_r,
                             num_buffers=num_buffers,
                             skip_index=skip_index, persistent=False,
                             visit_budget=visit_budget, interpret=interpret)
    return sf.stem_fused_pallas(words, roots, infix=infix, match=match,
                                block_b=block_b, residency=residency,
                                dict_block_r=dict_block_r,
                                num_buffers=num_buffers,
                                skip_index=skip_index,
                                visit_budget=visit_budget,
                                interpret=interpret)


def extract_roots_persistent(words, roots, *, infix: bool = True,
                             match: str = "bsearch", block_b: int = 256,
                             residency: str = "auto", dict_block_r: int = 8,
                             num_buffers: int = 2, skip_index: bool = True,
                             version_slot=0, visit_budget: int | None = None,
                             with_checksum: bool = False,
                             interpret: bool | None = None):
    """Persistent serving kernel: ONE launch whose body fori_loops over a
    scalar-prefetched work-descriptor ring of batch tiles, DMA-ing word
    tiles in and (root, source) tiles out (stem_fused.py,
    ``persistent=True``). Returns ``(root, source, flags)`` — flags
    int32[batch_tiles] is ``1 + version_slot`` per retired descriptor,
    the completion word the serving ring polls. Roots/sources are
    bit-identical to :func:`extract_roots_fused`. ``with_checksum=True``
    appends the :func:`tile_checksum` row, fused into the launch's jit
    scope.
    """
    if interpret is None:
        interpret = _interpret_default()
    _count_dispatches(sf.planned_launches(
        words.shape[0], roots, infix=infix, block_b=block_b,
        residency=residency, dict_block_r=dict_block_r, persistent=True,
        visit_budget=visit_budget))
    if with_checksum:
        return _stem_cs_call(words, roots, version_slot, infix=infix,
                             match=match, block_b=block_b,
                             residency=residency,
                             dict_block_r=dict_block_r,
                             num_buffers=num_buffers,
                             skip_index=skip_index, persistent=True,
                             visit_budget=visit_budget, interpret=interpret)
    return sf.stem_fused_pallas(words, roots, infix=infix, match=match,
                                block_b=block_b, residency=residency,
                                dict_block_r=dict_block_r,
                                num_buffers=num_buffers,
                                skip_index=skip_index, persistent=True,
                                version_slot=version_slot,
                                visit_budget=visit_budget,
                                interpret=interpret)


def extract_roots_sharded(words, roots, mesh, *, axis: str = "data",
                          infix: bool = True, match: str = "bsearch",
                          block_b: int = 256, residency: str = "auto",
                          dict_block_r: int = 8, num_buffers: int = 2,
                          skip_index: bool = True,
                          visit_budget: int | None = None,
                          with_checksum: bool = False,
                          interpret: bool | None = None):
    """Megakernel launch data-sharded over ``mesh[axis]``: the batch —
    including a multi-tile megabatch — is split into per-device shards
    whose grid spans every local [block_b, 16] tile, the packed
    dictionaries replicated. Same contract as :func:`extract_roots_fused`
    — bit-identical, ragged batches padded and sliced back (including the
    ``with_checksum=True`` integrity row, fused into the sharded jit
    scope). This is the serving path behind
    ``StemmerWorkload(data_devices=N)``.
    """
    from repro.dist import mesh_axis_size, shard_batch  # lazy

    if interpret is None:
        interpret = _interpret_default()
    n_dev = mesh_axis_size(mesh, axis)
    per_dev = -(-words.shape[0] // n_dev) if words.shape[0] else 0
    _count_dispatches(n_dev * sf.planned_launches(
        per_dev, roots, infix=infix, block_b=block_b, residency=residency,
        dict_block_r=dict_block_r, visit_budget=visit_budget))
    return shard_batch(words, roots, mesh, axis=axis, infix=infix,
                       match=match, block_b=block_b, residency=residency,
                       dict_block_r=dict_block_r, num_buffers=num_buffers,
                       skip_index=skip_index, visit_budget=visit_budget,
                       with_checksum=with_checksum, interpret=interpret)


# ---------------------------------------------------------------------------
# Retire-side integrity: a device-computed checksum row per block_b tile
# ---------------------------------------------------------------------------
# odd int32 weights; position term makes the fold order-sensitive inside
# a tile, so swapped rows are detected, not just flipped values
_CS_WEIGHTS = (1000003, 999983, 65599, 31337, 271829, 69069)
_CS_ROOT_W = np.array(_CS_WEIGHTS[:4], np.int32)   # host-fold constants
_CS_SRC_W = np.int32(_CS_WEIGHTS[4])


def _checksum_rows(roots, sources, block_b: int):
    """Traceable checksum body, shared by :func:`tile_checksum` and the
    ``with_checksum`` launch fusions (here and dist.shard_batch)."""
    w = _CS_WEIGHTS
    r = roots.astype(jnp.int32)
    s = sources.astype(jnp.int32).reshape(-1)
    idx = jnp.arange(r.shape[0], dtype=jnp.int32) % block_b
    row = (r[:, 0] * w[0] + r[:, 1] * w[1] + r[:, 2] * w[2]
           + r[:, 3] * w[3] + s * w[4] + idx * w[5] + 1)
    return row.reshape(-1, block_b).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("block_b",))
def tile_checksum(roots, sources, *, block_b: int):
    """Per-tile int32 checksum over a launch's (roots, sources) outputs.

    roots int32[rows, 4], sources int32[rows], rows a multiple of
    block_b -> int32[rows // block_b]. The serving path computes it in
    the SAME jit scope as the launch (``with_checksum=True`` on the
    extract_roots_* wrappers, so integrity costs no extra XLA dispatch);
    :func:`tile_checksum_host` re-derives it from the host copies at
    retire, so a torn readback or corrupted transfer fails loudly into
    the retry path instead of serving garbage. Int32 wraparound
    arithmetic, bit-exact between XLA and numpy.
    """
    return _checksum_rows(roots, sources, block_b)


@functools.partial(
    jax.jit,
    static_argnames=("infix", "match", "block_b", "residency",
                     "dict_block_r", "num_buffers", "skip_index",
                     "persistent", "visit_budget", "interpret"))
def _stem_cs_call(words, roots, version_slot, *, infix, match, block_b,
                  residency, dict_block_r, num_buffers, skip_index,
                  persistent, visit_budget, interpret):
    """stem_fused_pallas + per-tile checksum traced into ONE XLA program
    (the separate tile_checksum dispatch cost ~20% of a small serve
    drain). version_slot is traced so hot swaps replay the cache."""
    out = sf.stem_fused_pallas(words, roots, infix=infix, match=match,
                               block_b=block_b, residency=residency,
                               dict_block_r=dict_block_r,
                               num_buffers=num_buffers,
                               skip_index=skip_index, persistent=persistent,
                               version_slot=version_slot,
                               visit_budget=visit_budget,
                               interpret=interpret)
    return out + (_checksum_rows(out[0], out[1], block_b),)


@functools.lru_cache(maxsize=64)
def _cs_host_pos_term(rows: int, block_b: int) -> np.ndarray:
    """Precomputed ``idx * w5 + 1`` term of the host checksum — the
    retire path recomputes the checksum per tile, so the constant
    position fold is cached per (rows, block_b)."""
    idx = (np.arange(rows, dtype=np.int32) % block_b).astype(np.int32)
    return idx * np.int32(_CS_WEIGHTS[5]) + np.int32(1)


def tile_checksum_host(roots, sources, *, block_b: int) -> np.ndarray:
    """Numpy mirror of :func:`tile_checksum` (same int32 wraparound
    math; the matmul and sum force dtype=int32 because numpy would
    otherwise accumulate in int64). Runs on every serve retire, so the
    fold is a single int32 matvec plus cached constants."""
    r = np.asarray(roots).astype(np.int32, copy=False)
    s = np.asarray(sources).astype(np.int32, copy=False).reshape(-1)
    row = r @ _CS_ROOT_W + s * _CS_SRC_W
    row += _cs_host_pos_term(r.shape[0], block_b)
    return row.reshape(-1, block_b).sum(axis=1, dtype=np.int32)


# ---------------------------------------------------------------------------
# Corpus indexing: stemmer megakernel -> postings reduction, one jit scope
# ---------------------------------------------------------------------------
def _root_ids(root, source, vocab):
    """(root[W,4], source[W]) -> vocab ids int32[W]; unmatched/padding
    words get the drop bucket id ``n_roots = vocab.shape[0]``."""
    n_roots = vocab.shape[0]
    key = core_stemmer.pack_keys(root)
    idx = jnp.searchsorted(vocab, key).astype(jnp.int32)
    found = (jnp.take(vocab, jnp.minimum(idx, n_roots - 1), mode="clip")
             == key)
    valid = found & (source != pyref.SRC_NONE)
    return jnp.where(valid, idx, n_roots)


@functools.partial(
    jax.jit,
    static_argnames=("infix", "match", "block_b", "residency",
                     "dict_block_r", "num_buffers", "skip_index",
                     "visit_budget", "block_w", "interpret"))
def _index_jit(words, roots, vocab, doc_ids, positions, *, infix, match,
               block_b, residency, dict_block_r, num_buffers, skip_index,
               visit_budget, block_w, interpret):
    from repro.kernels import postings as pk

    root, source = sf.stem_fused_pallas(
        words, roots, infix=infix, match=match, block_b=block_b,
        residency=residency, dict_block_r=dict_block_r,
        num_buffers=num_buffers, skip_index=skip_index,
        visit_budget=visit_budget, interpret=interpret)
    ids = _root_ids(root, source, vocab)
    hist, rank = pk.postings_pallas(ids, n_roots=vocab.shape[0],
                                    block_w=block_w, interpret=interpret)
    return pk.finish_postings(hist, rank, ids, doc_ids, positions,
                              n_roots=vocab.shape[0], block_w=block_w)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "infix", "match", "block_b",
                     "residency", "dict_block_r", "num_buffers",
                     "skip_index", "visit_budget", "block_w", "interpret"))
def _index_sharded_jit(words, roots, vocab, doc_ids, positions, *, mesh,
                       axis, infix, match, block_b, residency, dict_block_r,
                       num_buffers, skip_index, visit_budget, block_w,
                       interpret):
    from jax.sharding import PartitionSpec as P

    from repro.dist import mesh_axis_size
    from repro.kernels import postings as pk

    n_dev = mesh_axis_size(mesh, axis)
    w = words.shape[0]
    # per-device slices must be whole postings tiles so the stacked
    # per-shard (tile, root) histograms keep global corpus order
    pad = (-w) % (n_dev * block_w)
    wp = jnp.pad(words, ((0, pad), (0, 0)))   # zero rows -> SRC_NONE -> drop

    def local(wds, r, v):
        root, source = sf.stem_fused_pallas(
            wds, r, infix=infix, match=match, block_b=block_b,
            residency=residency, dict_block_r=dict_block_r,
            num_buffers=num_buffers, skip_index=skip_index,
            visit_budget=visit_budget, interpret=interpret)
        ids = _root_ids(root, source, v)
        hist, rank = pk.postings_pallas(ids, n_roots=v.shape[0],
                                        block_w=block_w, interpret=interpret)
        return hist, rank, ids

    f = jax.shard_map(local, mesh=mesh, in_specs=(P(axis), P(), P()),
                      out_specs=(P(axis), P(axis), P(axis)), check_vma=False)
    hist, rank, ids = f(wp, roots, vocab)
    # the device-side shard merge: corpus shards are contiguous slices,
    # so stacking per-shard tile histograms restores corpus tile order
    # and the global exclusive cumsum in finish_postings *is* the merge
    return pk.finish_postings(hist, rank, ids[:w], doc_ids, positions,
                              n_roots=vocab.shape[0], block_w=block_w)


def build_root_index(words, roots, vocab, doc_ids, positions, *,
                     mesh=None, axis: str = "data", infix: bool = True,
                     match: str = "bsearch", block_b: int = 256,
                     residency: str = "auto", dict_block_r: int = 8,
                     num_buffers: int = 2, skip_index: bool = True,
                     visit_budget: int | None = None, block_w: int = 2048,
                     interpret: bool | None = None):
    """One corpus chunk -> one inverted-index partial, fully on device.

    words int32[W, 16], vocab int32[n_roots] (sorted packed root keys),
    doc_ids/positions int32[W] -> ``(counts int32[n_roots],
    docs int32[W_pad], poss int32[W_pad], n_postings int32)`` with root
    r's postings at ``[excl_cumsum(counts)[r], +counts[r])``, sorted by
    global word index (CSR layout; see kernels/postings.py).

    Chains the stemmer megakernel straight into the postings reduction
    kernel in ONE jit scope — roots/ids/histograms never visit the host,
    the id map + cumsums + final scatter are XLA ops in the same scope
    (the visit-index pattern), and there is no per-word host loop
    anywhere. With ``mesh`` the word tiles shard over ``mesh[axis]``
    (dictionaries + vocab replicated) and the per-shard (tile, root)
    histograms merge device-side via the same exclusive cumsum that
    merges tiles on one device. ``roots`` accepts plain RootDictArrays
    or a ResolvedRootDict handle, as everywhere.
    """
    if interpret is None:
        interpret = _interpret_default()
    kw = dict(infix=infix, match=match, block_b=block_b,
              residency=residency, dict_block_r=dict_block_r,
              num_buffers=num_buffers, skip_index=skip_index,
              visit_budget=visit_budget, block_w=block_w,
              interpret=interpret)
    words = jnp.asarray(words, jnp.int32)
    vocab = jnp.asarray(vocab, jnp.int32)
    doc_ids = jnp.asarray(doc_ids, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    from repro.kernels import postings as pk

    if mesh is None:
        _count_dispatches(
            sf.planned_launches(words.shape[0], roots, infix=infix,
                                block_b=block_b, residency=residency,
                                dict_block_r=dict_block_r,
                                visit_budget=visit_budget)
            + pk.postings_launches(words.shape[0], block_w=block_w))
        return _index_jit(words, roots, vocab, doc_ids, positions, **kw)
    from repro.dist import mesh_axis_size

    n_dev = mesh_axis_size(mesh, axis)
    per_dev = -(-words.shape[0] // n_dev) if words.shape[0] else 0
    _count_dispatches(n_dev * (
        sf.planned_launches(per_dev, roots, infix=infix, block_b=block_b,
                            residency=residency, dict_block_r=dict_block_r,
                            visit_budget=visit_budget)
        + pk.postings_launches(per_dev, block_w=block_w)))
    return _index_sharded_jit(words, roots, vocab, doc_ids, positions,
                              mesh=mesh, axis=axis, **kw)


def build_root_index_text(chars, roots, vocab, byte_off, *, doc0: int = 0,
                          word0_of_doc0: int = 0, block_w_text: int = 128,
                          max_words: int | None = None, block_w: int = 2048,
                          interpret: bool | None = None, **stem_kw):
    """Raw-text variant: codepoint tile + per-doc byte offsets -> the same
    inverted-index partial as :func:`build_root_index`.

    ``chars`` is a coalesced codepoint tile (textnorm.coalesce_docs),
    ``byte_off`` int64[D] each document's first utf-8 byte offset in it.
    Word->document attribution and in-document positions derive from the
    front end's byte spans as XLA searchsorted/scatter ops in the same
    jit scope — the byte stream goes in, postings come out, still no
    per-word host work. ``doc0`` offsets emitted doc ids for chunked
    corpora; ``word0_of_doc0`` is the global position of the chunk's
    first word inside its (chunk-straddling) first document, 0 when
    documents never straddle chunks.
    """
    if interpret is None:
        interpret = _interpret_default()
    root, source, spans, n_words = extract_roots_text(
        chars, roots, block_w=block_w_text, max_words=max_words,
        interpret=interpret, **stem_kw)
    from repro.kernels import postings as pk

    _count_dispatches(pk.postings_launches(root.shape[0], block_w=block_w))
    # doc0 / word0_of_doc0 ride as traced scalars so chunked corpora
    # replay one trace per tile shape instead of one per chunk
    return _finish_index_text(root, source, spans, n_words, vocab,
                              jnp.asarray(byte_off),
                              jnp.int32(doc0), jnp.int32(word0_of_doc0),
                              block_w=block_w, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def _finish_index_text(root, source, spans, n_words, vocab, byte_off,
                       doc0, word0_of_doc0, *, block_w, interpret):
    from repro.kernels import postings as pk

    wp = root.shape[0]
    arange = jnp.arange(wp, dtype=jnp.int32)
    in_tile = arange < n_words
    # byte span start -> owning document (serve/text.py retire path)
    doc_local = (jnp.searchsorted(byte_off, spans[:, 0].astype(byte_off.dtype),
                                  side="right") - 1).astype(jnp.int32)
    doc_local = jnp.maximum(doc_local, 0)
    # first word index per document via scatter-min (invalid rows carry
    # arange >= n_words, so they never win the min)
    n_docs = byte_off.shape[0]
    first = jnp.full((n_docs,), wp, jnp.int32).at[doc_local].min(
        arange, mode="drop")
    positions = arange - jnp.take(first, doc_local, mode="clip")
    positions = jnp.where(doc_local == 0, positions + word0_of_doc0,
                          positions)
    ids = _root_ids(root, source, vocab)
    ids = jnp.where(in_tile, ids, vocab.shape[0])
    hist, rank = pk.postings_pallas(ids, n_roots=vocab.shape[0],
                                    block_w=block_w, interpret=interpret)
    return pk.finish_postings(hist, rank, ids, doc_local + doc0, positions,
                              n_roots=vocab.shape[0], block_w=block_w)


def autotune_stem_fused(words, roots, *, infix: bool = True,
                        block_bs=(128, 256, 512), matches=("bank", "bsearch"),
                        residencies=("resident", "streamed"),
                        dict_block_rs=(4, 8, 16),
                        num_bufferss=(1, 2, 4), skip_indexes=(True,),
                        iters: int = 2, interpret: bool | None = None):
    """Time the megakernel over (block_b, match, residency, dict tile rows,
    DMA ladder depth, skip index) and return the best config.

    Returns ``{"block_b": int, "match": str, "residency": str,
    "dict_block_r": int, "num_buffers": int, "skip_index": bool,
    "timings": {(block_b, match, residency, dict_block_r, num_buffers,
    skip_index): seconds}}``. Timings include one warmup (compile) call,
    then ``iters`` measured calls each. Resident configs use
    ``dict_block_r=0`` / ``num_buffers=0`` in the timing key (the knobs
    only exist on the streamed path) and are skipped entirely when the
    dictionaries exceed the VMEM residency budget (counting only the
    tables ``infix`` loads).
    """
    if interpret is None:
        interpret = _interpret_default()
    roots, _, _ = core_stemmer.unwrap_dict(roots)
    resident_ok = (sf.choose_residency(roots, "auto", infix=infix)
                   == "resident")
    timings = {}
    # clamp tiles to the batch (small batches still tune over strategies)
    bbs = sorted({min(bb, words.shape[0]) for bb in block_bs})
    for bb in bbs:
        for m in matches:
            for res in residencies:
                if res == "resident" and not resident_ok:
                    continue
                # dict tiling / ladder depth / skip are no-op knobs on
                # the resident path
                streamed = res == "streamed"
                drs = dict_block_rs if streamed else (0,)
                nbs = num_bufferss if streamed else (0,)
                sks = skip_indexes if streamed else (True,)
                for dr in drs:
                    for nb in nbs:
                        for sk in sks:
                            call = functools.partial(
                                extract_roots_fused, words, roots,
                                infix=infix, match=m, block_b=bb,
                                residency=res, dict_block_r=dr or 8,
                                num_buffers=nb or 2, skip_index=sk,
                                interpret=interpret)
                            jax.block_until_ready(call())  # warmup/compile
                            t0 = time.perf_counter()
                            for _ in range(iters):
                                jax.block_until_ready(call())
                            timings[(bb, m, res, dr, nb, sk)] = (
                                time.perf_counter() - t0) / iters
    if not timings:
        raise ValueError(
            "autotune_stem_fused: no runnable config — the dictionaries"
            f" exceed the VMEM residency budget ({sf.MAX_RESIDENT_KEYS}"
            " keys) and residencies excludes 'streamed'")
    best = min(timings, key=timings.get)
    best_bb, best_m, best_res, best_dr, best_nb, best_sk = best
    return {"block_b": best_bb, "match": best_m, "residency": best_res,
            "dict_block_r": best_dr or 8, "num_buffers": best_nb or 2,
            "skip_index": best_sk, "timings": timings}
