"""Pallas TPU megakernel: the whole stemmer (stages 1-5) in ONE launch.

The paper's pipelined FPGA processor earns its speedup by keeping every
stage on-chip: values never leave the datapath between Check / Produce /
Generate / Filter / Compare. The previous "fused" TPU path was six
separate ``pallas_call`` launches (1 datapath + 5 dictionary matches)
that round-tripped keys, validity flags and hit masks through HBM. This
kernel is the faithful analogue of the paper's architecture: a word tile
enters VMEM once and ``(root, source)`` comes out — candidates, validity
flags and hit masks live only in registers/VMEM.

Layout (see DESIGN.md §5):
  - word tiles travel lane-dense as ``[16, block_b]`` (word index along
    lanes) and are transposed in VMEM; the output is one ``[8, block_b]``
    tile per word tile (root char codes in rows 0-3, source in row 4)
    — DESIGN.md §5.5 has why;
  - the three packed root dictionaries (tri/quad/bi, int32 keys; 5.5K
    entries for a general dictionary) ride along as VMEM-resident
    ``(rows, 128)`` blocks of sorted keys with a constant index map, so
    the pipeline fetches them once and revisits them for every batch
    tile;
  - stages 1-4 are the shared :func:`stem_datapath.candidate_columns`
    datapath (unrolled AND/OR masking networks, truncation grid, infix
    transforms, 24-bit key packing);
  - stage 5 (Compare) supports two in-kernel strategies:
      match="bank"     all-pairs equality against the dictionary, one
                       128-key row at a time — the paper's comparator
                       banks (O(R) per candidate);
      match="bsearch"  two-level sorted search — the paper's §7 proposed
                       tree search: a row-max count picks the key's row,
                       a one-hot MXU matmul fetches it, 128 compares
                       finish (stem_match.sorted_member);
  - the priority select (first hit in VHDL candidate order) is an
    unrolled first-hit scan over the candidate columns, so no gather is
    needed on the output side.

Dictionaries large enough to pressure VMEM (>~64K keys) take the
*streamed* Compare path (DESIGN.md §5.3), an explicitly pipelined sweep:

  - a jnp pre-pass (stages 1-4 on the padded batch, shared
    ``candidate_columns`` body) computes where every batch tile's live
    candidate keys land among the sorted `(dict_block_r x 128)`
    dictionary tiles, and emits a per-batch-tile **tile-visit index** —
    only tiles that can contain a hit are visited, not all of them. The
    index and per-tile visit counts reach the kernel through scalar
    prefetch (``pltpu.PrefetchScalarGridSpec``), so tile ids are
    available for DMA issue before the compute touches them.
  - the dictionary stream stays in HBM (``memory_space=ANY``) and the
    kernel drives its own multi-buffered ``pltpu.make_async_copy``
    ladder (``num_buffers`` deep): the DMA for visit k+num_buffers-1 is
    started before visit k's Compare runs, replacing the
    implicit single-stage Pallas pipeline of the previous layout. An
    OR-accumulating hit mask persists in VMEM scratch across the sweep;
    the priority select runs once per batch tile after it.

`residency="resident"|"streamed"|"auto"` selects the layout; "auto"
streams once the packed dictionaries exceed MAX_RESIDENT_KEYS
(counting only the tables the sweep loads: bi is excluded for
infix=False). `skip_index=False` degrades the visit index to the full
sweep — same kernel, every live tile visited — which is the baseline the
`dict_stream_pipeline` benchmark section compares against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import alphabet as ab
from repro.core import pyref
from repro.core import stemmer as core_stemmer
from repro.kernels import stem_datapath as sdp
from repro.kernels import stem_match as sm

N_CAND = 6
# candidate-group order == stem_datapath layout == core.stemmer priority
GROUP_DICTS = ("tri", "quad", "tri", "tri", "bi")
GROUP_TAGS = (
    pyref.SRC_TRI,
    pyref.SRC_QUAD,
    pyref.SRC_RESTORED,
    pyref.SRC_DEINFIX_TRI,
    pyref.SRC_DEINFIX_BI,
)
# VMEM residency budget for the three dictionaries combined (int32 words).
# Beyond this, residency="auto" switches to the streamed Compare path
# (minor grid axis over dictionary tiles, DESIGN.md §5.3).
MAX_RESIDENT_KEYS = 1 << 16
RESIDENCIES = ("resident", "streamed", "auto")
MAX_NUM_BUFFERS = 4
# rows of the transposed output tile: root char codes 0-3, source, 3 pad
OUT_ROWS = 8
# a resident dictionary ships as whole (8, 128) tiles (sm.pad_dict_tiles)
RESIDENT_TILE_ROWS = 8
_KEY_NOWHERE = jnp.iinfo(jnp.int32).min  # lands in no tile: below every min
# Scalar-prefetch budget for the streamed tile-visit table, in int32
# entries (the table is [batch_tiles, n_dict_tiles]). A megabatch whose
# table would exceed this is chunked along the batch axis into several
# pallas_calls, each with a within-budget table — so grid-over-queue
# megabatches can grow without outgrowing SMEM (the PR 5 open edge).
# 16K entries = 64 KB of scalar memory.
VISIT_SMEM_BUDGET = 1 << 14


def _loaded_keys(roots, infix: bool) -> int:
    """Keys the Compare sweep actually loads: bi only feeds the deinfix
    group, so infix=False never touches it."""
    dicts = (roots.tri, roots.quad) + ((roots.bi,) if infix else ())
    return sum(int(d.shape[0]) for d in dicts)


def choose_residency(roots, residency: str = "auto", *,
                     infix: bool = True) -> str:
    """Resolve residency="auto" against the VMEM budget: keep the packed
    dictionaries resident while they fit, stream tiles once they don't.

    Only the tables the sweep loads count toward the budget: with
    infix=False the bi dictionary never ships to VMEM, so it must not
    force a dictionary that otherwise fits onto the streamed path.
    """
    if residency not in RESIDENCIES:
        raise ValueError(f"unknown residency: {residency!r} (want one of"
                         f" {RESIDENCIES})")
    if residency != "auto":
        return residency
    return ("streamed" if _loaded_keys(roots, infix) > MAX_RESIDENT_KEYS
            else "resident")


def _candidates(words_t, n_groups: int):
    """Stages 1-4 on one transposed word tile int32[16, bb] -> (keys,
    valid): lists of n_slots int32 / bool ``[bb, 1]`` columns.

    Word tiles travel lane-dense as ``[16, bb]`` (a ``[bb, 16]`` slice
    would be a 16-of-128-lane DMA the TPU refuses); the in-kernel
    transpose hands the shared datapath its usual ``[bb, 16]`` tile.
    """
    key_cols, val_cols = sdp.candidate_columns(words_t.T)
    n_slots = n_groups * N_CAND
    return ([k[:, None] for k in key_cols[:n_slots]],
            [v[:, None] > 0 for v in val_cols[:n_slots]])


def _membership(rows_ref, match: str):
    """Stage 5a strategy over one sorted (rows, LANE) dictionary ref ->
    ``keys int32[bb, 1] -> bool[bb, 1]``. The bsearch row tables are
    built once here and reused for every candidate column."""
    if match == "bsearch":
        return functools.partial(sm.sorted_member,
                                 sm.sorted_tables(rows_ref[...]))
    return functools.partial(sm.bank_rows_member, rows_ref)


def _priority_select(keys, hits):
    """Stage 5b: first hit in VHDL candidate order -> the transposed
    output tile int32[8, bb]: rows 0-3 the root's char codes, row 4 the
    source tag, rows 5-7 zero. An unrolled first-hit scan over the
    candidate columns — no cumsum, no gather."""
    chosen = jnp.zeros_like(keys[0])
    source = jnp.zeros_like(keys[0])           # SRC_NONE when no hit
    found = jnp.zeros(keys[0].shape, bool)
    for s, (k, h) in enumerate(zip(keys, hits)):
        take = h & ~found
        chosen = jnp.where(take, k, chosen)
        source = jnp.where(take, int(GROUP_TAGS[s // N_CAND]), source)
        found = found | h
    zero = jnp.zeros_like(chosen)
    return jnp.concatenate(
        [(chosen >> 18) & 63, (chosen >> 12) & 63, (chosen >> 6) & 63,
         chosen & 63, source, zero, zero, zero], axis=1).T


def _resident_hits(keys, valid, dict_refs, *, match: str):
    """Stage 5a against VMEM-resident dictionaries -> bool [bb, 1] per
    candidate slot."""
    n_groups = len(keys) // N_CAND
    # dict.fromkeys, not set: a fixed trace order keeps the kernel (and
    # its persistent-cache key) identical across processes
    member = {name: _membership(dict_refs[name], match)
              for name in dict.fromkeys(GROUP_DICTS[:n_groups])}
    return [member[GROUP_DICTS[s // N_CAND]](k) & v
            for s, (k, v) in enumerate(zip(keys, valid))]


def _fused_kernel(words_ref, tri_ref, quad_ref, bi_ref, out_ref, *,
                  n_groups: int, match: str):
    keys, valid = _candidates(words_ref[...], n_groups)  # stages 1-4
    dicts = {"tri": tri_ref, "quad": quad_ref, "bi": bi_ref}
    hits = _resident_hits(keys, valid, dicts, match=match)   # stage 5a
    out_ref[...] = _priority_select(keys, hits)              # stage 5b


def _dict_slots(name: str, n_groups: int) -> list:
    """Candidate-slot columns fed by dictionary ``name`` (static)."""
    return [g * N_CAND + c for g in range(n_groups)
            if GROUP_DICTS[g] == name for c in range(N_CAND)]


def _visit_tables(keys, valid, tiles: sm.DictTileSet, *, n_groups: int,
                  block_b: int, skip_index: bool):
    """The tile-skipping pre-pass: per-batch-tile dictionary tile-visit
    index from the candidate keys and the sorted tile boundary tables.

    For every batch tile and every dictionary the live candidate keys'
    [min, max] range intersected with the tiles' sorted [mins, maxs]
    boundaries bounds which tiles can hold a hit; because the tiles
    partition a sorted dictionary, each key in fact lands in at most ONE
    tile — `searchsorted(mins, key) - 1`, kept only when the key also
    falls under that tile's max — so the mask marks exactly the landing
    tiles (a strict refinement of the range intersection). A hit requires
    key ∈ dictionary, which implies the key lands in its tile, so
    sweeping only marked tiles is bit-identical to the full sweep.

    keys int32[bp, n_slots], valid bool[bp, n_slots] (stages 1-4 output
    for the padded batch) ->

      n_visits  int32[batch_tiles]           live tiles per batch tile
      visit_idx int32[batch_tiles, n_tiles]  global tile ids, the
                n_visits live ones packed to the front in ascending
                order (pad entries are never fetched)

    skip_index=False marks every tile of every swept dictionary (bi is
    still excluded for infix=False) — the full-sweep baseline through
    the same kernel.
    """
    bt = keys.shape[0] // block_b
    tri_t, quad_t, bi_t = tiles.counts
    masks = []
    for name, base, td in (("tri", 0, tri_t), ("quad", tri_t, quad_t),
                           ("bi", tri_t + quad_t, bi_t)):
        slots = _dict_slots(name, n_groups)
        if not slots:                # bi with infix=False: never swept
            masks.append(jnp.zeros((bt, td), bool))
            continue
        if not skip_index:           # full sweep: every tile of the dict
            masks.append(jnp.ones((bt, td), bool))
            continue
        mins = tiles.mins[base:base + td]
        maxs = tiles.maxs[base:base + td]
        k = jnp.where(valid[:, slots], keys[:, slots], _KEY_NOWHERE)
        k = k.reshape(bt, -1)        # [bt, block_b * n_dict_slots]
        t = jnp.clip(jnp.searchsorted(mins, k, side="right") - 1, 0, td - 1)
        lands = (jnp.take(mins, t) <= k) & (k <= jnp.take(maxs, t))
        bi_idx = jnp.broadcast_to(jnp.arange(bt)[:, None], k.shape)
        mask = jnp.zeros((bt, td), bool)
        mask = mask.at[bi_idx.reshape(-1), t.reshape(-1)].max(lands.reshape(-1))
        masks.append(mask)
    mask = jnp.concatenate(masks, axis=1)              # [bt, n_tiles]
    n_visits = mask.sum(axis=1).astype(jnp.int32)
    # stable argsort on ~mask packs the marked tile ids to the front,
    # ascending — the visit order stays the sorted [tri | quad | bi] order
    visit_idx = jnp.argsort(~mask, axis=1, stable=True).astype(jnp.int32)
    return n_visits, visit_idx


def _ladder_sweep(n, vis_at, keys, valid, dict_ref, dict_bufs, hits_sc,
                  dma_sems, *, match: str, num_buffers: int,
                  dict_block_r: int, tri_tiles: int, quad_tiles: int):
    """Stage 5a over a visit list of HBM dictionary tiles: the rotating
    ``num_buffers``-deep make_async_copy ladder, OR-accumulating hits
    into the ``hits_sc`` columns; returns the final hits, bool [bb, 1]
    per candidate slot.

    ``vis_at(k)`` resolves visit ``k`` (of ``n``) to a *global tile id*
    — the grid kernel reads its batch tile's scalar-prefetched row, the
    persistent kernel its descriptor's. The copy for visit
    k + num_buffers - 1 is issued before visit k's compare runs, so
    tile DMA overlaps the Compare with a tunable lookahead
    (num_buffers=1 is the no-overlap baseline). Which dictionary a tile
    feeds is a boundary compare on its global tile id (not the loop
    index — the visit list has holes where tiles were skipped); only
    that dictionary's candidate columns are compared against it.
    """
    n_groups = len(keys) // N_CAND
    hits_sc[...] = jnp.zeros_like(hits_sc)

    def tile_dma(k, slot):
        t = vis_at(k)
        return pltpu.make_async_copy(
            dict_ref.at[pl.ds(t * dict_block_r, dict_block_r), :],
            dict_bufs.at[slot], dma_sems.at[slot])

    for s in range(num_buffers - 1):               # warm the ladder
        @pl.when(s < n)
        def _start(s=s):
            tile_dma(s, s).start()

    def visit(k, carry):
        look = k + num_buffers - 1                 # ladder lookahead
        @pl.when(look < n)
        def _fetch_ahead():
            tile_dma(look, jax.lax.rem(look, num_buffers)).start()
        slot = jax.lax.rem(k, num_buffers)
        tile_dma(k, slot).wait()
        tile_id = vis_at(k)
        dict_active = {
            "tri": tile_id < tri_tiles,
            "quad": (tile_id >= tri_tiles) & (tile_id < tri_tiles + quad_tiles),
            "bi": tile_id >= tri_tiles + quad_tiles}
        for name, active in dict_active.items():
            slots = _dict_slots(name, n_groups)
            if not slots:                          # bi with infix=False
                continue

            @pl.when(active)
            def _compare(slots=slots):             # stage 5a on this tile
                member = _membership(dict_bufs.at[slot], match)
                for s in slots:
                    hit = (member(keys[s]) & valid[s]).astype(jnp.int32)
                    hits_sc[:, s:s + 1] = hits_sc[:, s:s + 1] | hit
        return carry

    jax.lax.fori_loop(0, n, visit, 0)
    return [hits_sc[:, s:s + 1] > 0 for s in range(len(keys))]


def _fused_pipeline_kernel(nvis_ref, vis_ref, words_ref, dict_ref, out_ref,
                           dict_bufs, hits_sc, dma_sems, *, n_groups: int,
                           match: str, num_buffers: int, dict_block_r: int,
                           tri_tiles: int, quad_tiles: int):
    """Streamed Compare: grid (batch_tiles,), explicit DMA ladder inside.

    The dictionary stream stays in HBM (memory_space=ANY); the kernel
    walks this batch tile's visit list (scalar-prefetched ``vis_ref``,
    ``nvis_ref[i]`` entries) through :func:`_ladder_sweep`.
    """
    i = pl.program_id(0)
    keys, valid = _candidates(words_ref[...], n_groups)  # stages 1-4
    hits = _ladder_sweep(
        nvis_ref[i], lambda k: vis_ref[i, k], keys, valid, dict_ref,
        dict_bufs, hits_sc, dma_sems, match=match, num_buffers=num_buffers,
        dict_block_r=dict_block_r, tri_tiles=tri_tiles,
        quad_tiles=quad_tiles)
    out_ref[...] = _priority_select(keys, hits)          # stage 5b


def _persistent_io(desc_ref, d, words_hbm, words_vm, io_sems, block_b):
    """Pull descriptor ``d``'s transposed word tile from HBM into VMEM;
    returns its column offset (descriptor field 0, not the loop index —
    the ring is addressed through its metadata, so tiles can live
    anywhere in the queue buffer)."""
    off = pl.multiple_of(desc_ref[d, 0], block_b)
    cp = pltpu.make_async_copy(words_hbm.at[:, pl.ds(off, block_b)],
                               words_vm, io_sems.at[0])
    cp.start()
    cp.wait()
    return off


def _persistent_retire(d, off, desc_ref, out_vm, out_hbm, flags_ref,
                       io_sems, block_b):
    """Push descriptor ``d``'s finished output tile back to HBM and mark
    its completion flag: 1 + the descriptor's version slot, so the
    host-side retire can assert every tile completed under the dict
    version pinned at dispatch (0 = never processed)."""
    cp = pltpu.make_async_copy(out_vm, out_hbm.at[:, pl.ds(off, block_b)],
                               io_sems.at[1])
    cp.start()
    cp.wait()
    flags_ref[d] = 1 + desc_ref[d, 2]


def _persistent_streamed_kernel(desc_ref, vis_ref, words_hbm, dict_ref,
                                out_hbm, flags_ref, words_vm, out_vm,
                                dict_bufs, hits_sc, dma_sems, io_sems, *,
                                n_groups: int, match: str, num_buffers: int,
                                dict_block_r: int, tri_tiles: int,
                                quad_tiles: int, block_b: int, n_desc: int):
    """The persistent serving kernel, streamed Compare: ONE launch
    (grid=(1,)) fori_loops over a scalar-prefetched work-descriptor ring
    instead of paying one grid step — or worse, one ``pallas_call`` — per
    batch tile.

    Each descriptor is SMEM metadata ``(column offset, n_visits, version
    slot)``; its word tile is DMA'd from the HBM queue buffer, stages
    1-4 run in VMEM, stage 5a reuses the exact :func:`_ladder_sweep` DMA
    ladder over the descriptor's visit row, and the output tile DMAs
    back to HBM. A per-descriptor completion flag (``1 + version slot``)
    lands in an SMEM output the host polls — the retire side of the
    serving ring keeps its non-blocking ``is_ready`` contract unchanged.
    """
    def tile(d, carry):
        off = _persistent_io(desc_ref, d, words_hbm, words_vm, io_sems,
                             block_b)
        keys, valid = _candidates(words_vm[...], n_groups)   # stages 1-4
        hits = _ladder_sweep(                                # stage 5a
            desc_ref[d, 1], lambda k: vis_ref[d, k], keys, valid, dict_ref,
            dict_bufs, hits_sc, dma_sems, match=match,
            num_buffers=num_buffers, dict_block_r=dict_block_r,
            tri_tiles=tri_tiles, quad_tiles=quad_tiles)
        out_vm[...] = _priority_select(keys, hits)           # stage 5b
        _persistent_retire(d, off, desc_ref, out_vm, out_hbm, flags_ref,
                           io_sems, block_b)
        return carry

    jax.lax.fori_loop(0, n_desc, tile, 0)


def _persistent_resident_kernel(desc_ref, words_hbm, tri_ref, quad_ref,
                                bi_ref, out_hbm, flags_ref, words_vm, out_vm,
                                io_sems, *, n_groups: int, match: str,
                                block_b: int, n_desc: int):
    """Persistent serving kernel, resident Compare: the packed
    dictionaries sit in VMEM for the whole launch while the descriptor
    loop streams word tiles through; same descriptor/flag contract as
    the streamed variant."""
    dicts = {"tri": tri_ref, "quad": quad_ref, "bi": bi_ref}

    def tile(d, carry):
        off = _persistent_io(desc_ref, d, words_hbm, words_vm, io_sems,
                             block_b)
        keys, valid = _candidates(words_vm[...], n_groups)   # stages 1-4
        hits = _resident_hits(keys, valid, dicts, match=match)  # 5a
        out_vm[...] = _priority_select(keys, hits)           # stage 5b
        _persistent_retire(d, off, desc_ref, out_vm, out_hbm, flags_ref,
                           io_sems, block_b)
        return carry

    jax.lax.fori_loop(0, n_desc, tile, 0)


@functools.partial(
    jax.jit, static_argnames=("infix", "match", "block_b", "residency",
                              "dict_block_r", "num_buffers", "skip_index",
                              "persistent", "visit_budget", "interpret"))
def stem_fused_pallas(
    words: jnp.ndarray,
    roots,
    *,
    infix: bool = True,
    match: str = "bsearch",
    block_b: int = 256,
    residency: str = "auto",
    dict_block_r: int = 8,
    num_buffers: int = 2,
    skip_index: bool = True,
    persistent: bool = False,
    version_slot=0,
    visit_budget: int | None = None,
    interpret: bool = False,
):
    """words int32[B,16] + RootDictArrays -> (root int32[B,4], source int32[B]).

    The grid's batch axis spans every ``block_b`` tile of the batch, so
    one launch retires an arbitrarily deep queue megabatch; ``residency``
    picks the dictionary layout (DESIGN.md §5.3):

      "resident"  grid = batch tiles only; the packed dictionaries ride
                  along as constant-index-map VMEM blocks. Raises past
                  MAX_RESIDENT_KEYS (it would thrash VMEM).
      "streamed"  grid = batch tiles; per batch tile the kernel sweeps a
                  scalar-prefetched visit list of (dict_block_r x 128)
                  dictionary tiles, DMA'd from HBM through a
                  ``num_buffers``-deep explicit ladder; with
                  ``skip_index`` only the tiles a candidate key can land
                  in are visited at all. The visit table costs
                  ``batch_tiles x n_tiles`` int32 of scalar-prefetch
                  (SMEM) space; megabatches whose table would exceed
                  ``visit_budget`` (default VISIT_SMEM_BUDGET) are
                  chunked along the batch axis into several
                  pallas_calls, each with a within-budget table.
      "auto"      resident while the dictionaries fit, streamed beyond.

    ``persistent=True`` selects the persistent serving kernel: ONE
    launch (grid=(1,)) whose body fori_loops over a device-side
    work-descriptor ring — scalar-prefetched ``(row offset, n_visits,
    version slot)`` tuples in SMEM — DMA-ing each word tile in, running
    the full five-stage pipeline (the streamed variant reuses the exact
    DMA ladder), and DMA-ing (root, source) back out. The return value
    grows a third element: per-descriptor completion ``flags``
    int32[batch_tiles], ``1 + version_slot`` once a tile retires (0 =
    never processed), which the serving ring polls at retire.
    ``version_slot`` (traced, so hot swaps never re-trace) stamps the
    flags with the dictionary version pinned at dispatch.

    ``num_buffers`` (1..4; streamed only) sets the DMA lookahead depth —
    2 double-buffers, 1 is the no-overlap baseline. ``skip_index=False``
    (streamed only) disables tile skipping and sweeps every tile of the
    loaded dictionaries through the same ladder.

    Bit-identical to ``core.stemmer.extract_roots`` (and pyref) in every
    (residency, match, num_buffers, skip_index, persistent) combination.

    ``roots`` also accepts a ``core.stemmer.ResolvedRootDict`` handle:
    its pinned residency replaces the residency argument, and a handle
    carrying a prebuilt ``stem_match.DictTileSet`` of matching
    dict_block_r skips the per-call pad/concat of the tile stream
    (serving resolves both once at dictionary-publish time, so a hot
    swap whose arrays keep their shapes replays the cached trace).
    """
    if match not in ("bank", "bsearch"):
        raise ValueError(f"unknown in-kernel match strategy: {match}")
    if not 1 <= num_buffers <= MAX_NUM_BUFFERS:
        raise ValueError(f"num_buffers must be in 1..{MAX_NUM_BUFFERS},"
                         f" got {num_buffers}")
    n_groups = 5 if infix else 2
    roots, residency, tiles = core_stemmer.unwrap_dict(roots, residency)
    residency = choose_residency(roots, residency, infix=infix)

    loaded = _loaded_keys(roots, infix)
    if residency == "resident" and loaded > MAX_RESIDENT_KEYS:
        raise ValueError(
            f"dictionaries too large for VMEM residency ({loaded} keys >"
            f" {MAX_RESIDENT_KEYS}); use residency='streamed' or 'auto'"
            " (DESIGN.md §5.3)")

    b = words.shape[0]
    if b == 0:  # degenerate batch: nothing to launch
        empty = (jnp.zeros((0, 4), jnp.int32), jnp.zeros((0,), jnp.int32))
        return empty + (jnp.zeros((0,), jnp.int32),) if persistent else empty
    if not interpret:
        _check_tpu_tiling(block_b, dict_block_r, residency)
    pad = (-b) % block_b
    wp = jnp.pad(words, ((0, pad), (0, 0)))
    wt = wp.T                      # [16, bp]: word tiles travel lane-dense
    bp = wp.shape[0]
    bt = bp // block_b

    word_spec = pl.BlockSpec((ab.MAXLEN, block_b), lambda i, *a: (0, i))
    out_spec = pl.BlockSpec((OUT_ROWS, block_b), lambda i, *a: (0, i))

    def split(out):                # [8, rows] output tile -> (root, source)
        return out[:4, :b].T, out[4, :b]

    if residency == "resident":
        # infix=False never reads the bi dict: ship a one-tile placeholder
        # so the unused table doesn't occupy VMEM (see choose_residency)
        bi = roots.bi if infix else jnp.full((1,), sm.DICT_PAD, jnp.int32)
        dicts = tuple(sm.pad_dict_tiles(d, RESIDENT_TILE_ROWS)
                      for d in (roots.tri, roots.quad, bi))
        dict_spec = lambda d: pl.BlockSpec(d.shape, lambda i, *a: (0, 0))
        if persistent:
            out, flags = _persistent_resident_call(
                wt, dicts, dict_spec, version_slot, block_b=block_b,
                n_groups=n_groups, match=match, interpret=interpret)
            return split(out) + (flags,)
        out = pl.pallas_call(
            functools.partial(_fused_kernel, n_groups=n_groups, match=match),
            grid=(bt,),
            in_specs=[word_spec] + [dict_spec(d) for d in dicts],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((OUT_ROWS, bp), jnp.int32),
            interpret=interpret,
        )(wt, *dicts)
        return split(out)

    # ---- streamed: scalar-prefetched visit index + explicit DMA ladder ---
    if tiles is None or tiles.dict_block_r != dict_block_r:
        tiles = sm.build_dict_tiles(roots.tri, roots.quad, roots.bi,
                                    dict_block_r)
    tri_tiles, quad_tiles, _ = tiles.counts
    n_slots = n_groups * N_CAND

    # pre-pass (stages 1-4 in jnp, the same candidate_columns body the
    # kernel runs): which dictionary tiles can this batch tile hit?
    kc, vc = sdp.candidate_columns(wp)
    n_visits, visit_idx = _visit_tables(
        jnp.stack(kc[:n_slots], axis=1), jnp.stack(vc[:n_slots], axis=1) > 0,
        tiles, n_groups=n_groups, block_b=block_b, skip_index=skip_index)

    # chunk the scalar-prefetch table along the batch axis: each chunk's
    # [chunk_bt, n_tiles] table stays inside the SMEM budget (megabatches
    # otherwise grow it without bound — the PR 5 open edge)
    budget = VISIT_SMEM_BUDGET if visit_budget is None else visit_budget
    max_bt = max(1, budget // tiles.n_tiles)
    kern_args = dict(n_groups=n_groups, match=match, num_buffers=num_buffers,
                     dict_block_r=dict_block_r, tri_tiles=tri_tiles,
                     quad_tiles=quad_tiles)
    outs, flags_out = [], []
    for c0 in range(0, bt, max_bt):
        c1 = min(bt, c0 + max_bt)
        cw = wt[:, c0 * block_b:c1 * block_b]
        if persistent:
            o, f = _persistent_streamed_call(
                cw, tiles.stream, n_visits[c0:c1], visit_idx[c0:c1],
                version_slot, block_b=block_b, n_slots=n_slots,
                interpret=interpret, **kern_args)
            flags_out.append(f)
        else:
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,      # (n_visits, visit_idx) -> SMEM
                grid=(c1 - c0,),
                in_specs=[word_spec,
                          pl.BlockSpec(memory_space=pl.ANY)],  # dict: HBM
                out_specs=out_spec,
                scratch_shapes=[
                    pltpu.VMEM((num_buffers, dict_block_r, sm.LANE),
                               jnp.int32),
                    pltpu.VMEM((block_b, n_slots), jnp.int32),
                    pltpu.SemaphoreType.DMA((num_buffers,)),
                ],
            )
            o = pl.pallas_call(
                functools.partial(_fused_pipeline_kernel, **kern_args),
                grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct(
                    (OUT_ROWS, (c1 - c0) * block_b), jnp.int32),
                interpret=interpret,
            )(n_visits[c0:c1], visit_idx[c0:c1], cw, tiles.stream)
        outs.append(o)
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    if persistent:
        flags = (flags_out[0] if len(flags_out) == 1
                 else jnp.concatenate(flags_out))
        return split(out) + (flags,)
    return split(out)


def _check_tpu_tiling(block_b: int, dict_block_r: int, residency: str):
    """Compiled kernels slice word tiles along lanes and dictionary tiles
    along sublanes: both must cover whole (8, 128) vreg tiles."""
    if block_b % sm.LANE:
        raise ValueError(f"block_b={block_b} must be a multiple of"
                         f" {sm.LANE} on a TPU")
    if residency == "streamed" and dict_block_r % 8:
        raise ValueError(f"dict_block_r={dict_block_r} must be a multiple"
                         " of 8 on a TPU")


def _descriptors(bt: int, block_b: int, n_visits, version_slot):
    """Pack the work-descriptor ring: int32[bt, 3] of (column offset,
    n_visits, version slot) per tile, delivered via scalar prefetch."""
    ver = jnp.broadcast_to(jnp.asarray(version_slot, jnp.int32), (bt,))
    offs = jnp.arange(bt, dtype=jnp.int32) * block_b
    return jnp.stack([offs, n_visits.astype(jnp.int32), ver], axis=1)


def _persistent_out_shape(bp: int, bt: int):
    return [jax.ShapeDtypeStruct((OUT_ROWS, bp), jnp.int32),
            jax.ShapeDtypeStruct((bt,), jnp.int32)]


def _persistent_resident_call(wt, dicts, dict_spec, version_slot, *,
                              block_b: int, n_groups: int, match: str,
                              interpret: bool):
    bp = wt.shape[1]
    bt = bp // block_b
    desc = _descriptors(bt, block_b, jnp.zeros(bt, jnp.int32), version_slot)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,              # descriptor ring -> SMEM
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + [
            dict_spec(d) for d in dicts],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        scratch_shapes=[
            pltpu.VMEM((ab.MAXLEN, block_b), jnp.int32),
            pltpu.VMEM((OUT_ROWS, block_b), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_persistent_resident_kernel, n_groups=n_groups,
                          match=match, block_b=block_b, n_desc=bt),
        grid_spec=grid_spec,
        out_shape=_persistent_out_shape(bp, bt),
        interpret=interpret,
    )(desc, wt, *dicts)


def _persistent_streamed_call(wt, stream, n_visits, visit_idx, version_slot,
                              *, block_b: int, n_slots: int, n_groups: int,
                              match: str, num_buffers: int, dict_block_r: int,
                              tri_tiles: int, quad_tiles: int,
                              interpret: bool):
    bp = wt.shape[1]
    bt = bp // block_b
    desc = _descriptors(bt, block_b, n_visits, version_slot)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # (descriptors, visit rows)
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),   # word queue: HBM
                  pl.BlockSpec(memory_space=pl.ANY)],  # dict: HBM
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        scratch_shapes=[
            pltpu.VMEM((ab.MAXLEN, block_b), jnp.int32),
            pltpu.VMEM((OUT_ROWS, block_b), jnp.int32),
            pltpu.VMEM((num_buffers, dict_block_r, sm.LANE), jnp.int32),
            pltpu.VMEM((block_b, n_slots), jnp.int32),
            pltpu.SemaphoreType.DMA((num_buffers,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_persistent_streamed_kernel, n_groups=n_groups,
                          match=match, num_buffers=num_buffers,
                          dict_block_r=dict_block_r, tri_tiles=tri_tiles,
                          quad_tiles=quad_tiles, block_b=block_b, n_desc=bt),
        grid_spec=grid_spec,
        out_shape=_persistent_out_shape(bp, bt),
        interpret=interpret,
    )(desc, visit_idx, wt, stream)


def salvage_descriptor_rows(flags, version_slot: int, block_b: int) -> int:
    """Host-side watchdog helper: how many leading rows of an abandoned
    persistent launch its completion flags prove retired.

    Descriptors retire in ring order (the kernel's fori_loop), so a
    wedge leaves exactly a *prefix* of flags equal to ``1 +
    version_slot`` — anything after the first unretired descriptor is
    unproven even if its flag looks set (the flag write races the
    wedge). Returns ``block_b * k`` for the longest such prefix: the
    rows the watchdog may scatter; the rest re-dispatches down the
    megabatch path.
    """
    f = np.asarray(flags)
    good = f == 1 + version_slot
    k = int(f.size if good.all() else np.argmin(good))
    return k * block_b


def dict_tile_count(roots, dict_block_r: int) -> int:
    """Tiles in the streamed `[tri | quad | bi]` stream (mirrors
    stem_match.pad_dict_tiles: every table pads to >= one full tile)."""
    per = dict_block_r * sm.LANE
    return sum(max(1, -(-int(t.shape[0]) // per))
               for t in (roots.tri, roots.quad, roots.bi))


def planned_launches(n_words: int, roots, *, infix: bool = True,
                     block_b: int = 256, residency: str = "auto",
                     dict_block_r: int = 8, persistent: bool = False,
                     visit_budget: int | None = None) -> int:
    """``pallas_call`` dispatches one :func:`stem_fused_pallas` invocation
    issues for this configuration — the launch accounting behind
    ``ops.dispatch_count()``.

    Resident launches are always 1; streamed (and persistent-streamed)
    launches are ceil(batch_tiles / chunk) where chunk is the largest
    batch-tile count whose scalar-prefetch visit table fits the SMEM
    budget.
    """
    roots, residency, tiles = core_stemmer.unwrap_dict(roots, residency)
    residency = choose_residency(roots, residency, infix=infix)
    if n_words == 0:
        return 0
    if residency == "resident":
        return 1
    if tiles is not None and tiles.dict_block_r == dict_block_r:
        n_tiles = tiles.n_tiles
    else:
        n_tiles = dict_tile_count(roots, dict_block_r)
    budget = VISIT_SMEM_BUDGET if visit_budget is None else visit_budget
    max_bt = max(1, budget // n_tiles)
    bt = -(-n_words // block_b)
    return -(-bt // max_bt)


def tile_visit_stats(words, roots, *, infix: bool = True, block_b: int = 256,
                     dict_block_r: int = 8, skip_index: bool = True) -> dict:
    """Run only the tile-skipping pre-pass and report visit counts.

    Returns ``{"visited": total tile visits across batch tiles,
    "full_sweep": batch_tiles * live dictionary tiles (what
    skip_index=False visits), "batch_tiles", "dict_tiles"}`` — the
    numbers the ``dict_stream_pipeline`` benchmark rows record so the
    skip index's coverage is tracked next to its timings.
    """
    roots, _, tiles = core_stemmer.unwrap_dict(roots, "auto")
    if tiles is None or tiles.dict_block_r != dict_block_r:
        tiles = sm.build_dict_tiles(roots.tri, roots.quad, roots.bi,
                                    dict_block_r)
    n_groups = 5 if infix else 2
    b = words.shape[0]
    pad = (-b) % block_b
    wp = jnp.pad(words, ((0, pad), (0, 0)))
    n_slots = n_groups * N_CAND
    kc, vc = sdp.candidate_columns(wp)
    n_visits, _ = _visit_tables(
        jnp.stack(kc[:n_slots], axis=1), jnp.stack(vc[:n_slots], axis=1) > 0,
        tiles, n_groups=n_groups, block_b=block_b, skip_index=skip_index)
    bt = wp.shape[0] // block_b
    tri_t, quad_t, bi_t = tiles.counts
    live = tri_t + quad_t + (bi_t if infix else 0)
    return {"visited": int(jnp.sum(n_visits)), "full_sweep": bt * live,
            "batch_tiles": bt, "dict_tiles": live}
