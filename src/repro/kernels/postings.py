"""Pallas TPU kernel: the inverted-index postings reduction.

The corpus indexer (repro.index) needs root -> (doc, position) postings
for millions of words without a host loop. The device recipe is a
per-tile count + a global scan + a scatter, and this kernel runs the
per-tile half on the accelerator. Each grid step takes one
``block_w``-word tile of root ids, laid out lane-dense as
``(block_w / 128, 128)`` rows (word ``i`` at row ``i // 128``, lane
``i % 128``), and emits

  * the tile's root histogram — for each 128-root row of the id space,
    one ``[128 words, 128 roots]`` compare per 128-word chunk, summed
    over words (a segment reduce without a sort);
  * each word's stable rank within its root in the tile — the number of
    earlier words of the tile with the same id, one ``[128, 128]``
    compare per (word chunk, earlier chunk) pair.

(Tiles narrower than 128 words, a CPU-test size, use one row of
``block_w`` lanes.)

Every step is a static compare-and-sum on whole (8, 128) vreg tiles: no
gather, no data-dependent control flow. Histograms and ranks are tiny
next to the word stream, so the global side of the reduction —
exclusive cumsums over (tile, root) and the final scatter of (doc,
position) pairs into the postings array — runs as XLA ops in the same
jit scope (:func:`finish_postings`), exactly the PR 5/PR 7 visit-index
pattern: scatters in XLA, dense per-word work in the kernel. Ranks count
earlier words in (tile, lane) order, so postings within a root come out
sorted by global word index with no tie-breaking pass.

Invalid words (no root found, padding) are assigned the drop bucket
``id == n_roots``; their scatter destinations land out of bounds and
``mode="drop"`` discards them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
SUBLANE = 8

# the per-tile histogram block is (n_roots + 1) int32, double-buffered
# in VMEM next to the word tile; past this the id space is too large
HIST_VMEM_BYTES = 4 << 20


def _iota(shape, dim: int) -> jnp.ndarray:
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _postings_kernel(ids_ref, hist_ref, rank_ref):
    """Grid (n_tiles,): one word tile -> (root histogram, in-root rank).

    ids (nr, width) -> hist (hist_rows, LANE) with root ``h * LANE + l``
    at [h, l], and rank (nr, width) in the ids' layout.
    """
    ids = ids_ref[...]
    nr, width = ids.shape
    t = ids.T                                         # (width, nr)
    cols = [t[:, a:a + 1] for a in range(nr)]         # word chunk a, (width, 1)
    sub = _iota((width, width), 0)
    lane = _iota((width, width), 1)

    ranks = []
    for a in range(nr):
        def earlier(c, acc, a=a):
            eq = ((cols[a] == ids_ref[pl.ds(c, 1), :])
                  & (c * width + lane < a * width + sub))
            return acc + jnp.sum(eq.astype(jnp.int32), axis=1, keepdims=True)
        ranks.append(jax.lax.fori_loop(0, a + 1, earlier,
                                       jnp.zeros((width, 1), jnp.int32)))
    rank_ref[...] = jnp.concatenate(ranks, axis=1).T

    def hist_row(h, carry):
        roots = h * LANE + _iota((1, LANE), 1)
        n = jnp.zeros((1, LANE), jnp.int32)
        for col in cols:
            n = n + jnp.sum((col == roots).astype(jnp.int32), axis=0,
                            keepdims=True)
        hist_ref[pl.ds(h, 1), :] = n
        return carry

    jax.lax.fori_loop(0, hist_ref.shape[0], hist_row, 0)


@functools.partial(jax.jit,
                   static_argnames=("n_roots", "block_w", "interpret"))
def postings_pallas(ids: jnp.ndarray, *, n_roots: int, block_w: int = 2048,
                    interpret: bool = False):
    """Tile-local postings reduction: root ids -> (hist, rank).

    ids int32[W] in [0, n_roots] (== n_roots marks the drop bucket) ->
      hist int32[n_tiles, n_roots + 1]  per-tile root histogram
      rank int32[W_pad]                 stable rank within (tile, root)

    W pads up to a ``block_w`` multiple with drop-bucket ids; block_w is
    a power of two (a multiple of 1024 on a TPU, so a tile's
    ``(block_w / 128, 128)`` rows cover whole (8, 128) vreg tiles). One
    pallas_call, grid over word tiles; combine across tiles (and shards)
    with :func:`finish_postings`.
    """
    if block_w & (block_w - 1):
        raise ValueError(f"block_w must be a power of two, got {block_w}")
    if not interpret and block_w % (SUBLANE * LANE):
        raise ValueError(f"block_w={block_w} must be a multiple of"
                         f" {SUBLANE * LANE} on a TPU")
    n_roots_pad = n_roots + 1                  # +1: the drop bucket
    hist_rows = -(-n_roots_pad // (SUBLANE * LANE)) * SUBLANE
    if 2 * hist_rows * LANE * 4 > HIST_VMEM_BYTES:
        raise ValueError(
            f"per-tile histogram of {n_roots} roots overflows the"
            f" {HIST_VMEM_BYTES >> 20} MiB VMEM budget")
    w = ids.shape[0]
    pad = (-w) % block_w
    width = min(block_w, LANE)
    nr = block_w // width
    ids_p = jnp.pad(ids.astype(jnp.int32), (0, pad),
                    constant_values=n_roots).reshape(-1, width)
    n_tiles = ids_p.shape[0] // nr
    hist, rank = pl.pallas_call(
        _postings_kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((nr, width), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((hist_rows, LANE), lambda i: (i, 0)),
                   pl.BlockSpec((nr, width), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles * hist_rows, LANE), jnp.int32),
            jax.ShapeDtypeStruct(ids_p.shape, jnp.int32),
        ],
        interpret=interpret,
    )(ids_p)
    hist = hist.reshape(n_tiles, hist_rows * LANE)[:, :n_roots_pad]
    return hist, rank.reshape(-1)


def postings_launches(n_words: int, *, block_w: int = 2048) -> int:
    """pallas_call dispatches one :func:`postings_pallas` call issues —
    always 1 (the grid spans every word tile), 0 for an empty batch."""
    return 1 if n_words else 0


def finish_postings(hist, rank, ids, doc_ids, positions, *, n_roots: int,
                    block_w: int):
    """Global half of the reduction: cumsums + the postings scatter.

    hist int32[n_tiles, n_roots+1], rank int32[W_pad] from one or more
    :func:`postings_pallas` calls over *consecutive* word tiles (the
    sharded path stacks per-shard tiles in corpus order, which makes the
    shard merge the same exclusive cumsum as the tile merge); ids
    int32[W], doc_ids/positions int32[W] aligned with it.

    Returns ``(counts int32[n_roots], docs int32[W_pad],
    poss int32[W_pad], n_postings int32)`` — per-root posting counts,
    and the postings arrays laid out CSR-style: root r's postings occupy
    ``[offsets[r], offsets[r] + counts[r])`` with
    ``offsets = exclusive_cumsum(counts)``, sorted by global word index.
    Entries at and past ``n_postings`` are zero. Pure XLA (cumsums, one
    gather, two scatters) — no per-word host loop.
    """
    w = ids.shape[0]
    w_pad = rank.shape[0]
    # per-(tile, root) base: how many of root r landed in earlier tiles
    tile_base = jnp.cumsum(hist, axis=0) - hist          # exclusive, axis 0
    counts = hist.sum(axis=0)[:n_roots]
    offsets = jnp.cumsum(counts) - counts                # exclusive
    n_postings = counts.sum()

    tile_of = jnp.arange(w, dtype=jnp.int32) // block_w
    safe_ids = jnp.minimum(ids, n_roots)                 # gather-safe
    base = (jnp.take(jnp.concatenate([offsets, n_postings[None]]), safe_ids,
                     mode="clip")
            + tile_base[tile_of, safe_ids] + rank[:w])
    # drop bucket -> out of bounds -> mode="drop" discards
    dest = jnp.where(safe_ids < n_roots, base, w_pad)
    docs = jnp.zeros((w_pad,), jnp.int32).at[dest].set(
        doc_ids.astype(jnp.int32), mode="drop")
    poss = jnp.zeros((w_pad,), jnp.int32).at[dest].set(
        positions.astype(jnp.int32), mode="drop")
    return counts, docs, poss, n_postings
