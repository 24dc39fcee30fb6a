"""Pallas TPU kernel: tiled dictionary match (the paper's Compare stage).

The FPGA datapath instantiates banks of ``stem3/4_Comparator`` units that
compare candidate stems against stored roots in parallel. On TPU the root
dictionary lives in HBM and is streamed tile-by-tile through VMEM while a
tile of packed 24-bit candidate keys stays resident; each grid step performs
an all-pairs equality compare on the VPU and ORs the row-reduction into the
output tile.

Layout: both keys and dictionary are reshaped to (rows, 128) so the minor
dimension matches the VPU lane width; a (block_n x 128) key tile against a
(block_r x 128) dictionary tile compares (block_n*128) x (block_r*128)
pairs per step — the TPU analogue of the comparator bank, with the bank
"size" set by BlockSpec rather than LUT count.

Padding: keys are padded with -1 and the dictionary with -2, so padding
never produces a match.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
KEY_PAD = -1
DICT_PAD = -2
# bsearch padding sentinel: larger than any packed 24-bit key, so padding a
# sorted dictionary on the right keeps it sorted and never matches.
DICT_SENTINEL = 1 << 28


def _ceil_log2(n: int) -> int:
    k = 0
    while (1 << k) < n:
        k += 1
    return k


def pad_dict_sorted(dict_keys: jnp.ndarray) -> jnp.ndarray:
    """Pad a *sorted* dictionary to the next pow2 >= LANE with DICT_SENTINEL,
    reshaped (rows, LANE) so it ships to VMEM as a lane-aligned 2D tile."""
    r = dict_keys.shape[0]
    rp = max(LANE, 1 << _ceil_log2(r))
    return jnp.pad(dict_keys, (0, rp - r),
                   constant_values=DICT_SENTINEL).reshape(-1, LANE)


def pad_dict_tiles(dict_keys: jnp.ndarray, tile_rows: int) -> jnp.ndarray:
    """Pad a *sorted* dictionary to a whole number of (tile_rows, LANE) tiles
    with DICT_SENTINEL and reshape (n_tiles * tile_rows, LANE).

    Sentinel padding on the right keeps every tile internally sorted, so a
    consumer can search each tile independently (:func:`sorted_member`)
    and bound it by its first/last element (the streamed megakernel's
    tile-visit pre-pass, stem_fused._visit_tables). Empty / placeholder
    dictionaries still produce one full sentinel tile.
    """
    r = dict_keys.shape[0]
    per_tile = tile_rows * LANE
    rp = max(per_tile, ((r + per_tile - 1) // per_tile) * per_tile)
    return jnp.pad(dict_keys, (0, rp - r),
                   constant_values=DICT_SENTINEL).reshape(-1, LANE)


@jax.tree_util.register_pytree_node_class
@dataclass
class DictTileSet:
    """The streamed megakernel's dictionary layout, prebuilt.

    ``stream`` is the concatenated `[tri | quad | bi]` tile stream from
    :func:`pad_dict_tiles` (each `(dict_block_r x LANE)` tile internally
    sorted, sentinel-padded); ``mins`` / ``maxs`` are the per-tile sorted
    boundary tables (first/last element of every tile) that the tile-visit
    pre-pass intersects candidate keys against (stem_fused._visit_tables).
    Tile counts and the tile height ride as pytree aux data, so a jit
    trace is keyed on them: serving precomputes a DictTileSet once at
    dictionary-publish time (serve.DictStore -> core.stemmer.resolve_dict)
    and every launch — including hot swaps whose shapes match — replays
    the cached trace without re-padding or re-concatenating the tables.
    """

    stream: jnp.ndarray            # int32 [n_tiles * dict_block_r, LANE]
    mins: jnp.ndarray              # int32 [n_tiles] first element per tile
    maxs: jnp.ndarray              # int32 [n_tiles] last element per tile
    dict_block_r: int              # tile height in LANE rows (static)
    counts: tuple                  # (tri_tiles, quad_tiles, bi_tiles) (static)

    def tree_flatten(self):
        return ((self.stream, self.mins, self.maxs),
                (self.dict_block_r, self.counts))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def n_tiles(self) -> int:
        return sum(self.counts)


def build_dict_tiles(tri: jnp.ndarray, quad: jnp.ndarray, bi: jnp.ndarray,
                     dict_block_r: int) -> DictTileSet:
    """Pad + concatenate the three sorted dictionaries into the streamed
    tile stream and extract the per-tile [min, max] boundary tables.

    All three dictionaries are always present in the stream (the bi table
    too, even for infix=False sweeps): with the tile-visit index an unused
    table's tiles are simply never visited, and a single layout keeps one
    jit trace per shape regardless of the infix flag.
    """
    tiles = [pad_dict_tiles(d, dict_block_r) for d in (tri, quad, bi)]
    counts = tuple(t.shape[0] // dict_block_r for t in tiles)
    stream = jnp.concatenate(tiles, axis=0)
    flat = stream.reshape(-1, dict_block_r * LANE)   # one row per tile
    return DictTileSet(stream=stream, mins=flat[:, 0], maxs=flat[:, -1],
                       dict_block_r=dict_block_r, counts=counts)


# 8-bit planes a sorted key row is split into for the MXU row fetch: four
# cover any non-negative int32 (sentinels included), and every plane value
# (0..255) is exact in bf16
PLANES = 4


def _bf16(x: jnp.ndarray) -> jnp.ndarray:
    return x.astype(jnp.float32).astype(jnp.bfloat16)


def sorted_tables(rows: jnp.ndarray):
    """rows int32[n, LANE] (a sorted, sentinel-padded dictionary in
    row-major order, :func:`pad_dict_tiles`) -> the tables of
    :func:`sorted_member`: the row maxima int32[1, n] and the rows' 8-bit
    planes bf16[n, PLANES * LANE]."""
    row_max = rows.T[LANE - 1:, :]
    planes = jnp.concatenate(
        [_bf16((rows >> (8 * p)) & 255) for p in range(PLANES)], axis=1)
    return row_max, planes


def sorted_member(tables, keys: jnp.ndarray) -> jnp.ndarray:
    """Gather-free membership in a sorted dictionary (the in-kernel
    ``match="bsearch"`` Compare): keys int32[m, 1] -> bool[m, 1].

    Two levels replace the bisection's data-dependent loads, which
    Mosaic cannot lower:

      1. the key's row is the number of rows whose last (largest) entry
         is below it — one compare against the row maxima;
      2. that row is fetched with a one-hot ``[m, n] @ [n, PLANES*LANE]``
         MXU matmul over the rows' 8-bit planes (bf16 in, f32 out: each
         output sums exactly one plane value, so the fetch is exact), and
         the key is compared against the fetched row's LANE entries.

    Bit-identical to :func:`bsearch_hit` on the same dictionary.
    """
    row_max, planes = tables
    n = row_max.shape[1]
    row = jnp.minimum(jnp.sum((row_max < keys).astype(jnp.int32), axis=1,
                              keepdims=True), n - 1)             # (m, 1)
    onehot = _bf16(jax.lax.broadcasted_iota(jnp.int32, (keys.shape[0], n), 1)
                   == row)
    f = jnp.dot(onehot, planes,
                preferred_element_type=jnp.float32).astype(jnp.int32)
    val = f[:, :LANE]
    for p in range(1, PLANES):
        val = val | (f[:, p * LANE:(p + 1) * LANE] << (8 * p))
    return jnp.any(val == keys, axis=1, keepdims=True)


def bank_rows_member(rows_ref, keys: jnp.ndarray) -> jnp.ndarray:
    """All-pairs membership (``match="bank"``): keys int32[m, 1] against
    every row of the (rows, LANE) dictionary ref -> bool[m, 1], one
    ``[m, LANE]`` comparator bank per row in a loop (the whole
    ``[m, R]`` compare would not fit VMEM at lexicon sizes)."""
    def row(r, hit):
        eq = keys == rows_ref[pl.ds(r, 1), :]
        return hit | jnp.any(eq, axis=1, keepdims=True).astype(jnp.int32)

    return jax.lax.fori_loop(0, rows_ref.shape[0], row,
                             jnp.zeros(keys.shape, jnp.int32)) > 0


def bsearch_hit(flat_dict: jnp.ndarray, keys: jnp.ndarray) -> jnp.ndarray:
    """Membership via an unrolled branchless binary search.

    flat_dict int32[Rp] sorted ascending, Rp a power of two (sentinel
    padded); keys int32[...] -> bool[...]. Exactly ceil(log2 Rp) static
    bisection steps — the paper's §7 'tree search' Compare upgrade: each
    step halves the [lo, hi] window with a predicated select instead of a
    branch, so the whole search is a fixed-depth dataflow graph (the TPU
    analogue of a pipelined hardware tree walker).
    """
    rp = flat_dict.shape[0]
    lo = jnp.zeros(keys.shape, jnp.int32)
    hi = jnp.full(keys.shape, rp - 1, jnp.int32)
    for _ in range(_ceil_log2(rp)):
        mid = (lo + hi) // 2
        v = jnp.take(flat_dict, mid, mode="clip")
        ge = v >= keys
        hi = jnp.where(ge, mid, hi)
        lo = jnp.where(ge, lo, mid + 1)
    return jnp.take(flat_dict, lo, mode="clip") == keys


def _match_kernel(keys_ref, dict_ref, out_ref):
    """Grid (n_tiles, r_tiles); r (minor) accumulates OR into out_ref."""
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...]          # (bn, LANE) int32
    dic = dict_ref[...]           # (br, LANE) int32
    bn, _ = keys.shape
    # all-pairs compare: (bn*LANE, 1) vs (1, br*LANE)
    k_flat = keys.reshape(bn * LANE, 1)
    d_flat = dic.reshape(1, -1)
    hit = (k_flat == d_flat).any(axis=1).reshape(bn, LANE)
    out_ref[...] |= hit.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n", "block_r", "interpret"))
def dict_match_pallas(
    keys: jnp.ndarray,
    dict_keys: jnp.ndarray,
    *,
    block_n: int = 2,
    block_r: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """keys int32[N], dict_keys int32[R] -> bool[N] membership flags."""
    n = keys.shape[0]
    r = dict_keys.shape[0]

    n_pad = (-n) % (block_n * LANE)
    r_pad = (-r) % (block_r * LANE)
    keys_p = jnp.pad(keys, (0, n_pad), constant_values=KEY_PAD).reshape(-1, LANE)
    dict_p = jnp.pad(dict_keys, (0, r_pad), constant_values=DICT_PAD).reshape(-1, LANE)

    n_tiles = keys_p.shape[0] // block_n
    r_tiles = dict_p.shape[0] // block_r

    out = pl.pallas_call(
        _match_kernel,
        grid=(n_tiles, r_tiles),
        in_specs=[
            pl.BlockSpec((block_n, LANE), lambda i, j: (i, 0)),
            pl.BlockSpec((block_r, LANE), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, LANE), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(keys_p.shape, jnp.int32),
        interpret=interpret,
    )(keys_p, dict_p)
    return out.reshape(-1)[:n].astype(bool)


# ---------------------------------------------------------------------------
# O(log R) variant: in-kernel sorted search, dictionary resident in VMEM
# ---------------------------------------------------------------------------
def _bsearch_kernel(keys_ref, dict_ref, out_ref):
    """Grid (n_tiles,); the whole (sentinel-padded) dictionary rides along
    as a VMEM-resident block (constant index map), so one launch covers all
    key tiles with no HBM round-trips between bisection steps."""
    keys = keys_ref[...]                      # (bn, LANE) int32
    flat = dict_ref[...].reshape(-1)          # (Rp,) sorted + sentinel
    out_ref[...] = bsearch_hit(flat, keys).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def dict_match_bsearch_pallas(
    keys: jnp.ndarray,
    dict_keys: jnp.ndarray,
    *,
    block_n: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """keys int32[N], dict_keys int32[R] *sorted* -> bool[N].

    O(N log R) compare — the paper's proposed tree-search upgrade run
    inside the kernel: ceil(log2 R) predicated bisection steps per key
    against the VMEM-resident sorted dictionary.
    """
    n = keys.shape[0]
    n_pad = (-n) % (block_n * LANE)
    keys_p = jnp.pad(keys, (0, n_pad), constant_values=KEY_PAD).reshape(-1, LANE)
    dict_p = pad_dict_sorted(dict_keys)

    n_tiles = keys_p.shape[0] // block_n
    out = pl.pallas_call(
        _bsearch_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((block_n, LANE), lambda i: (i, 0)),
            pl.BlockSpec(dict_p.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(keys_p.shape, jnp.int32),
        interpret=interpret,
    )(keys_p, dict_p)
    return out.reshape(-1)[:n].astype(bool)
