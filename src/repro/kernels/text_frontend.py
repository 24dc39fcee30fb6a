"""Pallas TPU kernel: the raw-text ingestion front-end (DESIGN.md §7).

One launch turns per-word class windows into normalised, clitic-stripped
`[block_w, 16]` word-tile rows — the exact input `stem_fused_pallas`
consumes — so text feeds the stemmer megakernel with no host round-trip:

  grid = (Wp / block_w,)   one step per word tile
  win       int32[Wp, 32]  each word's first MAX_RAW codepoint classes
                           (textnorm.class_windows, an XLA gather)
  fw        (r, 128)       textnorm.FW_ROWS function-word keys

The irregular part — reading each word's raw window out of the
codepoint tile at a data-dependent offset — stays in XLA: Mosaic lowers
no gather across vregs. Word geometry (starts/lens/byte spans) comes
from ``textnorm.segment_geometry`` and the windows from
``textnorm.class_windows``, both jnp pre-passes in the same jit scope
(the PR 5 visit-index precedent). The kernel does the dense per-word
work: it compacts letters left with an inclusive letter count (a small
triangular matmul on the MXU, exact for counts <= 32) and the
count==k one-hot pattern, then hands the letter rows to the *shared*
``textnorm.strip_and_pack`` body — the same traced code the jnp
reference (``textnorm.frontend_reference``) runs, so clitic stripping
cannot drift between reference and kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import alphabet as ab
from repro.core import textnorm as tn



def _frontend_kernel(win_ref, fw_ref, words_ref):
    cls = win_ref[...]                             # [bw, MAX_RAW]
    is_letter = cls > 0
    # inclusive running letter count along the window: a triangular
    # matmul (0/1 operands, counts <= MAX_RAW: exact in bf16 -> f32)
    r = jax.lax.broadcasted_iota(jnp.int32, (tn.MAX_RAW, tn.MAX_RAW), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (tn.MAX_RAW, tn.MAX_RAW), 1)
    upper = (r <= c).astype(jnp.float32).astype(jnp.bfloat16)
    csum = jnp.dot(is_letter.astype(jnp.float32).astype(jnp.bfloat16), upper,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    nlet = jnp.minimum(csum[:, tn.MAX_RAW - 1], tn.CMAX)
    # compact letters left: position k letter = the column whose running
    # letter count hits k+1 (one-hot sum — no gather along traced offsets)
    codes = jnp.concatenate(
        [jnp.sum(jnp.where(is_letter & (csum == k + 1), cls, 0), axis=1,
                 keepdims=True) for k in range(tn.CMAX)], axis=1)
    words_ref[...] = tn.strip_and_pack(codes, nlet, fw_ref[...])


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def text_frontend_pallas(chars, starts, lens, *, block_w: int = 128,
                         interpret: bool = False):
    """chars int32[T] codepoints (0-padded), starts/lens int32[Wp] from
    ``textnorm.segment_geometry`` (Wp a block_w multiple) -> words
    int32[Wp, 16], bit-identical to ``textnorm.frontend_reference`` and
    to the host ``analyze_text_py`` rows.
    """
    chars = jnp.asarray(chars, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    wp = starts.shape[0]
    if wp % block_w:
        raise ValueError(f"word capacity {wp} not a multiple of"
                         f" block_w={block_w}")
    win = tn.class_windows(chars, starts, lens)
    fw = jnp.asarray(tn.FW_ROWS)

    return pl.pallas_call(
        _frontend_kernel,
        grid=(wp // block_w,),
        in_specs=[
            pl.BlockSpec((block_w, tn.MAX_RAW), lambda i: (i, 0)),
            pl.BlockSpec(fw.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_w, ab.MAXLEN), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((wp, ab.MAXLEN), jnp.int32),
        interpret=interpret,
    )(win, fw)
