"""Vectorised (batch-parallel) JAX implementation of the paper's stemmer.

The five FPGA pipeline stages (Fig 10) map onto tensor stages over a batch
of encoded words ``int32[B, 16]``:

  stage 1  Check Prefixes / Check Suffixes  -> broadcast membership tests
  stage 2  Produce Prefixes / Suffixes      -> anchored cumulative-AND runs
  stage 3  Generate Stems                   -> static 6x2 (prefix-cut x size)
                                               truncation grid (VHDL Fig 12)
  stage 4  Filter by Size                   -> implicit in the static grid
  stage 5  Compare Stems & Extract Root     -> dictionary match (dense /
                                               sorted-search / Pallas kernel)
                                               + priority select

Candidate grid: a stem is word[p+1 : p+1+L] for prefix cut p in {-1..4} and
L in {3, 4}; the suffix cut is determined as s = p+1+L. 6 trilateral + 6
quadrilateral candidates per word, matching the VHDL's 6-slot arrays (the
``count1 < 5`` cap never binds — see DESIGN.md).

Infix processing (paper §6.3) adds three recovery candidate groups:
restored hollow (ا→و), remove-infix quad→tri, remove-infix tri→bi.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import alphabet as ab
from repro.core import pyref

# candidate-group tags == pyref source tags
N_CAND = 6  # prefix cuts -1..4


@jax.tree_util.register_pytree_node_class
@dataclass
class RootDictArrays:
    """Packed, sorted root dictionaries (int32 keys; see alphabet.pack_key)."""

    tri: jnp.ndarray   # int32[Rt] sorted
    quad: jnp.ndarray  # int32[Rq] sorted
    bi: jnp.ndarray    # int32[Rb] sorted

    def tree_flatten(self):
        return (self.tri, self.quad, self.bi), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @staticmethod
    def from_rootdict(d: pyref.RootDict) -> "RootDictArrays":
        def pack(roots):
            keys = sorted(ab.pack_key(r) for r in roots) or [-1]
            return jnp.asarray(np.asarray(keys, np.int32))

        return RootDictArrays(tri=pack(d.tri), quad=pack(d.quad), bi=pack(d.bi))

    @property
    def n_keys(self) -> int:
        return sum(int(d.shape[0]) for d in (self.tri, self.quad, self.bi))


@jax.tree_util.register_pytree_node_class
@dataclass
class ResolvedRootDict:
    """A RootDictArrays plus its *pre-resolved* megakernel configuration.

    Serving hot-swaps dictionaries between tile launches (see
    serve/dict_store.py); resolving ``residency="auto"`` once at publish
    time pins the kernel's static configuration, so a swap whose arrays
    keep their shapes replays the existing jit trace instead of
    re-tracing. The residency rides as pytree aux data: two handles with
    equal shapes and equal residency hit the same cache entry.

    ``tiles`` optionally carries a prebuilt ``stem_match.DictTileSet``
    for the streamed layout: the padded `[tri | quad | bi]` tile stream
    plus the per-tile sorted boundary tables the tile-visit pre-pass
    intersects candidate keys against. Publishing with a ``dict_block_r``
    precomputes it once, so serving launches (and hot swaps) skip the
    per-call pad/concat of the dictionary stream.

    Every stemmer entry point (``extract_roots``/``stem_batch``/... and
    ``ops.extract_roots_fused``) accepts a handle anywhere it accepts
    plain arrays; the handle's pinned residency wins over the call-site
    default ("auto"), and conflicting explicit residencies raise.
    """

    arrays: RootDictArrays
    residency: str          # "resident" | "streamed" — never "auto"
    tiles: object = None    # stem_match.DictTileSet | None (streamed layout)

    def tree_flatten(self):
        return (self.arrays, self.tiles), self.residency

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux, children[1])

    @property
    def n_keys(self) -> int:
        return self.arrays.n_keys


def resolve_dict(roots, *, residency: str = "auto", infix: bool = True,
                 dict_block_r: int | None = None) -> ResolvedRootDict:
    """Pin a dictionary's residency against the VMEM budget once, up front.

    ``infix`` scopes the budget to the tables the sweep loads (bi never
    ships for infix=False). A streamed resolution with ``dict_block_r``
    set also prebuilds the ``DictTileSet`` (tile stream + boundary
    tables), so every later launch — including shape-matched hot swaps —
    reuses it instead of re-padding the tables per call.
    """
    if isinstance(roots, ResolvedRootDict):
        unwrap_dict(roots, residency)  # conflicting residency raises
        res, arrays = roots.residency, roots.arrays
    else:
        from repro.kernels import stem_fused as sf  # lazy: kernels need core

        res = sf.choose_residency(roots, residency, infix=infix)
        arrays = roots
    tiles = roots.tiles if isinstance(roots, ResolvedRootDict) else None
    if res == "streamed" and dict_block_r and (
            tiles is None or tiles.dict_block_r != dict_block_r):
        # an already-resolved handle without (matching) tiles still gets
        # them built here — publish-time prebuild must not silently skip
        from repro.kernels import stem_match as smm

        tiles = smm.build_dict_tiles(arrays.tri, arrays.quad, arrays.bi,
                                     dict_block_r)
    if isinstance(roots, ResolvedRootDict) and tiles is roots.tiles:
        return roots
    return ResolvedRootDict(arrays, res, tiles)


def unwrap_dict(roots, residency: str = "auto"):
    """-> (RootDictArrays, residency, tiles); a handle's pinned residency
    wins, tiles is the handle's prebuilt DictTileSet (None otherwise)."""
    if isinstance(roots, ResolvedRootDict):
        if residency not in ("auto", roots.residency):
            raise ValueError(
                f"residency={residency!r} conflicts with the resolved dict"
                f" handle's pinned residency {roots.residency!r}")
        return roots.arrays, roots.residency, roots.tiles
    return roots, residency, None


# ---------------------------------------------------------------------------
# Stages 1-2
# ---------------------------------------------------------------------------
def check_and_produce(words: jnp.ndarray):
    """words int32[B,16] -> (pp bool[B,5], valid_s bool[B,17], n int32[B])."""
    prefix_codes = jnp.asarray(ab.PREFIX_CODES)
    suffix_codes = jnp.asarray(ab.SUFFIX_CODES)
    in_word = words != 0
    n = in_word.sum(axis=-1).astype(jnp.int32)

    head = words[:, :5]
    is_pref = (head[..., None] == prefix_codes).any(-1)
    run = jnp.cumprod(is_pref.astype(jnp.int32), axis=1) > 0
    yeh = head == ab.YEH
    yeh_before = jnp.cumsum(yeh.astype(jnp.int32), axis=1) - yeh
    pp = run & (yeh_before == 0)

    is_suf = (words[..., None] == suffix_codes).any(-1)
    ok = is_suf | ~in_word                      # pads don't break the run
    rev = jnp.flip(jnp.cumprod(jnp.flip(ok, 1).astype(jnp.int32), 1), 1) > 0
    ps = rev & in_word                          # bool[B,16]

    s_grid = jnp.arange(ab.MAXLEN + 1, dtype=jnp.int32)  # 0..16
    ps_pad = jnp.pad(ps, ((0, 0), (0, 1)))
    valid_s = (s_grid[None, :] == n[:, None]) | (
        (s_grid[None, :] < n[:, None]) & ps_pad
    )
    return pp, valid_s, n


# ---------------------------------------------------------------------------
# Stages 3-4
# ---------------------------------------------------------------------------
def generate_stems(words: jnp.ndarray):
    """-> (tri int32[B,6,4] zero-padded, tri_valid, quad int32[B,6,4], quad_valid).

    Candidate order along axis 1 is prefix cut p = -1, 0, 1, 2, 3, 4 — the
    VHDL loop order, which also defines match priority.
    """
    pp, valid_s, _ = check_and_produce(words)
    tri_list, quad_list, tv_list, qv_list = [], [], [], []
    for p in range(-1, 5):
        start = p + 1
        p_ok = jnp.ones(words.shape[0], bool) if p == -1 else pp[:, p]
        tri_chars = jax.lax.slice_in_dim(words, start, start + 3, axis=1)
        tri_list.append(jnp.pad(tri_chars, ((0, 0), (0, 1))))
        tv_list.append(p_ok & valid_s[:, p + 4])
        quad_chars = jax.lax.slice_in_dim(words, start, start + 4, axis=1)
        quad_list.append(quad_chars)
        qv_list.append(p_ok & valid_s[:, p + 5])
    tri = jnp.stack(tri_list, axis=1)
    quad = jnp.stack(quad_list, axis=1)
    tri_valid = jnp.stack(tv_list, axis=1)
    quad_valid = jnp.stack(qv_list, axis=1)
    return tri, tri_valid, quad, quad_valid


def pack_keys(stems: jnp.ndarray) -> jnp.ndarray:
    """int32[..., 4] char codes -> int32[...] packed 24-bit keys."""
    c = stems.astype(jnp.int32)
    return ((c[..., 0] * 64 + c[..., 1]) * 64 + c[..., 2]) * 64 + c[..., 3]


# ---------------------------------------------------------------------------
# Stage 5 backends
# ---------------------------------------------------------------------------
def match_dense(keys: jnp.ndarray, dict_keys: jnp.ndarray) -> jnp.ndarray:
    """O(N*R) broadcast compare — the paper's baseline Compare process."""
    return (keys[..., None] == dict_keys).any(-1)


def match_sorted(keys: jnp.ndarray, dict_keys: jnp.ndarray) -> jnp.ndarray:
    """O(N log R) binary search — the paper's proposed tree-search upgrade."""
    idx = jnp.searchsorted(dict_keys, keys)
    idx = jnp.clip(idx, 0, dict_keys.shape[0] - 1)
    return dict_keys[idx] == keys


def _match(keys, dict_keys, backend: str):
    if backend == "dense":
        return match_dense(keys, dict_keys)
    if backend == "sorted":
        return match_sorted(keys, dict_keys)
    if backend == "fused":
        from repro.kernels import ops  # lazy: kernels depend on core

        # "fused" reaching stage 5 in isolation (e.g. through the extended
        # rule pool) uses the megakernel's in-kernel sorted search.
        shape = keys.shape
        return ops.dict_match(
            keys.reshape(-1), dict_keys, strategy="bsearch").reshape(shape)
    raise ValueError(f"unknown match backend: {backend}")


# ---------------------------------------------------------------------------
# Full extraction
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("infix", "backend", "extended",
                                             "residency", "num_buffers",
                                             "skip_index"))
def extract_roots(
    words: jnp.ndarray,
    roots: RootDictArrays,
    *,
    infix: bool = True,
    backend: str = "sorted",
    extended: bool = False,
    residency: str = "auto",
    num_buffers: int = 2,
    skip_index: bool = True,
):
    """words int32[B,16] -> (root int32[B,4], source int32[B]).

    source uses pyref.SRC_* tags; root rows are zero-padded char codes.
    extended=True adds the beyond-paper rule pool (final ى→ي, hollow ا→ي).
    roots may be plain RootDictArrays or a ResolvedRootDict handle whose
    pinned residency then overrides the residency argument.

    backend selects the Compare stage implementation: "dense" / "sorted"
    (pure jnp references) or "fused" — the single-launch stage 1-5
    megakernel (kernels/stem_fused.py; paper-exact, no intermediate HBM
    tensors). For the fused backend,
    residency picks the dictionary layout: "resident" (VMEM-held),
    "streamed" (a scalar-prefetched tile-visit sweep fed by an explicit
    DMA ladder — unbounded dictionary size), or "auto" (default:
    resident while it fits); ``num_buffers`` (DMA ladder depth) and
    ``skip_index`` (visit only tiles that can hit) tune the streamed
    sweep and are ignored elsewhere. The extended rule pool is not in
    the megakernel's candidate grid, so extended=True keeps the staged
    path and uses the megakernel's in-kernel sorted search for stage 5
    only.
    """
    if backend == "fused" and not extended:
        from repro.kernels import ops  # lazy: kernels depend on core

        # pass roots through unchanged: a ResolvedRootDict handle keeps
        # its pinned residency and prebuilt tile stream
        return ops.extract_roots_fused(words, roots, infix=infix,
                                       residency=residency,
                                       num_buffers=num_buffers,
                                       skip_index=skip_index)

    roots, residency, _ = unwrap_dict(roots, residency)
    tri, tri_valid, quad, quad_valid = generate_stems(words)
    infix_codes = jnp.asarray(ab.INFIX_CODES)

    groups = []  # (stems[B,6,4], valid[B,6], dict, src_tag)
    groups.append((tri, tri_valid, roots.tri, pyref.SRC_TRI))
    groups.append((quad, quad_valid, roots.quad, pyref.SRC_QUAD))
    if infix:
        restored = tri.at[..., 1].set(
            jnp.where(tri[..., 1] == ab.ALEF, ab.WAW, tri[..., 1])
        )
        r_valid = tri_valid & (tri[..., 1] == ab.ALEF)
        groups.append((restored, r_valid, roots.tri, pyref.SRC_RESTORED))

        is_inf_q = (quad[..., 1:2] == infix_codes).any(-1)
        deinf_q = jnp.stack(
            [quad[..., 0], quad[..., 2], quad[..., 3], jnp.zeros_like(quad[..., 0])],
            axis=-1,
        )
        groups.append((deinf_q, quad_valid & is_inf_q, roots.tri, pyref.SRC_DEINFIX_TRI))

        is_inf_t = (tri[..., 1:2] == infix_codes).any(-1)
        deinf_t = jnp.stack(
            [tri[..., 0], tri[..., 2], jnp.zeros_like(tri[..., 0]),
             jnp.zeros_like(tri[..., 0])],
            axis=-1,
        )
        groups.append((deinf_t, tri_valid & is_inf_t, roots.bi, pyref.SRC_DEINFIX_BI))

    if extended:  # beyond-paper rule pool (paper §7 future work)
        defect = tri.at[..., 2].set(
            jnp.where(tri[..., 2] == pyref.ALEF_MAQSURA, ab.YEH, tri[..., 2]))
        d_valid = tri_valid & (tri[..., 2] == pyref.ALEF_MAQSURA)
        groups.append((defect, d_valid, roots.tri, pyref.SRC_EXT_DEFECTIVE))

        hollow_y = tri.at[..., 1].set(
            jnp.where(tri[..., 1] == ab.ALEF, ab.YEH, tri[..., 1]))
        hy_valid = tri_valid & (tri[..., 1] == ab.ALEF)
        groups.append((hollow_y, hy_valid, roots.tri, pyref.SRC_EXT_HOLLOW_Y))

    all_stems = jnp.concatenate([g[0] for g in groups], axis=1)   # [B, 6G, 4]
    all_valid = jnp.concatenate([g[1] for g in groups], axis=1)   # [B, 6G]
    # One fused match per dictionary (tri dict serves groups 1/3/4).
    hits = []
    for stems, valid, dict_keys, _src in groups:
        keys = pack_keys(stems)
        hits.append(_match(keys, dict_keys, backend) & valid)
    all_hits = jnp.concatenate(hits, axis=1)

    first = jnp.argmax(all_hits, axis=1)                          # first True
    found = all_hits.any(axis=1)
    root = jnp.take_along_axis(all_stems, first[:, None, None], axis=1)[:, 0]
    root = jnp.where(found[:, None], root, 0)
    src_tags = jnp.asarray(
        np.repeat([g[3] for g in groups], N_CAND).astype(np.int32)
    )
    source = jnp.where(found, src_tags[first], pyref.SRC_NONE)
    return root, source


# ---------------------------------------------------------------------------
# The paper's three execution models — contract-identical signatures: each
# accepts the full (infix, backend, extended, residency) option set.
# ---------------------------------------------------------------------------
def stem_batch(words, roots, *, infix=True, backend="sorted", extended=False,
               residency="auto", num_buffers=2, skip_index=True):
    """'Non-pipelined processor' analogue: whole batch through all stages."""
    return extract_roots(words, roots, infix=infix, backend=backend,
                         extended=extended, residency=residency,
                         num_buffers=num_buffers, skip_index=skip_index)


@functools.partial(jax.jit, static_argnames=("infix", "backend", "extended",
                                             "residency", "num_buffers",
                                             "skip_index"))
def stem_sequential(words, roots, *, infix=True, backend="sorted",
                    extended=False, residency="auto", num_buffers=2,
                    skip_index=True):
    """'Software implementation' analogue: one word at a time (lax.scan)."""

    def step(carry, w):
        r, s = extract_roots(w[None], roots, infix=infix, backend=backend,
                             extended=extended, residency=residency,
                             num_buffers=num_buffers, skip_index=skip_index)
        return carry, (r[0], s[0])

    _, (root, source) = jax.lax.scan(step, 0, words)
    return root, source


def stem_pipelined(words, roots, *, infix=True, backend="sorted",
                   extended=False, residency="auto", num_buffers=2,
                   skip_index=True, microbatch=256):
    """'Pipelined processor' analogue on one host: microbatched streaming.

    On real hardware the per-microbatch stages overlap via async dispatch;
    across devices use repro.dist.pipeline.pipeline_map. Numerically
    identical to stem_batch.
    """
    b = words.shape[0]
    pad = (-b) % microbatch
    wp = jnp.pad(words, ((0, pad), (0, 0)))
    chunks = wp.reshape(-1, microbatch, words.shape[1])
    outs = [stem_batch(c, roots, infix=infix, backend=backend,
                       extended=extended, residency=residency,
                       num_buffers=num_buffers, skip_index=skip_index)
            for c in chunks]
    root = jnp.concatenate([o[0] for o in outs])[:b]
    source = jnp.concatenate([o[1] for o in outs])[:b]
    return root, source
