"""Device-pipelined execution of the paper's five stemmer stages.

The paper's pipelined FPGA processor (Fig 15) overlaps the five stages on
one word stream: while stage 5 compares word t, stage 1 is already
checking word t+4, giving the 28873x pipelined speedup. On a JAX device
mesh the analogue is one *stage per device* along a mesh axis:
microbatches flow stage-to-stage via ``ppermute`` in a software-pipelined
(skewed) loop of ``m + S - 1`` ticks, so all S devices are busy once the
pipeline fills.

``pipeline_map`` is generic over any list of bundle -> bundle stage
functions (the bundle pytree structure must be invariant, mirroring the
FPGA's fixed inter-stage registers). ``stemmer_stage_fns`` provides the
canonical 5-stage split of the stemmer matching the paper's datapath:
candidates / tri-compare / quad-compare / bi-compare / priority-select.

On a single host this degrades gracefully: with forced host devices
(XLA_FLAGS=--xla_force_host_platform_device_count=S) the same SPMD
program runs as a software pipeline — numerically identical to
``core.stemmer.stem_batch``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import stemmer
from repro.kernels import ref as kref

N_SLOTS = 30  # 5 groups x 6 candidates (stem_datapath layout)


def pipeline_map(stage_fns, bundle, mesh, axis: str = "stage"):
    """Run ``stage_fns[s]`` on device s of ``mesh[axis]``, streaming the
    leading (microbatch) dimension of ``bundle`` through the stages.

    bundle: pytree of arrays with identical leading dim m (microbatches).
    Each stage fn maps a one-microbatch bundle (leading dim dropped) to a
    bundle of the same structure. Returns the bundle after all stages,
    replicated across the mesh.
    """
    stage_fns = list(stage_fns)
    s_count = len(stage_fns)
    sizes = dict(zip(mesh.axis_names, np.shape(mesh.devices)))
    if sizes.get(axis) != s_count:
        raise ValueError(
            f"mesh axis {axis!r} has size {sizes.get(axis)}, need {s_count}")
    leaves = jax.tree.leaves(bundle)
    m = leaves[0].shape[0]

    def body(bundle):
        idx = jax.lax.axis_index(axis)
        state0 = jax.tree.map(lambda x: jnp.zeros_like(x[0]), bundle)
        outs0 = jax.tree.map(jnp.zeros_like, bundle)

        def tick(t, carry):
            state, outs = carry
            # stage 0 ingests microbatch t (clipped index; drained ticks
            # produce values that are never emitted)
            fresh = jax.tree.map(
                lambda x: x[jnp.clip(t, 0, m - 1)], bundle)
            state = jax.tree.map(
                lambda f, s: jnp.where(idx == 0, f, s), fresh, state)
            state = jax.lax.switch(idx, stage_fns, state)
            # the last stage emits microbatch t - (S-1) once the pipe fills
            t_out = t - (s_count - 1)
            emit = (idx == s_count - 1) & (t_out >= 0)
            j = jnp.clip(t_out, 0, m - 1)
            outs = jax.tree.map(
                lambda o, s: o.at[j].set(jnp.where(emit, s, o[j])),
                outs, state)
            # hand this stage's result to the next stage for tick t+1
            perm = [(i, (i + 1) % s_count) for i in range(s_count)]
            state = jax.tree.map(
                lambda x: jax.lax.ppermute(x, axis, perm), state)
            return state, outs

        _, outs = jax.lax.fori_loop(0, m + s_count - 1, tick, (state0, outs0))
        # results live on the last stage only; psum replicates them
        return jax.tree.map(
            lambda x: jax.lax.psum(
                jnp.where(idx == s_count - 1, x, jnp.zeros_like(x)), axis),
            outs)

    f = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)
    return f(bundle)


def _slot_mask(groups) -> np.ndarray:
    mask = np.zeros(32, bool)
    for g in groups:
        mask[g * 6 : (g + 1) * 6] = True
    return mask


def _streamed_match_sorted(keys, dict_keys, chunk_keys: int):
    """OR-accumulating chunked sorted match: the jnp analogue of the
    megakernel's streamed Compare path (stem_fused._fused_pipeline_kernel).

    The sorted dictionary is swept in ``chunk_keys``-sized sentinel-padded
    tiles (each tile stays sorted, so per-tile searchsorted is exact) while
    the candidate keys stay live — on a device this bounds the Compare
    stage's working set the same way the kernel's tile-visit sweep does.
    """
    from repro.kernels import stem_match as sm  # sentinel constant only

    r = dict_keys.shape[0]
    n_tiles = max(1, -(-r // chunk_keys))
    padded = jnp.pad(dict_keys, (0, n_tiles * chunk_keys - r),
                     constant_values=sm.DICT_SENTINEL)

    def tick(t, acc):
        tile = jax.lax.dynamic_slice(padded, (t * chunk_keys,), (chunk_keys,))
        return acc | stemmer.match_sorted(keys, tile)

    return jax.lax.fori_loop(0, n_tiles, tick,
                             jnp.zeros(keys.shape, bool))


def stemmer_stage_fns(roots: "stemmer.RootDictArrays", *,
                      residency: str = "auto", chunk_keys: int = 1 << 14):
    """The paper's 5-stage split over a bundle of
    {words[mb,16], keys[mb,32], valid[mb,32], root[mb,4], source[mb]}.

    Stage 1 runs the character datapath (stages 1-4 of the paper fused,
    as in the Pallas datapath kernel); stages 2-4 are the Compare stage
    split per dictionary (tri / quad / bi comparator banks — ``valid``
    doubles as the running hit mask, the FPGA's inter-stage flag
    register); stage 5 is the priority select.

    residency mirrors the megakernel policy (DESIGN.md §5.3): "resident"
    matches against the whole dictionary at once, "streamed" sweeps it in
    ``chunk_keys``-sized tiles with an OR-accumulating hit mask, "auto"
    (default) streams any dictionary larger than ``chunk_keys``.
    """
    if residency not in ("resident", "streamed", "auto"):
        raise ValueError(f"unknown residency: {residency!r}")
    tri_mask = jnp.asarray(_slot_mask((0, 2, 3)))   # tri, restored, deinf-quad
    quad_mask = jnp.asarray(_slot_mask((1,)))
    bi_mask = jnp.asarray(_slot_mask((4,)))

    def candidates(b):
        keys, valid = kref.stem_datapath_ref(b["words"])
        return {**b, "keys": keys, "valid": valid}

    def compare(dict_keys, mask):
        streamed = residency == "streamed" or (
            residency == "auto" and dict_keys.shape[0] > chunk_keys)

        def fn(b):
            if streamed:
                hit = _streamed_match_sorted(b["keys"], dict_keys, chunk_keys)
            else:
                hit = stemmer.match_sorted(b["keys"], dict_keys)
            valid = jnp.where(mask[None, :], b["valid"] * hit, b["valid"])
            return {**b, "valid": valid.astype(jnp.int32)}
        return fn

    def select(b):
        hits = b["valid"][:, :N_SLOTS] > 0
        first = jnp.argmax(hits, axis=1)
        found = hits.any(axis=1)
        chosen = jnp.take_along_axis(b["keys"], first[:, None], 1)[:, 0]
        root = jnp.where(
            found[:, None],
            jnp.stack([(chosen >> 18) & 63, (chosen >> 12) & 63,
                       (chosen >> 6) & 63, chosen & 63], axis=1), 0)
        tags = jnp.asarray(
            [t for t in kref.GROUP_TAGS for _ in range(6)], jnp.int32)
        source = jnp.where(found, tags[first], 0)
        return {**b, "root": root, "source": source}

    return [
        candidates,
        compare(roots.tri, tri_mask),
        compare(roots.quad, quad_mask),
        compare(roots.bi, bi_mask),
        select,
    ]
