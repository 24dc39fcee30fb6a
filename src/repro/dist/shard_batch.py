"""Data-sharded megakernel launches: one super-tile per tick, split
across a mesh axis.

The paper scales the pipelined processor by adding parallel hardware;
the serving analogue is a *data* axis: one ``[n_dev * block_b, 16]``
super-tile per launch, ``shard_map`` slicing it into per-device
``[block_b, 16]`` tiles that run :func:`kernels.stem_fused.
stem_fused_pallas` concurrently, with the packed dictionaries
replicated on every device. The StemmerWorkload dispatch path selects
this with ``data_devices=N`` (see serve/engine.py); standalone callers
get the same contract as ``ops.extract_roots_fused`` — bit-identical to
``core.stemmer.stem_batch``, ragged batches padded and sliced back.

The jitted body is keyed on the (hashable) Mesh plus the kernel's
static config, so serving replays one trace per (mesh, tile shape,
dictionary shape, residency) — a dictionary hot swap with matching
shapes never re-traces, exactly as on the single-device path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import stemmer as core_stemmer
from repro.kernels import stem_fused as sf


def device_downshift_ladder(n_dev: int) -> list[int]:
    """Data-device counts the degradation ladder reshards through:
    ``n_dev`` halving down to 1, descending.

    Any count d <= n_dev serves bit-identically — :func:`shard_batch`
    pads each launch to ``d * block_b`` and the per-word kernel output
    is independent of tile packing — so mid-stream resharding (a device
    lost from the mesh, sustained faults) only changes throughput,
    never results. Halving keeps the rung count logarithmic and every
    rung a divisor-friendly mesh shape.
    """
    if n_dev < 1:
        raise ValueError(f"n_dev must be >= 1, got {n_dev}")
    out, d = [], n_dev
    while d > 1:
        out.append(d)
        d //= 2
    out.append(1)
    return out


def mesh_axis_size(mesh, axis: str) -> int:
    """Size of ``axis`` in ``mesh`` (duck-typed via sharding.axis_sizes)."""
    from repro.dist import sharding

    sizes = sharding.axis_sizes(mesh)
    if axis not in sizes:
        raise ValueError(
            f"mesh has no axis {axis!r} (axes: {tuple(mesh.axis_names)})")
    return int(sizes[axis])


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "infix", "match", "block_b",
                     "residency", "dict_block_r", "num_buffers",
                     "skip_index", "visit_budget", "with_checksum",
                     "interpret"))
def _shard_call(words, roots, *, mesh, axis, infix, match, block_b,
                residency, dict_block_r, num_buffers, skip_index,
                visit_budget, with_checksum, interpret):
    n_dev = mesh_axis_size(mesh, axis)
    b = words.shape[0]
    pad = (-b) % (n_dev * block_b)
    wp = jnp.pad(words, ((0, pad), (0, 0)))

    def local(w, r):
        return sf.stem_fused_pallas(
            w, r, infix=infix, match=match, block_b=block_b,
            residency=residency, dict_block_r=dict_block_r,
            num_buffers=num_buffers, skip_index=skip_index,
            visit_budget=visit_budget, interpret=interpret)

    f = jax.shard_map(local, mesh=mesh, in_specs=(P(axis), P()),
                      out_specs=(P(axis), P(axis)), check_vma=False)
    root, source = f(wp, roots)
    root, source = root[:b], source[:b]
    if with_checksum:
        # retire-side integrity row, traced into the SAME program as the
        # sharded launch (b must be a multiple of block_b — the serving
        # ring's bucketed tiles always are)
        from repro.kernels.ops import _checksum_rows  # lazy: no cycle

        return root, source, _checksum_rows(root, source, block_b)
    return root, source


def shard_batch(words, roots, mesh, *, axis: str = "data",
                infix: bool = True, match: str = "bsearch",
                block_b: int = 256, residency: str = "auto",
                dict_block_r: int = 8, num_buffers: int = 2,
                skip_index: bool = True, visit_budget: int | None = None,
                with_checksum: bool = False, interpret: bool = False):
    """words int32[B,16] -> (root int32[B,4], source int32[B]), B split
    over ``mesh[axis]``.

    Same contract as ``ops.extract_roots_fused`` — including megabatches:
    each device's shard runs the whole grid-over-queue batch axis over
    its ``B / n_dev`` slice (chunked against ``visit_budget`` on the
    streamed path), so one sharded launch retires
    ``n_dev x megabatch_tiles`` queue tiles. ``roots`` accepts plain
    RootDictArrays or a pre-resolved ``ResolvedRootDict`` handle (the
    serving path — its pinned residency wins and its prebuilt tile
    stream replicates to every device, so hot swaps with matching shapes
    replay the cached trace). B is padded up to a multiple of
    ``n_dev * block_b`` and sliced back, so ragged final super-tiles are
    valid.
    """
    arrays, residency, _ = core_stemmer.unwrap_dict(roots, residency)
    residency = sf.choose_residency(arrays, residency, infix=infix)
    # roots passes through unchanged so a handle keeps its tile stream
    return _shard_call(words, roots, mesh=mesh, axis=axis, infix=infix,
                       match=match, block_b=block_b, residency=residency,
                       dict_block_r=dict_block_r, num_buffers=num_buffers,
                       skip_index=skip_index, visit_budget=visit_budget,
                       with_checksum=with_checksum, interpret=interpret)
