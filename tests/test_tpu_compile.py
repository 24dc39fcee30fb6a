"""Compile the main-path kernels for a described TPU v5e, without a chip.

Interpret mode (every other test) runs kernel bodies as jnp and cannot
see what Mosaic refuses: 1-D gathers, unaligned slices, VMEM overuse.
These tests lower and compile each kernel ahead of time for a ``v5e:2x2``
topology at the sizes ``chip_smoke.py`` serves, and assert the compiled
program holds the Pallas kernel (``tpu_custom_call``).

The topology is described only inside the fixture below: the TPU
library may be loaded by one process at a time, and describing it at
import would make the test workers collect different tests.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import corpus, stemmer
from repro.kernels import postings as pk
from repro.kernels import stem_fused as sf
from repro.kernels import text_frontend as tf

BLOCK_B = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def lexicons():
    """(resident, streamed) lexicons at chip_smoke.py's sizes."""
    d = corpus.build_dictionary(n_tri=5000, n_quad=500, seed=0)
    resident = stemmer.RootDictArrays.from_rootdict(d)
    return resident, corpus.grow_root_arrays(resident, 1 << 18)


def _specs(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("residency, persistent", [
    ("resident", False), ("streamed", False),
    ("resident", True), ("streamed", True)])
def test_stem_fused_compiles_for_v5e(one_chip, lexicons, residency,
                                     persistent):
    """The serving megakernel, match="bsearch", on a 4-tile megabatch."""
    resident, streamed = lexicons
    arrays = resident if residency == "resident" else streamed
    assert sf.choose_residency(arrays) == residency
    words = jax.ShapeDtypeStruct((4 * BLOCK_B, 16), jnp.int32,
                                 sharding=one_chip)
    _compile(lambda w, r: sf.stem_fused_pallas(
        w, r, block_b=BLOCK_B, persistent=persistent, interpret=False),
        words, _specs(arrays, one_chip))


def test_text_frontend_compiles_for_v5e(one_chip):
    """64 documents of 300 words: a 128K-codepoint tile."""
    t, wp = 1 << 17, 64 * 320
    chars = jax.ShapeDtypeStruct((t,), jnp.int32, sharding=one_chip)
    geo = jax.ShapeDtypeStruct((wp,), jnp.int32, sharding=one_chip)
    _compile(lambda c, s, n: tf.text_frontend_pallas(
        c, s, n, block_w=128, interpret=False), chars, geo, geo)


def test_postings_compiles_for_v5e(one_chip):
    """One 1M-word index chunk over a general dictionary's vocabulary."""
    ids = jax.ShapeDtypeStruct((1 << 20,), jnp.int32, sharding=one_chip)
    _compile(lambda i: pk.postings_pallas(i, n_roots=5600, block_w=2048,
                                          interpret=False), ids)


def test_shard_batch_compiles_for_v5e_2x2(topo, lexicons):
    """The 4-device serving launch (StemmerWorkload(data_devices=4))."""
    from repro.dist import shard_batch

    mesh = Mesh(np.array(topo.devices), ("data",),
                axis_types=(AxisType.Auto,))
    words = jax.ShapeDtypeStruct((4 * BLOCK_B, 16), jnp.int32,
                                 sharding=NamedSharding(mesh, P("data")))
    roots = _specs(lexicons[0], NamedSharding(mesh, P()))
    text = _compile(lambda w, r: shard_batch(w, r, mesh, block_b=BLOCK_B,
                                             interpret=False), words, roots)
    assert "num_partitions=4" in text
