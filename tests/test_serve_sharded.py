"""Sharded serving-path tests (4 forced host devices via subprocess —
the main pytest session must keep the default single device).

Covers dist.shard_batch parity (full and ragged super-tiles) against
stem_batch / the single-device megakernel, StemmerWorkload
``data_devices=4`` serving through the dispatch/retire ring, a
dictionary hot swap landing while sharded super-tiles are in flight,
a journaled 4-device kill/warm-restart, and an injected device loss
downshifting the degradation ladder onto a smaller mesh.
CI runs this file as its forced-4-device step.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.dist import mesh_axis_size

REPO = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import corpus, stemmer
    from repro.dist import shard_batch
    from repro.kernels import ops
    from repro.launch import mesh as mesh_mod
    from repro.serve import DictStore, Engine, StemmerWorkload

    assert len(jax.devices()) == 4
    mesh = mesh_mod.make_data_mesh(4)
    d = corpus.build_dictionary(n_tri=400, n_quad=60, seed=0)
    arrays = stemmer.RootDictArrays.from_rootdict(d)
    words, _, _ = corpus.build_corpus(n_words=200, seed=1)
    enc = corpus.encode_corpus(words)

    # --- shard_batch parity: full super-tile and ragged batches -------
    for n in (128, 100, 7):          # 4*32 exact | ragged | < one tile
        got_r, got_s = shard_batch(jnp.asarray(enc[:n]), arrays, mesh,
                                   block_b=32, interpret=True)
        want_r, want_s = stemmer.stem_batch(jnp.asarray(enc[:n]), arrays)
        np.testing.assert_array_equal(np.asarray(got_r), np.asarray(want_r))
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
        # and identical to the single-device megakernel launch
        one_r, one_s = ops.extract_roots_fused(jnp.asarray(enc[:n]), arrays,
                                               block_b=32)
        np.testing.assert_array_equal(np.asarray(got_r), np.asarray(one_r))
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(one_s))
    print("SHARD_BATCH_PARITY_OK")

    # --- streamed pipeline knobs across the mesh: a resolved handle with
    # publish-time tile/boundary tables, DMA ladder depth, skip on/off --
    grown = corpus.grow_root_arrays(arrays, 100_000, seed=3)
    handle = stemmer.resolve_dict(grown, dict_block_r=8)
    assert handle.residency == "streamed" and handle.tiles is not None
    want_r, want_s = stemmer.stem_batch(jnp.asarray(enc[:128]), grown)
    for nb, sk in ((1, True), (2, True), (2, False)):
        got_r, got_s = shard_batch(jnp.asarray(enc[:128]), handle, mesh,
                                   block_b=32, num_buffers=nb,
                                   skip_index=sk, interpret=True)
        np.testing.assert_array_equal(np.asarray(got_r), np.asarray(want_r))
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    print("SHARD_PIPELINE_KNOBS_OK")

    # --- sharded serving: super-tile coalescing through the ring ------
    store = DictStore(arrays)
    eng = Engine(StemmerWorkload(store, block_b=16, data_devices=4,
                                 max_inflight=2, megabatch_tiles=1))
    sizes = (37, 64, 5, 50)          # 156 words, super_b=64 -> 3 launches
    off, rids = 0, []
    for n in sizes:
        rids.append(eng.submit(enc[off:off + n])); off += n
    rep = eng.run_until_drained()
    assert rep.drained
    assert eng.workload.super_b == 64
    assert eng.workload.ticks_launched == -(-sum(sizes) // 64)
    want_r, want_s = stemmer.stem_batch(jnp.asarray(enc[:sum(sizes)]),
                                        arrays)
    got_r = np.concatenate([eng.result(r).roots for r in rids])
    got_s = np.concatenate([eng.result(r).sources for r in rids])
    np.testing.assert_array_equal(got_r, np.asarray(want_r))
    np.testing.assert_array_equal(got_s, np.asarray(want_s))
    assert all((eng.result(r).dict_versions == 0).all() for r in rids)
    print("SHARD_SERVE_PARITY_OK")

    # --- hot swap landing while sharded super-tiles are in flight -----
    store = DictStore(arrays)
    grown = corpus.grow_root_arrays(arrays, 2048, seed=7)
    eng = Engine(StemmerWorkload(store, block_b=16, data_devices=4,
                                 max_inflight=2, megabatch_tiles=1))
    rids = [eng.submit(enc[i * 32:(i + 1) * 32]) for i in range(6)]
    eng.step()                       # 2 super-tiles (128 words) in flight
    assert eng.workload.ticks_launched == 2
    v1 = store.publish(grown)
    rep = eng.run_until_drained()
    assert rep.drained and v1 == 1
    versions = np.concatenate([eng.result(r).dict_versions for r in rids])
    np.testing.assert_array_equal(versions[:128], 0)   # pinned at dispatch
    np.testing.assert_array_equal(versions[128:], 1)
    got_r = np.concatenate([eng.result(r).roots for r in rids])
    for v, sl in ((0, slice(0, 128)), (1, slice(128, 192))):
        want_r, _ = stemmer.stem_batch(jnp.asarray(enc[sl]),
                                       store.get(v).arrays)
        np.testing.assert_array_equal(got_r[sl], np.asarray(want_r))
    print("SHARD_SWAP_OK")

    # --- sharded megabatch: one launch spans megabatch_tiles super-tiles
    # across the mesh, bit-identical to the per-super-tile path ---------
    store = DictStore(arrays)
    eng = Engine(StemmerWorkload(store, block_b=16, data_devices=4,
                                 megabatch_tiles=2, max_inflight=1))
    sizes = (37, 64, 5, 50)          # 156 words, launch_b=128 -> 2 launches
    off, rids = 0, []
    for n in sizes:
        rids.append(eng.submit(enc[off:off + n])); off += n
    rep = eng.run_until_drained()
    assert rep.drained
    assert eng.workload.launch_b == 128
    assert eng.workload.ticks_launched == 2   # vs 3 per-super-tile above
    want_r, want_s = stemmer.stem_batch(jnp.asarray(enc[:sum(sizes)]),
                                        arrays)
    got_r = np.concatenate([eng.result(r).roots for r in rids])
    got_s = np.concatenate([eng.result(r).sources for r in rids])
    np.testing.assert_array_equal(got_r, np.asarray(want_r))
    np.testing.assert_array_equal(got_s, np.asarray(want_s))
    print("SHARD_MEGABATCH_OK")

    # --- text workload over the mesh: raw documents through the sharded
    # super-tile ring, bit-identical to the host pipeline --------------
    from repro.core import textnorm as tn
    from repro.launch.serve import build_documents
    from repro.serve import TextAnalysisWorkload

    store = DictStore(arrays)
    eng = Engine(TextAnalysisWorkload(store, block_b=16, data_devices=4,
                                      char_block=256, megabatch_tiles=2,
                                      max_inflight=2))
    docs = build_documents(4, 40, seed=2)
    rids = [eng.submit([d]) for d in docs]
    rep = eng.run_until_drained()
    assert rep.drained
    for rid, doc in zip(rids, docs):
        req = eng.result(rid)
        want_w, want_spans = tn.analyze_text_py(doc)
        np.testing.assert_array_equal(req.words, want_w)
        np.testing.assert_array_equal(req.spans, want_spans)
        want_r, want_s = stemmer.stem_batch(jnp.asarray(want_w), arrays)
        np.testing.assert_array_equal(req.roots, np.asarray(want_r))
        np.testing.assert_array_equal(req.sources, np.asarray(want_s))
    print("TEXT_SHARD_OK")

    # --- sharded retry parity: an injected launch failure on the first
    # sharded dispatch is retried and the drain stays bit-identical ----
    from repro.serve import FaultInjector, FaultPlan, FaultSpec

    inj = FaultInjector(FaultPlan(specs=(FaultSpec("dispatch", at=0),)))
    store = DictStore(arrays)
    eng = Engine(StemmerWorkload(store, block_b=16, data_devices=4,
                                 max_inflight=2, injector=inj))
    sizes = (37, 64, 5, 50)
    off, rids = 0, []
    for n in sizes:
        rids.append(eng.submit(enc[off:off + n])); off += n
    rep = eng.run_until_drained()
    assert rep.drained
    assert eng.workload.retries_total == 1
    assert inj.fired == [("dispatch", "fail", 0)]
    want_r, want_s = stemmer.stem_batch(jnp.asarray(enc[:sum(sizes)]),
                                        arrays)
    got_r = np.concatenate([eng.result(r).roots for r in rids])
    got_s = np.concatenate([eng.result(r).sources for r in rids])
    np.testing.assert_array_equal(got_r, np.asarray(want_r))
    np.testing.assert_array_equal(got_s, np.asarray(want_s))
    assert all(eng.result(r).failure is None for r in rids)
    print("SHARD_RETRY_OK")

    # --- 4-device warm restart: a journaled sharded engine killed after
    # one super-tile tick recovers from the WAL and the merged
    # (pre-crash + replayed) outputs are bit-identical -----------------
    import tempfile
    from repro.serve import DegradationPolicy, Journal

    jp = os.path.join(tempfile.mkdtemp(), "wal.jsonl")
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=16,
                                 data_devices=4, max_inflight=1),
                 journal=Journal(jp, fsync_every=1))
    rids = [eng.submit(enc[i * 32:(i + 1) * 32]) for i in range(6)]
    eng.step()                       # one sharded tick, then "crash"
    done = {r: eng.result(r) for r in rids if eng.result(r) is not None}
    eng2 = Engine.recover(jp, StemmerWorkload(DictStore(arrays),
                                              block_b=16, data_devices=4,
                                              max_inflight=1))
    assert eng2.run_until_drained().drained
    assert set(eng2.recovery.replayed) == {r for r in rids
                                           if r not in done}
    merged = np.concatenate([(done.get(r) or eng2.result(r)).roots
                             for r in rids])
    want_r, _ = stemmer.stem_batch(jnp.asarray(enc[:192]), arrays)
    np.testing.assert_array_equal(merged, np.asarray(want_r))
    print("SHARD_RECOVER_OK")

    # --- device loss under the ladder: an injected DeviceLost on the
    # first sharded launch downshifts to fewer data devices (capped —
    # a lost device does not come back) and the drain, re-served on the
    # smaller mesh, stays bit-identical --------------------------------
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("device_loss", at=0),)))
    pol = DegradationPolicy(down_after=1)
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=16,
                                 data_devices=4, max_inflight=1,
                                 injector=inj), policy=pol)
    rids = [eng.submit(enc[i * 32:(i + 1) * 32]) for i in range(6)]
    assert eng.run_until_drained().drained
    eng.step()                       # a requested mode lands at an
    assert eng.workload.device_losses == 1      # empty-ring tick
    assert any(t[2] == "device_loss" for t in pol.transitions)
    assert eng.workload.data_devices < 4
    got_r = np.concatenate([eng.result(r).roots for r in rids])
    np.testing.assert_array_equal(got_r, np.asarray(want_r))
    assert all(eng.result(r).failure is None for r in rids)
    print("SHARD_DEVICE_LOSS_OK")
""")


def test_sharded_serve_four_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    for marker in ("SHARD_BATCH_PARITY_OK", "SHARD_PIPELINE_KNOBS_OK",
                   "SHARD_SERVE_PARITY_OK", "SHARD_SWAP_OK",
                   "SHARD_MEGABATCH_OK", "TEXT_SHARD_OK",
                   "SHARD_RETRY_OK", "SHARD_RECOVER_OK",
                   "SHARD_DEVICE_LOSS_OK"):
        assert marker in proc.stdout, proc.stderr[-2000:]


# ---------------------------------------------------------------------------
# in-process validation (no multi-device requirements)
# ---------------------------------------------------------------------------
class FakeMesh:
    def __init__(self, sizes):
        import numpy as np

        self.axis_names = tuple(sizes)
        self.devices = np.empty(tuple(sizes.values()))


def test_mesh_axis_size_resolves_and_rejects():
    mesh = FakeMesh({"data": 4, "model": 2})
    assert mesh_axis_size(mesh, "data") == 4
    with pytest.raises(ValueError, match="no axis"):
        mesh_axis_size(mesh, "stage")


def test_workload_rejects_unavailable_devices():
    """data_devices beyond the backend's device count fails at
    construction, not at first launch (main session has one device)."""
    import jax

    from repro.core import corpus, stemmer
    from repro.serve import DictStore, StemmerWorkload

    d = corpus.build_dictionary(n_tri=50, n_quad=10, seed=0)
    store = DictStore(stemmer.RootDictArrays.from_rootdict(d))
    too_many = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="devices"):
        StemmerWorkload(store, data_devices=too_many)
    with pytest.raises(ValueError, match="max_inflight"):
        StemmerWorkload(store, max_inflight=0)
    with pytest.raises(ValueError, match="data_devices"):
        StemmerWorkload(store, data_devices=0)
