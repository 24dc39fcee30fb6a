"""Chaos smoke: every fault-injection site fired once, recovery verified.

Runs the seeded fault matrix end to end — one scenario per injector
site — and asserts the recovery invariant for each: a run that absorbs
the fault produces results bit-identical to the fault-free run (or, for
terminal faults, the correct structured FailureInfo), with no state
leaked into the serving engine, the dictionary store, or the index
checkpoint directory.

  dispatch    injected launch failure mid-ring -> retried, bit-identical
  retire      corrupted device readback -> checksum catch, redispatch,
              bit-identical
  publish     injected rejection between validation and the version
              bump -> store untouched, next publish lands, rollback
              restores the old lexicon as a new version
  checkpoint  torn index-partial write -> readback verify + rewrite,
              index bit-identical; plus a poison-pill request isolated
              by bisection quarantine while its tile-mates complete
  stall       wedged persistent descriptor ring -> watchdog abandons the
              launch, salvages the retired-prefix tiles, re-dispatches
              the rest down the megabatch path, bit-identical
  device_loss sharded launch loses a device -> the degradation ladder
              reshards onto fewer data devices (capped: the device does
              not come back), bit-identical
  journal     torn write-ahead-journal tail -> Engine.recover truncates
              to the last good record and replays the unfinished
              requests bit-identically (warm restart)

The script exits non-zero on any mismatch, so CI runs it as the chaos
step of the fault matrix.

  PYTHONPATH=src python examples/chaos_matrix.py
"""
import os

# the device_loss scenario reshards a 4-device mesh; force the host
# platform to expose 4 devices BEFORE jax initialises
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import tempfile
import time

import numpy as np

from repro.core import corpus, stemmer
from repro.index import builder
from repro.serve import (DegradationPolicy, DictStore, Engine,
                         FaultInjector, FaultPlan, FaultSpec,
                         InjectedFault, Journal, StemmerWorkload)

N_REQ = 8
WORDS_PER_REQ = 32
SEED = 20260809


def build_inputs():
    d = corpus.build_dictionary(n_tri=400, n_quad=60, seed=0)
    arrays = stemmer.RootDictArrays.from_rootdict(d)
    words, _, _ = corpus.build_corpus(n_words=N_REQ * WORDS_PER_REQ, seed=1)
    return arrays, corpus.encode_corpus(words)


def drain(arrays, enc, *, injector=None, **kw):
    # one tile a launch: the 8-tile queue then takes several launches,
    # so a fault on one of them lands mid-ring
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32,
                                 max_inflight=2, megabatch_tiles=1,
                                 injector=injector, **kw))
    rids = [eng.submit(enc[i * WORDS_PER_REQ:(i + 1) * WORDS_PER_REQ])
            for i in range(N_REQ)]
    assert eng.run_until_drained().drained
    return eng, rids


def check_identical(eng, rids, baseline, skip=()):
    for i, rid in enumerate(rids):
        req = eng.result(rid)
        if i in skip:
            continue
        assert req.failure is None, f"req {rid}: {req.failure}"
        np.testing.assert_array_equal(req.roots, baseline[i])
        np.testing.assert_array_equal(req.sources, baseline[i + N_REQ])


def main():
    arrays, enc = build_inputs()
    eng0, rids0 = drain(arrays, enc)
    baseline = ([np.array(eng0.result(r).roots) for r in rids0]
                + [np.array(eng0.result(r).sources) for r in rids0])

    # --- site dispatch: launch failure retried ------------------------
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("dispatch", at=1),),
                                  seed=SEED))
    eng, rids = drain(arrays, enc, injector=inj)
    assert inj.fired == [("dispatch", "fail", 1)], inj.fired
    assert eng.workload.retries_total == 1
    check_identical(eng, rids, baseline)
    print("CHAOS_DISPATCH_OK")

    # --- site retire: corrupted readback caught by checksum -----------
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("retire", at=0),),
                                  seed=SEED))
    eng, rids = drain(arrays, enc, injector=inj)
    assert eng.workload.checksum_failures == 1
    check_identical(eng, rids, baseline)
    print("CHAOS_RETIRE_OK")

    # --- site publish: two-phase publish rejected, then rollback ------
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("publish", at=0),),
                                  seed=SEED))
    store = DictStore(arrays, keep_history=True, injector=inj)
    v0 = store.version
    grown = corpus.grow_root_arrays(arrays, 2048, seed=7)
    try:
        store.publish(grown)
        raise AssertionError("injected publish rejection did not fire")
    except InjectedFault:
        pass
    assert store.version == v0          # phase 2 never ran
    v1 = store.publish(grown)           # next publish lands cleanly
    v2 = store.rollback(v0)             # restore as a NEW version
    assert v2 > v1 > v0
    np.testing.assert_array_equal(
        np.asarray(store.acquire().handle.arrays.tri),
        np.asarray(store.get(v0).handle.arrays.tri))
    print("CHAOS_PUBLISH_OK")

    # --- site checkpoint: torn partial rewritten, index identical -----
    table = corpus.build_token_table(forms_per_root=6)

    def stream():
        return corpus.stream_corpus_words(9000, seed=3, chunk_words=4096,
                                          table=table)

    ref = builder.build_corpus_index(stream(), arrays, block_b=512,
                                     block_w=512)
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("checkpoint", at=1),),
                                  seed=SEED))
    with tempfile.TemporaryDirectory() as td:
        idx = builder.build_corpus_index(stream(), arrays,
                                         checkpoint_dir=td, block_b=512,
                                         block_w=512, injector=inj)
    assert inj.fired == [("checkpoint", "tear", 1)], inj.fired
    np.testing.assert_array_equal(np.asarray(idx.counts),
                                  np.asarray(ref.counts))
    np.testing.assert_array_equal(np.asarray(idx.docs),
                                  np.asarray(ref.docs))
    np.testing.assert_array_equal(np.asarray(idx.positions),
                                  np.asarray(ref.positions))
    print("CHAOS_CHECKPOINT_OK")

    # --- poison pill: bisection quarantine, tile-mates complete -------
    inj = FaultInjector(FaultPlan(poison_rids=frozenset({2}), seed=SEED))
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=128,
                                 max_inflight=1, max_retries=1,
                                 injector=inj))
    rids = [eng.submit(enc[i * WORDS_PER_REQ:(i + 1) * WORDS_PER_REQ])
            for i in range(4)]
    assert eng.run_until_drained().drained
    assert eng.workload.quarantined == 1
    bad = eng.result(rids[2])
    assert bad.failure is not None and bad.failure.code == "quarantined"
    for i in (0, 1, 3):
        req = eng.result(rids[i])
        assert req.failure is None
        np.testing.assert_array_equal(req.roots, baseline[i])
    print("CHAOS_QUARANTINE_OK")

    # unknown sites are rejected at PLAN construction, not at fire time
    try:
        FaultSpec("gpu")
        raise AssertionError("unknown fault site accepted")
    except ValueError:
        pass

    # --- site stall: wedged persistent ring, watchdog salvage ---------
    inj = FaultInjector(FaultPlan(
        specs=(FaultSpec("stall", at=0, retired_tiles=2),), seed=SEED))
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32,
                                 max_inflight=1, persistent=True,
                                 megabatch_tiles=4, watchdog_s=0.05,
                                 injector=inj))
    rids = [eng.submit(enc[i * WORDS_PER_REQ:(i + 1) * WORDS_PER_REQ])
            for i in range(N_REQ)]
    assert eng.run_until_drained().drained
    assert eng.workload.watchdog_stalls == 1
    stalls = [e for e in eng.events() if e.kind == "watchdog_stall"]
    assert len(stalls) == 1 and stalls[0].data["salvaged_words"] == 64
    check_identical(eng, rids, baseline)
    print("CHAOS_STALL_OK")

    # --- site device_loss: ladder reshards onto fewer devices ---------
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("device_loss", at=0),),
                                  seed=SEED))
    pol = DegradationPolicy(down_after=1)
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32,
                                 max_inflight=2, data_devices=4,
                                 injector=inj), policy=pol)
    rids = [eng.submit(enc[i * WORDS_PER_REQ:(i + 1) * WORDS_PER_REQ])
            for i in range(N_REQ)]
    assert eng.run_until_drained().drained
    assert eng.workload.device_losses == 1
    assert any(t[2] == "device_loss" and t[1].startswith("devices-")
               for t in pol.transitions), pol.transitions
    eng.step()               # a requested mode lands at an empty-ring tick
    assert eng.workload.data_devices < 4      # resharded
    check_identical(eng, rids, baseline)
    print("CHAOS_DEVICE_LOSS_OK")

    # --- site journal: torn WAL tail, warm restart bit-identical ------
    with tempfile.TemporaryDirectory() as td:
        jp = os.path.join(td, "wal.jsonl")
        # tear the 9th append — the first RETIRE record (events 0..7 are
        # the admits) — so one served request must be re-served on replay
        inj = FaultInjector(FaultPlan(
            specs=(FaultSpec("journal", at=N_REQ),), seed=SEED))
        eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32,
                                     max_inflight=2),
                     journal=Journal(jp, fsync_every=1, injector=inj))
        rids = [eng.submit(enc[i * WORDS_PER_REQ:(i + 1) * WORDS_PER_REQ])
                for i in range(N_REQ)]
        for _ in range(2):
            eng.step()                        # serve a little, then "crash"
        done_before = {r: eng.result(r) for r in rids
                       if eng.result(r) is not None}
        eng2 = Engine.recover(jp, StemmerWorkload(DictStore(arrays),
                                                  block_b=32,
                                                  max_inflight=2))
        assert eng2.recovery.dropped_bytes > 0     # the tear was truncated
        assert eng2.run_until_drained().drained
        for i, r in enumerate(rids):
            req = done_before.get(r) or eng2.result(r)
            assert req is not None and req.failure is None
            np.testing.assert_array_equal(req.roots, baseline[i])
    print("CHAOS_JOURNAL_OK")


if __name__ == "__main__":
    t0 = time.time()
    main()
    print(f"CHAOS_MATRIX_OK ({time.time() - t0:.1f}s)")
