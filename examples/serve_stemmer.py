"""Serve the stemmer megakernel behind the workload-agnostic Engine,
with a versioned dictionary hot swap landing mid-stream.

Word-batch requests coalesce into fixed [block_b, 16] tiles (one
megakernel launch per tick); after a few ticks a grown lexicon is
publish()ed and picked up by the next tile launch without an engine
restart. Every served word is then verified bit-identical to
core.stemmer.stem_batch under the dict version that served it — the
script exits non-zero on any mismatch, so CI runs it as a smoke test.

A second pass re-serves the same queue with one injected launch
failure and reads the recovery off ``Engine.events()`` — the
structured incident stream — instead of grepping workload counters.

  PYTHONPATH=src python examples/serve_stemmer.py
"""
import time

import jax.numpy as jnp
import numpy as np

from repro.core import corpus, stemmer
from repro.serve import (DictStore, Engine, FaultInjector, FaultPlan,
                         FaultSpec, StemmerWorkload)

N_REQUESTS, WORDS_PER_REQ, BLOCK_B = 12, 40, 64
# one tile a launch (the workload's default coalesces this whole queue
# into one launch): the swap and the injected fault then land mid-stream
MEGABATCH_TILES = 1


def main():
    d = corpus.build_dictionary(n_tri=800, n_quad=100, seed=0)
    arrays = stemmer.RootDictArrays.from_rootdict(d)
    store = DictStore(arrays)
    eng = Engine(StemmerWorkload(store, block_b=BLOCK_B,
                                 megabatch_tiles=MEGABATCH_TILES))

    words, _, _ = corpus.build_corpus(n_words=N_REQUESTS * WORDS_PER_REQ,
                                      seed=1)
    enc = corpus.encode_corpus(words)

    t0 = time.time()
    rids = [eng.submit(enc[i * WORDS_PER_REQ:(i + 1) * WORDS_PER_REQ])
            for i in range(N_REQUESTS)]
    for _ in range(3):           # a few ticks on dict v0 ...
        eng.step()
    v1 = store.publish(corpus.grow_root_arrays(arrays, 4096, seed=7))
    rep = eng.run_until_drained()  # ... the rest on the hot-swapped v1
    dt = time.time() - t0

    n_words = N_REQUESTS * WORDS_PER_REQ
    versions = np.concatenate([eng.result(r).dict_versions for r in rids])
    split = {int(v): int((versions == v).sum()) for v in np.unique(versions)}
    print(f"served {N_REQUESTS} requests / {n_words} words in {dt:.2f}s "
          f"({n_words / dt:.1f} Wps, {rep.ticks} ticks)")
    print(f"dict versions served: {split} (hot swap published v{v1} "
          f"mid-stream)")
    assert sorted(split) == [0, v1], "the hot swap did not land mid-stream"

    # bit-exact parity per served version against the batch stemmer
    checked = 0
    for rid in rids:
        req = eng.result(rid)
        assert req.done and req.dict_versions.shape == (req.n_words,)
        for v in np.unique(req.dict_versions):
            da = store.get(int(v)).arrays
            mask = req.dict_versions == v
            want_r, want_s = stemmer.stem_batch(jnp.asarray(req.words[mask]),
                                                da)
            assert np.array_equal(req.roots[mask], np.asarray(want_r)), (
                f"req {rid}: roots diverge from stem_batch under dict v{v}")
            assert np.array_equal(req.sources[mask], np.asarray(want_s)), (
                f"req {rid}: sources diverge from stem_batch under dict v{v}")
            checked += int(mask.sum())
    assert checked == n_words
    print(f"parity ok: {checked} words bit-identical to stem_batch under "
          f"their serving dict version")

    # -- faulted re-serve, observed through the structured event stream
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("dispatch", at=1),)))
    eng2 = Engine(StemmerWorkload(DictStore(arrays), block_b=BLOCK_B,
                                  megabatch_tiles=MEGABATCH_TILES,
                                  injector=inj))
    rids2 = [eng2.submit(enc[i * WORDS_PER_REQ:(i + 1) * WORDS_PER_REQ])
             for i in range(N_REQUESTS)]
    assert eng2.run_until_drained().drained
    retries = [e for e in eng2.events() if e.kind == "retry"]
    assert len(retries) == 1 and retries[0].data["attempt"] == 1
    assert not any(e.kind == "failure" for e in eng2.events())
    for rid in rids2:
        req = eng2.result(rid)
        want_r, _ = stemmer.stem_batch(jnp.asarray(req.words), arrays)
        assert np.array_equal(req.roots, np.asarray(want_r))
    print(f"fault recovery ok: retry observed via Engine.events()"
          f" (rids {retries[0].data['rids']}), drain bit-identical")


if __name__ == "__main__":
    main()
