"""On-chip smoke run: the analyser's main path on a TPU, checked bit for bit.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded paths only

One chip, through the entry points a user calls, at the sizes users run:

  serve words     Engine(StemmerWorkload(DictStore(...))) with a
                  general-dictionary lexicon (5,000 tri + 500 quad roots,
                  VMEM-resident): 256 requests x 64 words at block_b=256,
                  per-tile, 4-tile megabatches and the persistent kernel
  serve streamed  the same runs against a production-scale lexicon of
                  262,144 keys, streamed from HBM
  serve text      TextAnalysisWorkload(frontend="kernel") on 64 raw
                  documents of 300 words
  index           build_corpus_index over 4 chunks of 1M words

Each phase is compared bit for bit with its plain reference:
``stem_batch(backend="sorted")`` for roots, the host ``textnorm`` path for
text, ``index/reference.py`` for the index. No request may fail and no
failure, retry, checksum_failure or degrade event may appear.

``--chips 4`` runs StemmerWorkload(data_devices=4) on the same requests
and build_corpus_index on a 4-device mesh, compares both bit for bit
with the one-chip result computed in this process on device 0, and
checks that the sharded launch put its output on all four devices.

All data comes from seeds; everything runs in this one process. The
last line of stdout is ``{"ok": true, "device": {...}}`` only when every
phase passed; any failure exits non-zero, and so does a run where JAX
finds no TPU. Per-phase seconds printed before it are smoke timings that
include compilation, not measurements.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_TRI, N_QUAD = 5000, 500          # general dictionary (core/corpus.py)
STREAMED_KEYS = 1 << 18            # production lexicon: > MAX_RESIDENT_KEYS
REQUESTS, WORDS_PER_REQUEST, BLOCK_B = 256, 64, 256
TEXT_DOCS, TEXT_WORDS = 64, 300
INDEX_CHUNKS, CHUNK_WORDS = 4, 1 << 20
SERVE_MODES = (("per-tile", {"megabatch_tiles": 1}),
               ("megabatch", {"megabatch_tiles": 4}),
               ("persistent", {"megabatch_tiles": 4, "persistent": True}))
BAD_EVENTS = ("failure", "retry", "checksum_failure", "degrade")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def check_equal(got, want, what: str) -> None:
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape and np.array_equal(got, want),
          f"{what}: differs from its reference")


# ---------------------------------------------------------------------------
# data (all from seeds)
# ---------------------------------------------------------------------------
def lexicons():
    from repro.core import corpus, stemmer

    d = corpus.build_dictionary(n_tri=N_TRI, n_quad=N_QUAD, seed=0)
    resident = stemmer.RootDictArrays.from_rootdict(d)
    return resident, corpus.grow_root_arrays(resident, STREAMED_KEYS)


def word_requests(n_requests: int = REQUESTS,
                  words_per_request: int = WORDS_PER_REQUEST):
    from repro.core import corpus

    words, _, _ = corpus.build_corpus(
        n_words=n_requests * words_per_request, seed=1)
    enc = corpus.encode_corpus(words)
    return [enc[i * words_per_request:(i + 1) * words_per_request]
            for i in range(n_requests)]


def index_stream(n_chunks: int = INDEX_CHUNKS,
                 chunk_words: int = CHUNK_WORDS):
    from repro.core import corpus

    return corpus.stream_corpus_words(n_chunks * chunk_words, seed=0,
                                      chunk_words=chunk_words)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def drain(eng, payloads) -> list:
    """Submit, drain, and hold the engine to zero failed requests and
    zero failure/retry/checksum/degrade events; returns the results."""
    rids = [eng.submit(p) for p in payloads]
    eng.run_until_drained(max_ticks=1_000_000)
    reqs = [eng.result(r) for r in rids]
    failed = [r.rid for r in reqs if r is None or r.failure is not None]
    check(not failed, f"{len(failed)} requests failed: {failed[:8]}")
    bad = sorted({ev.kind for ev in eng.events() if ev.kind in BAD_EVENTS})
    check(not bad, f"engine events {bad}")
    return reqs


def serve_words(arrays, requests, *, block_b: int = BLOCK_B, **mode):
    """-> (roots int32[n, 4], sources int32[n]) over all requests."""
    import numpy as np

    from repro.serve import DictStore, Engine, StemmerWorkload

    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=block_b, **mode))
    reqs = drain(eng, requests)
    return (np.concatenate([r.roots for r in reqs]),
            np.concatenate([r.sources for r in reqs]))


def stem_reference(arrays, words):
    from repro.core import stemmer

    import jax.numpy as jnp

    return stemmer.stem_batch(jnp.asarray(words), arrays, backend="sorted")


def phase_serve_words(arrays, requests, label: str, **kw):
    import numpy as np

    want_r, want_s = stem_reference(arrays, np.concatenate(requests))
    for mode, opts in SERVE_MODES:
        roots, sources = serve_words(arrays, requests, **opts, **kw)
        check_equal(roots, want_r, f"{label} {mode} roots")
        check_equal(sources, want_s, f"{label} {mode} sources")


def phase_serve_text(arrays, n_docs: int = TEXT_DOCS,
                     words_per_doc: int = TEXT_WORDS):
    from repro.core import textnorm as tn
    from repro.launch.serve import build_documents
    from repro.serve import DictStore, Engine, TextAnalysisWorkload

    docs = build_documents(n_docs, words_per_doc)
    eng = Engine(TextAnalysisWorkload(DictStore(arrays), frontend="kernel"))
    for doc, req in zip(docs, drain(eng, docs)):
        words, spans = tn.analyze_text_py(doc)          # host reference
        want_r, want_s = stem_reference(arrays, words)
        check_equal(req.words, words, f"text req {req.rid} word rows")
        check_equal(req.spans, spans, f"text req {req.rid} byte spans")
        check_equal(req.roots, want_r, f"text req {req.rid} roots")
        check_equal(req.sources, want_s, f"text req {req.rid} sources")


def check_index(got, want, what: str) -> None:
    check_equal(got.counts, want.counts, f"{what} counts")
    check_equal(got.docs, want.docs, f"{what} docs")
    check_equal(got.positions, want.positions, f"{what} positions")


def phase_index(arrays, **sizes):
    import numpy as np

    from repro import index as ix

    got = ix.build_corpus_index(index_stream(**sizes), arrays)
    vocab = ix.build_vocab(arrays)
    parts = []
    for ch in index_stream(**sizes):                     # host reference
        ids = ix.host_root_ids(ch.words, arrays, vocab)
        parts.append(ix.IndexPartial(*ix.host_index(
            ids, ch.doc_ids.astype(np.int32), ch.positions, len(vocab))))
    check_index(got, ix.merge_partials(parts, vocab), "index")
    check(got.n_postings > 0, "index holds no postings")


def phase_sharded_serve(arrays, requests, n_dev: int = 4,
                        block_b: int = BLOCK_B):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.launch.mesh import make_data_mesh

    one = serve_words(arrays, requests, block_b=block_b)
    many = serve_words(arrays, requests, block_b=block_b,
                       data_devices=n_dev)
    check_equal(many[0], one[0], f"{n_dev}-device roots vs one chip")
    check_equal(many[1], one[1], f"{n_dev}-device sources vs one chip")
    # the sharded launch must split its output over every device
    words = jnp.asarray(np.concatenate(requests)[:n_dev * block_b])
    root, _ = ops.extract_roots_sharded(words, arrays, make_data_mesh(n_dev),
                                        block_b=block_b)
    split = {sh.device for sh in root.addressable_shards
             if sh.data.shape[0] == words.shape[0] // n_dev}
    check(len(split) == n_dev,
          f"sharded output on {len(split)} of {n_dev} devices")


def phase_sharded_index(arrays, n_dev: int = 4, **sizes):
    from repro import index as ix
    from repro.launch.mesh import make_data_mesh

    one = ix.build_corpus_index(index_stream(**sizes), arrays)
    many = ix.build_corpus_index(index_stream(**sizes), arrays,
                                 mesh=make_data_mesh(n_dev))
    check_index(many, one, f"{n_dev}-device index vs one chip")


def run(name: str, fn, *args, **kw) -> None:
    t0 = time.perf_counter()
    fn(*args, **kw)
    print(f"smoke timing (includes compilation, not a measurement):"
          f" {name} {time.perf_counter() - t0:.3f} s", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded serve and index paths, each"
                         " compared with one chip")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform});"
              " this smoke run only runs on a chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found"
              f" {len(devices)} devices", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    entries = len(list(cache.glob("*"))) if cache.is_dir() else 0
    resident, streamed = lexicons()
    requests = word_requests()
    if args.chips == 1:
        run("serve words (resident lexicon)", phase_serve_words, resident,
            requests, "resident")
        run("serve words (streamed lexicon)", phase_serve_words, streamed,
            requests, "streamed")
        run("serve text", phase_serve_text, resident)
        run("index", phase_index, resident)
    else:
        run("sharded serve", phase_sharded_serve, resident, requests,
            n_dev=args.chips)
        run("sharded index", phase_sharded_index, resident,
            n_dev=args.chips)
    # a warm cache gains no entries: every compile above was a hit
    print(f"compile cache {cache}: {entries} entries before,"
          f" {len(list(cache.glob('*')))} after", flush=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
