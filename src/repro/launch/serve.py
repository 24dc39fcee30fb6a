"""Serving launcher: workload-agnostic continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --workload text --requests 16
  PYTHONPATH=src python -m repro.launch.serve --workload stemmer --requests 16
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.serve import (DegradationPolicy, DictStore, Engine, Journal,
                         StemmerWorkload, TextAnalysisWorkload)
from repro.serve.engine import MEGABATCH_TILES


def _engine_kw(args) -> dict:
    """Engine admission-control + crash-safety kwargs shared by both
    workloads (the journal/policy flags are validated in main() before
    any engine is constructed)."""
    kw = dict(queue_cap=args.queue_cap or None, on_full=args.on_full)
    if args.journal:
        kw["journal"] = Journal(args.journal)
    if args.degrade == "on":
        kw["policy"] = DegradationPolicy()
    return kw


def _deadline_s(args) -> float | None:
    return args.deadline_ms / 1000.0 if args.deadline_ms else None


def _retry_kw(args) -> dict:
    """StemmerWorkload/TextAnalysisWorkload retry kwargs."""
    kw = {} if args.max_retries is None else dict(
        max_retries=args.max_retries)
    if args.watchdog_ms:
        kw["watchdog_s"] = args.watchdog_ms / 1000.0
    return kw


def _report_events(eng) -> None:
    """Structured incident stream (Engine.events): the supported way to
    see retries, stalls, device losses and ladder transitions."""
    events = eng.events()
    if not events:
        return
    counts: dict[str, int] = {}
    for ev in events:
        counts[ev.kind] = counts.get(ev.kind, 0) + 1
    print("  events: " + ", ".join(f"{k} x{n}"
                                   for k, n in sorted(counts.items())))
    for ev in events:
        if ev.kind in ("degrade", "upshift"):
            print(f"    {ev.kind}: {ev.data['from']} -> {ev.data['to']}"
                  f" ({ev.data['reason']})")


def _failed(eng, rids) -> list:
    reqs = [eng.result(r) for r in rids]
    return [r for r in reqs if r is not None and r.failure is not None]


def _report_failures(failed, eng) -> str:
    for req in failed[:4]:
        print(f"  req {req.rid} FAILED: {req.failure.code}"
              f" ({req.failure.detail})")
    return f", {len(failed)} failed, {eng.shed} shed" if failed else ""


def serve_stemmer(args) -> int:
    from repro.core import corpus, stemmer

    d = corpus.build_dictionary(n_tri=1000, n_quad=120, seed=0)
    # the store pins residency AND the streamed tile/boundary tables per
    # publish, so hot swaps replay the serving trace (DESIGN.md §6)
    store = DictStore(stemmer.RootDictArrays.from_rootdict(d),
                      dict_block_r=args.dict_block_r)
    eng = Engine(StemmerWorkload(store, block_b=args.block_b,
                                 dict_block_r=args.dict_block_r,
                                 num_buffers=args.num_buffers,
                                 skip_index=not args.full_sweep,
                                 max_inflight=args.inflight,
                                 data_devices=args.devices,
                                 megabatch_tiles=args.megabatch,
                                 persistent=args.persistent,
                                 **_retry_kw(args)), **_engine_kw(args))

    wpr = args.words_per_request
    words, _, _ = corpus.build_corpus(n_words=args.requests * wpr, seed=1)
    enc = corpus.encode_corpus(words)

    t0 = time.time()
    rids = [eng.submit(enc[i * wpr:(i + 1) * wpr],
                       deadline_s=_deadline_s(args))
            for i in range(args.requests)]
    rep = eng.run_until_drained()
    dt = time.time() - t0
    failed = _failed(eng, rids)
    n_words = args.requests * wpr
    print(f"served {args.requests} word-batch requests / {n_words} words in "
          f"{dt:.2f}s ({n_words / dt:.1f} Wps, {rep.ticks} ticks, "
          f"{eng.workload.ticks_launched} launches, dict v{store.version}, "
          f"super-tile {args.devices}x{args.block_b}, "
          f"megabatch {args.megabatch}"
          f"{', persistent' if args.persistent else ''}, "
          f"inflight {args.inflight}{_report_failures(failed, eng)})")
    _report_events(eng)
    for rid in rids[:2]:
        req = eng.result(rid)
        if req.failure is None:
            print(f"  req {rid}: {req.n_words} roots,"
                  f" dict v{req.dict_version}")
    return len(failed)


def build_documents(n_docs: int, words_per_doc: int, seed: int = 1):
    """Synthesise raw Arabic documents from the conjugated corpus: words
    joined with spaces, an Arabic comma sprinkled every ~8 words, and a
    rotating clitic attached to every third word so the front end's
    stripping path is exercised end to end."""
    from repro.core import corpus

    words, _, _ = corpus.build_corpus(n_words=n_docs * words_per_doc,
                                      seed=seed)
    pro = ("وال", "ب", "ف", "لل", "ك")
    docs = []
    for i in range(n_docs):
        chunk = words[i * words_per_doc:(i + 1) * words_per_doc]
        toks = [pro[j % len(pro)] + w if j % 3 == 0 else w
                for j, w in enumerate(chunk)]
        toks = [t + "،" if j % 8 == 7 else t for j, t in enumerate(toks)]
        docs.append(" ".join(toks))
    return docs


def serve_text(args) -> int:
    from repro.core import corpus, stemmer

    d = corpus.build_dictionary(n_tri=1000, n_quad=120, seed=0)
    store = DictStore(stemmer.RootDictArrays.from_rootdict(d),
                      dict_block_r=args.dict_block_r)
    eng = Engine(TextAnalysisWorkload(store, block_b=args.block_b,
                                      char_block=args.char_block,
                                      frontend=args.frontend,
                                      dict_block_r=args.dict_block_r,
                                      num_buffers=args.num_buffers,
                                      skip_index=not args.full_sweep,
                                      max_inflight=args.inflight,
                                      data_devices=args.devices,
                                      megabatch_tiles=args.megabatch,
                                      persistent=args.persistent,
                                      **_retry_kw(args)), **_engine_kw(args))

    docs = build_documents(args.requests, args.words_per_request)
    n_bytes = sum(len(doc.encode("utf-8")) for doc in docs)
    t0 = time.time()
    rids = [eng.submit(doc, deadline_s=_deadline_s(args)) for doc in docs]
    rep = eng.run_until_drained()
    dt = time.time() - t0
    failed = _failed(eng, rids)
    n_words = sum(eng.result(r).n_words for r in rids)
    print(f"served {args.requests} documents / {n_bytes} bytes /"
          f" {n_words} words in {dt:.2f}s ({n_bytes / dt:.0f} B/s,"
          f" {n_words / dt:.1f} Wps, {rep.ticks} ticks,"
          f" {eng.workload.ticks_launched} launches,"
          f" frontend {args.frontend}, megabatch {args.megabatch},"
          f" inflight {args.inflight}{_report_failures(failed, eng)})")
    _report_events(eng)
    for rid in rids[:2]:
        req = eng.result(rid)
        if req.failure is not None:
            continue
        root, src, span = req.analyses()[0][0]
        print(f"  req {rid}: {req.n_words} tokens, first root {root!r}"
              f" (src {src}, bytes {span})")
    return len(failed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("text", "stemmer"),
                    default="text")
    ap.add_argument("--requests", type=int, default=8)
    # stemmer knobs
    ap.add_argument("--words-per-request", type=int, default=64)
    ap.add_argument("--block-b", type=int, default=256)
    ap.add_argument("--inflight", type=int, default=2,
                    help="dispatch ring depth: outstanding megakernel"
                         " launches (1 = synchronous tick, overlap off)")
    ap.add_argument("--devices", type=int, default=1,
                    help="data devices per super-tile: each launch is a"
                         " [devices * block_b, 16] tile shard_map'd over"
                         " a ('data',) mesh (dist.shard_batch)")
    ap.add_argument("--dict-block-r", type=int, default=8,
                    help="streamed dictionary tile height in 128-lane"
                         " rows; also pins the publish-time tile stream")
    ap.add_argument("--num-buffers", type=int, default=2,
                    help="streamed-path DMA ladder depth (1 = no"
                         " overlap, 2 = double buffering, up to 4)")
    ap.add_argument("--full-sweep", action="store_true",
                    help="disable the tile-visit skip index (sweep every"
                         " dictionary tile; the skip-off baseline)")
    ap.add_argument("--megabatch", type=int, default=MEGABATCH_TILES,
                    help="most super-tiles coalesced per launch: the"
                         " grid's batch axis spans the whole megabatch, so"
                         " one dispatch retires up to this many queue tiles"
                         " (default %(default)s, the workload's own;"
                         " 1 = the per-tile baseline)")
    ap.add_argument("--persistent", action="store_true",
                    help="persistent serving kernel: ONE launch loops a"
                         " device-side work-descriptor ring over the"
                         " megabatch (single-device only)")
    # text knobs
    ap.add_argument("--char-block", type=int, default=2048,
                    help="codepoint-tile bucket for the text front end"
                         " (requests round up to a pow2 multiple)")
    ap.add_argument("--frontend", choices=("kernel", "reference", "host"),
                    default="kernel",
                    help="text front end: Pallas kernel, pure-jnp"
                         " reference, or the python oracle")
    # robustness knobs (DESIGN.md §11)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline in milliseconds; expired"
                         " requests finish with FailureInfo code"
                         " 'deadline' (0 = no deadline)")
    ap.add_argument("--max-retries", type=int, default=None,
                    help="launch retries before bisect/quarantine"
                         " (0 = strict fail-fast, default 2)")
    ap.add_argument("--queue-cap", type=int, default=0,
                    help="admission-control bound on queued requests"
                         " (0 = unbounded)")
    ap.add_argument("--on-full", choices=Engine.ON_FULL, default="raise",
                    help="full-queue policy: raise QueueFull, shed the"
                         " new request (FailureInfo 'shed'), or block"
                         " until a slot frees")
    # crash safety + degraded modes (DESIGN.md §12)
    ap.add_argument("--journal", default="", metavar="PATH",
                    help="write-ahead request journal: every accepted"
                         " request is durable before it is served, so a"
                         " killed server warm-restarts via"
                         " Engine.recover(PATH) with zero lost requests")
    ap.add_argument("--watchdog-ms", type=float, default=0.0,
                    help="persistent-kernel stall watchdog: a launch"
                         " whose completion flags stop advancing for"
                         " this long is abandoned, its retired-prefix"
                         " salvaged and the rest re-dispatched down the"
                         " megabatch path (requires --persistent;"
                         " 0 = off)")
    ap.add_argument("--degrade", choices=("on", "off"), default="off",
                    help="graceful-degradation ladder: under sustained"
                         " faults or queue pressure the serving mode"
                         " downshifts persistent -> megabatch ->"
                         " per-tile -> streamed-dict -> fewer devices,"
                         " and upshifts when healthy")
    args = ap.parse_args()

    if args.deadline_ms < 0:
        ap.error("--deadline-ms must be >= 0")
    if args.queue_cap < 0:
        ap.error("--queue-cap must be >= 0")
    if args.max_retries is not None and args.max_retries < 0:
        ap.error("--max-retries must be >= 0")
    if args.on_full != "raise" and not args.queue_cap:
        ap.error(f"--on-full {args.on_full} needs --queue-cap > 0"
                 " (an unbounded queue is never full)")
    # cross-validate the crash-safety flags BEFORE any engine exists, so
    # an invalid combination never half-constructs serving state
    if args.watchdog_ms < 0:
        ap.error("--watchdog-ms must be >= 0")
    if args.watchdog_ms and not args.persistent:
        ap.error("--watchdog-ms guards the persistent descriptor ring;"
                 " it requires --persistent")

    enable_compile_cache()
    serve = {"stemmer": serve_stemmer, "text": serve_text}[args.workload]
    n_failed = serve(args)
    if n_failed:
        print(f"{n_failed} request(s) failed", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
