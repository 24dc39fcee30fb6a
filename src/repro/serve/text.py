"""Text serving: documents in, per-token (root, source, byte span) out.

``TextAnalysisWorkload`` moves the serving boundary from pre-packed
`[block_b, 16]` word tiles to raw text, without touching the machinery
underneath: it subclasses :class:`StemmerWorkload`. ``make_request``
only validates a request and counts its bytes; it launches nothing, and
the request awaits the front end. Each tick, before the stemmer's
dispatch/retire pass, segments every admitted request still awaiting
it: their documents, in admission order, are packed into as few
codepoint tiles of at most ``char_block * FRONTEND_BLOCKS`` codepoints
as will hold them (single 0 separator between docs; a tile pads to
one ``char_block`` or to the cap, so the front end has two launch
shapes; a longer document gets a tile of its own), the text front end
(kernels/text_frontend.py by default) runs once a tile, every tile's
outputs come back in one fetch, and each word is attributed to its document by byte offset. The
word rows then ride the *unchanged* PR 4-6 pipeline: dispatch/retire
ring, megabatching, ``data_devices`` sharding and ``persistent``
descriptor-ring launches all serve text requests exactly as they serve
word-tile requests. Results scatter back per document through
:meth:`TextRequest.analyses`.

The front end runs on the host's tick, not inside the stemmer launch:
word counts are data-dependent, so the ring's fixed [launch_b, 16]
staging contract — the thing that keeps one jit trace — needs the
counts on the host anyway. Running it once a tick over the whole queue,
rather than once a request at admission, lets one launch and one fetch
serve every document that arrived since the last tick. The fully fused
device-side chain exists as ``ops.extract_roots_text`` for the batch
path.

A front end that raises is retried, as a stemmer launch is; if it
keeps failing, each request of the tick is segmented alone and one that
still fails is quarantined with its ``FailureInfo`` while the others are
served, so a document the front end cannot process never wedges the
engine, nor does its replay after ``Engine.recover``. Strict mode
(``max_retries=0``) propagates the first failure out of ``Engine.step``
with every request of the tick still awaiting the front end. A request
that expires or is cancelled while it awaits the front end finishes with
its ``FailureInfo``, as any other.

Crash safety (DESIGN.md §12) comes for free through the same
inheritance: the write-ahead journal stores a text submission as its
raw document list (the ``strs`` payload codec), so ``Engine.recover``
replays the *text* through ``make_request`` and the next tick
re-runs normalisation + segmentation — the front end is deterministic,
so the recovered word rows, spans and roots are bit-identical;
``pin_version`` (a StemRequest field) re-pins the admitted lexicon
exactly as on the word-tile path.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np

from repro.core import alphabet as ab
from repro.core import textnorm as tn
from repro.serve.engine import (StemmerWorkload, StemRequest,
                               _deterministic_launch_error)
from repro.serve.faults import FailureInfo
from repro.serve.spans import TEXT_FETCH, TEXT_FRONTEND, span

FRONTENDS = ("kernel", "reference", "host")

# A front-end launch holds at most this many char_blocks of documents
# (32,768 codepoints at the default char_block), so the documents that
# sixteen clients send in one tick share one launch and one fetch.
FRONTEND_BLOCKS = 16


@dataclass
class TextRequest(StemRequest):
    """A document-batch request; words/roots/sources/dict_versions hold
    the flattened per-token state in document order (StemRequest fields),
    plus the text-level view needed to scatter results back per doc.
    Until the front end has run (``awaiting_frontend``) the request
    holds no words."""

    docs: list = field(default_factory=list)   # original documents
    doc_ids: np.ndarray = None                 # int32 [n] doc index per word
    spans: np.ndarray = None                   # int32 [n, 2] per-doc byte span
    n_bytes: int = 0                           # utf-8 bytes across docs
    awaiting_frontend: bool = False            # words not segmented yet

    def analyses(self) -> list[list[tuple[str, int, tuple[int, int]]]]:
        """Per-document [(root, source, (byte_start, byte_end))].

        A terminally failed request (quarantined / deadline / shed /
        cancelled — ``self.failure`` is set) holds zero-filled roots for
        its unserved words; reading analyses off it would silently
        return garbage, so it raises instead — check ``failure`` first.
        """
        if self.failure is not None:
            raise RuntimeError(
                f"request {self.rid} failed ({self.failure.code}:"
                f" {self.failure.detail}); no analyses to read")
        out: list[list] = [[] for _ in self.docs]
        for i in range(self.n_words):
            out[int(self.doc_ids[i])].append(
                (ab.decode_word(self.roots[i]), int(self.sources[i]),
                 (int(self.spans[i, 0]), int(self.spans[i, 1]))))
        return out

    def set_words(self, words, spans, doc_ids) -> None:
        """Install the front end's output; the request can now be served."""
        n = words.shape[0]
        self.words = np.ascontiguousarray(words, np.int32)
        self.spans, self.doc_ids = spans, doc_ids
        self.roots = np.zeros((n, 4), np.int32)
        self.sources = np.zeros(n, np.int32)
        self.dict_versions = np.zeros(n, np.int32)
        self.awaiting_frontend = False


def _no_words():
    """(words, spans, doc_ids) of zero words."""
    return (np.zeros((0, ab.MAXLEN), np.int32), np.zeros((0, 2), np.int32),
            np.zeros(0, np.int32))


class TextAnalysisWorkload(StemmerWorkload):
    """StemmerWorkload whose public payload is text.

    frontend="kernel"     text_frontend_pallas + geometry pre-pass (default)
    frontend="reference"  pure-jnp textnorm.frontend_reference
    frontend="host"       python textnorm.analyze_text_py per document

    All three are bit-identical (parity-tested); the host path is the
    oracle the others are checked against in tests.
    ``frontend_launches`` counts the front end's passes, one a tile of
    queued documents (on the device, one launch each).
    """

    def __init__(self, store, *, char_block: int = 2048,
                 text_block_w: int = 128, frontend: str = "kernel", **kw):
        if frontend not in FRONTENDS:
            raise ValueError(f"unknown frontend {frontend!r}"
                             f" (choose from {FRONTENDS})")
        if char_block < 128:
            raise ValueError(f"char_block must be >= 128, got {char_block}")
        super().__init__(store, **kw)
        self.char_block = char_block
        self.char_cap = char_block * FRONTEND_BLOCKS
        self.text_block_w = text_block_w
        self.frontend = frontend
        self.frontend_launches = 0
        self._frontend_warm = frontend != "kernel"

    # -- admission: validate, count bytes; no device work ------------------
    def _char_bucket(self, n: int) -> int:
        """The tile ``n`` codepoints pad to: one char_block, else the cap,
        else (a document over the cap, alone) the cap times the smallest
        power of two that holds it. Two shapes below the cap, as the
        stemmer's launches have, because each shape costs set-up time to
        trace, lower and compile, and a tick's documents fill most of
        the cap anyway."""
        if n <= self.char_block:
            return self.char_block
        b = self.char_cap
        while b < n:
            b *= 2
        return b

    def make_request(self, rid: int, docs, **opts) -> TextRequest:
        if opts:
            raise ValueError(f"unknown text request options: {sorted(opts)}")
        if isinstance(docs, str):
            docs = [docs]
        docs = list(docs)
        for d in docs:
            if not isinstance(d, str):
                raise ValueError(
                    "text workload takes str documents, got"
                    f" {type(d).__name__}")
        words, spans, doc_ids = _no_words()
        return TextRequest(
            rid, words, roots=np.zeros((0, 4), np.int32),
            sources=np.zeros(0, np.int32),
            dict_versions=np.zeros(0, np.int32), docs=docs,
            doc_ids=doc_ids, spans=spans,
            n_bytes=sum(len(d.encode("utf-8")) for d in docs),
            awaiting_frontend=True)

    def tick(self) -> list:
        waiting = [r for r in self.inflight if r.awaiting_frontend]
        if waiting:
            self._segment(waiting)
        return super().tick()

    # -- the front end, once a tick over every waiting request -------------
    def _pack(self, reqs) -> list[list[tuple[int, int]]]:
        """Documents of ``reqs`` in order, as tiles of (request index, doc
        index): as many as fit in ``char_cap`` codepoints with their
        separators; a longer document gets a tile of its own."""
        tiles, cur, fill = [], [], 0
        for r, req in enumerate(reqs):
            for j, d in enumerate(req.docs):
                if cur and fill + 1 + len(d) > self.char_cap:
                    tiles.append(cur)
                    cur, fill = [], 0
                fill += len(d) + (1 if cur else 0)
                cur.append((r, j))
        if cur:
            tiles.append(cur)
        return tiles

    def _segment(self, reqs) -> None:
        """Give every request of ``reqs`` its words. The front end runs
        over all of them together; if that fails for good, over each
        request alone, and a request that fails alone is quarantined, so
        one poison document never holds up the rest. ``max_retries=0``
        (strict) propagates the first failure with every request still
        awaiting the front end."""
        try:
            outs = self._frontend_retried(reqs)
        except Exception as exc:
            if self.max_retries == 0:
                raise
            if len(reqs) > 1:
                for req in reqs:
                    self._segment([req])
                return
            (req,) = reqs
            req.failure = FailureInfo(
                req.rid, "quarantined",
                retries=(0 if _deterministic_launch_error(exc)
                         else self.max_retries),
                detail=f"text front end: {exc}")
            req.awaiting_frontend = False
            self.quarantined += 1
            return
        for req, out in zip(reqs, outs):
            req.set_words(*out)

    def _frontend_retried(self, reqs) -> list[tuple]:
        """``_frontend``, retried up to ``max_retries`` times unless it
        failed to trace, lower or compile (that fails alike every time)."""
        for attempt in range(1, self.max_retries + 1):
            try:
                return self._frontend(reqs)
            except Exception as e:
                if _deterministic_launch_error(e):
                    raise
                self.events.emit("retry", attempt=attempt,
                                 rids=[r.rid for r in reqs], detail=str(e))
        return self._frontend(reqs)

    def _frontend(self, reqs) -> list[tuple]:
        """One front-end pass over ``reqs`` -> per request (words, spans,
        doc_ids). Every tile is launched before any is fetched, and all
        come back in one read."""
        tiles = self._pack(reqs)
        outs = []
        for tile in tiles:
            with span(TEXT_FRONTEND):
                docs = [reqs[r].docs[j] for r, j in tile]
                if self.frontend == "host":
                    outs.append(self._frontend_host(docs))
                else:
                    chars, _char_off, byte_off = tn.coalesce_docs(docs)
                    outs.append((self._launch_padded(chars), byte_off))
                self.frontend_launches += 1
        if self.frontend != "host":
            with span(TEXT_FETCH):
                got = jax.device_get([out for out, _ in outs])
            outs = [_attribute(g, byte_off)
                    for g, (_, byte_off) in zip(got, outs)]
        parts = [[_no_words()] for _ in reqs]
        for tile, (words, spans, doc_ids) in zip(tiles, outs):
            cuts = np.cumsum(np.bincount(doc_ids, minlength=len(tile)))[:-1]
            for (r, j), w, s in zip(tile, np.split(words, cuts),
                                    np.split(spans, cuts)):
                parts[r].append((w, s, np.full(w.shape[0], j, np.int32)))
        return [tuple(np.concatenate(x) for x in zip(*got))
                for got in parts]

    def _frontend_host(self, docs):
        parts = [tn.analyze_text_py(d) for d in docs]
        words = np.concatenate([w for w, _ in parts])
        spans = np.concatenate([s for _, s in parts])
        doc_ids = np.concatenate([np.full(w.shape[0], i, np.int32)
                                  for i, (w, _) in enumerate(parts)])
        return words, spans, doc_ids

    def _launch_frontend(self, tile):
        """One front-end call on a codepoint tile -> device arrays
        (words, spans, n_words)."""
        from repro.kernels import ops  # lazy: keep engine import light

        if self.frontend == "kernel":
            return ops.text_to_words(tile, block_w=self.text_block_w,
                                     interpret=self.interpret)
        words, geo = tn.frontend_reference(tile, block_w=self.text_block_w)
        return words, geo.spans, geo.n_words

    def _warm_frontend(self) -> None:
        """On the kernel's first launch, compile both shapes on a zero
        tile, so no tick below the cap compiles while traffic waits (the
        reference runs op by op and has nothing to compile ahead). Warm
        launches count in neither ``frontend_launches`` nor
        ``ops.dispatch_count()``."""
        if self._frontend_warm:
            return
        from repro.kernels import ops

        with ops.uncounted_dispatches():
            for n in (self.char_block, self.char_cap):
                jax.block_until_ready(self._launch_frontend(
                    np.zeros(n, np.int32)))
        self._frontend_warm = True

    def _launch_padded(self, chars):
        """Launch the front end on ``chars`` padded to its bucket."""
        tile = np.zeros(self._char_bucket(max(chars.shape[0], 1)), np.int32)
        tile[:chars.shape[0]] = chars
        self._warm_frontend()
        return self._launch_frontend(tile)


def _attribute(fetched, byte_off):
    """A tile's fetched (words, spans, n_words) -> (words, spans,
    doc_ids), spans relative to each word's own document."""
    words, spans_abs, n = fetched
    n = int(n)
    words = words[:n]
    spans_abs = spans_abs[:n].astype(np.int64)
    # word -> owning doc: the last doc whose byte offset is <= the word's
    # absolute byte start (separators add one byte)
    doc_ids = (np.searchsorted(byte_off, spans_abs[:, 0],
                               side="right") - 1).astype(np.int32)
    spans = (spans_abs - byte_off[doc_ids][:, None]).astype(np.int32)
    return words, spans, doc_ids
