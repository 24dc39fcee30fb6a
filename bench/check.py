"""Decides ``correct``: what the timed path produced, against the plain
reference, number by number.

Every number counts answers that differ from the reference, so each
limit is 0 (an exact comparison). The numbers cover each layer a cell's
``why`` names: the front end's word rows, byte spans and document
attribution; the stemmer's root and source of every word; the index's
counts, documents and positions; and requests or builds that failed or
never finished.

``control=True`` puts the reference in the program's place with one
guarantee that the configurations state broken, to show that the
comparison fails it: every word served exactly once (each request's
answers shifted by one word), and every corpus word indexed exactly once
(the last device's share of every chunk left out of the merge).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from bench.gen import textrules as tr
from bench.ref import index as ref_index
from bench.ref.stemmer import stem_rows


@dataclass(frozen=True)
class Number:
    name: str
    value: int
    limit: int

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def _shift(a: np.ndarray) -> np.ndarray:
    """Word k gets word k-1's answer: the first served twice, the last never."""
    return np.concatenate([a[:1], a[:-1]]) if len(a) else a


def _diff_rows(got: np.ndarray, want: np.ndarray) -> int:
    """Words whose answer differs, counting a missing or extra word once."""
    n = min(len(got), len(want))
    g = np.asarray(got[:n]).reshape(n, -1)
    w = np.asarray(want[:n]).reshape(n, -1)
    return int((g != w).any(axis=1).sum()) + abs(len(got) - len(want))


def sample(done: list, rng, n: int, size) -> list:
    """``n`` of the finished requests drawn by ``rng``, and the longest."""
    if len(done) <= n:
        return list(done)
    pick = set(rng.choice(len(done), size=n, replace=False).tolist())
    pick.add(max(range(len(done)), key=lambda k: size(done[k])))
    return [done[k] for k in sorted(pick)]


def failed_requests(system, served) -> int:
    failed = sum(system.result(rid).failure is not None
                 for _i, rid, _d, _t in served.done)
    return failed + len(served.missing)


def text_numbers(system, served, bodies: list, roots, *, infix: bool, rng,
                 n_sample: int, control: bool = False) -> list[Number]:
    """Raw-text requests: every word's row, byte span, document, root and
    source, for a seeded sample of the finished requests."""
    rows = spans = docs = stems = 0
    ok_done = [d for d in served.done
               if system.result(d[1]).failure is None]
    for i, rid, _due, _t in sample(ok_done, rng, n_sample,
                                   lambda d: len(bodies[d[0]])):
        req = system.result(rid)
        want_w, want_sp = tr.analyze_text_py(bodies[i])
        want_r, want_s = stem_rows(want_w, roots, infix=infix)
        got = (req.words, req.spans, req.doc_ids, req.roots, req.sources)
        if control:
            got = tuple(_shift(a) for a in (want_w, want_sp, np.zeros(
                len(want_w), np.int32), want_r, want_s))
        rows += _diff_rows(got[0], want_w)
        spans += _diff_rows(got[1], want_sp)
        docs += _diff_rows(got[2], np.zeros(len(want_w), np.int32))
        stems += _diff_rows(np.column_stack([got[3], got[4]]),
                            np.column_stack([want_r, want_s]))
    return [Number("word_rows", rows, 0), Number("byte_spans", spans, 0),
            Number("doc_ids", docs, 0), Number("roots", stems, 0),
            Number("requests_failed", failed_requests(system, served), 0)]


def word_numbers(system, served, bodies: list, roots, *, infix: bool,
                 control: bool = False) -> list[Number]:
    """Pre-encoded requests: every word of every finished request, root
    and source (each distinct body's reference is computed once)."""
    want: dict[int, np.ndarray] = {}
    stems = 0
    for i, rid, _due, _t in served.done:
        req = system.result(rid)
        if req.failure is not None:
            continue
        if i not in want:
            r, s = stem_rows(bodies[i], roots, infix=infix)
            want[i] = np.column_stack([r, s])
        got = (_shift(want[i]) if control
               else np.column_stack([req.roots, req.sources]))
        stems += _diff_rows(got, want[i])
    return [Number("roots", stems, 0),
            Number("requests_failed", failed_requests(system, served), 0)]


def index_numbers(results: list, chunks: list, roots, tables: dict, *,
                  infix: bool, devices: int,
                  control: bool = False) -> list[Number]:
    """Every build of the window: root keys and counts, and each posting's
    document and position."""
    keys = ref_index.vocab(tables)

    def reference(parts):
        return ref_index.build(np.concatenate([p[0] for p in parts]),
                               np.concatenate([p[1] for p in parts]),
                               np.concatenate([p[2] for p in parts]),
                               roots, keys, infix=infix)

    whole = [(c.words, c.doc_ids, c.positions) for c in chunks]
    want = reference(whole)
    if control:
        kept = [tuple(a[:len(a) - max(1, len(a) // max(devices, 2))] for a in p)
                for p in whole]
        got_all = [(keys, *reference(kept))] * len(results)
    else:
        got_all = [(r.root_keys, r.counts, r.docs, r.positions) for r in results]
    counts = postings = 0
    for got_keys, got_counts, got_docs, got_pos in got_all:
        if not np.array_equal(got_keys, keys):
            counts += len(keys)
            continue
        counts += int((np.asarray(got_counts) != want[0]).sum())
        postings += _diff_rows(np.column_stack([got_docs, got_pos]),
                               np.column_stack([want[1], want[2]]))
    return [Number("index_counts", counts, 0),
            Number("index_postings", postings, 0)]
