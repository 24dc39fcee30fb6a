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

Durability, where a configuration states a journal: every request the
client got an answer for is read back from the journal's file, parsed
here by the record format the program documents (``<sha16>
<canonical-json>\n``, ``sha16`` the first 16 hex digits of the sha256
of the JSON's UTF-8 bytes), with no function of the program. Its admit
record has to end within the file's size as the harness read it when
``submit`` returned: written to the operating system before the
acknowledgement, so it survives the death of the process. And the
window's records have to be met by at least one fsync of the file for
every ``fsync_every`` of them, counted by the harness around
``os.fsync``. The control reads the file with the admit record of the
window's first finished request left out.

What a run cannot show: whether an fsync reached stable storage. That
depends on the filesystem and the disk under it, which only a loss of
power tests; the run refuses a journal on tmpfs or ramfs, where an
fsync does nothing, and logs the filesystem's type.
"""
from __future__ import annotations

import base64
import hashlib
import json
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


def _sha16(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


def read_journal(path) -> tuple[list[tuple[int, dict]], int]:
    """-> (each record of a journal file in file order, with the offset
    at which its line ends; the lines whose framing or checksum fails, an
    unterminated last line among them)."""
    with open(path, "rb") as f:
        lines = f.read().split(b"\n")
    torn = int(lines.pop() != b"")
    records, end = [], 0
    for line in lines:
        end += len(line) + 1
        sha, sep, body = line.partition(b" ")
        if not sep or sha != _sha16(body).encode():
            torn += 1
            continue
        try:
            rec = json.loads(body.decode("utf-8"))
        except ValueError:
            torn += 1
            continue
        if isinstance(rec, dict):
            records.append((end, rec))
        else:
            torn += 1
    return records, torn


def _sent(payload, body) -> bool:
    """Whether an admit record's ``payload`` is the body the client sent:
    the text itself, or the word rows' bytes, dtype and shape."""
    if not isinstance(payload, dict):
        return False
    if isinstance(body, str):
        return payload.get("t") == "str" and payload.get("s") == body
    a = np.ascontiguousarray(body)
    try:
        raw = base64.b64decode(payload.get("b64", ""), validate=True)
    except ValueError:
        return False
    return (payload.get("t") == "nd" and payload.get("dtype") == str(a.dtype)
            and payload.get("shape") == list(a.shape) and raw == a.tobytes())


def journal_numbers(system, served, bodies: list, *, fsync_every: int,
                    control: bool = False) -> list[Number]:
    """Every finished request of the window that did not fail, read back
    from the system's journal file: exactly one admit record holding the
    body sent, before exactly one retire record whose digest is that of
    the answer the client got (sha16 of its roots' bytes, then its
    sources' bytes); the admit in the file when ``submit`` returned; and
    an fsync of the file for every ``fsync_every`` records the window
    wrote."""
    records, torn = read_journal(system.journal.path)
    ok = [(i, rid) for i, rid, _d, _t in served.done
          if system.result(rid).failure is None]
    if control and ok:
        first = ok[0][1]
        records = [(end, r) for end, r in records
                   if not (r.get("kind") == "admit" and r.get("rid") == first)]
    admits: dict[int, list] = {}
    retires: dict[int, list] = {}
    for end, rec in records:
        by_kind = {"admit": admits, "retire": retires}.get(rec.get("kind"))
        if by_kind is not None and isinstance(rec.get("rid"), int):
            by_kind.setdefault(rec["rid"], []).append((end, rec))
    bad_admits = bad_retires = unflushed = 0
    for i, rid in ok:
        req = system.result(rid)
        adm, ret = admits.get(rid, []), retires.get(rid, [])
        bad_admits += not (len(adm) == 1 and _sent(adm[0][1].get("payload"),
                                                   bodies[i])
                           and all(adm[0][0] < end for end, _r in ret))
        unflushed += len(adm) == 1 and adm[0][0] > system.acked[rid]
        digest = _sha16(np.ascontiguousarray(req.roots).tobytes()
                        + np.ascontiguousarray(req.sources).tobytes())
        bad_retires += not (len(ret) == 1
                            and ret[0][1].get("digest") == digest)
    lo = served.journal_from
    written = sum(lo < end <= lo + served.journal_bytes for end, _r in records)
    return [Number("journal_torn", torn, 0),
            Number("journal_admits", bad_admits, 0),
            Number("journal_retires", bad_retires, 0),
            Number("journal_unflushed", unflushed, 0),
            Number("journal_fsyncs_short",
                   max(0, written // fsync_every - served.journal_fsyncs), 0)]
