"""Faults planted under a run, to show that ``correct`` catches them.

Each is planted in this process before the system is built, through the
benchmark's own system (``bench/drive.py``) or a public entry point of
the program, never a private method; a run with any of them must come
out ``correct: false``. Only the tests plant them: no run of
``bench/run.py`` does.

  answer     the lexicon the program gets (the engine's or the index
             builder's) differs from the reference's in one key, that of
             the most frequent root: the kernel produces altered roots
             for its words, with checksums that agree with them (an
             answer altered where it is produced)
  frontend   the text front end (``ops.text_to_words``) returns one
             altered word row per launch
  unchanged  every request the engine finishes keeps its answers as
             they were allocated (a step that leaves the state unchanged)
  exchange   the last quarter of every corpus chunk never reaches the
             index (one device's share of the exchange left out)
  journal    every 50th admit to the write-ahead journal
             (``Journal.admit``) writes nothing and returns as if it
             had written (an acknowledged request that is not durable)
  journal_late  each admit is held back and written just before its
             request's retire (acknowledged before it is durable)
  journal_fsync  the journal fsyncs every 1000th record, not every
             ``fsync_every``-th (a batch longer than the one stated)
"""
from __future__ import annotations

import itertools

import numpy as np


def _patch(owner, name: str, make):
    orig = getattr(owner, name)
    setattr(owner, name, make(orig))
    return lambda: setattr(owner, name, orig)


def _answer():
    from bench import drive
    from bench.gen import alphabet as ab
    from bench.gen import corpus as gen

    key = ab.pack_key(ab.encode_word(gen.REAL_TRI_ROOTS[0])[:3])

    def make(orig):
        def init(self, config, tables, **kw):
            tri = tables["tri"]
            moved = key + 1
            while moved in tri:
                moved += 1
            tables = {**tables, "tri": np.sort(np.where(tri == key, moved, tri))}
            return orig(self, config, tables, **kw)
        return init

    undo = [_patch(cls, "__init__", make)
            for cls in (drive.EngineSystem, drive.IndexSystem)]
    return lambda: [u() for u in undo]


def _frontend():
    from repro.kernels import ops

    def make(orig):
        def altered(*a, **kw):
            out = orig(*a, **kw)
            return (out[0].at[0, 0].add(1),) + tuple(out[1:])
        return altered

    return _patch(ops, "text_to_words", make)


def _unchanged():
    from repro.serve import Engine

    def make(orig):
        def step(self):
            seen = set(self.finished)
            out = orig(self)
            for rid in set(self.finished) - seen:
                req = self.finished[rid]
                req.roots[...] = 0
                req.sources[...] = 0
            return out
        return step

    return _patch(Engine, "step", make)


def _exchange():
    from repro.kernels import ops

    def make(orig):
        def partial(words, *a, **kw):
            words = np.array(words, copy=True)
            words[len(words) - len(words) // 4:] = 0    # no root: never indexed
            return orig(words, *a, **kw)
        return partial

    return _patch(ops, "build_root_index", make)


def _journal():
    from repro.serve.journal import Journal

    def make(orig):
        count = itertools.count(1)

        def admit(self, *a, **kw):
            if next(count) % 50:
                return orig(self, *a, **kw)
            return None
        return admit

    return _patch(Journal, "admit", make)


def _journal_late():
    from repro.serve.journal import Journal

    held: dict = {}

    def make_admit(orig):
        def admit(self, rid, *a, **kw):
            held[rid] = lambda: orig(self, rid, *a, **kw)
        return admit

    def make_retire(orig):
        def retire(self, req):
            held.pop(req.rid, lambda: None)()
            return orig(self, req)
        return retire

    undo = [_patch(Journal, "admit", make_admit),
            _patch(Journal, "retire", make_retire)]
    return lambda: [u() for u in undo]


def _journal_fsync():
    from repro.serve.journal import Journal

    def make(orig):
        def init(self, path, **kw):
            orig(self, path, **{**kw, "fsync_every": 1000})
        return init

    return _patch(Journal, "__init__", make)


PLANT = {"answer": _answer, "frontend": _frontend, "unchanged": _unchanged,
         "exchange": _exchange, "journal": _journal,
         "journal_late": _journal_late, "journal_fsync": _journal_fsync}


def plant(name: str):
    """Plant fault ``name``; returns the function that removes it."""
    return PLANT[name]()
