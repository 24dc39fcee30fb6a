"""Faults planted under a run, to show that ``correct`` catches them.

Each is planted in this process before the system is built, through the
benchmark's own system (``bench/drive.py``) or a public entry point of
the program, never a private method; a run with any of them must come
out ``correct: false``. Only the tests plant them: no run of
``bench/run.py`` does.

  answer     the lexicon the program gets differs from the reference's
             in one key, that of the most frequent root: the kernel
             produces altered roots for its words, with checksums that
             agree with them (an answer altered where it is produced)
  frontend   the text front end (``ops.text_to_words``) returns one
             altered word row per launch
  unchanged  every request the engine finishes keeps its answers as
             they were allocated (a step that leaves the state unchanged)
  exchange   the last quarter of every corpus chunk never reaches the
             index (one device's share of the exchange left out)
"""
from __future__ import annotations

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

    return _patch(drive.EngineSystem, "__init__", make)


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


PLANT = {"answer": _answer, "frontend": _frontend, "unchanged": _unchanged,
         "exchange": _exchange}


def plant(name: str):
    """Plant fault ``name``; returns the function that removes it."""
    return PLANT[name]()
