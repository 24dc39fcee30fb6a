"""The plain reference of the root index: counts, documents, positions.

Root ids come from the reference stemmer (:mod:`bench.ref.stemmer`); the
postings are one stable sort by root id, so within a root they keep
corpus order. It imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from bench.ref.stemmer import SRC_NONE, RootDict, stem_rows


def vocab(tables: dict) -> np.ndarray:
    """Sorted packed keys of every lexicon table; an index root id is a
    position in it."""
    keys = np.unique(np.concatenate([np.asarray(t).ravel()
                                     for t in tables.values()]))
    return keys[(keys >= 0) & (keys < (1 << 24))].astype(np.int32)


def root_ids(rows: np.ndarray, roots: RootDict, keys: np.ndarray, *,
             infix: bool = True) -> np.ndarray:
    """Word rows -> root ids; a word with no root gets ``len(keys)``."""
    root, source = stem_rows(rows, roots, infix=infix)
    c = root.astype(np.int64)
    key = ((c[:, 0] * 64 + c[:, 1]) * 64 + c[:, 2]) * 64 + c[:, 3]
    at = np.searchsorted(keys, key)
    found = keys[np.minimum(at, len(keys) - 1)] == key
    return np.where(found & (source != SRC_NONE), at, len(keys)).astype(np.int64)


def build(words: np.ndarray, doc_ids: np.ndarray, positions: np.ndarray,
          roots: RootDict, keys: np.ndarray, *, infix: bool = True):
    """-> (counts int64[n_roots], docs int32[P], positions int32[P])."""
    ids = root_ids(words, roots, keys, infix=infix)
    valid = ids < len(keys)
    order = np.argsort(ids[valid], kind="stable")
    counts = np.bincount(ids[valid], minlength=len(keys)).astype(np.int64)
    return (counts, doc_ids[valid][order].astype(np.int32),
            positions[valid][order].astype(np.int32))
