"""The plain reference of the analyser: verb-root extraction in Python.

A copy of ``repro/core/pyref.py``, the executable specification of the
paper's algorithm (Figs 1-4, the VHDL truncation order of Fig 12 and the
infix passes of Figs 18-19), plus :func:`stem_rows`, which applies it to
a batch of int32[16] word rows. It imports nothing of the program.

A stem is ``word[p+1 : s]`` for a prefix cut ``p`` (-1 == no prefix) and
suffix start ``s``; only lengths 3 and 4 are kept, so each prefix cut
gives at most one trilateral and one quadrilateral candidate. The
prefix run ends after the first person marker ي (the paper's worked
example سيلعبون -> prefixes mask 1100000).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from bench.gen import alphabet as ab

PREFIX_SET = frozenset(int(c) for c in ab.PREFIX_CODES)
SUFFIX_SET = frozenset(int(c) for c in ab.SUFFIX_CODES)
INFIX_SET = frozenset(int(c) for c in ab.INFIX_CODES)

# Root-source tags (the served path returns the same numbers).
SRC_NONE = 0          # no root found
SRC_TRI = 1           # direct trilateral match
SRC_QUAD = 2          # direct quadrilateral match
SRC_RESTORED = 3      # Restore-Original-Form (hollow verb, ا→و)
SRC_DEINFIX_TRI = 4   # Remove-Infix on a quadrilateral stem → trilateral
SRC_DEINFIX_BI = 5    # Remove-Infix on a trilateral stem → bilateral


@dataclass
class RootDict:
    """Stored root lists (dense-code tuples)."""

    tri: frozenset = field(default_factory=frozenset)    # {(c0,c1,c2)}
    quad: frozenset = field(default_factory=frozenset)   # {(c0,c1,c2,c3)}
    bi: frozenset = field(default_factory=frozenset)     # {(c0,c1)}

    @staticmethod
    def from_words(tri=(), quad=(), bi=()):
        enc = lambda w: tuple(int(c) for c in ab.encode_word(w) if c)
        return RootDict(
            tri=frozenset(enc(w) for w in tri),
            quad=frozenset(enc(w) for w in quad),
            bi=frozenset(enc(w) for w in bi),
        )


def check_and_produce(word: list[int]):
    """Stages 1-2: affix checks + contiguous-run masking.

    Returns (pp, ps): pp[i] true iff chars 0..i form a valid prefix run
    (i < 5); ps[j] true iff chars j..n-1 are all suffix letters.
    """
    n = len(word)
    pp = []
    run = True
    seen_yeh = False
    for i in range(min(5, n)):
        if seen_yeh:
            run = False
        run = run and word[i] in PREFIX_SET
        pp.append(run)
        if word[i] == ab.YEH:
            seen_yeh = True
    ps = [False] * n
    run = True
    for j in range(n - 1, -1, -1):
        run = run and word[j] in SUFFIX_SET
        ps[j] = run
    return pp, ps


def generate_stems(word: list[int]):
    """Stages 3-4: substring truncation + size filter (VHDL Fig 12 order).

    Returns (tri, quad): lists of stems in prefix-cut-ascending order, with
    validity implied by inclusion.
    """
    n = len(word)
    pp, ps = check_and_produce(word)

    def p_valid(p):
        return p == -1 or (p < len(pp) and pp[p])

    def s_valid(s):
        return s == n or (0 <= s < n and ps[s])

    tri, quad = [], []
    for p in range(-1, 5):
        if not p_valid(p):
            continue
        for L, out in ((3, tri), (4, quad)):
            s = p + 1 + L
            if s <= n and s_valid(s):
                out.append(tuple(word[p + 1 : s]))
    return tri, quad


def extract_root(word_codes, roots: RootDict, infix: bool = True):
    """Full stage-5 compare + infix recovery. Returns (root_tuple, source).

    Priority: direct tri > direct quad > restored tri (ا→و) >
    remove-infix quad→tri > remove-infix tri→bi. The served path has no
    extended rule pool, so neither has this copy.
    """
    word = [int(c) for c in word_codes if int(c) != 0]
    tri, quad = generate_stems(word)

    for st in tri:
        if st in roots.tri:
            return st, SRC_TRI
    for st in quad:
        if st in roots.quad:
            return st, SRC_QUAD
    if infix:
        # Restore Original Form (Fig 19): 2nd char ا → و on trilaterals.
        for st in tri:
            if st[1] == ab.ALEF:
                cand = (st[0], ab.WAW, st[2])
                if cand in roots.tri:
                    return cand, SRC_RESTORED
        # Remove Infix (Fig 18): drop infix 2nd char.
        for st in quad:
            if st[1] in INFIX_SET:
                cand = (st[0], st[2], st[3])
                if cand in roots.tri:
                    return cand, SRC_DEINFIX_TRI
        for st in tri:
            if st[1] in INFIX_SET:
                cand = (st[0], st[2])
                if cand in roots.bi:
                    return cand, SRC_DEINFIX_BI
    return (), SRC_NONE


def stem_rows(rows: np.ndarray, roots: RootDict, *, infix: bool = True):
    """int32[n, 16] word rows -> (roots int32[n, 4], sources int32[n]).

    Each distinct row is stemmed once: the result is a function of the
    row alone, and a corpus repeats its words many times.
    """
    rows = np.ascontiguousarray(rows, np.int32)
    uniq, inverse = unique_rows(rows)
    out_r = np.zeros((uniq.shape[0], 4), np.int32)
    out_s = np.zeros(uniq.shape[0], np.int32)
    for i, row in enumerate(uniq):
        root, src = extract_root(row, roots, infix=infix)
        out_r[i, :len(root)] = root
        out_s[i] = src
    return out_r[inverse], out_s[inverse]


def unique_rows(rows: np.ndarray):
    """-> (distinct rows, inverse) of an int32[n, 16] array of 6-bit codes.

    Rows pack exactly into two 48-bit keys (8 codes each); one stable
    sort of the key pairs groups equal rows.
    """
    if rows.shape[0] == 0:
        return rows, np.zeros(0, np.int64)
    codes = rows.astype(np.uint64)
    shifts = np.arange(7, -1, -1, dtype=np.uint64) * np.uint64(6)
    hi = (codes[:, :8] << shifts).sum(axis=1, dtype=np.uint64)
    lo = (codes[:, 8:] << shifts).sum(axis=1, dtype=np.uint64)
    order = np.lexsort((lo, hi))
    hi_s, lo_s = hi[order], lo[order]
    new = np.ones(order.shape[0], bool)
    new[1:] = (hi_s[1:] != hi_s[:-1]) | (lo_s[1:] != lo_s[:-1])
    group = np.cumsum(new) - 1
    inverse = np.empty(order.shape[0], np.int64)
    inverse[order] = group
    return rows[order[new]], inverse
