"""Raw-text normalisation + segmentation rules: the shared single source
of truth for the text ingestion front-end (DESIGN.md §7).

Three implementations consume the tables defined here and must agree
bit-for-bit on every document:

  host reference   ``analyze_text_py`` — plain python over strings; the
                   independent oracle the parity tests trust
  jnp reference    ``frontend_reference`` — scatter-based, whole-tile
                   vectorised; what the Pallas kernel must match
  Pallas kernel    ``kernels/text_frontend.py`` — gather-based, one grid
                   step per [block_w] word tile, sharing
                   :func:`strip_and_pack` with the jnp reference (the
                   ``candidate_columns`` precedent: one jnp datapath body
                   traced both standalone and inside the kernel)

The rule pipeline (SNIPPETS.md Snippet 1, ``alif/sentence_validator``):

  classify    every codepoint is a LETTER (dense 6-bit code with
              normalisation baked in: alef variants -> ا, ة -> ت), a
              MARK (diacritics + tatweel: deleted in place, never
              splits a word), or a SEPARATOR (whitespace, punctuation,
              digits, anything non-Arabic — including the 0 pad)
  segment     words are maximal runs of non-separator codepoints;
              each word records its [start, end) utf-8 byte span
  strip       one longest-match proclitic (و ف ب ل ك | لل | وال بال
              فال كال) and one longest-match enclitic (ه ك | ها هم هن
              كم كن نا ني | هما), each only if >= MIN_STEM letters
              remain — EXCEPT for function words: a word whose
              normalised form is in FUNCTION_WORDS is never stripped
              (كانت is the verb "she was", not ك + انت "like you")
  pack        first 15 letters -> the [16] word-tile row the stemmer
              megakernel consumes

Fixed windows keep all three implementations identical on degenerate
input: at most MAX_RAW raw codepoints of a word are examined and at most
CMAX normalised letters kept before stripping, so a 100-codepoint "word"
truncates the same way in a python loop, a jnp scatter, and the kernel's
fixed-size gather.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import alphabet as ab

# ---------------------------------------------------------------------------
# classes + windows
# ---------------------------------------------------------------------------
CLS_SEP = 0       # separator (also the 0 pad codepoint)
CLS_MARK = -1     # diacritic/tatweel: deleted in place, does not split
# class > 0: the letter's dense 6-bit code, normalisation applied

MAX_RAW = 32      # raw codepoints examined per word (letters + marks)
CMAX = 20         # normalised letters kept before clitic stripping
MIN_STEM = 3      # letters a clitic strip must leave (tri stems are the
                  # shortest the candidate grid analyses directly)


def classify_cp(cp: int) -> int:
    """Codepoint -> CLS_SEP | CLS_MARK | dense letter code (> 0)."""
    if cp in ab.DIACRITICS or cp == ab.TATWEEL:
        return CLS_MARK
    return ab.CP_TO_CODE.get(ab.NORMALISE.get(cp, cp), CLS_SEP)


def _build_class_lut() -> np.ndarray:
    lut = np.zeros(0x100, np.int32)
    for off in range(0x100):
        lut[off] = classify_cp(0x0600 + off)
    return lut


# int32[256] over the 0x0600 Arabic page; codepoints outside the page are
# separators by construction (classify_codes range-checks before take)
CLASS_LUT = _build_class_lut()

# ---------------------------------------------------------------------------
# clitic patterns (longest first == match priority) and function words
# ---------------------------------------------------------------------------
PROCLITICS = ("وال", "بال", "فال", "كال", "لل", "و", "ف", "ب", "ل", "ك")
ENCLITICS = ("هما", "ها", "هم", "هن", "كم", "كن", "نا", "ني", "ه", "ك")

# Clitic stripping is NOT applied to these (Snippet 1): particles,
# pronouns, demonstratives and common function verbs whose first/last
# letters happen to look like clitics — stripping them manufactures a
# false analysis (كانت -> ك+انت, لكن -> ل+كن, هل -> ه+ل...). Stored
# unnormalised; keys are built through the same classify pipeline.
FUNCTION_WORDS = (
    # prepositions + particles
    "في", "من", "عن", "إلى", "على", "حتى", "منذ", "عند", "لدى", "مع",
    "بين", "فوق", "تحت", "أمام", "خلف", "وراء", "دون", "بعد", "قبل",
    "ضد", "نحو", "عبر", "بل", "قد", "سوف", "لقد", "هل", "لا", "لم",
    "لن", "ما", "إن", "أن", "لو", "لولا", "لعل", "ليت", "كي", "ثم",
    "أو", "أم", "إذ", "إذا", "لما", "لكن", "إنما", "أيضا", "إلا",
    "أما", "كل", "بعض", "غير", "مثل", "أي",
    # pronouns
    "هو", "هي", "هم", "هن", "هما", "أنا", "نحن", "أنت", "أنتم", "أنتن",
    # demonstratives + relatives
    "هذا", "هذه", "ذلك", "تلك", "هؤلاء", "أولئك", "الذي", "التي",
    "الذين",
    # the basmala nouns: ه/هم endings here are part of the word, not
    # object pronouns (الله -> الل under the enclitic rule otherwise)
    "الله", "اللهم",
    # interrogatives
    "ماذا", "لماذا", "متى", "أين", "كيف", "كم",
    # high-frequency function verbs (the Snippet-1 كانت example)
    "كان", "كانت", "كانوا", "يكون", "ليس", "ليست",
)


def _word_codes(word: str) -> tuple[int, ...]:
    return tuple(c for c in (classify_cp(ord(ch)) for ch in word) if c > 0)


PROCLITIC_CODES = tuple(_word_codes(p) for p in PROCLITICS)
ENCLITIC_CODES = tuple(_word_codes(e) for e in ENCLITICS)

FW_MAXLEN = 5                     # packed exemption key covers <= 5 letters
FW_SENTINEL = np.int32(1 << 30)   # > any packed 5-letter key (64^5 - 1)


def pack5(codes) -> int:
    """<= 5 dense codes -> base-64 key < 2^30 (PAD-extended right)."""
    cs = list(codes)[:FW_MAXLEN]
    cs += [0] * (FW_MAXLEN - len(cs))
    k = 0
    for c in cs:
        k = k * 64 + int(c)
    return k


def _build_fw_keys() -> np.ndarray:
    keys = set()
    for w in FUNCTION_WORDS:
        codes = _word_codes(w)
        if not 0 < len(codes) <= FW_MAXLEN:
            raise AssertionError(
                f"function word {w!r} has {len(codes)} letters; the packed"
                f" exemption key covers 1..{FW_MAXLEN}")
        keys.add(pack5(codes))
    return np.asarray(sorted(keys), np.int32)


FW_KEYS = _build_fw_keys()                 # sorted unique, host membership
FW_KEY_SET = frozenset(int(k) for k in FW_KEYS)


def _fw_rows(keys: np.ndarray, lane: int = 128) -> np.ndarray:
    rp = -(-keys.shape[0] // lane) * lane
    return np.pad(keys, (0, rp - keys.shape[0]),
                  constant_values=FW_SENTINEL).reshape(-1, lane)


# sentinel-padded to whole 128-lane rows: the exemption check compares a
# word's packed key against every entry (a few dozen keys, so the
# all-pairs compare is one vreg row per word tile, in XLA and in the
# kernel alike)
FW_ROWS = _fw_rows(FW_KEYS)


# ---------------------------------------------------------------------------
# host reference (python strings; the oracle)
# ---------------------------------------------------------------------------
def utf8_len(cp: int) -> int:
    return 1 + (cp >= 0x80) + (cp >= 0x800) + (cp >= 0x10000)


def tokenize_py(text: str) -> list[tuple[tuple[int, ...], int, int]]:
    """text -> [(raw codepoints, byte_start, byte_end)] per word.

    Words are maximal runs of non-separator codepoints; byte offsets are
    utf-8 offsets into ``text.encode()``. Mark-only runs (e.g. a stray
    shadda between spaces) still tokenize — they normalise to an empty
    word row, which the stemmer maps to SRC_NONE.
    """
    toks: list[tuple[tuple[int, ...], int, int]] = []
    cur: list[int] = []
    b = b0 = 0
    for ch in text:
        cp = ord(ch)
        if classify_cp(cp) == CLS_SEP:
            if cur:
                toks.append((tuple(cur), b0, b))
                cur = []
        else:
            if not cur:
                b0 = b
            cur.append(cp)
        b += utf8_len(cp)
    if cur:
        toks.append((tuple(cur), b0, b))
    return toks


def letters_py(cps) -> list[int]:
    """Raw word codepoints -> normalised letter codes (windows applied)."""
    codes: list[int] = []
    for cp in tuple(cps)[:MAX_RAW]:
        c = classify_cp(cp)
        if c > 0:
            codes.append(c)
            if len(codes) == CMAX:
                break
    return codes


def strip_clitics_py(codes) -> tuple[list[int], int, int]:
    """Letter codes -> (stripped codes, proclitic len, enclitic len)."""
    codes = list(codes)
    n = len(codes)
    if n <= FW_MAXLEN and pack5(codes) in FW_KEY_SET:
        return codes, 0, 0
    pro = 0
    for pat in PROCLITIC_CODES:
        ln = len(pat)
        if n - ln >= MIN_STEM and tuple(codes[:ln]) == pat:
            pro = ln
            break
    rem = codes[pro:]
    m = len(rem)
    enc = 0
    for pat in ENCLITIC_CODES:
        ln = len(pat)
        if m - ln >= MIN_STEM and tuple(rem[m - ln:]) == pat:
            enc = ln
            break
    return (rem[:m - enc] if enc else rem), pro, enc


def word_row_py(cps) -> np.ndarray:
    """Raw word codepoints -> the int32[16] stemmer word-tile row."""
    codes, _, _ = strip_clitics_py(letters_py(cps))
    row = codes[:ab.MAXLEN - 1]
    return np.asarray(row + [0] * (ab.MAXLEN - len(row)), np.int32)


def analyze_text_py(text: str) -> tuple[np.ndarray, np.ndarray]:
    """Document -> (words int32[W, 16], spans int32[W, 2] byte offsets)."""
    toks = tokenize_py(text)
    if not toks:
        return (np.zeros((0, ab.MAXLEN), np.int32),
                np.zeros((0, 2), np.int32))
    words = np.stack([word_row_py(cps) for cps, _, _ in toks])
    spans = np.asarray([[b0, b1] for _, b0, b1 in toks], np.int32)
    return words, spans


def coalesce_docs(docs) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Documents -> one codepoint tile with a single 0 separator between
    consecutive docs; returns (chars int32[T], char_offsets int64[D],
    byte_offsets int64[D]) — the offsets of each doc's first codepoint /
    utf-8 byte inside the coalesced tile, so per-tile word positions and
    byte spans map back to per-document ones by subtraction.
    """
    parts: list[np.ndarray] = []
    char_off, byte_off = [], []
    c = b = 0
    for i, d in enumerate(docs):
        if i:
            parts.append(np.zeros(1, np.int32))
            c += 1
            b += 1
        char_off.append(c)
        byte_off.append(b)
        if d:
            parts.append(np.frombuffer(
                d.encode("utf-32-le"), np.uint32).astype(np.int32))
        c += len(d)
        b += len(d.encode("utf-8"))
    chars = (np.concatenate(parts) if parts else np.zeros(0, np.int32))
    return (chars, np.asarray(char_off, np.int64),
            np.asarray(byte_off, np.int64))


# ---------------------------------------------------------------------------
# shared jnp bodies (traced standalone by the reference AND inside the
# Pallas kernel — tables ride in as arguments, never captured constants)
# ---------------------------------------------------------------------------
def classify_codes(chars, lut):
    """int32[...] codepoints -> class, via the CLASS_LUT tile ``lut``
    (int32[256]); anything off the 0x0600 page is a separator."""
    off = chars - 0x0600
    in_page = (off >= 0) & (off < 0x100)
    return jnp.where(in_page,
                     jnp.take(lut, jnp.clip(off, 0, 0xFF), mode="clip"),
                     CLS_SEP)


def _iota_row(n: int):
    """int32[1, n] 0..n-1 (2-D: Mosaic has no 1-D iota)."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)


def strip_and_pack(codes, lens, fw_rows):
    """Normalised letter rows -> stripped, packed word-tile rows.

    codes int32[n, CMAX]  left-aligned letter codes, 0 beyond ``lens``
    lens  int32[n]        letters per row (<= CMAX)
    fw_rows int32[r, 128] FW_ROWS (function-word keys, sentinel-padded)
    -> int32[n, 16]

    Branchless and gather-free, so it lowers inside a TPU kernel:
    function-word exemption by comparing the packed 5-letter key against
    every FW_ROWS entry; proclitic as a first-match scan over the
    pattern list (longest first); enclitic chars located by one-hot sums
    at absolute position lens - L + k; the proclitic shift realised as a
    select over the 4 static shifts.
    """
    codes = codes.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    n, cm = codes.shape
    key5 = ((((codes[:, 0] * 64 + codes[:, 1]) * 64 + codes[:, 2]) * 64
             + codes[:, 3]) * 64 + codes[:, 4])
    listed = jnp.zeros((n,), bool)
    for r in range(fw_rows.shape[0]):
        listed |= jnp.any(key5[:, None] == fw_rows[r:r + 1, :], axis=1)
    exempt = (lens <= FW_MAXLEN) & listed

    pro = jnp.zeros((n,), jnp.int32)
    found = exempt
    for pat in PROCLITIC_CODES:
        ln = len(pat)
        m = lens - ln >= MIN_STEM
        for k, c in enumerate(pat):
            m &= codes[:, k] == c
        pro = jnp.where(m & ~found, ln, pro)
        found |= m

    rem_len = lens - pro
    j = _iota_row(cm)

    def char_at(pos):   # codes[i, pos[i]] without a gather (one-hot sum)
        return jnp.sum(jnp.where(j == pos[:, None], codes, 0), axis=1)

    enc = jnp.zeros((n,), jnp.int32)
    found = exempt
    for pat in ENCLITIC_CODES:
        ln = len(pat)
        m = rem_len - ln >= MIN_STEM
        for k, c in enumerate(pat):
            # the enclitic's chars sit at absolute column lens - ln + k
            # regardless of the proclitic cut (both count from the left)
            m &= char_at(lens - ln + k) == c
        enc = jnp.where(m & ~found, ln, enc)
        found |= m

    out_len = jnp.minimum(rem_len - enc, ab.MAXLEN - 1)
    # shift left by pro (0..3): select over the static shifts; cm >= 19
    # guarantees every [p, p + 16) window exists
    shifted = jnp.zeros((n, ab.MAXLEN), jnp.int32)
    for p in sorted({len(pat) for pat in PROCLITIC_CODES} | {0}):
        shifted = jnp.where((pro == p)[:, None],
                            codes[:, p:p + ab.MAXLEN], shifted)
    keep = _iota_row(ab.MAXLEN) < out_len[:, None]
    return jnp.where(keep, shifted, 0)


def class_windows(chars, starts, lens):
    """Per-word class windows: int32[T] codepoints + word starts/lengths
    int32[W] -> int32[W, MAX_RAW], the class of each word's first MAX_RAW
    raw codepoints, CLS_SEP past the word's end. The irregular
    per-word read of the codepoint tile, done as one XLA gather ahead of
    the front-end kernel."""
    cls = classify_codes(chars, jnp.asarray(CLASS_LUT))
    idx = starts[:, None] + _iota_row(MAX_RAW)
    win = jnp.take(cls, jnp.clip(idx, 0, chars.shape[0] - 1), mode="clip")
    live = _iota_row(MAX_RAW) < jnp.minimum(lens, MAX_RAW)[:, None]
    return jnp.where(live, win, CLS_SEP)


# ---------------------------------------------------------------------------
# jnp geometry pre-pass + scatter-based reference
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TextGeometry:
    """Per-word layout of a codepoint tile (all jnp, shapes static).

    starts  int32[Wp]    char index of each word's first codepoint
    lens    int32[Wp]    raw codepoint count (un-windowed; 0 past n_words)
    spans   int32[Wp,2]  utf-8 byte [start, end) into the tile's encoding
    n_words int32        actual word count (rows past it are zero)
    """

    starts: object
    lens: object
    spans: object
    n_words: object


def _word_capacity(t: int, block_w: int, max_words) -> int:
    w = (t // 2 + 1) if max_words is None else max_words
    return -(-w // block_w) * block_w


def segment_geometry(chars, *, block_w: int = 128,
                     max_words: int | None = None) -> TextGeometry:
    """Codepoint tile -> word starts/lengths/byte spans (scatter-based).

    The capacity default T // 2 + 1 is exact (words alternate with at
    least one separator), so no word is ever dropped unless the caller
    caps ``max_words`` below the true count.
    """
    chars = jnp.asarray(chars, jnp.int32)
    t = chars.shape[0]
    if t == 0:
        raise ValueError("segment_geometry needs a non-empty codepoint"
                         " tile; pad with the 0 separator")
    wp = _word_capacity(t, block_w, max_words)
    cls = classify_codes(chars, jnp.asarray(CLASS_LUT))
    is_word = cls != CLS_SEP
    prev = jnp.concatenate([jnp.zeros(1, bool), is_word[:-1]])
    nxt = jnp.concatenate([is_word[1:], jnp.zeros(1, bool)])
    wstart = is_word & ~prev
    wend = is_word & ~nxt
    wid = jnp.cumsum(wstart.astype(jnp.int32)) - 1
    n_words = jnp.sum(wstart.astype(jnp.int32))
    idx = jnp.arange(t, dtype=jnp.int32)
    drop = jnp.int32(wp)                       # OOB row -> mode="drop"
    sidx = jnp.where(wstart, wid, drop)
    eidx = jnp.where(wend, wid, drop)
    starts = jnp.zeros(wp, jnp.int32).at[sidx].set(idx, mode="drop")
    ends = jnp.zeros(wp, jnp.int32).at[eidx].set(idx, mode="drop")
    blen = (1 + (chars >= 0x80).astype(jnp.int32)
            + (chars >= 0x800).astype(jnp.int32)
            + (chars >= 0x10000).astype(jnp.int32))
    boff = jnp.cumsum(blen) - blen             # bytes before each char
    b0 = jnp.zeros(wp, jnp.int32).at[sidx].set(boff, mode="drop")
    b1 = jnp.zeros(wp, jnp.int32).at[eidx].set(boff + blen, mode="drop")
    valid = jnp.arange(wp) < n_words
    lens = jnp.where(valid, ends - starts + 1, 0)
    spans = jnp.where(valid[:, None], jnp.stack([b0, b1], axis=-1), 0)
    return TextGeometry(starts=jnp.where(valid, starts, 0), lens=lens,
                        spans=spans, n_words=n_words)


def frontend_reference(chars, *, block_w: int = 128,
                       max_words: int | None = None):
    """Pure-jnp front end: codepoint tile -> (words int32[Wp, 16],
    TextGeometry). Bit-identical to the host reference row-by-row and to
    kernels.text_frontend.text_frontend_pallas (which shares
    strip_and_pack but gathers per word instead of scattering per char).
    """
    chars = jnp.asarray(chars, jnp.int32)
    t = chars.shape[0]
    geo = segment_geometry(chars, block_w=block_w, max_words=max_words)
    wp = geo.starts.shape[0]
    lut = jnp.asarray(CLASS_LUT)
    cls = classify_codes(chars, lut)
    is_word = cls != CLS_SEP
    is_letter = cls > 0
    prev = jnp.concatenate([jnp.zeros(1, bool), is_word[:-1]])
    wid = jnp.cumsum((is_word & ~prev).astype(jnp.int32)) - 1
    start_of = jnp.take(geo.starts, jnp.clip(wid, 0, wp - 1), mode="clip")
    raw_off = jnp.arange(t, dtype=jnp.int32) - start_of
    g_excl = jnp.cumsum(is_letter.astype(jnp.int32)) - is_letter
    pos = g_excl - jnp.take(g_excl, start_of, mode="clip")
    cond = is_letter & (raw_off < MAX_RAW) & (pos < CMAX) & (wid < wp)
    rows = jnp.where(cond, wid, wp)            # OOB -> dropped
    grid = jnp.zeros((wp, CMAX), jnp.int32).at[rows, pos].set(
        cls, mode="drop")
    nlet = jnp.zeros(wp, jnp.int32).at[rows].add(1, mode="drop")
    words = strip_and_pack(grid, nlet, jnp.asarray(FW_ROWS))
    return words, geo
