"""Seeded generators of the benchmark's data: lexicons and traffic.

Started as copies of the program's own generators
(``repro/core/corpus.py`` and ``repro/launch/serve.build_documents``) and
kept with the benchmark, so that a change to the program cannot move the
yardstick. Unlike the originals, words are drawn over every root of the
run's lexicon (its seeded pseudo-roots too), and text can carry names
and loanwords that no root explains. Everything here is a function of
its arguments and a seed; nothing touches JAX.

Sizes that shape the work (document lengths, chapter lengths, query
lengths, arrival gaps) are drawn from a fixed generator, so every seed
gets the same set of sizes; the run's seed only orders them and picks
the words, and the lexicon's pseudo-roots.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from bench.gen import alphabet as ab
from bench.gen import conjugator
from bench.gen import textrules as tr
from bench.ref.stemmer import RootDict

# The sizes every seed shares are drawn from this fixed generator.
SHAPE_SEED = 20260

# The paper's Table 7 roots first.
TABLE7_ROOTS = ["علم", "كفر", "قول", "نفس", "نزل", "عمل", "خلق", "جعل", "كذب", "كون"]

REAL_TRI_ROOTS = TABLE7_ROOTS + [
    "كتب", "درس", "لعب", "سقي", "قرا", "فتح", "نصر", "ضرب", "سمع", "بصر",
    "قلب", "رحم", "غفر", "صبر", "شكر", "ذكر", "دخل", "خرج", "رجع", "وصل",
    "قطع", "جمع", "فرق", "حمل", "رفع", "وضع", "منع", "دفع", "قتل", "ولد",
    "كبر", "صغر", "طلب", "وجد", "فقد", "اكل", "شرب", "قوم", "جلس", "مشي",
    "جري", "سبح", "زرع", "حصد", "بيع", "ملك", "حكم", "عدل", "ظلم", "صدق",
    "حسب", "عدد", "قسم", "ضعف", "سعد", "حزن", "فرح", "غضب", "خوف", "رجو",
    "دعو", "سجد", "ركع", "طهر", "حرم", "وجب", "سقط", "نهض", "بني", "هدم",
    "سكن", "رحل", "سفر", "عبر", "غرق", "هلك", "سلم", "نظر", "سال", "جوب",
    "حضر", "غيب", "قرب", "بعد", "وقف", "سير", "طير", "نوم", "صحو", "موت",
    "حيي", "زاد", "نقص", "بدا", "ختم", "وعد", "نكث", "شهد", "غزو", "صون",
    "ذهب", "جاء", "عرف", "جهل", "فهم", "حفظ", "نسي", "صنع", "كسب", "خسر",
    "ربح", "تجر", "زور", "صار", "ظهر", "بطن", "علن", "خفي", "كشف", "ستر",
]

REAL_QUAD_ROOTS = [
    "دحرج", "زلزل", "ترجم", "بعثر", "طمان", "وسوس", "زخرف", "سيطر",
    "هيمن", "عسكر", "قهقه", "غرغر", "ثرثر", "برهن", "سلسل", "زحزح",
]

REAL_BI_ROOTS = [
    "مد", "شد", "ظن", "عد", "حب", "حج", "حس", "حق", "حل", "دق",
    "دل", "رد", "سب", "سد", "شق", "صب", "صد", "ضل", "ضم", "عض",
    "غش", "فر", "قص", "كف", "لف", "لم", "مس", "من", "هز", "ود",
]

# Letters used for pseudo-root sampling: strong consonants only, so random
# roots neither collide with affix machinery nor look degenerate.
_STRONG = list("بجدحخذرزسشصضطظعغفقكلمهث")


def _pseudo_roots(n: int, length: int, seed: int, taken: set) -> list[str]:
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        letters = rng.choice(len(_STRONG), size=length)
        if len(set(letters.tolist())) < length:  # no geminates in pseudo roots
            continue
        r = "".join(_STRONG[i] for i in letters)
        if r in taken:
            continue
        taken.add(r)
        out.append(r)
    return out


def build_dictionary(n_tri: int, n_quad: int, seed: int) -> RootDict:
    """The real roots, then seeded pseudo-roots up to ``n_tri`` trilateral
    and ``n_quad`` quadrilateral roots, and the 30 real bilateral roots."""
    taken = set(REAL_TRI_ROOTS) | set(REAL_QUAD_ROOTS)
    tri = REAL_TRI_ROOTS + _pseudo_roots(max(0, n_tri - len(REAL_TRI_ROOTS)), 3, seed, taken)
    quad = REAL_QUAD_ROOTS + _pseudo_roots(max(0, n_quad - len(REAL_QUAD_ROOTS)), 4, seed + 1, taken)
    return RootDict.from_words(tri=tri, quad=quad, bi=REAL_BI_ROOTS)


def packed_tables(roots: RootDict) -> dict[str, np.ndarray]:
    """Sorted packed int32 keys per table, as the served path takes them
    (an empty table holds the one key -1)."""
    def pack(table):
        return np.asarray(sorted(ab.pack_key(r) for r in table) or [-1], np.int32)

    return {"tri": pack(roots.tri), "quad": pack(roots.quad), "bi": pack(roots.bi)}


def _zipf(n: int, a: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** (-a)
    return p / p.sum()


# Names and loanwords of 1990s wire news, written as Arabic newswire
# writes them: words that the analyser's lexicon does not explain.
NAMES = (
    "واشنطن", "أمريكا", "كلينتون", "بوش", "فرنسا", "باريس", "لندن",
    "بريطانيا", "موسكو", "روسيا", "يلتسين", "ألمانيا", "برلين", "إيطاليا",
    "روما", "إسبانيا", "مدريد", "اليابان", "طوكيو", "الصين", "بكين",
    "الهند", "باكستان", "إيران", "طهران", "تركيا", "أنقرة", "إسرائيل",
    "نتنياهو", "باراك", "شارون", "أوروبا", "أفريقيا", "آسيا", "كندا",
    "أستراليا", "البرازيل", "الأرجنتين", "المكسيك", "كوبا", "كاسترو",
    "كوسوفو", "البوسنة", "سراييفو", "بلغراد", "ميلوسيفيتش", "الناتو",
    "اليونسكو", "أوبك", "الفيفا", "جنيف", "فيينا", "نيويورك", "شيكاغو",
    "كاليفورنيا", "تكساس", "أولبرايت", "أنان", "شيراك", "جوسبان", "بلير",
    "شرودر", "كول", "بوتين", "تلفزيون", "راديو", "كمبيوتر", "إنترنت",
    "تكنولوجيا", "ديمقراطية", "استراتيجية", "برلمان", "دولار", "يورو",
    "فرنك", "بنك", "بورصة", "أولمبياد", "كيلومتر", "كيلوغرام", "ميغاواط",
    "بترول", "غاز", "تلفون", "فيديو", "هوليوود", "ميكروسوفت", "بوينغ",
    "إيرباص",
)

# Stand-ins for a root's letters: a root of distinct letters none of
# which is weak (و ي ا) conjugates to the same forms as these slots, with
# its letters put in their places.
_SLOTS = "\ue000\ue001\ue002\ue003"
_WEAK = set("وىيا")


@functools.lru_cache(maxsize=None)
def _templates(length: int) -> tuple:
    forms = [w for w, _ in conjugator.conjugate(_SLOTS[:length], rich=True)]
    return tuple(dict.fromkeys(forms))


@functools.lru_cache(maxsize=None)
def _conjugated(root: str) -> tuple:
    return tuple(dict.fromkeys(w for w, _ in conjugator.conjugate(root, rich=True)))


def _templated(root: str) -> bool:
    return not (_WEAK & set(root)) and len(set(root)) == len(root)


def _fill(root: str, template: str) -> str:
    return template.translate(str.maketrans(dict(zip(_SLOTS, root))))


def root_forms(root: str, n: int | None = None) -> tuple:
    """The first ``n`` (default: every) distinct conjugated forms of
    ``root``, in ``conjugate``'s order."""
    if not _templated(root):
        return _conjugated(root)[:n]
    return tuple(_fill(root, t) for t in _templates(len(root))[:n])


def n_forms(root: str) -> int:
    return len(_templates(len(root)) if _templated(root) else _conjugated(root))


def root_form(root: str, k: int) -> str:
    """The ``k``-th of ``root_forms(root)``."""
    if not _templated(root):
        return _conjugated(root)[k]
    return _fill(root, _templates(len(root))[k])


def _lexicon_roots(roots: RootDict) -> list[str]:
    """The lexicon's tri- and quadrilateral roots as strings, the real
    roots first (the most frequent under a Zipf draw), then the rest in
    key order."""
    letter = {code: chr(cp) for cp, code in ab.CP_TO_CODE.items() if code}
    real = REAL_TRI_ROOTS + REAL_QUAD_ROOTS
    rest = sorted("".join(letter[c] for c in key)
                  for key in roots.tri | roots.quad)
    taken = set(real)
    return real + [r for r in rest if r not in taken]


# ---------------------------------------------------------------------------
# the token table: every surface token, and its front-end word row
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TokenTable:
    """Distinct surface tokens.

    texts  tuple[str]            surface forms (clitics attached)
    probs  float64[n_tokens]     Zipf over roots, uniform over a root's tokens
    """

    texts: tuple
    probs: np.ndarray

    @property
    def n_tokens(self) -> int:
        return len(self.texts)

    def rows(self, ids: np.ndarray) -> np.ndarray:
        """The front end's int32[n, 16] word row of each token id."""
        uniq, inverse = np.unique(ids, return_inverse=True)
        rows = np.stack([tr.word_row_py(tuple(map(ord, self.texts[t])))
                         for t in uniq.tolist()])
        return rows[inverse.reshape(-1)]


def build_token_table(roots: RootDict, *, forms_per_root: int = 24,
                      clitic_every: int = 3, zipf_a: float = 1.3) -> TokenTable:
    """Every root's first ``forms_per_root`` conjugated forms; every
    ``clitic_every``-th form also with a proclitic, the next with an
    enclitic (cycled, not sampled: the table holds no randomness beyond
    the lexicon's). Zipf over the lexicon's roots, the real roots first."""
    names = _lexicon_roots(roots)
    root_p = _zipf(len(names), zipf_a)
    texts, probs = [], []
    pro, enc = tr.PROCLITICS, tr.ENCLITICS
    for ridx, root in enumerate(names):
        forms = root_forms(root, forms_per_root)
        toks = list(forms)
        for i, w in enumerate(forms):
            if clitic_every and i % clitic_every == 0:
                toks.append(pro[(ridx + i) % len(pro)] + w)
            if clitic_every and i % clitic_every == 1:
                toks.append(w + enc[(ridx + i) % len(enc)])
        toks = list(dict.fromkeys(toks))
        texts.extend(toks)
        probs.extend([root_p[ridx] / len(toks)] * len(toks))
    probs = np.asarray(probs, np.float64)
    return TokenTable(texts=tuple(texts), probs=probs / probs.sum())


# ---------------------------------------------------------------------------
# raw text: documents and queries
# ---------------------------------------------------------------------------
COMMA = "\u060c"


def fixed_lengths(n: int, *, median: float, sigma: float, lo: int,
                  hi: int) -> np.ndarray:
    """``n`` lognormal lengths clipped to [lo, hi], the same for every
    seed (drawn from SHAPE_SEED)."""
    rng = np.random.default_rng(SHAPE_SEED)
    x = rng.lognormal(np.log(median), sigma, size=n)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def draw_words(n: int, table: TokenTable, rng, names_share: float) -> list[str]:
    """``n`` words: each a name or loanword (uniform over NAMES) with
    probability ``names_share``, else a token of the table."""
    toks = rng.choice(table.n_tokens, size=n, p=table.probs)
    name = rng.random(n) < names_share
    pick = rng.integers(len(NAMES), size=n)
    return [NAMES[k] if m else table.texts[t]
            for t, m, k in zip(toks.tolist(), name.tolist(), pick.tolist())]


def render_text(words: list[str], *, comma_every: int) -> str:
    """Words -> one raw document, an Arabic comma after every
    ``comma_every``-th word, as ``build_documents`` renders them."""
    return " ".join(w + COMMA if comma_every and j % comma_every == comma_every - 1
                    else w for j, w in enumerate(words))


def documents(seed: int, table: TokenTable, *, count: int, words_median: float,
              words_sigma: float, words_min: int, words_max: int,
              names_share: float = 0.0, comma_every: int = 0) -> list[str]:
    """``count`` raw documents: the fixed length set in a seeded order,
    words drawn by the seed."""
    rng = np.random.default_rng(seed)
    lengths = rng.permutation(fixed_lengths(
        count, median=words_median, sigma=words_sigma, lo=words_min,
        hi=words_max))
    words = draw_words(int(lengths.sum()), table, rng, names_share)
    out, at = [], 0
    for n in lengths.tolist():
        out.append(render_text(words[at:at + n], comma_every=comma_every))
        at += n
    return out


def uniform_lengths(n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` lengths spread evenly over lo..hi, the same for every seed."""
    return lo + np.arange(n) % (hi - lo + 1)


def queries(seed: int, table: TokenTable, *, count: int, words_min: int,
            words_max: int, names_share: float = 0.0) -> list[str]:
    """``count`` short raw queries of ``words_min``..``words_max`` words."""
    rng = np.random.default_rng(seed)
    lengths = rng.permutation(uniform_lengths(count, words_min, words_max))
    words = draw_words(int(lengths.sum()), table, rng, names_share)
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    return [" ".join(words[a:b]) for a, b in zip(bounds[:-1], bounds[1:])]


def arrival_gaps(n: int, seconds: float) -> np.ndarray:
    """``n`` exponential gaps (Poisson arrivals) summing to ``seconds``,
    the same set for every seed."""
    g = np.random.default_rng(SHAPE_SEED).exponential(size=n)
    return g * (seconds / g.sum())


# ---------------------------------------------------------------------------
# pre-encoded word streams: chapters and corpus chunks
# ---------------------------------------------------------------------------
def chapter_lengths(n: int, *, total: int, lo: int, hi: int) -> np.ndarray:
    """``n`` heavy-tailed lengths from ``lo`` to ``hi`` words that sum to
    ``total``: a fixed lognormal draw mapped onto [log lo, log hi] with
    the exponent that meets the total (the same for every seed)."""
    x = np.sort(np.random.default_rng(SHAPE_SEED).lognormal(size=n))
    u = (np.log(x) - np.log(x[0])) / (np.log(x[-1]) - np.log(x[0]))

    def lengths(gamma):
        return np.rint(np.exp(np.log(lo) + (np.log(hi) - np.log(lo)) * u ** gamma))

    a, b = 0.01, 100.0
    for _ in range(100):
        mid = np.sqrt(a * b)
        a, b = (mid, b) if lengths(mid).sum() > total else (a, mid)
    out = lengths(b).astype(np.int64)
    out[-2] += total - out.sum()   # rounding remainder onto the second longest
    return out


def chapters(seed: int, roots: RootDict, *, count: int, total_words: int,
             words_min: int, words_max: int, zipf_a: float) -> list[np.ndarray]:
    """``count`` pre-encoded chapters (int32[n, 16] word rows) of the fixed
    lengths in a seeded order. Words are conjugated forms of the
    lexicon's roots, Zipf over the roots (the real roots first) and
    uniform over a root's forms, encoded the way ``build_corpus`` +
    ``encode_corpus`` encode them."""
    rng = np.random.default_rng(seed)
    lengths = rng.permutation(chapter_lengths(count, total=total_words,
                                              lo=words_min, hi=words_max))
    names = _lexicon_roots(roots)
    sizes = np.asarray([n_forms(r) for r in names])
    n = int(lengths.sum())
    ridx = rng.choice(len(names), size=n, p=_zipf(len(names), zipf_a))
    fidx = (rng.random(n) * sizes[ridx]).astype(np.int64)
    pair = ridx * 4096 + fidx
    uniq, inverse = np.unique(pair, return_inverse=True)
    enc = np.stack([ab.encode_word(root_form(names[p // 4096], p % 4096))
                    for p in uniq.tolist()])
    rows = enc[inverse.reshape(-1)]
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    return [rows[a:b] for a, b in zip(bounds[:-1], bounds[1:])]


@dataclass(frozen=True)
class CorpusChunk:
    """One slice of a pre-encoded corpus, as ``build_corpus_index`` takes it.

    words      int32[n, 16]  front-end word rows
    doc_ids    int64[n]      global document id per word
    positions  int32[n]      word position within its document
    start_word int           global index of words[0] in the corpus
    """

    words: np.ndarray
    doc_ids: np.ndarray
    positions: np.ndarray
    start_word: int

    @property
    def n_words(self) -> int:
        return self.words.shape[0]


def corpus_chunks(seed: int, table: TokenTable, *, chunks: int,
                  chunk_words: int, words_per_doc: int) -> list[CorpusChunk]:
    """A seeded corpus of ``chunks`` x ``chunk_words`` word rows; chunk
    ``c`` is drawn from ``default_rng([seed, c])``. Documents are
    ``words_per_doc`` words long and run across chunk boundaries."""
    out = []
    for c in range(chunks):
        w0 = c * chunk_words
        rng = np.random.default_rng([seed, c])
        tok = rng.choice(table.n_tokens, size=chunk_words, p=table.probs)
        gwi = w0 + np.arange(chunk_words, dtype=np.int64)
        out.append(CorpusChunk(words=table.rows(tok),
                               doc_ids=gwi // words_per_doc,
                               positions=(gwi % words_per_doc).astype(np.int32),
                               start_word=w0))
    return out
