"""Normalisation + segmentation rule parity: the host string pipeline,
the jnp reference, and the Pallas text front-end kernel must agree on
every rule in the shared tables (core/textnorm.py) — per diacritic, per
alef variant, per clitic pattern, per function word."""
import numpy as np
import pytest

from repro.core import alphabet as ab
from repro.core import textnorm as tn
from repro.kernels import text_frontend as tf


def _tile(text: str, t: int = 0) -> np.ndarray:
    chars, _, _ = tn.coalesce_docs([text])
    t = t or max(128, -(-chars.shape[0] // 128) * 128)
    tile = np.zeros(t, np.int32)
    tile[:chars.shape[0]] = chars
    return tile


def three_way(text: str, block_w: int = 128):
    """Run host / jnp-reference / kernel on one document, assert parity,
    return the host (words, spans)."""
    words_py, spans_py = tn.analyze_text_py(text)
    tile = _tile(text)
    words_j, geo = tn.frontend_reference(tile, block_w=block_w)
    n = int(geo.n_words)
    assert n == words_py.shape[0]
    np.testing.assert_array_equal(np.asarray(words_j)[:n], words_py)
    np.testing.assert_array_equal(np.asarray(geo.spans)[:n], spans_py)
    words_k = tf.text_frontend_pallas(tile, geo.starts, geo.lens,
                                      block_w=block_w, interpret=True)
    np.testing.assert_array_equal(np.asarray(words_k),
                                  np.asarray(words_j))
    # zero rows past n_words (the stemmer maps them to SRC_NONE)
    assert not np.asarray(words_j)[n:].any()
    return words_py, spans_py


# ---------------------------------------------------------------------------
# table-level rule checks (host side: the single source of truth)
# ---------------------------------------------------------------------------
def test_class_lut_matches_classify_cp_everywhere():
    for off in range(0x100):
        assert tn.CLASS_LUT[off] == tn.classify_cp(0x0600 + off)
    # off-page codepoints are separators by construction
    for cp in (0x20, 0x41, 0x39, 0x5FF, 0x700, 0x1F600):
        assert tn.classify_cp(cp) == tn.CLS_SEP


def test_every_diacritic_and_tatweel_is_a_mark():
    for cp in sorted(ab.DIACRITICS) + [ab.TATWEEL]:
        assert tn.classify_cp(cp) == tn.CLS_MARK, hex(cp)
        assert ab.normalise("د" + chr(cp) + "رس") == "درس", hex(cp)


def test_every_normalise_rule_collapses():
    for src, dst in ab.NORMALISE.items():
        assert tn.classify_cp(src) == ab.CP_TO_CODE[dst], hex(src)
        assert ab.normalise(chr(src)) == chr(dst)
    # the satellite rules named in the issue, explicitly
    assert ab.normalise("ٱ") == "ا"          # alef wasla
    assert ab.normalise("مـــد") == "مد"     # tatweel
    assert ab.normalise("مدرسة") == "مدرست"  # taa marbuta -> teh


def test_encode_is_a_thin_wrapper_over_the_tables():
    # encode_word == normalise + CP_TO_CODE; textnorm letters_py must
    # agree on plain (unsegmented) words
    for w in ("مدرسة", "ٱلرَّحْمَٰنِ", "وَالْكِتَابُ", "مـــدرسة"):
        via_alphabet = [int(c) for c in ab.encode_word(w) if c]
        via_textnorm = tn.letters_py(tuple(map(ord, w)))
        assert via_alphabet == via_textnorm, w


def test_jnp_classify_matches_host_over_page_and_ascii():
    cps = np.asarray(list(range(0x0600, 0x0700))
                     + list(range(0, 0x80)) + [0x5FF, 0x700], np.int32)
    import jax.numpy as jnp

    got = np.asarray(tn.classify_codes(jnp.asarray(cps),
                                       jnp.asarray(tn.CLASS_LUT)))
    want = np.asarray([tn.classify_cp(int(c)) for c in cps], np.int32)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# three-way parity per rule family
# ---------------------------------------------------------------------------
def test_parity_every_diacritic_in_context():
    # one word per mark: د<mark>رس — all three paths must strip it
    words = ["د" + chr(cp) + "رس" for cp in sorted(ab.DIACRITICS)]
    rows, _ = three_way(" ".join(words))
    want = ab.encode_word("درس")
    for i, row in enumerate(rows):
        np.testing.assert_array_equal(row, want)


def test_parity_alef_variants_and_taa_marbuta():
    rows, _ = three_way("آمن أمن إمن ٱمن مدرسة مـــد")
    np.testing.assert_array_equal(rows[0], ab.encode_word("امن"))
    np.testing.assert_array_equal(rows[1], ab.encode_word("امن"))
    np.testing.assert_array_equal(rows[2], ab.encode_word("امن"))
    np.testing.assert_array_equal(rows[3], ab.encode_word("امن"))
    np.testing.assert_array_equal(rows[4], ab.encode_word("مدرست"))
    np.testing.assert_array_equal(rows[5], ab.encode_word("مد"))


@pytest.mark.parametrize("pro", tn.PROCLITICS)
def test_parity_each_proclitic_strips(pro):
    base = "قلم"                      # 3 letters: always >= MIN_STEM
    rows, _ = three_way(pro + base)
    np.testing.assert_array_equal(rows[0], ab.encode_word(base))


@pytest.mark.parametrize("enc", tn.ENCLITICS)
def test_parity_each_enclitic_strips(enc):
    base = "قلم"
    rows, _ = three_way(base + enc)
    np.testing.assert_array_equal(rows[0], ab.encode_word(base))


def test_parity_longest_match_precedence():
    rows, _ = three_way("والقلم للعلم قلمهما وكتبها كتبهما")
    np.testing.assert_array_equal(rows[0], ab.encode_word("قلم"))   # وال not و
    np.testing.assert_array_equal(rows[1], ab.encode_word("علم"))   # لل not ل
    np.testing.assert_array_equal(rows[2], ab.encode_word("قلم"))   # هما not ه/ها
    np.testing.assert_array_equal(rows[3], ab.encode_word("كتب"))   # و + ها
    # single pass, proclitic first: ك strips, then هما is blocked by the
    # MIN_STEM guard (5 - 3 < 3) — the spec'd order, not a bug
    np.testing.assert_array_equal(rows[4], ab.encode_word("تبهما"))


def test_parity_min_stem_guard():
    # stripping must leave >= 3 letters: none of these strip
    rows, _ = three_way("به لك كمن بكر")
    np.testing.assert_array_equal(rows[0], ab.encode_word("به"))
    np.testing.assert_array_equal(rows[1], ab.encode_word("لك"))
    np.testing.assert_array_equal(rows[2], ab.encode_word("كمن"))
    np.testing.assert_array_equal(rows[3], ab.encode_word("بكر"))


def test_parity_every_function_word_is_exempt():
    fws = list(tn.FUNCTION_WORDS)
    rows, _ = three_way(" ".join(fws))
    want = ab.encode_batch(fws)
    np.testing.assert_array_equal(rows, want)


def test_function_word_exemption_vs_stripping():
    # the Snippet-1 example: كانت is exempt; a non-function word with the
    # same shape (كتبت -> ك is NOT stripped as remainder < MIN_STEM after
    # a match? no: كتبت has 4 letters, ك strips to تبت) is not
    rows, _ = three_way("كانت كتبت")
    np.testing.assert_array_equal(rows[0], ab.encode_word("كانت"))
    np.testing.assert_array_equal(rows[1], ab.encode_word("تبت"))


def test_fw_table_layout():
    # sorted, unique, sentinel-padded to whole 128-lane rows
    assert tn.FW_ROWS.ndim == 2 and tn.FW_ROWS.shape[1] == 128
    flat = tn.FW_ROWS.reshape(-1)
    keys = tn.FW_KEYS
    assert (np.diff(keys) > 0).all()
    np.testing.assert_array_equal(flat[:len(keys)], keys)
    assert len(flat) - len(keys) < 128
    assert (flat[len(keys):] == tn.FW_SENTINEL).all()
    assert int(keys[-1]) < int(tn.FW_SENTINEL)


def test_quranic_annotation_marks_strip():
    # U+06D6.. small high signs ride along in Quranic text
    rows, _ = three_way("قلمۖ دۡرس")
    np.testing.assert_array_equal(rows[0], ab.encode_word("قلم"))
    np.testing.assert_array_equal(rows[1], ab.encode_word("درس"))
