"""The work each kernel's job needs, counted from the shapes of its inputs.

Bytes are the inputs read once, the outputs written once and the lexicon
read once per launch. Operations are the algorithm's own comparisons:
a membership test against a sorted table of ``k`` keys is
``ceil(log2(k + 1))`` comparisons. How today's kernels do the job (one-hot
MXU fetches, DMA ladders, compare-and-count blocks) is not counted, so a
kernel that does the same job with less work reads a higher share and no
implementation can read over 100%. Integer operations are set against the
chip's int8 peak, the highest integer rate it publishes.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

from bench.gen import alphabet as ab
from bench.gen import textrules as tr

INT = 4                       # bytes of an int32
PREFIX_CUTS = 6               # candidate prefix cuts p = -1..4 per word
ROW = ab.MAXLEN * INT         # one word row


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: Path | None = None) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    path = path or Path(__file__).with_name("peaks.json")
    table = json.loads(path.read_text(encoding="utf-8"))["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}"
                            f" (known: {sorted(table)})")
    return table[device_kind]


def _lg(k: int) -> int:
    return math.ceil(math.log2(k + 1))


@dataclass(frozen=True)
class Work:
    ops: float
    bytes: float

    def roofline(self, seconds: float, peak: dict) -> tuple[float, str]:
        """(share of the roofline in %, the bound that set it) for a kernel
        that did this work in ``seconds`` of device time."""
        t_ops = self.ops / peak["int8_op_per_s"]
        t_mem = self.bytes / peak["hbm_byte_per_s"]
        bound = "ops" if t_ops >= t_mem else "bytes"
        return 100.0 * max(t_ops, t_mem) / seconds, bound


def stemmer(words: int, launches: int, *, tri: int, quad: int, bi: int,
            infix: bool = True) -> Work:
    """Root extraction of ``words`` word rows in ``launches`` launches
    against a lexicon of ``tri``/``quad``/``bi`` keys. Per word: six
    trilateral and six quadrilateral candidates, and with infix
    processing six restored and six de-infixed trilaterals and six
    bilaterals, each one membership test. Out: a 4-code root and a
    source per word."""
    per_word = PREFIX_CUTS * (_lg(tri) + _lg(quad))
    if infix:
        per_word += PREFIX_CUTS * (2 * _lg(tri) + _lg(bi))
    return Work(ops=words * per_word,
                bytes=words * (ROW + 5 * INT) + launches * (tri + quad + bi) * INT)


def frontend(chars: int, words: int) -> Work:
    """Text to word rows: one class lookup per codepoint; per word the
    proclitic and enclitic patterns and the function-word keys. In: the
    codepoints; out: a row and a byte span per word."""
    per_word = (len(tr.PROCLITICS) + len(tr.ENCLITICS)
                + len(tr.FW_KEY_SET))
    return Work(ops=chars + words * per_word,
                bytes=chars * INT + words * (ROW + 2 * INT))


def postings(words: int, launches: int, *, roots: int) -> Work:
    """Grouping ``words`` root ids by root: one search among ``roots``
    per word. In: an id per word; out: a rank per word and a count per
    root per launch."""
    return Work(ops=words * _lg(roots),
                bytes=words * 2 * INT + launches * roots * INT)
