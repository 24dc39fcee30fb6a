"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases pass at tiny sizes in interpret mode (so a chip call only finds
what the CPU cannot)."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke as cs  # noqa: E402


def test_chip_smoke_refuses_to_run_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, cwd=tmp_path, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.fixture(scope="module")
def tiny():
    from repro.core import corpus, stemmer

    d = corpus.build_dictionary(n_tri=300, n_quad=40, seed=0)
    resident = stemmer.RootDictArrays.from_rootdict(d)
    return resident, cs.word_requests(6, 16)


@pytest.mark.parametrize("lexicon", ["resident", "streamed"])
def test_chip_smoke_serve_words_phase_tiny(tiny, lexicon):
    from repro.core import corpus
    from repro.kernels import stem_fused as sf

    resident, requests = tiny
    arrays = (resident if lexicon == "resident" else
              corpus.grow_root_arrays(resident, sf.MAX_RESIDENT_KEYS + 4096))
    assert sf.choose_residency(arrays) == lexicon
    cs.phase_serve_words(arrays, requests, lexicon, block_b=32)


def test_chip_smoke_text_and_index_phases_tiny(tiny):
    resident, _ = tiny
    cs.phase_serve_text(resident, n_docs=3, words_per_doc=20)
    cs.phase_index(resident, n_chunks=2, chunk_words=4096)


def test_chip_smoke_checks_catch_a_wrong_result():
    with pytest.raises(cs.SmokeFailure, match="differs"):
        cs.check_equal([1, 2], [1, 3], "roots")
