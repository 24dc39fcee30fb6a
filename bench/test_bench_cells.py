"""Whole runs of the harness on the CPU, at sizes a test can hold.

The look for a chip is skipped (``find_chips`` is replaced); everything
else runs as on the chip: the cell's files are resolved by name, data is
made from the seed, the system is warmed up and driven through a short
window, and ``correct`` is decided against the plain reference. The
control and every planted fault a cell can have must come out
``correct: false``; a clean run must come out true.
"""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SEED = 2**31 + 99
TINY = {
    "docs": {"clients": 2, "check_sample": 4,
             "payload": {"count": 6, "words_median": 40, "words_min": 5,
                         "words_max": 80}},
    "queries": {"rate_per_s": 8, "check_sample": 8},
    "words": {"clients": 2,
              "payload": {"count": 6, "total_words": 600, "words_min": 10,
                          "words_max": 300}},
    "index": {"payload": {"chunks": 2, "chunk_words": 4096}},
}


# every mix of bench/traffic/, as a cell, whether or not BENCHMARK.json
# measures it yet
CELLS = {"newswire.docs": ("newswire", "docs"),
         "newswire.queries": ("newswire", "queries"),
         "quran.words": ("quran", "words"),
         "newswire-x4.index": ("newswire-x4", "index")}


def _tiny_root(tmp: Path) -> Path:
    """A checkout holding a copy of ``bench/`` with every mix cut to a
    test's size and every cell on one device."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, cut in TINY.items():
        path = tmp / "bench" / "traffic" / f"{name}.json"
        mix = json.loads(path.read_text())
        payload = {**mix["payload"], **cut.get("payload", {})}
        mix.update(cut)
        mix["payload"] = payload
        path.write_text(json.dumps(mix))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    have = {c["name"] for c in bench["workloads"]}
    bench["workloads"] += [{"name": n, "config": c, "traffic": t, "why": "test"}
                           for n, (c, t) in CELLS.items() if n not in have]
    have = {c["name"] for c in bench["configs"]}
    bench["configs"] += [{"name": c, "file": f"bench/configs/{c}.json"}
                         for c, _t in CELLS.values() if c not in have]
    measured = {m["name"] for m in bench["end_to_end"]}
    bench["end_to_end"] += [
        {"name": m, "unit": "u", "workloads": [n]}
        for m, n in (("p95_ms", "newswire.queries"),
                     ("index_words_per_s", "newswire-x4.index"))
        if m not in measured]
    for cell in bench["workloads"]:
        cell["chips"] = 1
    for entry in bench["configs"]:
        path = tmp / entry["file"]
        cfg = json.loads(path.read_text())
        cfg["chips"] = 1
        path.write_text(json.dumps(cfg))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture
def harness(monkeypatch, tmp_path):
    import jax

    from bench import run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(run, "find_chips", lambda n: (
        jax.devices()[:n], {"int8_op_per_s": 393e12, "hbm_byte_per_s": 819e9}))
    return run


def _run(harness, root, capsys, cell, fault="none", trace=0):
    """One run of ``cell``; ``fault`` is ``none``, ``control`` (the run's
    own option) or a fault of ``bench/faults.py`` planted under it."""
    from bench import faults

    argv = ["--workload", cell, "--seed", str(SEED), "--seconds", "0.5",
            "--trace", str(trace)]
    if fault in ("none", "control"):
        rc = harness.main(argv + ["--fault", fault], root=root)
    else:
        remove = faults.plant(fault)
        try:
            rc = harness.main(argv, root=root)
        finally:
            remove()
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell, fault, correct", [
    ("quran.words", "none", True),
    ("quran.words", "control", False),
    ("quran.words", "answer", False),
    ("quran.words", "unchanged", False),
    ("newswire.docs", "none", True),
    ("newswire.docs", "control", False),
    ("newswire.docs", "frontend", False),
    ("newswire.queries", "none", True),
    ("newswire.queries", "control", False),
    ("newswire-x4.index", "none", True),
    ("newswire-x4.index", "control", False),
    ("newswire-x4.index", "exchange", False),
])
def test_correct_catches_control_and_faults(harness, tiny, capsys, cell,
                                            fault, correct):
    res = _run(harness, tiny, capsys, cell, fault)
    assert res["correct"] is correct, res["check"]
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "check"
    assert res["attempted"] > 0
    if correct:
        assert all(v["value"] <= v["limit"] for v in res["check"].values())
        assert "setup_s" in res["metrics"]


def test_a_new_cell_is_files_and_entries(harness, tmp_path, capsys):
    """A configuration, a mix and a per-layer metric added as new files,
    with entries in BENCHMARK.json, run with no edit to any file that
    was already there."""
    root = _tiny_root(tmp_path / "checkout")

    def digests():
        return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
                for p in (root / "bench").rglob("*") if p.is_file()}

    before = digests()
    b = root / "bench"
    cfg = json.loads((b / "configs" / "quran.json").read_text())
    cfg.update(name="pocket", lexicon={"n_tri": 200, "n_quad": 20, "n_bi": 30})
    (b / "configs" / "pocket.json").write_text(json.dumps(cfg))
    (b / "traffic" / "trickle.json").write_text(json.dumps({
        "entry": "words", "loop": "closed", "clients": 1,
        "payload": {"kind": "chapters", "count": 3, "total_words": 90,
                    "words_min": 10, "words_max": 50, "zipf_a": 1.3}}))
    (b / "layers" / "engine.requests_per_launch.trickle.json").write_text(
        json.dumps({"layer": "Engine (serve/engine.py)", "unit": "requests",
                    "moves": "words_per_s", "workloads": ["pocket.trickle"],
                    "reader": "counter_ratio", "num": "requests",
                    "den": "stemmer_launches"}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "pocket", "source": "https://arxiv.org/abs/1904.07148",
                             "file": "bench/configs/pocket.json", "reduced": [],
                             "why": "a test's pocket lexicon"})
    bench["workloads"].append({"name": "pocket.trickle", "config": "pocket",
                               "traffic": "trickle", "chips": 1,
                               "why": "one client"})
    bench["end_to_end"][0]["workloads"].append("pocket.trickle")
    bench["per_layer"].append({"name": "engine.requests_per_launch.trickle",
                               "unit": "requests", "better": "higher",
                               "source": "program_counter",
                               "layer": "Engine (serve/engine.py)",
                               "moves": "words_per_s",
                               "workloads": ["pocket.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = digests()
    assert all(after[p] == d for p, d in before.items())

    from bench import spec

    cell = spec.resolve(root, "pocket.trickle")
    assert cell.config["lexicon"]["n_tri"] == 200
    assert cell.traffic["clients"] == 1
    assert [m["name"] for m in cell.end_to_end] == ["words_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "engine.requests_per_launch.trickle"]
    res = _run(harness, root, capsys, "pocket.trickle")
    assert res["correct"] and set(res["metrics"]) == {"words_per_s", "setup_s"}
    res = _run(harness, root, capsys, "pocket.trickle", trace=1)
    assert res["correct"]
    assert set(res["metrics"]) == {"engine.requests_per_launch.trickle"}
    assert 0 < res["metrics"]["engine.requests_per_launch.trickle"]["value"]
