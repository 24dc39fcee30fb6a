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
# a journaled copy of newswire, only in the tiny checkout, under docs
JOURNALED = "newswire-journal.docs"
# each accepted cell's numbers of correct, by name, in order
CHECKS = {
    "newswire.docs": ["word_rows", "byte_spans", "doc_ids", "roots",
                      "requests_failed"],
    "quran.words": ["roots", "requests_failed"],
    "newswire.queries": ["word_rows", "byte_spans", "doc_ids", "roots",
                         "requests_failed"],
    "newswire-x4.index": ["index_counts", "index_postings"],
}
JOURNAL_CHECKS = ["journal_torn", "journal_admits", "journal_retires",
                  "journal_unflushed", "journal_fsyncs_short"]
# the journal number each way of breaking durability has to move
JOURNAL_FAULTS = {"control": "journal_admits", "journal": "journal_admits",
                  "journal_late": "journal_unflushed",
                  "journal_fsync": "journal_fsyncs_short"}


def _tiny_root(tmp: Path) -> Path:
    """A checkout holding a copy of ``bench/`` with every mix cut to a
    test's size and every cell on one device, and a journaled copy of
    ``newswire`` with a cell of its own under the docs mix."""
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
    for cell in bench["workloads"]:
        cell["chips"] = 1
    for entry in bench["configs"]:
        path = tmp / entry["file"]
        cfg = json.loads(path.read_text())
        cfg["chips"] = 1
        path.write_text(json.dumps(cfg))
    config, traffic = JOURNALED.split(".")
    cfg = json.loads((tmp / "bench" / "configs" / "newswire.json").read_text())
    cfg.update(name=config, journal={"fsync_every": 2})
    cfg["guarantees"]["durability"] = "write-ahead journal, fsync every 2"
    (tmp / "bench" / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": config, "source": "a test's",
                             "file": f"bench/configs/{config}.json",
                             "reduced": [], "why": "journaled newswire"})
    bench["workloads"].append({"name": JOURNALED, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "docs through the journal"})
    bench["end_to_end"][0]["workloads"].append(JOURNALED)
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


def _run(harness, root, capsys, cell, fault="none", trace=0, stderr=False,
         seconds=0.5):
    """One run of ``cell``; ``fault`` is ``none``, ``control`` (the run's
    own option) or a fault of ``bench/faults.py`` planted under it. The
    result line, and with ``stderr`` what the run logged."""
    from bench import faults

    argv = ["--workload", cell, "--seed", str(SEED), "--seconds", str(seconds),
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
    res = json.loads(out.out.strip().splitlines()[-1])
    return (res, out.err) if stderr else res


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
    ("newswire.queries", "answer", False),
    ("newswire.queries", "frontend", False),
    ("newswire.queries", "unchanged", False),
    ("newswire-x4.index", "none", True),
    ("newswire-x4.index", "control", False),
    ("newswire-x4.index", "exchange", False),
    ("newswire-x4.index", "answer", False),
])
def test_correct_catches_control_and_faults(harness, tiny, capsys, cell,
                                            fault, correct):
    res = _run(harness, tiny, capsys, cell, fault)
    assert res["correct"] is correct, res["check"]
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "check"
    assert list(res["check"]) == CHECKS[cell]
    assert res["attempted"] > 0
    if correct:
        assert all(v["value"] <= v["limit"] for v in res["check"].values())
        assert "setup_s" in res["metrics"]


@pytest.mark.parametrize("fault, correct", [
    ("none", True), ("control", False), ("journal", False),
    ("journal_late", False), ("journal_fsync", False)])
def test_a_journaled_cell_reads_every_acknowledged_request_back(
        harness, tiny, capsys, fault, correct):
    """The engine serves through the program's journal, in a directory
    of the checkout that is gone after the run. Clean, every finished
    request is read back, its admit written before ``submit`` returned
    and the window's records fsynced in the stated batch; the control's
    missing admit record, a journal that drops every 50th admit (a
    window long enough to admit over a hundred requests), one that
    writes each admit only at its retire and one that fsyncs every
    1000th record are caught, each by its own number."""
    res, err = _run(harness, tiny, capsys, JOURNALED, fault, stderr=True,
                    seconds=2 if fault == "journal" else 0.5)
    assert res["correct"] is correct, res["check"]
    assert list(res["check"]) == CHECKS["newswire.docs"] + JOURNAL_CHECKS
    journal = {k: res["check"][k]["value"] for k in JOURNAL_CHECKS}
    assert journal["journal_torn"] == 0
    assert not (tiny / harness.JOURNAL_DIR).exists()
    assert "journal in" in err and "filesystem" in err
    counters = dict(line.split()[1::2] for line in err.splitlines()
                    if line.startswith("counter journal_"))
    assert int(counters["journal_bytes"]) > 0
    if fault == "none":
        assert journal == dict.fromkeys(JOURNAL_CHECKS, 0)
        # a closed loop admits and retires every request in the window
        assert int(counters["journal_records"]) == 2 * res["attempted"]
    else:
        assert journal[JOURNAL_FAULTS[fault]] >= 1
    if fault == "control":
        assert res["check"]["word_rows"]["value"] > 0
    if fault == "journal_late":
        assert journal["journal_admits"] == 0


def test_a_journal_on_a_memory_filesystem_is_refused(harness, tiny, capsys,
                                                     monkeypatch):
    """On tmpfs an fsync makes nothing durable: the run fails, printing
    no result, and leaves no journal directory behind."""
    monkeypatch.setattr(harness, "filesystem_type", lambda _path: "tmpfs")
    rc = harness.main(["--workload", JOURNALED, "--seed", str(SEED),
                       "--seconds", "0.5", "--trace", "0"], root=tiny)
    out = capsys.readouterr()
    assert rc == 1 and out.out == ""
    assert "filesystem tmpfs" in out.err
    assert not (tiny / harness.JOURNAL_DIR).exists()


@pytest.mark.parametrize("journal", ["absent", None])
def test_a_configuration_without_a_journal_serves_without_one(tiny, journal):
    from bench import drive, run, spec

    cell = spec.resolve(tiny, "newswire.docs")
    assert "journal" not in cell.config and cell.fsync_every is None
    if journal is None:
        assert spec.journal_fsync_every({**cell.config, "journal": None}) is None
    _roots, tables = drive.lexicon(cell.config, SEED)
    system = run.build_system(cell, tables)
    assert system.journal is None and system.engine.journal is None


@pytest.mark.parametrize("journal", [
    {}, {"fsync_every": 0}, {"fsync_every": "32"}, {"fsync_every": 1.5},
    {"fsync_every": True}, {"fsync_every": 32, "path": "/x"}, 32])
def test_a_journal_states_its_fsync_batch(journal):
    from bench import spec

    with pytest.raises(spec.SpecError):
        spec.journal_fsync_every({"journal": journal})
    assert spec.journal_fsync_every({"journal": {"fsync_every": 7}}) == 7


def test_the_index_builder_takes_no_journal(tmp_path):
    from bench import spec

    root = _tiny_root(tmp_path / "checkout")
    path = root / "bench" / "configs" / "newswire-x4.json"
    path.write_text(json.dumps({**json.loads(path.read_text()),
                                "journal": {"fsync_every": 32}}))
    with pytest.raises(spec.SpecError, match="no journal"):
        spec.resolve(root, "newswire-x4.index")
    assert spec.resolve(root, JOURNALED).fsync_every == 2


def _committed() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell, metrics", [
    ("newswire.docs", {"words_per_s", "setup_s"}),
    ("quran.words", {"words_per_s", "setup_s"}),
    ("newswire.queries", {"p95_ms", "setup_s"}),
    ("newswire-x4.index", {"index_words_per_s", "setup_s"}),
])
def test_committed_cell_resolves(cell, metrics):
    """Each cell of the committed BENCHMARK.json resolves to its own
    end-to-end metrics, and each of its per-layer metrics moves one of
    them through a reader that exists."""
    from bench import spec

    c = spec.resolve(ROOT, cell)
    assert {m["name"] for m in c.end_to_end} == metrics
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in metrics, m["name"]
        assert (ROOT / "bench" / "readers" / f"{m['reader']}.py").is_file()
    for m in c.end_to_end:
        assert (ROOT / "bench" / "measures" / f"{m['name']}.py").is_file()


def test_every_per_layer_entry_has_its_layer_file():
    """A per-layer entry and its layer file agree on the layer, unit,
    metric moved and cells."""
    for m in _committed()["per_layer"]:
        path = ROOT / "bench" / "layers" / f"{m['name']}.json"
        assert path.is_file(), m["name"]
        layer = json.loads(path.read_text())
        for key in ("layer", "unit", "moves", "workloads"):
            assert layer.get(key) == m.get(key), (m["name"], key)


def test_at_most_half_the_cells_take_four_chips():
    cells = _committed()["workloads"]
    assert {c["chips"] for c in cells} <= {1, 4}
    assert sum(c["chips"] == 4 for c in cells) <= len(cells) // 2


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
