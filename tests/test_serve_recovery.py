"""Crash-safety tests (DESIGN.md §12): the write-ahead request journal
and warm restart, DictStore catalog snapshots, the persistent-kernel
stall watchdog, and the graceful-degradation ladder.

The load-bearing invariant throughout: a recovered / degraded / salvaged
run returns bit-identical results to an uninterrupted one — the
megakernel's per-word output is independent of tile packing, so replay
through different coalescing boundaries, a watchdog's megabatch
re-dispatch, and every ladder rung all reproduce the same bytes.
"""
import os

import numpy as np
import pytest

from repro.core import corpus, stemmer
from repro.serve import (DegradationPolicy, DictSnapshotError, DictStore,
                         Engine, EventLog, FaultInjector, FaultPlan,
                         FaultSpec, Journal, JournalError, ServingMode,
                         StemmerWorkload, TextAnalysisWorkload,
                         build_ladder, payload_digest)
from repro.serve import journal as journal_mod

N_REQ, WPR = 6, 32


@pytest.fixture(scope="module")
def dict_and_words():
    d = corpus.build_dictionary(n_tri=400, n_quad=60, seed=0)
    arrays = stemmer.RootDictArrays.from_rootdict(d)
    words, _, _ = corpus.build_corpus(n_words=N_REQ * WPR, seed=1)
    return arrays, corpus.encode_corpus(words)


@pytest.fixture(scope="module")
def baseline(dict_and_words):
    arrays, enc = dict_and_words
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32,
                                 max_inflight=2))
    rids = [eng.submit(enc[i * WPR:(i + 1) * WPR]) for i in range(N_REQ)]
    assert eng.run_until_drained().drained
    return [np.array(eng.result(r).roots) for r in rids]


# ---------------------------------------------------------------------------
# the journal itself
# ---------------------------------------------------------------------------
def test_journal_roundtrip_and_unfinished(tmp_path):
    jp = tmp_path / "wal.jsonl"
    j = Journal(jp, fsync_every=2)
    pay = np.arange(32, dtype=np.int32).reshape(2, 16)
    j.admit(0, pay, deadline_s=1.5, dict_version=3, opts={"k": 1})
    j.admit(1, ["doc one", "doc two"])

    class _Req:
        rid = 0
        failure = None
        roots = np.ones((2, 4), np.int32)
        sources = np.zeros(2, np.int32)
    j.retire(_Req())
    j.close()

    records, dropped = Journal.read(jp)
    assert dropped == 0 and len(records) == 3
    a0, a1, r0 = records
    assert a0["kind"] == "admit" and a0["rid"] == 0
    assert a0["deadline_s"] == 1.5 and a0["dict_version"] == 3
    assert a0["opts"] == {"k": 1}
    got = journal_mod.decode_payload(a0["payload"])
    np.testing.assert_array_equal(got, pay)
    assert payload_digest(got) == a0["digest"]
    assert journal_mod.decode_payload(a1["payload"]) == ["doc one",
                                                         "doc two"]
    assert r0["kind"] == "retire" and r0["rid"] == 0
    assert isinstance(r0["digest"], str)
    # rid 1 has no retire: it is exactly what recovery owes
    unfinished = journal_mod.unfinished_admits(records)
    assert [r["rid"] for r in unfinished] == [1]


def test_journal_torn_tail_truncated(tmp_path):
    jp = tmp_path / "wal.jsonl"
    j = Journal(jp)
    for rid in range(4):
        j.admit(rid, [rid])
    j.close()
    good_size = os.path.getsize(jp)
    with open(jp, "ab") as f:       # a crash mid-append: half a record
        f.write(b"deadbeefdeadbeef {\"kind\": \"adm")
    records, dropped = Journal.read(jp)
    assert len(records) == 4 and dropped > 0
    assert os.path.getsize(jp) == good_size     # physically truncated
    # a corrupt record mid-file hides everything after it (WAL ordering
    # beyond a tear is unprovable)
    data = open(jp, "rb").read().splitlines(keepends=True)
    data[1] = b"0" * 16 + data[1][16:]
    open(jp, "wb").write(b"".join(data))
    records, dropped = Journal.read(jp, truncate=False)
    assert [r["rid"] for r in records] == [0] and dropped > 0


def test_payload_codec_rejects_unknown(tmp_path):
    with pytest.raises(TypeError, match="encode payload"):
        journal_mod.encode_payload({"not": "supported"})
    with pytest.raises(JournalError, match="codec"):
        journal_mod.decode_payload({"t": "mystery"})
    with pytest.raises(ValueError, match="fsync_every"):
        Journal(tmp_path / "j", fsync_every=0)


def test_fault_plan_rejects_unknown_sites_at_construction():
    with pytest.raises(ValueError, match="site"):
        FaultSpec("gpu")
    with pytest.raises(TypeError, match="FaultSpec"):
        FaultPlan(specs=(FaultSpec("dispatch"), "stall"))  # not a FaultSpec
    with pytest.raises(TypeError, match="FaultSpec"):
        FaultPlan(specs=(42,))
    with pytest.raises(ValueError, match="retired_tiles"):
        FaultSpec("stall", retired_tiles=-1)
    # the three new sites all construct + default to their only kind
    assert FaultSpec("stall").kind == "wedge"
    assert FaultSpec("device_loss").kind == "lost"
    assert FaultSpec("journal").kind == "tear"


# ---------------------------------------------------------------------------
# DictStore snapshots
# ---------------------------------------------------------------------------
def test_dict_snapshot_restore_roundtrip(dict_and_words, tmp_path):
    arrays, _ = dict_and_words
    store = DictStore(arrays, keep_history=True)
    grown = corpus.grow_root_arrays(arrays, 2048, seed=7)
    v1 = store.publish(grown)
    sp = tmp_path / "dict.npz"
    sha = store.snapshot(sp)
    assert isinstance(sha, str) and len(sha) == 16

    r = DictStore.restore(sp)
    assert r.version == v1 == 1
    for v in (0, 1):
        np.testing.assert_array_equal(
            np.asarray(r.get(v).arrays.tri),
            np.asarray(store.get(v).arrays.tri))
    # versions stay monotone across the restart (no renumbering)
    v2 = r.publish(corpus.grow_root_arrays(arrays, 1024, seed=9))
    assert v2 == 2


def test_dict_snapshot_tamper_detected(dict_and_words, tmp_path):
    arrays, _ = dict_and_words
    sp = tmp_path / "dict.npz"
    DictStore(arrays).snapshot(sp)
    with np.load(sp) as z:
        tables = {k: np.array(z[k]) for k in z.files}
    tables["v0_tri"][0] ^= 0x5A
    np.savez(sp, **tables)
    with pytest.raises(DictSnapshotError, match="content hash"):
        DictStore.restore(sp)


# ---------------------------------------------------------------------------
# warm restart: kill at every tick boundary
# ---------------------------------------------------------------------------
def test_kill_at_every_tick_boundary_bit_identical(dict_and_words,
                                                   baseline, tmp_path):
    """A journaled engine killed after k ticks, for EVERY k up to full
    drain, recovers with (pre-crash finished + replayed) outputs
    bit-identical to the uninterrupted run — including k=0 (nothing
    served) and the torn coalescing boundaries replay creates."""
    arrays, enc = dict_and_words
    for k in range(6):
        jp = tmp_path / f"wal_{k}.jsonl"
        eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32,
                                     max_inflight=2),
                     journal=Journal(jp, fsync_every=1))
        rids = [eng.submit(enc[i * WPR:(i + 1) * WPR])
                for i in range(N_REQ)]
        for _ in range(k):
            eng.step()
        done_before = {r: eng.result(r) for r in rids
                       if eng.result(r) is not None}
        # the process dies here: no close(), no sync — flushed appends
        # are all recovery gets
        eng2 = Engine.recover(jp, StemmerWorkload(DictStore(arrays),
                                                  block_b=32,
                                                  max_inflight=2))
        assert eng2.run_until_drained().drained
        assert sorted(eng2.recovery.replayed) == [
            r for r in rids if r not in done_before]
        for i, r in enumerate(rids):
            req = done_before.get(r) or eng2.result(r)
            assert req is not None and req.failure is None, (k, r)
            np.testing.assert_array_equal(req.roots, baseline[i],
                                          err_msg=f"kill at tick {k},"
                                                  f" rid {r}")
        # recovered rids are retired into the reopened journal: a second
        # recovery finds nothing left to replay
        eng3 = Engine.recover(jp, StemmerWorkload(DictStore(arrays),
                                                  block_b=32))
        assert eng3.recovery.replayed == []
        # and fresh submissions never reuse a journaled rid
        assert eng3._next_rid == N_REQ


def test_recovery_repins_admit_version_across_publish(dict_and_words,
                                                      baseline, tmp_path):
    """Requests admitted under dict v0 and recovered AFTER a v1 publish
    still serve under v0 (the journal pins the admitted lexicon), while
    post-restart submissions serve under v1."""
    arrays, enc = dict_and_words
    jp, sp = tmp_path / "wal.jsonl", tmp_path / "dict.npz"
    store = DictStore(arrays, keep_history=True)
    store.snapshot(sp)
    eng = Engine(StemmerWorkload(store, block_b=32),
                 journal=Journal(jp, fsync_every=1))
    rids = [eng.submit(enc[i * WPR:(i + 1) * WPR]) for i in range(2)]
    # crash before anything serves; the restarted store has moved on
    store2 = DictStore.restore(sp)
    grown = corpus.grow_root_arrays(arrays, 2048, seed=7)
    v1 = store2.publish(grown)
    eng2 = Engine.recover(jp, StemmerWorkload(store2, block_b=32))
    fresh = eng2.submit(enc[2 * WPR:3 * WPR])
    assert eng2.run_until_drained().drained
    for i, r in enumerate(rids):
        req = eng2.result(r)
        assert (req.dict_versions == 0).all()       # pinned at admit
        np.testing.assert_array_equal(req.roots, baseline[i])
    req = eng2.result(fresh)
    assert (req.dict_versions == v1).all()          # current lexicon
    want_r, _ = stemmer.stem_batch(req.words, grown)
    np.testing.assert_array_equal(req.roots, np.asarray(want_r))


def test_recovery_rejects_tampered_payload(dict_and_words, tmp_path):
    arrays, enc = dict_and_words
    jp = tmp_path / "wal.jsonl"
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32),
                 journal=Journal(jp, fsync_every=1))
    eng.submit(enc[:WPR])
    eng.journal.close()
    records, _ = Journal.read(jp)
    records[0]["digest"] = "0" * 16     # payload no longer matches
    j2 = Journal(tmp_path / "wal2.jsonl")
    j2._append(records[0])
    j2.close()
    with pytest.raises(JournalError, match="digest"):
        Engine.recover(tmp_path / "wal2.jsonl",
                       StemmerWorkload(DictStore(arrays), block_b=32))


def test_text_requests_replay_from_raw_documents(dict_and_words, tmp_path):
    """The journal stores text submissions as raw docs; replay re-runs
    the front end and reproduces identical analyses."""
    arrays, _ = dict_and_words
    docs = ["كتب الولد درسا", "ذهب الرجل الى السوق"]
    ref = Engine(TextAnalysisWorkload(DictStore(arrays), block_b=32,
                                      frontend="host"))
    ref_rids = [ref.submit([d]) for d in docs]
    assert ref.run_until_drained().drained
    want = [ref.result(r).analyses() for r in ref_rids]

    jp = tmp_path / "wal.jsonl"
    eng = Engine(TextAnalysisWorkload(DictStore(arrays), block_b=32,
                                      frontend="host"),
                 journal=Journal(jp, fsync_every=1))
    rids = [eng.submit([d]) for d in docs]
    # crash with both docs accepted, nothing served
    eng2 = Engine.recover(jp, TextAnalysisWorkload(DictStore(arrays),
                                                   block_b=32,
                                                   frontend="host"))
    assert eng2.run_until_drained().drained
    assert [eng2.result(r).analyses() for r in rids] == want


def test_text_replay_segments_the_replayed_requests_together(
        dict_and_words, tmp_path):
    """Requests accepted but not served before a crash replay through the
    kernel front end in one launch, bit-identical to an uninterrupted
    host-front-end run; those retired before the crash are not re-served."""
    from repro.launch.serve import build_documents

    arrays, _ = dict_and_words
    docs = build_documents(5, 24, seed=4)
    payloads = [[docs[0]], docs[1], docs[2:4], [docs[4], ""]]
    ref = Engine(TextAnalysisWorkload(DictStore(arrays), block_b=32,
                                      frontend="host"))
    ref_rids = [ref.submit(p) for p in payloads]
    assert ref.run_until_drained().drained
    want = [ref.result(r).analyses() for r in ref_rids]

    jp = tmp_path / "wal.jsonl"
    eng = Engine(TextAnalysisWorkload(DictStore(arrays), block_b=32,
                                      char_block=256),
                 journal=Journal(jp, fsync_every=1))
    rids = [eng.submit(payloads[0])]
    assert eng.run_until_drained().drained
    rids += [eng.submit(p) for p in payloads[1:]]
    # crash with the last three accepted, none segmented
    w2 = TextAnalysisWorkload(DictStore(arrays), block_b=32, char_block=256)
    eng2 = Engine.recover(jp, w2)
    assert eng2.recovery.replayed == rids[1:]
    assert eng2.run_until_drained().drained
    assert w2.frontend_launches == 1
    assert eng.result(rids[0]).analyses() == want[0]
    assert [eng2.result(r).analyses() for r in rids[1:]] == want[1:]


def test_a_poison_text_request_replays_into_quarantine(
        dict_and_words, tmp_path, poison_frontend):
    """A request accepted before a crash whose document the front end
    cannot process is quarantined after recovery too, its neighbours
    served bit-identically; its failure is journaled, so a second
    recovery replays nothing."""
    arrays, _ = dict_and_words
    docs = ["كتب الولد درسا", "ذهب الرجل الى السوق", "قرأ الطالب"]
    ref = Engine(TextAnalysisWorkload(DictStore(arrays), block_b=32,
                                      frontend="host"))
    ref_rids = [ref.submit([d]) for d in docs]
    assert ref.run_until_drained().drained
    want = [ref.result(r).analyses() for r in ref_rids]

    def workload():
        w = TextAnalysisWorkload(DictStore(arrays), block_b=32,
                                 char_block=256)
        return w, poison_frontend(w, ValueError)

    jp = tmp_path / "wal.jsonl"
    w, marker = workload()
    eng = Engine(w, journal=Journal(jp, fsync_every=1))
    rids = [eng.submit([docs[0]]), eng.submit([docs[1] + " " + marker]),
            eng.submit([docs[2]])]
    # crash with all three accepted, none segmented
    w2, _ = workload()
    eng2 = Engine.recover(jp, w2)
    assert eng2.recovery.replayed == rids
    assert eng2.run_until_drained().drained
    assert eng2.result(rids[1]).failure.code == "quarantined"
    assert [eng2.result(rids[i]).analyses() for i in (0, 2)] == [
        want[0], want[2]]
    eng2.journal.close()
    w3, _ = workload()
    assert Engine.recover(jp, w3).recovery.replayed == []


# ---------------------------------------------------------------------------
# the stall watchdog
# ---------------------------------------------------------------------------
def test_watchdog_requires_persistent(dict_and_words):
    arrays, _ = dict_and_words
    with pytest.raises(ValueError, match="persistent"):
        StemmerWorkload(DictStore(arrays), watchdog_s=0.1)
    with pytest.raises(ValueError, match="watchdog_s"):
        StemmerWorkload(DictStore(arrays), persistent=True, watchdog_s=0)


@pytest.mark.parametrize("retired_tiles", [0, 2])
def test_watchdog_abandons_wedged_launch(dict_and_words, baseline,
                                         retired_tiles):
    """A wedged persistent launch is abandoned at watchdog_s; the
    retired-prefix descriptors are salvaged (checksum-verified), the
    rest re-dispatch down the megabatch path, and zero requests are
    lost — bit-identical even at max_retries=0 (a stall charges no
    retry)."""
    arrays, enc = dict_and_words
    inj = FaultInjector(FaultPlan(
        specs=(FaultSpec("stall", at=0, retired_tiles=retired_tiles),)))
    w = StemmerWorkload(DictStore(arrays), block_b=32, max_inflight=1,
                        persistent=True, megabatch_tiles=4,
                        watchdog_s=0.05, max_retries=0, injector=inj)
    eng = Engine(w)
    rids = [eng.submit(enc[i * WPR:(i + 1) * WPR]) for i in range(N_REQ)]
    assert eng.run_until_drained().drained
    assert w.watchdog_stalls == 1 and w.retries_total == 0
    ev, = [e for e in eng.events() if e.kind == "watchdog_stall"]
    assert ev.data["salvaged_words"] == retired_tiles * 32
    assert ev.data["redispatched_words"] > 0
    for i, r in enumerate(rids):
        req = eng.result(r)
        assert req.failure is None
        np.testing.assert_array_equal(req.roots, baseline[i])


# ---------------------------------------------------------------------------
# the degradation ladder
# ---------------------------------------------------------------------------
def test_build_ladder_rungs():
    rungs = build_ladder(persistent=True, megabatch_tiles=4,
                         data_devices=4, resident_dict=True)
    labels = [r.label for r in rungs]
    assert labels == ["persistent", "megabatch x4", "per-tile",
                      "streamed-dict", "devices-2", "devices-1"]
    assert rungs[0].persistent and not rungs[1].persistent
    assert rungs[-1].data_devices == 1
    # minimal config: the ladder still has a rung to stand on
    assert [r.label for r in build_ladder(resident_dict=False)] == [
        "per-tile"]


class _FakeWorkload:
    def __init__(self, data_devices=1):
        self.persistent = True
        self.megabatch_tiles = 2
        self.data_devices = data_devices
        self.retries_total = 0
        self.checksum_failures = 0
        self.timeouts = 0
        self.watchdog_stalls = 0
        self.device_losses = 0
        self.modes: list[ServingMode] = []

    def request_mode(self, mode):
        self.modes.append(mode)


class _FakeEngine:
    def __init__(self):
        self.queue = []


def _policy(w, **kw):
    p = DegradationPolicy(rungs=build_ladder(
        persistent=w.persistent, megabatch_tiles=w.megabatch_tiles,
        data_devices=w.data_devices, resident_dict=False), **kw)
    p.attach(w, EventLog())
    return p


def test_policy_hysteresis_down_and_up():
    w, eng = _FakeWorkload(), _FakeEngine()
    p = _policy(w, down_after=2, up_after=3)
    w.retries_total += 1
    p.observe(eng)                       # 1 unhealthy: no shift yet
    assert p.mode.label == "persistent" and not w.modes
    w.retries_total += 1
    p.observe(eng)                       # 2 consecutive: downshift
    assert p.mode.label == "megabatch x2"
    assert w.modes[-1].label == "megabatch x2"
    for _ in range(2):
        p.observe(eng)                   # healthy, but under up_after
    assert p.mode.label == "megabatch x2"
    p.observe(eng)                       # 3rd healthy: upshift
    assert p.mode.label == "persistent"
    assert [t[2] for t in p.transitions] == ["faults", "healthy"]
    # a fault burst resets the healthy streak (no oscillation)
    w.checksum_failures += 1
    p.observe(eng)
    assert p._healthy == 0


def test_policy_queue_pressure_downshifts():
    w, eng = _FakeWorkload(), _FakeEngine()
    p = _policy(w, queue_high=4, down_after=2)
    eng.queue = list(range(5))
    p.observe(eng)
    p.observe(eng)
    assert p.mode.label == "megabatch x2"
    assert p.transitions[-1][2] == "queue"


def test_policy_device_loss_downshifts_and_caps():
    w, eng = _FakeWorkload(data_devices=4), _FakeEngine()
    p = _policy(w, down_after=2, up_after=1)
    assert [r.label for r in p.rungs] == [
        "persistent", "megabatch x2", "per-tile", "devices-2", "devices-1"]
    w.device_losses += 1
    p.observe(eng)                       # immediate, no hysteresis
    assert p.mode.label == "devices-2"
    assert p.transitions[-1][2] == "device_loss"
    for _ in range(8):
        p.observe(eng)                   # healthy forever...
    assert p.mode.data_devices <= 2      # ...but never past the cap
    w.device_losses += 1
    p.observe(eng)                       # second loss: down to 1
    assert p.mode.label == "devices-1"


def test_policy_validation():
    with pytest.raises(ValueError, match="queue_high"):
        DegradationPolicy(queue_high=0)
    with pytest.raises(ValueError, match="down_after"):
        DegradationPolicy(down_after=0)
    with pytest.raises(ValueError, match="request_mode"):
        DegradationPolicy().attach(object(), EventLog())


def test_ladder_transition_serves_bit_identical(dict_and_words, baseline):
    """A mid-stream downshift (persistent -> megabatch -> per-tile ->
    streamed-dict) re-chunks waiting work to the new launch width and
    keeps every result bit-identical."""
    arrays, enc = dict_and_words
    inj = FaultInjector(FaultPlan(specs=(FaultSpec("stall", count=3),)))
    w = StemmerWorkload(DictStore(arrays), block_b=32, max_inflight=1,
                        persistent=True, megabatch_tiles=2,
                        watchdog_s=0.02, injector=inj)
    pol = DegradationPolicy(down_after=1, up_after=100)
    eng = Engine(w, policy=pol)
    rids = [eng.submit(enc[i * WPR:(i + 1) * WPR]) for i in range(N_REQ)]
    assert eng.run_until_drained().drained
    assert pol.transitions and pol.transitions[0][0] == "persistent"
    assert not w.persistent              # off the wedged rung
    kinds = {e.kind for e in eng.events()}
    assert "degrade" in kinds and "watchdog_stall" in kinds
    for i, r in enumerate(rids):
        req = eng.result(r)
        assert req.failure is None
        np.testing.assert_array_equal(req.roots, baseline[i])


# ---------------------------------------------------------------------------
# the structured event stream
# ---------------------------------------------------------------------------
def test_events_surface_failures_and_recovery(dict_and_words, tmp_path):
    arrays, enc = dict_and_words
    eng = Engine(StemmerWorkload(DictStore(arrays), block_b=32),
                 queue_cap=1, on_full="shed",
                 journal=Journal(tmp_path / "wal.jsonl", fsync_every=1))
    eng.submit(enc[:WPR])
    eng.submit(enc[:WPR])                # shed: terminal, never journaled
    fails = [e for e in eng.events() if e.kind == "failure"]
    assert len(fails) == 1 and fails[0].data["code"] == "shed"
    assert eng.run_until_drained().drained
    eng2 = Engine.recover(tmp_path / "wal.jsonl",
                          StemmerWorkload(DictStore(arrays), block_b=32))
    rec, = [e for e in eng2.events() if e.kind == "recovered"]
    # both rids count as retired: the served one AND the shed one (shed
    # is terminal — retired without ever being admitted)
    assert rec.data["replayed"] == 0 and rec.data["already_retired"] == 2
    # events(drain=True) hands the stream over exactly once
    assert eng2.events(drain=True) and not eng2.events()


# ---------------------------------------------------------------------------
# launcher flag cross-validation (before any engine is constructed)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("argv", [
    ["--workload", "stemmer", "--watchdog-ms", "50"],        # no --persistent
    ["--workload", "lm", "--watchdog-ms", "50"],
    ["--workload", "lm", "--degrade", "on"],
    ["--workload", "stemmer", "--watchdog-ms", "-1", "--persistent"],
])
def test_serve_launcher_rejects_bad_flag_combos(argv, monkeypatch):
    from repro.launch import serve as serve_mod

    monkeypatch.setattr("sys.argv", ["serve.py"] + argv)
    with pytest.raises(SystemExit) as exc:
        serve_mod.main()
    assert exc.value.code == 2          # argparse .error(), pre-engine


def test_serve_launcher_exits_nonzero_on_failed_requests(monkeypatch,
                                                         capsys):
    """A run that finished with failed requests is not a success: the
    launcher reports them and exits 1 (here every request misses a
    1 µs deadline)."""
    from repro.launch import serve as serve_mod

    monkeypatch.setattr(serve_mod, "enable_compile_cache", lambda: None)
    monkeypatch.setattr("sys.argv", [
        "serve.py", "--workload", "stemmer", "--requests", "2",
        "--words-per-request", "8", "--deadline-ms", "0.001"])
    with pytest.raises(SystemExit) as exc:
        serve_mod.main()
    assert exc.value.code == 1
    assert "2 request(s) failed" in capsys.readouterr().err
