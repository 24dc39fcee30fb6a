"""Workload-agnostic serving core: queue/admit/finish continuous batching.

The scheduler (:class:`Engine`) owns what is generic about continuous
batching — the FIFO request queue, rid allocation, admission while the
workload has capacity, the finished table, and drain accounting. What a
"tick" of work means is delegated to a :class:`Workload`:

  StemmerWorkload    the paper's workload behind that machinery:
                     queued word-batch requests coalesce into megabatches
                     of up to ``megabatch_tiles`` [data_devices *
                     block_b, 16] super-tiles, each megabatch ONE
                     megakernel launch whose grid spans every coalesced
                     tile (ops.extract_roots_fused,
                     ops.extract_roots_persistent for the
                     descriptor-ring kernel, or ops.extract_roots_sharded
                     across a data mesh). A
                     tick is a dispatch/retire pipeline pass: up to
                     max_inflight launches stay outstanding as device
                     arrays while the host coalesces the next tiles;
                     results scatter back at retire, when they are
                     ready. The dictionary is acquired from a
                     serve.dict_store.DictStore at each *dispatch* and
                     pinned per launch, so lexicon hot swaps land
                     between launches — never inside one — and every
                     served word records the dict version that actually
                     served it, even when the publish lands while its
                     tile is in flight.
  TextAnalysisWorkload  (serve/text.py) the same ring behind the text
                     front end: raw UTF-8 documents in, roots with their
                     source and byte span out.

Keeping the tile shape fixed means every launch replays the same jit
trace; dictionary swaps with matching shapes also replay it (the
DictStore pins residency in a ResolvedRootDict handle at publish time).

Failure model (DESIGN.md "Failure model & recovery"): requests carry
optional deadlines, the queue has optional cap-based admission control
(``on_full="raise"|"shed"|"block"``), and the stemmer's dispatch/retire
ring retries failed / timed-out / corrupted launches up to
``max_retries`` before bisecting the tile to quarantine the poison
request(s) — every terminal failure is returned through the finished
table with a structured :class:`~repro.serve.faults.FailureInfo`
instead of wedging the batch. Retire verifies a device-computed
per-tile checksum on every path (the persistent kernel's completion
flags generalised), and ``run_until_drained(on_undrained="raise")``
cancels stranded requests so the engine stays reusable after the
exception.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import alphabet as ab
from repro.serve.faults import DeviceLost, FailureInfo
from repro.serve.health import EventLog
from repro.serve.spans import (ENGINE_STEP, ENGINE_SUBMIT, STEM_COALESCE,
                               STEM_FETCH, STEM_LAUNCH, STEM_SCATTER,
                               STEM_STAGE, STEM_VERIFY, span)

# JaxRuntimeError messages of a launch the TPU compiler refused
_COMPILE_FAILURES = ("Mosaic failed to compile", "memory space vmem",
                     "UNIMPLEMENTED")


def _deterministic_launch_error(exc: BaseException) -> bool:
    """True for a launch that failed to trace, lower or compile: a
    TypeError / ValueError / Pallas lowering error (not RuntimeErrors), a
    NotImplementedError (a lowering rule Mosaic lacks), or a
    JaxRuntimeError from the compiler. Such a launch fails identically
    on every retry, so retrying it would only quarantine healthy
    requests and hide the fault."""
    if (not isinstance(exc, RuntimeError)
            or isinstance(exc, NotImplementedError)):
        return True
    return (isinstance(exc, jax.errors.JaxRuntimeError)
            and any(m in str(exc) for m in _COMPILE_FAILURES))


# ---------------------------------------------------------------------------
# the workload contract
# ---------------------------------------------------------------------------
@runtime_checkable
class Workload(Protocol):
    """What the generic Engine needs from a servable workload."""

    def make_request(self, rid: int, payload, **opts):
        """Validate + wrap a submission; raise ValueError on bad configs."""

    def has_capacity(self) -> bool:
        """Can admit() take one more request right now?"""

    def admit(self, request) -> None:
        """Move a queued request in-flight (e.g. stage it for a launch)."""

    def tick(self) -> list:
        """Advance all in-flight work one step; return finished requests."""

    @property
    def active(self) -> int:
        """Number of in-flight (admitted, unfinished) requests."""

    def pending_rids(self) -> list[int]:
        """rids of in-flight requests (for drain reports)."""

    def expire(self, now: float) -> list:
        """Fail + return in-flight requests whose deadline passed."""

    def cancel_pending(self) -> list:
        """Tear down all in-flight work; fail + return the requests."""


# ---------------------------------------------------------------------------
# drain accounting
# ---------------------------------------------------------------------------
@dataclass
class DrainReport:
    """Outcome of run_until_drained: ticks spent and what is still owed."""

    ticks: int
    drained: bool
    pending: list[int]   # rids still queued or in flight at max_ticks
    cancelled: list = field(default_factory=list)
    # rids cancelled+returned through finished (on_undrained="raise"
    # tears stranded work down so the engine is reusable; each cancelled
    # request carries a FailureInfo(code="cancelled"))


class EngineUndrained(RuntimeError):
    """max_ticks elapsed with requests still queued or in flight."""

    def __init__(self, report: DrainReport):
        self.report = report
        super().__init__(
            f"engine not drained after {report.ticks} ticks:"
            f" {len(report.pending)} request(s) unfinished"
            f" (rids {report.pending};"
            f" {len(report.cancelled)} cancelled + returned)")


class QueueFull(RuntimeError):
    """submit() against a full queue under on_full="raise"."""


# ---------------------------------------------------------------------------
# the generic scheduler
# ---------------------------------------------------------------------------
class Engine:
    """Continuous batching over any Workload.

    submit() validates through the workload and queues; step() admits
    while the workload has capacity, then runs one workload tick;
    finished requests move to the results table keyed by rid.

    ``queue_cap`` bounds the *queued* (not yet admitted) requests;
    submits against a full queue follow ``on_full``: "raise" rejects
    with :class:`QueueFull`, "shed" finishes the request immediately
    with ``FailureInfo(code="shed")`` (the overload-protection path —
    the caller still gets a rid and a structured result), "block"
    serves the backlog inline until a slot opens. ``deadline_s`` on
    submit stamps an absolute deadline; expiry (checked each step,
    whether the request is queued or in flight) finishes it with
    ``FailureInfo(code="deadline")`` while later requests proceed.
    """

    ON_FULL = ("raise", "shed", "block")

    def __init__(self, workload: Workload, *, queue_cap: int | None = None,
                 on_full: str = "raise", journal=None, policy=None):
        if on_full not in self.ON_FULL:
            raise ValueError(f"unknown on_full policy {on_full!r}"
                             f" (choose from {self.ON_FULL})")
        if queue_cap is not None and queue_cap < 1:
            raise ValueError(f"queue_cap must be >= 1, got {queue_cap}")
        if on_full != "raise" and queue_cap is None:
            raise ValueError(f"on_full={on_full!r} needs a queue_cap"
                             " (an unbounded queue is never full)")
        self.workload = workload
        self.queue: list = []
        self.finished: dict[int, object] = {}
        self.queue_cap = queue_cap
        self.on_full = on_full
        self.shed = 0            # requests rejected by admission control
        self._next_rid = 0
        # crash safety + health (DESIGN.md §12): the write-ahead journal
        # makes accepted work durable; the event log is the one stream
        # failures / stalls / ladder transitions surface through; the
        # degradation policy (observed each step) walks the mode ladder
        self.journal = journal
        self.events_log: EventLog = (getattr(workload, "events", None)
                                     or EventLog())
        self.policy = policy
        if policy is not None:
            policy.attach(workload, self.events_log)
        self.recovery = None     # RecoveryReport when built via recover()

    # -- client API --------------------------------------------------------
    def _queue_full(self) -> bool:
        return (self.queue_cap is not None
                and len(self.queue) >= self.queue_cap)

    def submit(self, payload, *, deadline_s: float | None = None,
               **opts) -> int:
        with span(ENGINE_SUBMIT):
            if deadline_s is not None and deadline_s <= 0:
                raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
            if self._queue_full():
                if self.on_full == "raise":
                    raise QueueFull(
                        f"queue at cap {self.queue_cap}; submit rejected"
                        " (on_full='raise')")
                if self.on_full == "block":
                    for _ in range(100_000):
                        self.step()
                        if not self._queue_full():
                            break
                    else:
                        raise RuntimeError(
                            "on_full='block' made no progress against a"
                            " full queue — the workload is wedged")
            req = self.workload.make_request(self._next_rid, payload, **opts)
            rid = self._next_rid
            self._next_rid += 1
            if deadline_s is not None:
                req.deadline = time.monotonic() + deadline_s
            if self._queue_full():           # only reachable under "shed"
                req.failure = FailureInfo(
                    rid, "shed", detail=f"queue at cap {self.queue_cap}")
                req.done = True
                self._finish(req)           # shed work is terminal, never
                self.shed += 1              # journaled as an admit
                return rid
            if self.journal is not None:
                # write-ahead: the admit is durable BEFORE the request can
                # be served, so a crash between here and retire re-serves it
                store = getattr(self.workload, "store", None)
                self.journal.admit(
                    rid, payload, deadline_s=deadline_s,
                    dict_version=None if store is None else store.version,
                    opts=opts)
            self.queue.append(req)
            return rid

    def result(self, rid: int):
        return self.finished.get(rid)

    def events(self, *, drain: bool = False) -> list:
        """The structured event stream (failures, retries, checksum and
        flag mismatches, watchdog stalls, device losses, ladder
        transitions, recovery) — the supported alternative to grepping
        workload counters."""
        return (self.events_log.drain() if drain
                else self.events_log.snapshot())

    def _finish(self, req) -> None:
        """Single exit into the finished table: emits the failure event
        and the journal retire record alongside."""
        self.finished[req.rid] = req
        if req.failure is not None:
            self.events_log.emit("failure", rid=req.rid,
                                 code=req.failure.code,
                                 detail=req.failure.detail)
        if self.journal is not None:
            self.journal.retire(req)

    @property
    def active(self) -> int:
        return self.workload.active

    # -- scheduling --------------------------------------------------------
    def step(self):
        """One engine tick: expire deadlines, admit while there is
        capacity, then tick the workload."""
        with span(ENGINE_STEP):
            now = time.monotonic()
            if self.queue:
                still = []
                for req in self.queue:
                    dl = getattr(req, "deadline", None)
                    if dl is not None and now > dl:
                        req.failure = FailureInfo(
                            req.rid, "deadline", detail="expired while queued")
                        req.done = True
                        self._finish(req)
                    else:
                        still.append(req)
                self.queue = still
            expire = getattr(self.workload, "expire", None)
            if expire is not None:
                for req in expire(now):
                    self._finish(req)
            while self.queue and self.workload.has_capacity():
                self.workload.admit(self.queue.pop(0))
            for req in self.workload.tick():
                self._finish(req)
            if self.policy is not None:
                self.policy.observe(self)

    def run_until_drained(self, max_ticks: int = 1000, *,
                          on_undrained: str = "raise") -> DrainReport:
        """Tick until queue + in-flight are empty, or max_ticks elapse.

        Hitting max_ticks with work outstanding never silently drops it:
        on_undrained="raise" (default) cancels the stranded requests —
        each lands in the finished table with FailureInfo(code=
        "cancelled") — and raises EngineUndrained carrying the report
        (pending + cancelled rids), leaving the engine empty and
        reusable for new work; "return" hands back the report with
        drained=False and the unfinished rids, leaving the queue and
        in-flight work intact so the same drain can be resumed.
        """
        if on_undrained not in ("raise", "return"):
            raise ValueError(f"unknown on_undrained policy: {on_undrained!r}")
        ticks = 0
        while (self.queue or self.workload.active) and ticks < max_ticks:
            self.step()
            ticks += 1
        pending = ([r.rid for r in self.queue]
                   + self.workload.pending_rids())
        if pending and on_undrained == "raise":
            cancelled = []
            for req in self.queue:
                req.failure = FailureInfo(req.rid, "cancelled",
                                          detail="undrained at max_ticks"
                                                 " (still queued)")
                req.done = True
                self._finish(req)
                cancelled.append(req.rid)
            self.queue = []
            cancel = getattr(self.workload, "cancel_pending", None)
            if cancel is not None:
                for req in cancel():
                    self._finish(req)
                    cancelled.append(req.rid)
            raise EngineUndrained(DrainReport(ticks=ticks, drained=False,
                                              pending=pending,
                                              cancelled=cancelled))
        return DrainReport(ticks=ticks, drained=not pending,
                           pending=pending)

    # -- warm restart ------------------------------------------------------
    @classmethod
    def recover(cls, journal_path, workload: Workload, *,
                queue_cap: int | None = None, on_full: str = "raise",
                policy=None, fsync_every: int = 32) -> "Engine":
        """Rebuild an engine from a write-ahead journal after a crash.

        Reads the journal (truncating any torn tail), re-queues every
        admit with no matching retire — in rid order, through the normal
        FIFO path, so replay coalesces and serves deterministically —
        and reopens the journal for appending. Replayed requests
        re-verify their payload digest, re-arm their original deadline
        window, and re-pin the dict version they were admitted under
        (``workload.store`` must still hold it: pair the journal with
        ``DictStore.snapshot``/``restore``). Requests already retired
        are NOT re-served; their responses live in the journal's retire
        digests. The combined (pre-crash finished + recovered) outputs
        are bit-identical to an uninterrupted run.
        """
        from repro.serve import journal as journal_mod

        records, dropped = journal_mod.Journal.read(journal_path)
        injector = getattr(workload, "injector", None)
        jr = journal_mod.Journal(journal_path, fsync_every=fsync_every,
                                 injector=injector)
        eng = cls(workload, queue_cap=queue_cap, on_full=on_full,
                  journal=jr, policy=policy)
        retired = {int(r["rid"]) for r in records
                   if r.get("kind") == "retire"}
        max_rid, replayed = -1, []
        for rec in records:
            if rec.get("kind") == "retire":
                max_rid = max(max_rid, int(rec["rid"]))
                continue
            rid = int(rec["rid"])
            max_rid = max(max_rid, rid)
            if rid in retired:
                continue
            payload = journal_mod.decode_payload(rec["payload"])
            if journal_mod.payload_digest(payload) != rec["digest"]:
                raise journal_mod.JournalError(
                    f"admit record for rid {rid} fails its payload digest")
            req = workload.make_request(rid, payload,
                                        **(rec.get("opts") or {}))
            if rec.get("deadline_s") is not None:
                req.deadline = time.monotonic() + float(rec["deadline_s"])
            dv = rec.get("dict_version")
            if dv is not None and hasattr(req, "pin_version"):
                req.pin_version = int(dv)
            eng.queue.append(req)
            replayed.append(rid)
        eng._next_rid = max_rid + 1
        eng.recovery = journal_mod.RecoveryReport(
            replayed=replayed, already_retired=len(retired),
            dropped_bytes=dropped)
        eng.events_log.emit("recovered", replayed=len(replayed),
                            already_retired=len(retired),
                            dropped_bytes=dropped)
        return eng


# ---------------------------------------------------------------------------
# stemmer workload: word-batch requests through the megakernel
# ---------------------------------------------------------------------------
@dataclass
class StemRequest:
    """A word-batch request and its (incrementally filled) response.

    dict_versions[i] is the DictStore version whose tile launch served
    word i — across a mid-stream publish() a single request may span two
    versions, and the per-word record keeps served roots auditable
    against exactly the lexicon that produced them. ``dispatched`` runs
    ahead of ``served`` while tiles are in flight: a word counts as
    dispatched when its super-tile launches and as served only when the
    launch retires (its results scattered back to this request).
    """

    rid: int
    words: np.ndarray          # int32 [n, 16] encoded words
    roots: np.ndarray          # int32 [n, 4] zero-padded char codes
    sources: np.ndarray        # int32 [n] pyref.SRC_* tags
    dict_versions: np.ndarray  # int32 [n] DictStore version per word
    dispatched: int = 0        # words claimed by a launch (or retry group)
    served: int = 0            # words completed (results scattered back)
    done: bool = False
    deadline: float | None = None       # absolute time.monotonic() bound
    failure: FailureInfo | None = None  # set iff terminally failed
    pin_version: int | None = None      # recovery: serve under exactly this
    # dict version (the one the request was admitted under, per its
    # journal record) instead of whatever is current at dispatch

    @property
    def n_words(self) -> int:
        return int(self.words.shape[0])

    @property
    def dict_version(self) -> int | None:
        """Version that served the request (the last word's, if a hot
        swap landed mid-request; None for empty requests)."""
        return int(self.dict_versions[-1]) if self.dict_versions.size else None


@dataclass
class InflightTile:
    """One dispatched super-tile awaiting retire.

    The results stay device arrays until retire; ``version`` pins the
    DictStore version acquired at *dispatch* time, so a publish() landing
    while this tile is in flight never relabels (or re-serves) its words.
    """

    segments: list             # [(req, req_start, tile_start, count)]
    version: int               # DictStore version pinned at dispatch
    roots_dev: object          # device int32 [launch_b, 4]
    sources_dev: object        # device int32 [launch_b]
    slot: int                  # staging-buffer ring slot held until retire
    flags_dev: object = None   # persistent mode: int32 [n_tiles] completion
    checksums_dev: object = None  # int32 [n_tiles] device-computed per-tile
    retries: int = 0           # retry generation of this dispatch
    t_dispatch: float = 0.0    # launch_timeout_s / watchdog_s accounting
    stalled: object = None     # injected wedge spec: never reads as ready
    via_megabatch: bool = False  # watchdog fallback: bypassed persistent

    def is_ready(self) -> bool:
        """True once the device arrays can be fetched without blocking.

        checksums_dev is never polled: it is an output of the SAME XLA
        program as roots/sources (with_checksum= fuses the fold into the
        launch), so it is ready exactly when they are — and the retire
        tick busy-polls this, so every extra is_ready() call here is paid
        hundreds of times per drain."""
        return bool(self.roots_dev.is_ready()
                    and self.sources_dev.is_ready()
                    and (self.flags_dev is None
                         or self.flags_dev.is_ready()))


@dataclass
class RetryGroup:
    """A claimed segment set awaiting (re-)dispatch.

    Segments are ``(req, req_start, count)`` — tile offsets are assigned
    at dispatch time, since a retried group repacks from the front of a
    fresh staging slot. ``retries`` counts failed dispatch attempts;
    ``not_before`` implements the retry backoff.
    """

    segments: list             # [(req, req_start, count)]
    retries: int = 0
    not_before: float = 0.0
    via_megabatch: bool = False  # force the megabatch path even when the
    # workload is persistent — the watchdog's descriptor re-dispatch
    # route (a wedged descriptor ring must not be relaunched into)


# Default cap on super-tiles per launch. A launch costs about a
# millisecond of host time whatever its size, so a deep queue should
# spread it over many tiles.
MEGABATCH_TILES = 16


class StemmerWorkload:
    """Continuous batching of word-batch requests into megakernel tiles,
    dispatch/retire-pipelined so host coalescing overlaps device compute.

    A tick is one scheduling pass over a ring of in-flight launches:

      retire    scatter back every launch whose device arrays are ready
                (non-blocking readiness check; results land in the
                per-request arrays, words move from dispatched to served)
      dispatch  coalesce pending words FIFO into a megabatch of up to
                ``megabatch_tiles`` [data_devices * block_b, 16]
                super-tiles and launch the whole megabatch as ONE
                megakernel call (the grid's batch axis spans every
                coalesced tile) — repeatedly, until ``max_inflight``
                launches are outstanding or no undispatched words remain
      drain     only a tick that would otherwise make NO progress
                blocks: saturated (every slot outstanding, none ready)
                waits for the oldest launch; draining (nothing left to
                dispatch either) hard-syncs the whole ring. A tick that
                retired or launched something never blocks, so a
                trickle-fed server keeps its launches in flight across
                submit/step iterations

    With ``max_inflight=1`` the pipeline degenerates to the synchronous
    dispatch-then-retire tick (overlap off). A launch carries as many
    queued super-tiles as the queue holds, up to ``megabatch_tiles``
    (default :data:`MEGABATCH_TILES`); ``megabatch_tiles=1`` is the
    per-tile contract, one super-tile a launch. Coalesced words that
    fit in one super-tile launch as one, so a lone short request still
    launches one super-tile; more launch as the whole megabatch, zero
    rows padding it, so a ragged queue replays two jit traces instead
    of one per fill level. The first launch under a lexicon handle of a
    new shape (or a new launch geometry) first compiles the other of
    the two on a zero tile, so no later launch compiles while traffic
    waits; that warm launch counts in neither ``ticks_launched`` nor
    ``ops.dispatch_count()``. Tile inputs are built in a preallocated
    host staging buffer per ring slot (no per-tick allocation); each
    launch pins the DictStore version it acquired at dispatch, so hot
    swaps landing between dispatch and retire stay exact per word.
    ``data_devices > 1``
    routes launches through ``ops.extract_roots_sharded``
    (dist.shard_batch), splitting each megabatch across a ("data",)
    mesh. ``persistent=True`` routes launches through
    ``ops.extract_roots_persistent`` — the single-launch descriptor-ring
    kernel — and retire additionally checks the per-tile completion
    flags against the pinned dict version (the device-side proof that
    every descriptor retired under the version acquired at dispatch).

    Fault tolerance: ``checksum=True`` (default) computes a per-tile
    int32 checksum over (roots, sources) on device at dispatch and
    re-derives it on the host copies at retire — a mismatch (torn
    readback, injected corruption) discards the launch and re-dispatches
    its words. A launch that raises, times out (``launch_timeout_s``),
    or fails the checksum is retried up to ``max_retries`` times (with
    exponential ``retry_backoff_s`` between attempts); a group that
    keeps failing is *bisected* — its segment list split in half, each
    half retried independently — until single-request groups that still
    fail are quarantined with ``FailureInfo(code="quarantined")`` while
    the rest of the batch completes. ``max_retries=0`` restores the
    strict pre-fault-tolerance contract: the first failure unwinds the
    claims and propagates. ``injector`` accepts a
    :class:`~repro.serve.faults.FaultInjector` (None = no fault layer on
    the hot path).
    """

    def __init__(self, store, *, block_b: int = 256, infix: bool = True,
                 match: str = "bsearch", dict_block_r: int = 8,
                 num_buffers: int = 2, skip_index: bool = True,
                 max_inflight: int = 2, data_devices: int = 1,
                 megabatch_tiles: int = MEGABATCH_TILES,
                 persistent: bool = False,
                 max_requests: int | None = None,
                 max_retries: int = 2, retry_backoff_s: float = 0.0,
                 launch_timeout_s: float | None = None,
                 watchdog_s: float | None = None,
                 checksum: bool = True, injector=None,
                 interpret: bool | None = None):
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
        if data_devices < 1:
            raise ValueError(f"data_devices must be >= 1, got {data_devices}")
        if megabatch_tiles < 1:
            raise ValueError(
                f"megabatch_tiles must be >= 1, got {megabatch_tiles}")
        if persistent and data_devices > 1:
            raise ValueError(
                "persistent=True is single-device (the descriptor ring is"
                " one kernel's SMEM); use megabatch_tiles for multi-device"
                " coalescing")
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        if retry_backoff_s < 0:
            raise ValueError(
                f"retry_backoff_s must be >= 0, got {retry_backoff_s}")
        if launch_timeout_s is not None and launch_timeout_s <= 0:
            raise ValueError(
                f"launch_timeout_s must be > 0, got {launch_timeout_s}")
        if watchdog_s is not None and watchdog_s <= 0:
            raise ValueError(f"watchdog_s must be > 0, got {watchdog_s}")
        if watchdog_s is not None and not persistent:
            raise ValueError(
                "watchdog_s guards the persistent descriptor ring"
                " (completion-flag stalls); non-persistent launches use"
                " launch_timeout_s")
        self.store = store
        self.block_b = block_b
        self.infix = infix
        self.match = match
        self.dict_block_r = dict_block_r
        self.num_buffers = num_buffers
        self.skip_index = skip_index
        self.max_inflight = max_inflight
        self.data_devices = data_devices
        self.megabatch_tiles = megabatch_tiles
        self.persistent = persistent
        self.max_requests = max_requests
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.launch_timeout_s = launch_timeout_s
        self.watchdog_s = watchdog_s
        self.checksum = checksum
        self.injector = injector
        self.interpret = interpret
        self.super_b = block_b * data_devices
        self.launch_b = self.super_b * megabatch_tiles
        self.inflight: list[StemRequest] = []
        self.ring: list[InflightTile] = []
        self._requeue: list[RetryGroup] = []
        self.ticks_launched = 0   # megakernel launches (not engine ticks)
        self._warmed: set = set()  # launch signatures whose buckets compiled
        # fault-path accounting
        self.retries_total = 0    # failed dispatch attempts charged
        self.bisections = 0       # groups split after exhausting retries
        self.quarantined = 0      # requests isolated with FailureInfo
        self.timeouts = 0         # launches abandoned at launch_timeout_s
        self.checksum_failures = 0  # retires discarded on checksum mismatch
        self.watchdog_stalls = 0  # persistent launches abandoned as wedged
        self.device_losses = 0    # sharded launches failed with DeviceLost
        # structured incident stream; the Engine adopts this log so
        # workload- and engine-level events interleave in one place
        self.events = EventLog()
        # degradation-ladder state: a requested ServingMode lands at the
        # next tick whose ring is empty; "streamed" overrides resident
        # published handles (degraded re-resolutions cached per version)
        self.residency_override: str | None = None
        self._pending_mode = None
        self._degraded: dict = {}
        self._mesh = None
        if data_devices > 1:
            from repro.launch import mesh as mesh_mod

            self._mesh = mesh_mod.make_data_mesh(data_devices)
        # one reusable host staging buffer per ring slot: dispatch fills
        # segments + zeroes the tail instead of allocating per tick
        self._staging = [np.zeros((self.launch_b, ab.MAXLEN), np.int32)
                         for _ in range(max_inflight)]
        self._free_slots = list(range(max_inflight))

    # -- workload protocol -------------------------------------------------
    def make_request(self, rid: int, words, **opts) -> StemRequest:
        if opts:
            raise ValueError(f"unknown stemmer request options: {sorted(opts)}")
        if isinstance(words, np.ndarray):
            if words.ndim != 2 or words.shape[1] != ab.MAXLEN:
                raise ValueError(
                    f"encoded word batch must be [n, {ab.MAXLEN}], got"
                    f" {words.shape}")
            enc = words.astype(np.int32, copy=True)
        else:
            enc = ab.encode_batch(list(words))  # raw strings
        n = enc.shape[0]
        return StemRequest(rid, enc,
                           roots=np.zeros((n, 4), np.int32),
                           sources=np.zeros(n, np.int32),
                           dict_versions=np.zeros(n, np.int32))

    def has_capacity(self) -> bool:
        return (self.max_requests is None
                or len(self.inflight) < self.max_requests)

    def admit(self, req: StemRequest):
        self.inflight.append(req)

    @property
    def active(self) -> int:
        return len(self.inflight)

    def pending_rids(self) -> list[int]:
        return [r.rid for r in self.inflight]

    def tick(self) -> list[StemRequest]:
        self._apply_pending_mode()
        retired = self._retire_ready()
        dispatched = self._fill_ring()
        if not retired and not dispatched and self.ring:
            # a would-be-zero-progress tick must still make progress.
            # Ticks that retired or launched something never block here,
            # so a trickle-fed server (submit/step one request at a
            # time) keeps its launches in flight and its overlap.
            if self._has_undispatched():
                # saturated: every slot outstanding, none ready — wait
                # for the oldest, then refill its slot
                self._retire_blocking(self.ring.pop(0))
                self._fill_ring()
            else:
                # draining: nothing left to launch, so overlap buys
                # nothing — hard-sync the whole ring
                while self.ring:
                    self._retire_blocking(self.ring.pop(0))
        finished, still = [], []
        for req in self.inflight:
            if req.failure is not None:     # quarantined mid-flight
                req.done = True
                finished.append(req)
            elif req.served >= req.n_words:  # includes empty requests
                req.done = True
                finished.append(req)
            else:
                still.append(req)
        self.inflight = still
        return finished

    def expire(self, now: float) -> list[StemRequest]:
        """Fail + hand back in-flight requests past their deadline.

        Words of an expired request still riding a launch are dropped at
        retire (the scatter skips failed requests); partial results up
        to ``served`` stay on the request for the caller to inspect.
        """
        out, still = [], []
        for req in self.inflight:
            if (req.failure is None and req.deadline is not None
                    and now > req.deadline):
                req.failure = FailureInfo(
                    req.rid, "deadline",
                    detail=f"{req.served}/{req.n_words} words served")
                req.done = True
                out.append(req)
            else:
                still.append(req)
        self.inflight = still
        return out

    def cancel_pending(self) -> list[StemRequest]:
        """Tear down the ring + retry queue; fail every in-flight
        request with FailureInfo(code="cancelled") and return them."""
        for entry in self.ring:
            self._free_slots.append(entry.slot)
        self.ring = []
        self._requeue = []
        out = []
        for req in self.inflight:
            if req.failure is None:
                req.failure = FailureInfo(
                    req.rid, "cancelled",
                    detail=f"{req.served}/{req.n_words} words served")
            req.done = True
            out.append(req)
        self.inflight = []
        return out

    # -- degradation ladder (serve/health.py) ------------------------------
    def request_mode(self, mode) -> None:
        """Ask for a ladder transition: applied at the next tick whose
        ring is empty (in-flight launches keep the geometry they
        dispatched with; resharding mid-launch is never attempted)."""
        self._pending_mode = mode

    def _apply_pending_mode(self) -> None:
        m = self._pending_mode
        if m is None or self.ring:
            return
        self._pending_mode = None
        geom_changed = (m.data_devices != self.data_devices
                        or m.megabatch_tiles != self.megabatch_tiles)
        self.persistent = m.persistent
        self.megabatch_tiles = m.megabatch_tiles
        self.residency_override = m.residency
        if m.data_devices != self.data_devices:
            self.data_devices = m.data_devices
            if m.data_devices > 1:
                from repro.launch import mesh as mesh_mod

                self._mesh = mesh_mod.make_data_mesh(m.data_devices)
            else:
                self._mesh = None
        if geom_changed:
            self.super_b = self.block_b * self.data_devices
            self.launch_b = self.super_b * self.megabatch_tiles
            self._staging = [np.zeros((self.launch_b, ab.MAXLEN), np.int32)
                             for _ in range(self.max_inflight)]
            self._free_slots = list(range(self.max_inflight))
            self._split_requeue(self.launch_b)

    def _split_requeue(self, cap: int) -> None:
        """Re-chunk waiting retry groups so none exceeds the (possibly
        shrunken) launch width after a ladder transition."""
        out = []
        for grp in self._requeue:
            cur, fill = [], 0
            for req, r0, take in grp.segments:
                while take > 0:
                    t = min(take, cap - fill)
                    if t == 0:
                        out.append(RetryGroup(cur, retries=grp.retries,
                                              not_before=grp.not_before,
                                              via_megabatch=grp.via_megabatch))
                        cur, fill = [], 0
                        continue
                    cur.append((req, r0, t))
                    fill += t
                    r0 += t
                    take -= t
            if cur:
                out.append(RetryGroup(cur, retries=grp.retries,
                                      not_before=grp.not_before,
                                      via_megabatch=grp.via_megabatch))
        self._requeue = out

    def _degraded_handle(self, dv):
        """This version's arrays re-resolved at the ladder's residency
        override (e.g. resident -> streamed), cached per (version,
        override) so repeated launches reuse one handle/trace."""
        key = (dv.version, self.residency_override)
        h = self._degraded.get(key)
        if h is None:
            from repro.core import stemmer as core_stemmer

            h = core_stemmer.resolve_dict(
                dv.arrays, residency=self.residency_override,
                infix=self.infix, dict_block_r=self.dict_block_r)
            self._degraded[key] = h
        return h

    # -- dispatch side -----------------------------------------------------
    def _has_undispatched(self) -> bool:
        return bool(self._requeue) or any(
            req.n_words > req.dispatched for req in self.inflight
            if req.failure is None)

    def _coalesce(self) -> list[tuple[StemRequest, int, int]]:
        """FIFO-claim one megabatch (up to ``megabatch_tiles``
        super-tiles) of undispatched words: -> [(req, req_start, count)].

        Claiming advances ``req.dispatched`` immediately — a failed
        launch keeps its words through the RetryGroup rather than
        releasing them for re-coalescing (which could double-dispatch
        against an in-flight retry).

        A launch acquires ONE dict version, so requests with different
        ``pin_version``s (recovery pins the admit-time version; fresh
        requests pin nothing) never share a group — coalescing breaks
        at the first pin mismatch and picks the rest up next launch.
        """
        segments, fill, pin = [], 0, None
        for req in self.inflight:
            if req.failure is not None:
                continue
            if fill >= self.launch_b:
                break
            take = min(req.n_words - req.dispatched, self.launch_b - fill)
            if take > 0:
                if not segments:
                    pin = req.pin_version
                elif req.pin_version != pin:
                    break
                segments.append((req, req.dispatched, take))
                req.dispatched += take
                fill += take
        return segments

    def _bucket_rows(self, fill: int) -> int:
        """Staging rows to launch for ``fill`` coalesced words: one
        super-tile if they fit in one, else the whole megabatch. A
        launch's host cost hardly grows with its rows, while every
        launch shape costs set-up time to trace, lower and load (PERF.md
        §6), so a ragged queue replays two jit traces, not one per fill
        level."""
        return self.super_b if fill <= self.super_b else self.launch_b

    def _warm(self, handle, version: int, use_persistent: bool,
              rows: int) -> None:
        """On the first launch of a signature (launch path, geometry and
        the handle's pytree shapes), run a zero tile through the other
        bucket's launch and wait for it, so both buckets are traced,
        lowered and compiled before traffic needs them. A hot swap to a
        handle of the same shapes hits the same jit cache entries and
        warms nothing again."""
        leaves, tree = jax.tree.flatten(handle)
        key = (use_persistent, self.data_devices, self.launch_b, tree,
               tuple((x.shape, x.dtype) for x in leaves))
        if key in self._warmed:
            return
        from repro.kernels import ops

        with ops.uncounted_dispatches():
            # the launch itself compiles its own bucket
            for n in {self.super_b, self.launch_b} - {rows}:
                jax.block_until_ready(self._launch(
                    np.zeros((n, ab.MAXLEN), np.int32), handle, version,
                    use_persistent))
        self._warmed.add(key)

    def _next_group(self) -> RetryGroup | None:
        """The next dispatchable group: an eligible retry first (FIFO),
        else a freshly coalesced one. Drops segments of requests that
        failed while their group waited."""
        now = time.monotonic()
        found, keep = None, []
        for grp in self._requeue:
            grp.segments = [(req, r0, take) for req, r0, take in grp.segments
                            if req.failure is None]
            if not grp.segments:
                continue                # everything in it already failed
            if found is None and grp.not_before <= now:
                found = grp
            else:
                keep.append(grp)
        self._requeue = keep
        if found is not None:
            return found
        segments = self._coalesce()
        return RetryGroup(segments) if segments else None

    def _fill_ring(self) -> int:
        """Dispatch until max_inflight launches are outstanding or
        nothing is dispatchable; returns the number of launches."""
        n = 0
        waited = False
        while len(self.ring) < self.max_inflight:
            with span(STEM_COALESCE):
                grp = self._next_group()
            if grp is None:
                if self._requeue and not self.ring and not waited:
                    # every retryable group is backing off and nothing
                    # else is in flight: wait out the soonest backoff —
                    # once per tick, so a repeatedly failing group burns
                    # at most ~one retry per tick instead of sleeping
                    # through its whole quarantine budget here
                    wait = (min(g.not_before for g in self._requeue)
                            - time.monotonic())
                    if wait > 0:
                        time.sleep(wait)
                    waited = True
                    continue
                break
            n += self._dispatch_group(grp)
        return n

    @staticmethod
    def _unclaim(grp: RetryGroup) -> None:
        """Return a group's words to their requests, undispatched."""
        for req, _r0, take in grp.segments:
            req.dispatched -= take

    def _launch_failed(self, grp: RetryGroup, exc: BaseException) -> int:
        """Shared failure path for dispatch errors, timeouts, and retire
        checksum mismatches: retry with backoff, bisect after
        ``max_retries``, quarantine single-request leaves."""
        if self.max_retries == 0:
            # strict mode: unwind the claims so every word is
            # re-coalesced from scratch, and propagate to the caller
            self._unclaim(grp)
            raise exc
        grp.retries += 1
        self.retries_total += 1
        self.events.emit("retry", attempt=grp.retries,
                         rids=[req.rid for req, _r0, _t in grp.segments],
                         detail=str(exc))
        if grp.retries > self.max_retries:
            if len(grp.segments) > 1:
                # the whole group keeps failing: split it so a poison
                # request is isolated in O(log segments) rounds while
                # the healthy halves complete
                mid = len(grp.segments) // 2
                self.bisections += 1
                self.events.emit("bisect", segments=len(grp.segments))
                self._requeue.append(RetryGroup(
                    grp.segments[:mid], via_megabatch=grp.via_megabatch))
                self._requeue.append(RetryGroup(
                    grp.segments[mid:], via_megabatch=grp.via_megabatch))
            else:
                (req, _r0, _take), = grp.segments
                req.failure = FailureInfo(
                    req.rid, "quarantined", retries=grp.retries,
                    detail=str(exc))
                self.quarantined += 1
        else:
            backoff = self.retry_backoff_s * (2 ** (grp.retries - 1))
            grp.not_before = time.monotonic() + backoff
            self._requeue.append(grp)
        return 0

    def _dispatch_group(self, grp: RetryGroup) -> int:
        """Launch one group; returns 1 on success, 0 when the failure
        was absorbed into the retry machinery."""
        if self.injector is not None:
            try:
                self.injector.on_dispatch(
                    rids=[req.rid for req, _r0, _take in grp.segments])
                if self._mesh is not None:
                    self.injector.on_device_loss()
            except Exception as e:
                if isinstance(e, DeviceLost):
                    self.device_losses += 1
                    self.events.emit("device_loss",
                                     data_devices=self.data_devices,
                                     detail=str(e))
                return self._launch_failed(grp, e)
        # one version per megabatch launch: recovered requests pin the
        # version they were admitted under, everything else serves the
        # current one (_coalesce never mixes pins in one group)
        pin = grp.segments[0][0].pin_version
        if pin is None:
            dv = self.store.acquire()
        else:
            try:
                dv = self.store.get(pin)
            except KeyError as e:
                # the pinned lexicon is gone from the catalog (snapshot
                # not restored / history dropped): fail loudly into the
                # retry machinery rather than silently serving another
                # version — auditability beats availability here
                return self._launch_failed(grp, e)
        handle = dv.handle
        if (self.residency_override is not None
                and handle.residency != self.residency_override):
            handle = self._degraded_handle(dv)
        use_persistent = self.persistent and not grp.via_megabatch
        slot = self._free_slots.pop()
        with span(STEM_STAGE):
            tile = self._staging[slot]
            placed, fill = [], 0
            for req, r0, take in grp.segments:
                tile[fill:fill + take] = req.words[r0:r0 + take]
                placed.append((req, r0, fill, take))
                fill += take
            rows = self._bucket_rows(fill)
            tile[fill:rows] = 0         # padded words must stay empty
        with span(STEM_LAUNCH):
            try:
                self._warm(handle, dv.version, use_persistent, rows)
                roots, sources, flags, checksums = self._launch(
                    tile[:rows], handle, dv.version, use_persistent)
            except BaseException as e:
                # a failed launch must not wedge the engine: return the
                # slot and route the group through the retry machinery
                # (strict mode re-raises with the words unclaimed). A
                # launch that failed to trace, lower or compile fails the
                # same way on every attempt: it propagates with the words
                # unclaimed instead of being retried into quarantine.
                self._free_slots.append(slot)
                if isinstance(e, (KeyboardInterrupt, SystemExit)):
                    raise
                if _deterministic_launch_error(e):
                    self._unclaim(grp)
                    raise
                return self._launch_failed(grp, e)
            for arr in (roots, sources, flags, checksums):
                if arr is not None:     # start D2H early; retire just reads
                    arr.copy_to_host_async()
        entry = InflightTile(placed, dv.version, roots, sources, slot,
                             flags, checksums_dev=checksums,
                             retries=grp.retries,
                             t_dispatch=time.monotonic(),
                             via_megabatch=grp.via_megabatch)
        if flags is not None and self.injector is not None:
            # a wedge is observable only through the completion flags,
            # so the stall site covers persistent launches alone
            entry.stalled = self.injector.on_stall()
        self.ring.append(entry)
        self.ticks_launched += 1
        return 1

    def _launch(self, rows, handle, version: int, use_persistent: bool):
        """One megakernel call on the staged ``rows`` -> device arrays
        (roots, sources, completion flags or None, checksums or None)."""
        from repro.kernels import ops  # lazy: keep engine import light

        # with_checksum fuses the per-tile integrity row into the
        # launch's own jit scope (verified against a host recompute at
        # retire) — fault tolerance costs no extra XLA dispatch
        cs = self.checksum
        kw = dict(infix=self.infix, match=self.match, block_b=self.block_b,
                  dict_block_r=self.dict_block_r,
                  num_buffers=self.num_buffers, skip_index=self.skip_index,
                  with_checksum=cs, interpret=self.interpret)
        flags = None
        if self._mesh is not None:
            out = ops.extract_roots_sharded(jnp.asarray(rows), handle,
                                            self._mesh, **kw)
        elif use_persistent:
            out = ops.extract_roots_persistent(jnp.asarray(rows), handle,
                                               version_slot=version, **kw)
            flags = out[2]
        else:
            out = ops.extract_roots_fused(jnp.asarray(rows), handle, **kw)
        return out[0], out[1], flags, out[-1] if cs else None

    # -- retire side -------------------------------------------------------
    def _retire_ready(self) -> int:
        """Retire every in-flight launch whose results are ready (and
        abandon any past ``watchdog_s`` / ``launch_timeout_s``), oldest
        first, without blocking; returns the number processed."""
        still, n = [], 0
        now = time.monotonic()
        for entry in self.ring:
            stalled = entry.stalled is not None
            if not stalled and entry.is_ready():
                self._retire(entry)
                n += 1
            elif (self.watchdog_s is not None
                  and entry.flags_dev is not None
                  and now - entry.t_dispatch > self.watchdog_s):
                # persistent launch wedged: salvage the retired prefix,
                # re-dispatch the rest down the megabatch path
                self._watchdog_abandon(entry)
                n += 1
            elif (not stalled and self.launch_timeout_s is not None
                  and now - entry.t_dispatch > self.launch_timeout_s):
                # abandon the launch: drop the device refs, free the
                # slot, and re-dispatch its words through the retry path
                self.timeouts += 1
                self._free_slots.append(entry.slot)
                grp = RetryGroup([(req, r0, take) for req, r0, _t0, take
                                  in entry.segments], retries=entry.retries,
                                 via_megabatch=entry.via_megabatch)
                self._launch_failed(grp, TimeoutError(
                    f"launch exceeded launch_timeout_s="
                    f"{self.launch_timeout_s}"))
                n += 1
            else:
                still.append(entry)
        self.ring = still
        return n

    def _retire_blocking(self, entry: InflightTile) -> None:
        """Blocking drain of one launch. A launch marked wedged (an
        injected stall) must NOT be read — a real wedge never completes,
        and reading would block forever — so wait out the watchdog
        window and abandon it instead."""
        if entry.stalled is not None and self.watchdog_s is not None:
            wait = self.watchdog_s - (time.monotonic() - entry.t_dispatch)
            if wait > 0:
                time.sleep(wait)
            self._watchdog_abandon(entry)
        else:
            self._retire(entry)

    def _watchdog_abandon(self, entry: InflightTile) -> None:
        """Abandon a wedged persistent launch (DESIGN.md §12).

        Descriptors retire in ring order, so a wedge leaves a *prefix*
        of completion flags reading done: salvage that prefix (checksum-
        verified per tile), scatter its words, and re-dispatch the rest
        as a ``via_megabatch`` RetryGroup — never back into the wedged
        descriptor ring. No retry is charged: the stall is the launch's
        fault, not the group's, so zero requests are lost even at
        max_retries=0.
        """
        from repro.kernels import ops, stem_fused

        self.watchdog_stalls += 1
        self._free_slots.append(entry.slot)
        rows_ok = 0
        spec = entry.stalled
        if spec is not None:
            # injected wedge: the kernel actually completed (interpret
            # mode cannot truly hang), so synthesize the flag state a
            # real wedge would leave — the first `retired_tiles`
            # descriptors done, the rest untouched — then salvage
            flags = np.asarray(entry.flags_dev).copy()
            flags[min(spec.retired_tiles, flags.size):] = 0
            rows_ok = stem_fused.salvage_descriptor_rows(
                flags, entry.version, self.block_b)
        # a REAL wedge's arrays live in a launch that never completes;
        # reading them would block forever, so nothing is salvaged and
        # every word re-dispatches
        roots = sources = None
        if rows_ok > 0:
            roots = np.asarray(entry.roots_dev)[:rows_ok]
            sources = np.asarray(entry.sources_dev)[:rows_ok]
            if entry.checksums_dev is not None:
                want = np.asarray(
                    entry.checksums_dev)[:rows_ok // self.block_b]
                got = ops.tile_checksum_host(roots, sources,
                                             block_b=self.block_b)
                bad = np.flatnonzero(got != want)
                if bad.size:       # trust only the clean flag+sum prefix
                    rows_ok = int(bad[0]) * self.block_b
        salvaged = redispatched = 0
        redo = []
        for req, r0, t0, take in entry.segments:
            if req.failure is not None:   # expired/cancelled mid-flight
                continue
            good = max(0, min(take, rows_ok - t0))
            if good > 0:
                req.roots[r0:r0 + good] = roots[t0:t0 + good]
                req.sources[r0:r0 + good] = sources[t0:t0 + good]
                req.dict_versions[r0:r0 + good] = entry.version
                req.served += good
                salvaged += good
            if take > good:
                redo.append((req, r0 + good, take - good))
                redispatched += take - good
        if redo:
            self._requeue.append(RetryGroup(redo, retries=entry.retries,
                                            via_megabatch=True))
        self.events.emit("watchdog_stall", injected=spec is not None,
                         salvaged_words=salvaged,
                         redispatched_words=redispatched,
                         version=entry.version)

    def _retire(self, entry: InflightTile) -> bool:
        """Scatter one launch's results back (blocks if not yet ready).

        Returns False when the tile failed checksum verification and was
        re-queued for redispatch instead of scattered.
        """
        with span(STEM_FETCH):
            roots = np.asarray(entry.roots_dev)
            sources = np.asarray(entry.sources_dev)
            flags = (None if entry.flags_dev is None
                     else np.asarray(entry.flags_dev))
            want = (None if entry.checksums_dev is None
                    else np.asarray(entry.checksums_dev))
        self._free_slots.append(entry.slot)
        if self.injector is not None:
            roots, sources = self.injector.on_retire(roots, sources)
        # descriptor-ring integrity: every tile of the persistent launch
        # must have completed under the version pinned at dispatch
        # (flag = 1 + version slot; 0 = never processed)
        if flags is not None and not (flags == 1 + entry.version).all():
            raise RuntimeError(
                "persistent launch retired with bad completion flags:"
                f" expected {1 + entry.version}, got {flags.tolist()}")
        if want is not None:
            from repro.kernels import ops

            with span(STEM_VERIFY):
                got = ops.tile_checksum_host(roots, sources,
                                             block_b=self.block_b)
                ok = np.array_equal(got, want)
            if not ok:
                bad = np.nonzero(got != want)[0].tolist()
                err = RuntimeError(
                    f"retire checksum mismatch on tile(s) {bad} of"
                    f" {want.shape[0]} (device vs host copy) — discarding"
                    " the launch")
                if self.max_retries == 0:
                    raise err
                self.checksum_failures += 1
                self.events.emit("checksum_failure", tiles=bad,
                                 rids=[req.rid for req, *_ in entry.segments])
                grp = RetryGroup([(req, r0, take) for req, r0, _t0, take
                                  in entry.segments], retries=entry.retries,
                                 via_megabatch=entry.via_megabatch)
                self._launch_failed(grp, err)
                return False
        with span(STEM_SCATTER):
            for req, r0, t0, take in entry.segments:
                if req.failure is not None:   # expired/cancelled mid-flight
                    continue
                req.roots[r0:r0 + take] = roots[t0:t0 + take]
                req.sources[r0:r0 + take] = sources[t0:t0 + take]
                req.dict_versions[r0:r0 + take] = entry.version
                req.served += take
        return True
