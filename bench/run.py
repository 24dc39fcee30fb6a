"""One run of one benchmark cell on the chips of this machine.

    python bench/run.py --workload newswire.docs --seed 7 --seconds 10 --trace 0

Resolves the cell's files by name (``bench/spec.py``), makes the lexicon
and the traffic from ``--seed``, builds the system under test, warms up
every shape the traffic reaches (set-up, reported as ``setup_s``), then
runs the traffic for ``--seconds``. With ``--trace 1`` the window is
profiled and the cell's per-layer metrics are read from the trace, the
program's counters and the benchmark's spans; with ``--trace 0`` the
end-to-end metrics are reported. After the window, what the timed path
produced is compared with the plain reference (``bench/check.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``check``, each compared number with its limit. The run fails,
printing no result, where JAX finds no TPU, fewer chips than the cell
asks for, or a device kind that ``bench/peaks.json`` does not list.

Where the configuration states a ``journal``, the engine writes its
write-ahead journal to a fresh directory under ``.bench_journal/`` in the
checkout (on the checkout's disk: the temporary directory may be a
tmpfs, where an fsync costs nothing), the run logs that directory's
filesystem type and fails, printing no result, where it is tmpfs or
ramfs; the check reads every acknowledged request back from the file,
and the directory is removed when the run ends, however it ends.

``--fault control`` (never used by a measured run) puts the reference,
with one stated guarantee broken, in the program's place; it must come
out ``correct: false``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the package, not this directory, so no module here shadows another
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import check, drive, spec, trace, work  # noqa: E402
from bench.drive import percentile  # noqa: E402

GRACE_S = 60.0          # an open-loop request unfinished this long after
                        # the window is missing
JOURNAL_DIR = ".bench_journal"  # under the checkout: a directory a run
MEMORY_FS = ("tmpfs", "ramfs")  # where an fsync does nothing


class NoChip(RuntimeError):
    pass


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=("none", "control"), default="none")
    return ap.parse_args(argv)


def find_chips(n: int):
    """-> (the first ``n`` devices, their peaks); no TPU is an error."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r});"
                     " this benchmark measures only on the chip")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n], work.peaks(devices[0].device_kind)


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or, unset,
    at the fixed ``<checkout>/.jax_cache``; every compile is cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Counts XLA compiles and lowerings while ``on`` (the window)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.on = False
        self.compiles = self.lowerings = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def close(self) -> None:
        import jax

        self.on = False
        jax.monitoring.unregister_event_duration_listener(self._event)

    def _event(self, event: str, _secs: float, **_kw) -> None:
        if self.on:
            self.compiles += event == self.COMPILE
            self.lowerings += event == self.LOWER


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def filesystem_type(path: Path) -> str:
    """The type of the filesystem holding ``path``: that of the longest
    mount point in ``/proc/self/mounts`` that contains it."""
    path = os.path.realpath(path)
    best, fstype = "", "unknown"
    try:
        with open("/proc/self/mounts", encoding="utf-8") as f:
            mounts = [line.split() for line in f]
    except OSError:
        return fstype
    for fields in mounts:
        if len(fields) < 3:
            continue
        # spaces, tabs and backslashes in a mount point come escaped: \040
        mnt = re.sub(r"\\([0-7]{3})", lambda m: chr(int(m.group(1), 8)),
                     fields[1])
        inside = path == mnt or path.startswith(mnt.rstrip("/") + "/")
        if inside and len(mnt) >= len(best):    # a later mount hides one
            best, fstype = mnt, fields[2]       # at the same point
    return fstype


@contextlib.contextmanager
def journal_directory(root: Path):
    """A fresh directory under ``<root>/.bench_journal/``, removed (with
    the parent, once that is empty) when the block ends, however it
    ends."""
    parent = Path(root) / JOURNAL_DIR
    parent.mkdir(exist_ok=True)
    try:
        with tempfile.TemporaryDirectory(prefix=f"{os.getpid()}-",
                                         dir=parent) as path:
            yield Path(path)
    finally:
        with contextlib.suppress(OSError):
            parent.rmdir()


def run_window(cell, system, bodies, seconds: float, trace_dir: str | None):
    """The measured window (profiled when ``trace_dir`` is given)."""
    import jax

    loop = cell.traffic["loop"]
    if trace_dir:
        # no Python function tracing: it would record every call of the
        # host loop, slow the window and bloat the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with drive.span(trace.WINDOW_SPAN):
            if loop == "closed":
                served = system.closed(bodies, cell.traffic["clients"], seconds)
            elif loop == "open":
                served = system.open(bodies, seconds, GRACE_S)
            elif loop == "repeat":
                served = system.repeat(bodies, seconds)
            else:
                raise spec.SpecError(f"unknown loop {loop!r}")
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return served


def counters(cell, system, bodies, served, lex_tables) -> dict:
    """The program's counters and the window's sizes, by name, for the
    per-layer readers."""
    c = {f"lexicon_{k}": int((v >= 0).sum()) for k, v in lex_tables.items()}
    c["lexicon_roots"] = sum(c.values())
    c["devices"] = cell.chips
    if cell.traffic["loop"] == "repeat":
        words = sum(ch.n_words for ch in bodies)
        builds = len(served.done)
        c.update(index_words=builds * words,
                 index_launches=builds * len(bodies) * cell.chips)
        return c
    ok = [system.result(rid) for _i, rid, _d, _t in served.done]
    ok = [r for r in ok if r.failure is None]
    c.update(words_served=sum(r.n_words for r in ok),
             stemmer_launches=served.launches,
             dispatches=served.dispatches,
             requests=len(ok))
    if isinstance(bodies[0], str):
        c["chars_frontend"] = sum(len(bodies[i]) for i, *_ in served.done)
        c["words_frontend"] = c["words_served"]
    if served.journal_records is not None:
        c.update(journal_records=served.journal_records,
                 journal_bytes=served.journal_bytes)
    return c


def end_to_end(cell, run: dict) -> dict:
    """Each end-to-end metric of the cell, by ``bench/measures/<name>.py``."""
    out = {}
    for m in cell.end_to_end:
        out[m["name"]] = {"value": cell.measure(m["name"])(run),
                          "unit": m["unit"]}
    return out


def per_layer(cell, events, ctx_counters, peak) -> dict:
    ctx = {"events": events, "counters": ctx_counters, "peak": peak}
    out = {}
    for m in cell.per_layer:
        v = cell.reader(m["reader"])(ctx, m)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None, root: Path = ROOT) -> int:
    """One run; ``root`` is the checkout whose ``BENCHMARK.json`` and
    ``bench/`` files define the cell."""
    args = parse_args(argv)
    cell = spec.resolve(root, args.workload)
    try:
        devices, peak = find_chips(cell.chips)
    except (NoChip, work.UnknownDevice) as e:
        log(f"run.py: {e}")
        return 1
    cache = enable_compile_cache()
    if cell.fsync_every is None:
        return measure(args, cell, devices, peak, cache)
    with journal_directory(root) as journal_dir:
        fs = filesystem_type(journal_dir)
        log(f"journal in {journal_dir}: filesystem {fs},"
            f" fsync every {cell.fsync_every} records")
        if fs in MEMORY_FS:
            log(f"run.py: the journal's filesystem is {fs}: an fsync there"
                " makes nothing durable")
            return 1
        return measure(args, cell, devices, peak, cache, journal_dir)


def build_system(cell, tables: dict, journal_dir: Path | None = None):
    """The system under test that the cell's mix enters, with the
    configuration's journal in ``journal_dir``."""
    if cell.traffic["entry"] == "index":
        return drive.IndexSystem(cell.config, tables)
    return drive.EngineSystem(cell.config, tables,
                              text=cell.traffic["entry"] == "text",
                              journal_dir=journal_dir,
                              fsync_every=cell.fsync_every)


def measure(args, cell, devices, peak, cache: str,
            journal_dir: Path | None = None) -> int:
    """Set-up, window, check and report of one run."""
    counter = CompileCounter()
    roots, tables = drive.lexicon(cell.config, args.seed)
    bodies = drive.payloads(cell.traffic, args.seed, args.seconds, roots)
    entry = cell.traffic["entry"]
    system = build_system(cell, tables, journal_dir)
    system.warm_up(bodies)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s (compile cache {cache})")

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        counter.on = True
        served = run_window(cell, system, bodies, args.seconds,
                            tdir if args.trace else None)
        counter.close()
        mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)
        events = None
        if args.trace:
            path = next(Path(tdir).rglob("*.xplane.pb"))
            events = trace.load_xplane(str(path))
    ctr = counters(cell, system, bodies, served, tables)
    metrics = (per_layer(cell, events, ctr, peak) if args.trace
               else end_to_end(cell, {"system": system, "bodies": bodies,
                                      "served": served, "setup_s": setup_s,
                                      "grace_s": GRACE_S}))

    results = getattr(system, "results", None)
    system.release()
    gc.collect()
    rng = np.random.default_rng([args.seed, 1])
    control = args.fault == "control"
    infix = cell.config["infix"]
    if entry == "index":
        numbers = check.index_numbers(results, bodies, roots, tables,
                                      infix=infix, devices=cell.chips,
                                      control=control)
        attempted, failed = len(served.done), 0
    else:
        if entry == "text":
            numbers = check.text_numbers(
                system, served, bodies, roots, infix=infix, rng=rng,
                n_sample=cell.traffic["check_sample"], control=control)
        else:
            numbers = check.word_numbers(system, served, bodies, roots,
                                         infix=infix, control=control)
        if system.journal is not None:
            numbers += check.journal_numbers(
                system, served, bodies, fsync_every=cell.fsync_every,
                control=control)
        attempted = len(served.done) + len(served.missing)
        failed = check.failed_requests(system, served)

    window = served.t_end - served.t0
    log(f"window {window:.3f} s; {len(served.done)} finished,"
        f" {len(served.missing)} missing; compiles in window"
        f" {counter.compiles}, lowerings {counter.lowerings}")
    if served.lateness:
        late = sorted(served.lateness)
        log(f"generator lateness: median {1e3 * statistics.median(late):.3f} ms,"
            f" p95 {1e3 * percentile(late, 95):.3f} ms, max {1e3 * late[-1]:.3f} ms"
            f" over {len(late)} requests")
    for k, v in sorted(ctr.items()):
        log(f"counter {k} = {v}")
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    result = {"correct": all(n.ok for n in numbers),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        lo, hi = trace.window(events)
        planes = trace.devices(events)
        busy = [trace.busy_ns(events, p, lo, hi) for p in planes]
        device["busy_s"] = sum(busy) / max(len(busy), 1) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {"device_ops": trace.top_ops(events),
                               "idle_gaps": trace.idle_gaps(events)}
    result["check"] = {n.name: {"value": n.value, "limit": n.limit}
                       for n in numbers}
    for name, v in result["check"].items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
