"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``: planes (one per device, one
for the host), lines and events with a start and a duration in
nanoseconds on one clock. :func:`load_xplane` flattens it to a list of
:class:`Event`; everything else here works on that list, so a test can
check the reduction on a small trace saved as JSON (:func:`save_json`).

  busy      the union of one device's op intervals inside the window
  idle      1 - busy / window, per device
  selected  device time of the events a selector matches (a Pallas
            kernel by its name, an XLA program by its module name)
  gaps      the idle stretches of a device, each named by the host span
            that covered its middle
"""
from __future__ import annotations

import bisect
import json
import re
from dataclasses import asdict, dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"
# benchmark spans that name what the host was doing during a device gap
HOST_SPANS = ("submit", "Engine.step", "wait", "build_corpus_index")
# the program's own host spans (src/repro/serve/spans.py), kept for readers
PROGRAM_SPAN_PREFIX = "repro."


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""        # the XLA program an op ran in, where given

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def short_name(name: str) -> str:
    """An op's HLO text cut to its name and result shape
    (``%fusion.1 = s32[4096]{...} fusion(...)`` -> ``%fusion.1 s32[4096]``);
    a module's name without its fingerprint."""
    if " = " in name:
        op, rest = name.split(" = ", 1)
        return f"{op} {rest.split('{', 1)[0].split(' ', 1)[0]}"
    return re.sub(r"\(\d+\)$", "", name)


def load_xplane(path: str) -> list[Event]:
    """Every event of the device planes' op and module lines, the
    benchmark's host spans and the program's (``repro.*``). An op's
    ``module`` is the XLA program whose event on the module line
    encloses it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = DEVICE_PLANE.match(plane.name)
        if not dev and plane.name != HOST_PLANE:
            continue
        modules, ops = [], []
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not dev and not (ev.name in HOST_SPANS + (WINDOW_SPAN,)
                                    or ev.name.startswith(PROGRAM_SPAN_PREFIX)):
                    continue
                e = (line.name, ev.name, float(ev.start_ns),
                     float(ev.duration_ns))
                (modules if line.name == MODULES_LINE else ops).append(e)
        modules.sort(key=lambda m: m[2])
        starts = [m[2] for m in modules]
        for line, name, start, dur in ops:
            k = bisect.bisect_right(starts, start) - 1
            mod = (short_name(modules[k][1]) if k >= 0
                   and start <= modules[k][2] + modules[k][3] else "")
            out.append(Event(plane.name, line, name, start, dur, mod))
        out += [Event(plane.name, line, name, start, dur)
                for line, name, start, dur in modules]
    return out


def save_json(events: list[Event], path: str) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump([asdict(e) for e in events], f)


def load_json(path: str) -> list[Event]:
    with open(path, encoding="utf-8") as f:
        return [Event(**e) for e in json.load(f)]


def window(events: list[Event]) -> tuple[float, float]:
    """(start, end) ns of the benchmark's measured window span."""
    spans = [e for e in events if e.plane == HOST_PLANE
             and e.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"trace holds {len(spans)} window spans, not 1")
    return spans[0].start_ns, spans[0].end_ns


def devices(events: list[Event]) -> list[str]:
    return sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)},
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def _clip(intervals, lo: float, hi: float):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted intervals covering the same time."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_intervals(events: list[Event], plane: str, lo: float, hi: float):
    """The union of ``plane``'s op intervals within [lo, hi]."""
    ops = [(e.start_ns, e.end_ns) for e in events
           if e.plane == plane and e.line == OPS_LINE]
    return union(_clip(ops, lo, hi))


def busy_ns(events: list[Event], plane: str, lo: float, hi: float) -> float:
    return sum(e - s for s, e in busy_intervals(events, plane, lo, hi))


def idle_share(events: list[Event]) -> float | None:
    """1 - busy / window, averaged over the devices; None without any
    device op in the window."""
    lo, hi = window(events)
    planes = devices(events)
    if not planes or hi <= lo:
        return None
    busy = [busy_ns(events, p, lo, hi) for p in planes]
    if not any(busy):
        return None
    return 1.0 - sum(busy) / len(busy) / (hi - lo)


def matches(e: Event, selector: dict) -> bool:
    """``{"line": "XLA Ops" | "XLA Modules", "pattern": regex}``: the
    regex is searched in the event's name and in its module's name."""
    if e.line != selector["line"] or not DEVICE_PLANE.match(e.plane):
        return False
    pat = re.compile(selector["pattern"])
    return bool(pat.search(e.name) or pat.search(e.module))


def selected_ns(events: list[Event], selector: dict) -> float:
    """Device time of the matched events inside the window, summed over
    every device (clipped to the window)."""
    lo, hi = window(events)
    return sum(e - s for s, e in _clip(
        ((ev.start_ns, ev.end_ns) for ev in events
         if matches(ev, selector)), lo, hi))


def host_spans(events: list[Event], name: str) -> list[Event]:
    lo, hi = window(events)
    return [e for e in events if e.plane == HOST_PLANE and e.name == name
            and e.start_ns >= lo and e.end_ns <= hi]


def top_ops(events: list[Event], n: int = 10) -> list[list]:
    """The ``n`` device ops that took most time in the window (seconds,
    summed over devices), each named ``<module>:<op> <shape>``."""
    lo, hi = window(events)
    tot: dict[str, float] = {}
    for e in events:
        if e.line == OPS_LINE and DEVICE_PLANE.match(e.plane):
            name = f"{e.module}:{short_name(e.name)}"
            for s, t in _clip([(e.start_ns, e.end_ns)], lo, hi):
                tot[name] = tot.get(name, 0.0) + (t - s)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(events: list[Event], n: int = 10) -> list[list]:
    """The ``n`` longest idle stretches of the first device, each named by
    the innermost benchmark host span around its middle ("none" where
    the host was in none)."""
    lo, hi = window(events)
    planes = devices(events)
    if not planes:
        return []
    gaps, at = [], lo
    for s, e in busy_intervals(events, planes[0], lo, hi):
        if s > at:
            gaps.append((at, s))
        at = e
    if hi > at:
        gaps.append((at, hi))
    spans = [e for e in events if e.plane == HOST_PLANE
             and e.name in HOST_SPANS]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        around = [sp for sp in spans if sp.start_ns <= mid <= sp.end_ns]
        name = min(around, key=lambda sp: sp.dur_ns).name if around else "none"
        out.append([name, (e - s) / 1e9])
    return out
