"""Drives the system under test: builds it from a configuration, warms it
up, and runs a traffic mix against it for the measured window.

One general generator serves every mix file. A mix names

  payload  what a request carries: ``documents`` or ``queries`` (raw
           text, through ``TextAnalysisWorkload``), ``chapters``
           (pre-encoded word rows, through ``StemmerWorkload``) or
           ``corpus`` (chunks, through ``build_corpus_index``)
  loop     ``closed`` (each of ``clients`` sends its next request when
           its last one is done), ``open`` (requests due on a fixed
           Poisson schedule at ``rate_per_s``, whether or not the system
           keeps up) or ``repeat`` (one whole index build after another)

and the payload's own sizes. Spans of the benchmark's own
(``jax.profiler.TraceAnnotation``) mark the calls into the program.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from bench.gen import corpus as gen

now = time.perf_counter


def percentile(values: list, q: float) -> float:
    """Nearest-rank ``q``-th percentile of every value."""
    v = sorted(values)
    return v[max(0, int(np.ceil(q / 100 * len(v))) - 1)]


def span(name: str):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


# ---------------------------------------------------------------------------
# inputs, from the seed
# ---------------------------------------------------------------------------
def lexicon(config: dict, seed: int):
    """-> (reference RootDict, packed tables) of the configuration."""
    lex = config["lexicon"]
    if lex["n_bi"] != len(gen.REAL_BI_ROOTS):
        raise ValueError(f"the generator holds {len(gen.REAL_BI_ROOTS)}"
                         f" bilateral roots, the configuration asks {lex['n_bi']}")
    roots = gen.build_dictionary(lex["n_tri"], lex["n_quad"], seed)
    return roots, gen.packed_tables(roots)


def payloads(traffic: dict, seed: int, seconds: float, roots) -> list:
    """Every request body the run can send, in the order it sends them
    (closed loops cycle through them), with words over the lexicon
    ``roots``."""
    p = dict(traffic["payload"])
    kind = p.pop("kind")
    if kind == "chapters":
        return gen.chapters(seed, roots, **p)
    table = gen.build_token_table(roots)
    if kind == "documents":
        return gen.documents(seed, table, **p)
    if kind == "queries":
        count = round(traffic["rate_per_s"] * seconds)
        return gen.queries(seed, table, count=count, **p)
    if kind == "corpus":
        return gen.corpus_chunks(seed, table, **p)
    raise ValueError(f"unknown payload kind {kind!r}")


def size_classes(items: list, size) -> list:
    """One item per power-of-two size class, the largest of each: every
    power-of-two bucket a padded launch can land in is reached by one of
    them."""
    best: dict[int, object] = {}
    for it in items:
        n = max(size(it), 1)
        k = (n - 1).bit_length()
        if k not in best or size(best[k]) < n:
            best[k] = it
    return [best[k] for k in sorted(best)]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
@dataclass
class Served:
    """What one window did: per request (payload index, rid, due, done)."""

    done: list = field(default_factory=list)      # (i, rid, due, t_done)
    missing: list = field(default_factory=list)   # (i, rid, due)
    lateness: list = field(default_factory=list)  # open loop: s behind due
    t0: float = 0.0
    t_end: float = 0.0
    launches: int = 0
    dispatches: int = 0


class EngineSystem:
    """``Engine`` over ``TextAnalysisWorkload`` or ``StemmerWorkload`` with
    the program's default launch settings."""

    def __init__(self, config: dict, tables: dict, text: bool):
        import jax.numpy as jnp

        from repro.core.stemmer import RootDictArrays
        from repro.serve import (DictStore, Engine, StemmerWorkload,
                                 TextAnalysisWorkload)

        arrays = RootDictArrays(**{k: jnp.asarray(v) for k, v in tables.items()})
        store = DictStore(arrays, infix=config["infix"])
        kw = dict(infix=config["infix"], data_devices=config["chips"])
        self.workload = (TextAnalysisWorkload(store, frontend="kernel", **kw)
                         if text else StemmerWorkload(store, **kw))
        self.engine = Engine(self.workload)
        self.finished = self.engine.finished

    def drain(self, bodies: list) -> None:
        rids = [self.engine.submit(b) for b in bodies]
        self.engine.run_until_drained(max_ticks=1_000_000)
        bad = [r for r in rids if self.engine.result(r).failure is not None]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad[:8]}")

    def warm_up(self, bodies: list) -> None:
        """One request per size class of the mix: every launch shape the
        window can reach compiles (or loads from the cache) here."""
        self.drain(size_classes(bodies, len))

    def result(self, rid: int):
        return self.finished[rid]

    def release(self) -> None:
        """Drop the engine, its workload and the lexicon on the device;
        the finished requests (host arrays) stay for the check."""
        self.finished = self.engine.finished
        self.engine = self.workload = None

    def _counters(self) -> tuple[int, int]:
        from repro.kernels import ops

        return self.workload.ticks_launched, ops.dispatch_count()

    def closed(self, bodies: list, clients: int, seconds: float) -> Served:
        """``clients`` callers, each sending its next body (cycling
        through ``bodies``) when its last request is done; none is sent
        after ``seconds``, and the window closes when the last is done."""
        eng, out = self.engine, Served()
        outstanding: dict[int, tuple[int, float]] = {}
        sent = 0
        launches0, disp0 = self._counters()
        out.t0 = t = now()
        stop = out.t0 + seconds

        def send(t):
            nonlocal sent
            i = sent % len(bodies)
            with span("submit"):
                outstanding[eng.submit(bodies[i])] = (i, t)
            sent += 1

        for _ in range(clients):
            send(t)
        while outstanding:
            with span("Engine.step"):
                eng.step()
            t = now()
            for rid in [r for r in outstanding if r in eng.finished]:
                i, t_sent = outstanding.pop(rid)
                out.done.append((i, rid, t_sent, t))
                if t < stop:
                    send(t)
        out.t_end = now()
        launches1, disp1 = self._counters()
        out.launches, out.dispatches = launches1 - launches0, disp1 - disp0
        return out

    def open(self, bodies: list, seconds: float, grace_s: float) -> Served:
        """``bodies[k]`` falls due at the k-th point of a Poisson schedule
        over ``seconds``; latency runs from the due time. Requests still
        unfinished ``grace_s`` after the window are missing."""
        eng, out = self.engine, Served()
        due = np.cumsum(gen.arrival_gaps(len(bodies), seconds))
        outstanding: dict[int, tuple[int, float]] = {}
        launches0, disp0 = self._counters()
        out.t0 = now()
        due = out.t0 + due
        give_up = out.t0 + seconds + grace_s
        k = 0
        while k < len(bodies) or outstanding:
            t = now()
            if t > give_up:
                break
            while k < len(bodies) and due[k] <= t:
                out.lateness.append(t - due[k])
                with span("submit"):
                    outstanding[eng.submit(bodies[k])] = (k, due[k])
                k += 1
                t = now()
            if outstanding:
                with span("Engine.step"):
                    eng.step()
                t = now()
                for rid in [r for r in outstanding if r in eng.finished]:
                    i, t_due = outstanding.pop(rid)
                    out.done.append((i, rid, t_due, t))
            elif k < len(bodies):
                with span("wait"):
                    time.sleep(max(0.0, due[k] - now()))
        out.t_end = now()
        out.missing = [(i, rid, d) for rid, (i, d) in outstanding.items()]
        launches1, disp1 = self._counters()
        out.launches, out.dispatches = launches1 - launches0, disp1 - disp0
        return out


class IndexSystem:
    """``build_corpus_index`` over the configuration's ``("data",)`` mesh,
    on the lexicon's packed arrays, as a user calls it."""

    def __init__(self, config: dict, tables: dict):
        import jax.numpy as jnp

        from repro import index
        from repro.core.stemmer import RootDictArrays
        from repro.launch.mesh import make_data_mesh

        self.arrays = RootDictArrays(**{k: jnp.asarray(v) for k, v in tables.items()})
        self.mesh = make_data_mesh(config["chips"]) if config["chips"] > 1 else None
        self.infix = config["infix"]
        self._build = index.build_corpus_index
        self.results: list = []

    def build(self, chunks: list):
        return self._build(iter(chunks), self.arrays, mesh=self.mesh,
                           infix=self.infix)

    def warm_up(self, chunks: list) -> None:
        self.build(chunks)

    def release(self) -> None:
        self.arrays = self.mesh = None

    def repeat(self, chunks: list, seconds: float) -> Served:
        """Whole builds back to back until ``seconds`` have passed; the
        window closes when the last build is done."""
        from repro.kernels import ops

        out = Served()
        disp0 = ops.dispatch_count()
        out.t0 = t = now()
        while t < out.t0 + seconds:
            with span("build_corpus_index"):
                self.results.append(self.build(chunks))
            t = now()
            out.done.append((0, len(self.results) - 1, out.t0, t))
        out.t_end = t
        out.dispatches = ops.dispatch_count() - disp0
        return out
