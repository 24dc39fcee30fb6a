"""Finds the knee of an open-loop cell: the highest offered rate at which
the backlog does not grow over a window.

    python bench/sweep.py --config newswire --traffic queries --seed 5 \
        --seconds 5 --rates 200,400,800,1600

The configuration and mix are read from their files by name, so a cell
can be swept before ``BENCHMARK.json`` lists it.

One process, one warm-up, then the cell's traffic at each rate in turn.
For each rate it prints the backlog (requests due and not yet done) at
each quarter of the window, how long the backlog took to drain after the
window, and the 95th percentile latency from the due time. The cell's
``rate_per_s`` is then set, once and by hand, to about 4/5 of the knee;
no measured run searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run  # noqa: F401  (puts the checkout and its src/ on sys.path)

from bench import drive, spec  # noqa: E402


def backlog_at(served, t: float) -> int:
    due = sum(1 for _i, _r, d, _t in served.done if d <= t)
    due += sum(1 for _i, _r, d in served.missing if d <= t)
    done = sum(1 for _i, _r, _d, td in served.done if td <= t)
    return due - done


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    bench = run.ROOT / spec.BENCH
    config = spec._load(bench / "configs" / f"{args.config}.json", "configuration")
    mix = spec._load(bench / "traffic" / f"{args.traffic}.json", "traffic")
    run.find_chips(config["chips"])
    run.enable_compile_cache()
    roots, tables = drive.lexicon(config, args.seed)
    system = drive.EngineSystem(config, tables, text=mix["entry"] == "text")
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = {**mix, "rate_per_s": rate}
        bodies = drive.payloads(traffic, args.seed, args.seconds, roots)
        system.warm_up(bodies)
        served = system.open(bodies, args.seconds, 30.0)
        t0 = served.t0
        quarters = [backlog_at(served, t0 + q * args.seconds / 4)
                    for q in (1, 2, 3, 4)]
        lat = [t - d for _i, _r, d, t in served.done]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(bodies),
            "backlog_at_quarters": quarters,
            "drain_s": served.t_end - t0 - args.seconds,
            "p50_ms": 1e3 * float(np.median(lat)),
            "p95_ms": 1e3 * drive.percentile(lat, 95),
            "missing": len(served.missing),
            "late_p95_ms": 1e3 * drive.percentile(served.lateness, 95)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
