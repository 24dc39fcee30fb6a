"""Finds a cell's files by name: nothing here knows any cell.

``BENCHMARK.json`` at the checkout's root lists configurations, cells
and metrics. A cell ``<config>.<traffic>`` resolves to

  configs   the ``file`` its configuration entry names
  traffic   ``bench/traffic/<traffic>.json``
  metrics   the end-to-end metrics whose ``workloads`` name the cell
            (or that have none), and likewise the per-layer metrics,
            each joined with ``bench/layers/<metric>.json``
  readers   ``bench/readers/<reader>.py``, named by a layer file
  measures  ``bench/measures/<metric>.py`` for each end-to-end metric

so a later cell, mix or metric is added as files and entries, without
an edit to any file already here.

A configuration may state ``"journal": {"fsync_every": N}`` (a whole
N >= 1, no default): the engine then serves through the program's
write-ahead journal, fsynced every N records. Without the key, or with
``null``, it has none. The index builder takes no journal.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = "bench"


class SpecError(ValueError):
    """A cell, configuration, mix or metric that cannot be resolved."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: Path
    fsync_every: int | None = None      # the configuration's journal

    def _function(self, folder: str, name: str, fn: str):
        path = self.root / BENCH / folder / f"{name}.py"
        if not path.is_file():
            raise SpecError(f"no {folder} file {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_{folder}_{name}".replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return getattr(mod, fn)

    def reader(self, name: str):
        """``read(ctx, layer)`` of ``bench/readers/<name>.py``."""
        return self._function("readers", name, "read")

    def measure(self, metric: str):
        """``measure(run)`` of ``bench/measures/<metric>.py``."""
        return self._function("measures", metric, "measure")


def _load(path: Path, what: str) -> dict:
    if not path.is_file():
        raise SpecError(f"no {what} file {path}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"BENCHMARK.json has no {what} named {name!r}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def journal_fsync_every(config: dict) -> int | None:
    """The ``fsync_every`` a configuration's ``journal`` states, or None
    where it states no journal."""
    j = config.get("journal")
    if j is None:
        return None
    n = j.get("fsync_every") if isinstance(j, dict) else None
    if (not isinstance(j, dict) or set(j) != {"fsync_every"}
            or not isinstance(n, int) or isinstance(n, bool) or n < 1):
        raise SpecError(f"journal must be null or {{\"fsync_every\": N}}"
                        f" with a whole N >= 1, not {j!r}")
    return n


def resolve(root: Path, workload: str) -> Cell:
    """The cell named ``workload`` of the checkout at ``root``."""
    root = Path(root)
    bench = _load(root / "BENCHMARK.json", "benchmark")
    cell = _named(bench["workloads"], workload, "workload")
    entry = _named(bench["configs"], cell["config"], "config")
    config = _load(root / entry["file"], "configuration")
    traffic = _load(root / BENCH / "traffic" / f"{cell['traffic']}.json",
                    "traffic")
    fsync_every = journal_fsync_every(config)
    if fsync_every is not None and traffic["entry"] == "index":
        raise SpecError(f"{workload}: the index builder takes no journal")
    layers = []
    for m in bench["per_layer"]:
        if _applies(m, workload):
            extra = _load(root / BENCH / "layers" / f"{m['name']}.json",
                          "per-layer metric")
            layers.append({**extra, **m})
    return Cell(name=workload, chips=int(cell["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=layers, root=root, fsync_every=fsync_every)
