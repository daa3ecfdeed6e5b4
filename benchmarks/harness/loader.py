"""Find everything by name.  ``BENCHMARK.json`` names cells, configurations
and metrics; each resolves to files of its own under ``benchmarks/``:

    workloads/<cell>.json     config, traffic, chips, why, who, limits
    configs/<config>.json     the sizes as run, source, reduced, assumed
    traffic/<traffic>.json    the driver kind and its parameters
    drivers/<kind>.py         the code that drives one kind of traffic
    families/<family>.py      what depends on the architecture: the program's
                              constructor, the plain reference, the counts
    metrics/<metric>.json     unit, layer, moves, source, reader
    metrics/<module>.py       the reader functions

so a later PR adds files and entries and edits none.  An unknown cell,
metric or device kind is an error, not a default."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)
REHEARSAL = os.path.join(ROOT, "rehearsal")
FAMILIES = os.path.join(ROOT, "families")
#: what every family brings, whatever drives it (a driver names the rest)
FAMILY_PROTOCOL = ("constructor", "reference.Arch", "reference.leaf_plan",
                   "reference.PRECISIONS", "counts")


class BenchmarkError(Exception):
    """Something named is not there, or does not fit together."""


def _read(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise BenchmarkError(f"unknown {what}: no file {os.path.relpath(path, REPO)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, what: str):
    if not os.path.isfile(path):
        raise BenchmarkError(f"unknown {what}: no file {os.path.relpath(path, REPO)}")
    name = "bench_" + os.path.relpath(path, ROOT)[:-3].replace(os.sep, "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: str | None = None
    moves: str | None = None
    reader: object = None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    rehearsal: bool = False

    @property
    def driver_kind(self) -> str:
        return self.traffic["driver"]


def benchmark_json() -> dict:
    return _read(os.path.join(REPO, "BENCHMARK.json"), "benchmark file")


def _in_cell(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_metric(entry: dict) -> Metric:
    """A per-layer metric: its entry in BENCHMARK.json and its own file
    must agree, and its reader must exist."""
    meta = _read(os.path.join(ROOT, "metrics", entry["name"] + ".json"),
                 "per-layer metric")
    for key in ("unit", "layer", "moves", "source", "better"):
        if meta.get(key) != entry.get(key):
            raise BenchmarkError(
                f"metric {entry['name']}: {key} is {entry.get(key)!r} in "
                f"BENCHMARK.json and {meta.get(key)!r} in its file")
    module, _, func = meta["reader"].partition(":")
    mod = load_module(os.path.join(ROOT, "metrics", module + ".py"),
                      "metric reader module")
    if not hasattr(mod, func):
        raise BenchmarkError(f"metric {entry['name']}: no reader {meta['reader']}")
    return Metric(entry["name"], entry["unit"], entry["better"],
                  entry["source"], entry["layer"], entry["moves"],
                  getattr(mod, func))


def load_cell(name: str, rehearsal: bool = False) -> Cell:
    """The cell ``name`` with everything it names.  A rehearsal cell lives
    under ``rehearsal/`` and is in no BENCHMARK.json: it borrows the
    metrics of the benchmark cell it rehearses (``rehearses``)."""
    bench = benchmark_json()
    listed = {w["name"]: w for w in bench["workloads"]}
    base = REHEARSAL if rehearsal else ROOT
    if not rehearsal and name not in listed:
        raise BenchmarkError(
            f"unknown cell {name!r}: BENCHMARK.json lists {sorted(listed)}")
    cell = _read(os.path.join(base, "workloads", name + ".json"), "cell")
    config = _read(os.path.join(base, "configs", cell["config"] + ".json"),
                   "configuration")
    traffic = _read(os.path.join(base, "traffic", cell["traffic"] + ".json"),
                    "traffic mix")
    if rehearsal:
        if not config.get("rehearsal_only"):
            raise BenchmarkError(
                "--rehearsal takes nothing but the tiny rehearsal files")
        metrics_of = cell["rehearses"]
    else:
        entry = listed[name]
        for key in ("config", "traffic", "chips"):
            if entry[key] != cell[key]:
                raise BenchmarkError(
                    f"cell {name}: {key} is {entry[key]!r} in BENCHMARK.json "
                    f"and {cell[key]!r} in its file")
        metrics_of = name
    e2e = [Metric(m["name"], m["unit"], m["better"], m["source"])
           for m in bench["end_to_end"] if _in_cell(m, metrics_of)]
    per_layer = [load_metric(m) for m in bench["per_layer"]
                 if _in_cell(m, metrics_of)]
    return Cell(name=name, chips=int(cell["chips"]), why=cell["why"],
                config_name=cell["config"], config=config,
                traffic_name=cell["traffic"], traffic=traffic,
                limits=cell.get("limits", {}), end_to_end=e2e,
                per_layer=per_layer, rehearsal=rehearsal)


def load_family(name: str, needs=()):
    """The family ``name``: ``families/<name>.py`` with ``constructor``,
    ``reference`` and ``counts``.  ``needs`` are the dotted names the
    caller will use beside the protocol's (``"counts.serve_flops"``).
    What is missing is an error that names it: no architecture is ever
    stood in for by another."""
    family = load_module(os.path.join(FAMILIES, name + ".py"), "model family")
    for dotted in FAMILY_PROTOCOL + tuple(needs):
        obj = family
        for part in dotted.split("."):
            if not hasattr(obj, part):
                raise BenchmarkError(
                    f"model family {name!r} (families/{name}.py) lacks "
                    f"{dotted!r}")
            obj = getattr(obj, part)
    return family


def load_driver(kind: str):
    return load_module(os.path.join(ROOT, "drivers", kind + ".py"), "driver kind")
