"""Finds a cell's pieces by name: nothing here names a cell.

  BENCHMARK.json                 cells, metrics and bounds
  bench/configs/<config>.json    a configuration as it is run
  bench/configs/<config>_reference.py
                                 its plain reference (numpy, float64)
  bench/traffic/<mix>.json       a traffic mix: the generator that runs
                                 it and its parameters
  bench/generators/<name>.py     a general generator, ``run(cell)``
  bench/metrics/<metric>.py      a per-layer metric's reader, ``read(ctx)``;
                                 a metric with none reads with its stem's
  bench/staged/<cells>.json      entries of cells built and tested on the
                                 CPU, not yet in BENCHMARK.json

A later cell adds files and entries; it edits none of these.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def with_staged(bench: dict, bench_dir: Path = BENCH) -> dict:
    """``bench`` with the entries of ``staged/*.json`` added, where there
    are any: cells built and tested on the CPU whose chip readings are
    not taken yet, so that ``readings.py`` can take them. The entries of
    a staged file move into BENCHMARK.json once the cell is proven on the
    chip, and the file goes."""
    bench = dict(bench)
    for path in sorted((bench_dir / "staged").glob("*.json")):
        for key, entries in _json(path).items():
            bench[key] = bench.get(key, []) + entries
    return bench


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str, bench_dir: Path = BENCH) -> dict:
    return _json(bench_dir / "configs" / f"{name}.json")


def load_traffic(name: str, bench_dir: Path = BENCH) -> dict:
    return _json(bench_dir / "traffic" / f"{name}.json")


def load_module(path: Path, name: str):
    """Import one file as a module (file names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_generator(name: str, bench_dir: Path = BENCH):
    return load_module(bench_dir / "generators" / f"{name}.py",
                       f"bench_generator_{name}")


def load_reference(config: str, bench_dir: Path = BENCH):
    return load_module(bench_dir / "configs" / f"{config}_reference.py",
                       f"bench_reference_{config}")


def load_reader(metric: str, bench_dir: Path = BENCH):
    """The ``read(ctx)`` function of a per-layer metric: from
    ``metrics/<metric>.py``, or where there is none from the file of its
    stem, the name less its last ``.<part>`` (``device.idle_pct.batch``
    reads with ``device.idle_pct.py``), so one reader serves a quantity
    that is split by the cells it is read in."""
    name = metric
    while not (bench_dir / "metrics" / f"{name}.py").is_file() and (
            "." in name):
        name = name.rsplit(".", 1)[0]
    mod = load_module(bench_dir / "metrics" / f"{name}.py",
                      "bench_metric_" + name.replace(".", "_"))
    return mod.read


def end_to_end_for(bench: dict, cell: str) -> list[dict]:
    """The end-to-end metrics that ``cell`` reports."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(bench: dict, cell: str) -> list[dict]:
    """The per-layer metrics read in ``cell``'s traced run."""
    reported = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]
