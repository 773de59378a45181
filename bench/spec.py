"""Finds a cell's pieces by name, so a later change adds a cell, a
configuration, a traffic mix or a metric by adding files and entries only.

  BENCHMARK.json                     at the root of the checkout
  bench/configs/<config>.json        the configuration as it is run
  bench/traffic/<traffic>.json       the traffic mix's parameters
  bench/metrics/<metric>.py          one reader per per-layer metric
  bench/layouts/<reference>.py       one layout module per architecture
  bench/reference/<reference>.py     one plain float32 reference per architecture

A configuration names its architecture in ``reference``; the draft's is the
same unless ``draft.reference`` names another.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = "bench"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: tuple  # the end-to-end metric entries this cell reports
    per_layer: tuple  # the per-layer metric entries this cell reports


def load_benchmark(root: Path) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"no BENCHMARK.json in {root}")
    return json.loads(path.read_text())


def _load_json(root: Path, sub: str, name: str) -> dict:
    path = Path(root) / BENCH_DIR / sub / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"{sub} {name!r}: no file {path}")
    data = json.loads(path.read_text())
    data.setdefault("name", name)
    return data


def load_config(root: Path, name: str) -> dict:
    return _load_json(root, "configs", name)


def load_mix(root: Path, name: str) -> dict:
    return _load_json(root, "traffic", name)


def reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports an end-to-end metric (no ``workloads`` key:
    every cell does)."""
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")
    return Cell(name=name, chips=int(entry["chips"]),
                config=load_config(root, entry["config"]),
                mix=load_mix(root, entry["traffic"]),
                end_to_end=tuple(m for m in bench["end_to_end"] if reports(m, name)),
                per_layer=tuple(m for m in bench["per_layer"] if name in m["workloads"]))


def _exec(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # where a dataclass looks for its module
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``: it returns
    the metric's value, or None where the run holds nothing to read."""
    path = Path(root) / BENCH_DIR / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {metric!r}: no reader {path}")
    return _exec(path, f"bench_metric_{metric}").read


_MODULES: dict = {}  # path -> module, so a reference's jitted programs are kept


def _load_module(root: Path, sub: str, name: str):
    path = (Path(root) / BENCH_DIR / sub / f"{name}.py").resolve()
    if not path.is_file():
        raise FileNotFoundError(f"{sub} {name!r}: no module {path}")
    if path not in _MODULES:
        _MODULES[path] = _exec(path, f"bench_{sub}_{name}_{len(_MODULES)}")
    return _MODULES[path]


def load_layout(root: Path, name: str):
    """The layout module ``bench/layouts/<name>.py`` (its contract: the
    docstring of the repository's first one, PERF.md section 3)."""
    return _load_module(root, "layouts", name)


def load_reference(root: Path, name: str):
    """The plain reference ``bench/reference/<name>.py``."""
    return _load_module(root, "reference", name)
