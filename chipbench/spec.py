"""Finding a cell's pieces by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix, and
each metric.  Every one of them is a file of its own, found by that name:

  configs/<config>.json    the deployment, its source and its cuts
  traffic/<mix>.json       the traffic mix; its ``loop`` names the module,
                           its ``runtime`` the engine (``cell.RUNTIMES``)
  loops/<loop>.py          a loop kind: ``warm(bench, traffic)`` and
                           ``window(bench, traffic, seconds)``
  metrics/<metric>.py      a reader: ``read(record) -> float | None``

so a cell, a mix or a metric is added by adding files, never by editing
one.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType

#: the benchmark's own directory, where configs, traffic, loops and
#: metrics live
HERE = Path(__file__).resolve().parent
#: the checkout root, where ``BENCHMARK.json`` lives
ROOT = HERE.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json", encoding="utf-8") as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (have: "
                   f"{[c['name'] for c in bench['workloads']]})")


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_config(name: str, root: Path = HERE) -> dict:
    return _json(Path(root) / "configs" / f"{name}.json")


def load_traffic(name: str, root: Path = HERE) -> dict:
    return _json(Path(root) / "traffic" / f"{name}.json")


def _module(path: Path, qualname: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(qualname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[qualname] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_loop(kind: str, root: Path = HERE) -> ModuleType:
    return _module(Path(root) / "loops" / f"{kind}.py",
                   f"chipbench_loop_{kind}")


def load_metric(name: str, root: Path = HERE) -> ModuleType:
    return _module(Path(root) / "metrics" / f"{name}.py",
                   f"chipbench_metric_{name}")


def metrics_for(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics the cell reports: those
    that list it under ``workloads``, or that have no such list."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_peaks(kind: str, root: Path = HERE) -> dict:
    """The published peaks of one device kind; an unknown kind is an
    error, never a default."""
    table = _json(Path(root) / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json (have: "
                       f"{sorted(table['devices'])})")
    return dict(table["devices"][kind], source=table["source"])
