"""Finding a cell's pieces by name.

``BENCHMARK.json`` names the cells and metrics.  Everything that belongs
to one of them sits in a file of its own, found by that name:

  - ``bench/workloads/<cell>.json``: the configuration's name, the chips,
    the request kind, the traffic parameters and the limits of the
    comparison that decides ``correct``;
  - ``bench/configs/<config>.json``: one deployment;
  - ``bench/requests/<kind>.py``: drives the program for one kind of
    request, counts its work and holds the comparison with the plain
    reference;
  - ``bench/metrics/<metric>.py``: reads one metric from a finished run.

Adding a cell or a metric adds files and entries; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class UnknownCell(KeyError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, imported under a private name."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise UnknownCell(f"no {kind} file {path.relative_to(ROOT)}")
    modname = f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    chips: int
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list

    def request(self):
        """A fresh request runner of the cell's kind."""
        return load_module("requests", self.workload["request"]).Request(
            self.workload, self.config)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec: dict | None = None,
              workload: dict | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (or of ``spec``), with its
    workload file (or ``workload``, for rehearsals at other sizes)."""
    spec = spec if spec is not None else load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise UnknownCell(f"BENCHMARK.json has no cell {name!r}")
    if workload is None:
        path = BENCH / "workloads" / f"{name}.json"
        if not path.is_file():
            raise UnknownCell(f"no workload file {path.relative_to(ROOT)}")
        workload = load_json(path)
    if (workload["config"] != entry["config"]
            or int(workload["chips"]) != int(entry["chips"])):
        raise ValueError(f"{name}: workload file and BENCHMARK.json disagree "
                         "on the configuration or the chips")
    config = load_json(BENCH / "configs" / f"{workload['config']}.json")
    return Cell(name=name, workload=workload, config=config,
                chips=int(entry["chips"]),
                end_to_end=[m for m in spec["end_to_end"] if applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if applies(m, name)])
