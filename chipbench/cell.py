"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of its
own, and each metric in a reader of its own:

* ``chipbench/configs/<config>.json`` (the ``file`` of the configuration),
  whose ``reference`` names the architecture's module
  ``chipbench/reference/<reference>.py``: its weight recipe, its mapping
  onto the program's tree, its plain forward pass and training step, and
  its work count (the one interface every module there gives)
* ``chipbench/traffic/<traffic>.json``, whose ``runner`` (``serve`` where
  absent, or ``train``) names the module ``chipbench/<runner>.py`` that
  drives the program, and whose ``generator`` names the module
  ``chipbench/traffic/<generator>.py`` that draws its work from the seed
  (``generate`` for a serving mix, ``dataset`` for a training mix)
* ``chipbench/metrics/<metric>.py`` with ``read(run) -> float | None``

so a new cell, mix, metric or architecture is new files and entries, never
an edit.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file, parsed
    traffic: Dict[str, Any]         # the mix file, parsed
    generator: Any                  # the mix's generator module
    runner: str                     # the module that drives the program
    reference: str                  # the architecture's reference module
    metrics: Dict[str, Any]         # metric name -> its BENCHMARK.json entry
    readers: Dict[str, Any]         # metric name -> reader module
    run_seconds: int


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, trace: bool, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"cells: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
    generator = load_module(
        root / "chipbench" / "traffic" / f"{traffic['generator']}.py")
    kind = "per_layer" if trace else "end_to_end"
    metrics = {m["name"]: m for m in bench[kind] if _applies(m, name)}
    readers = {m: load_module(root / "chipbench" / "metrics" / f"{m}.py")
               for m in metrics}
    return Cell(name, int(w["chips"]), config, traffic, generator,
                traffic.get("runner", "serve"), config["reference"], metrics,
                readers, int(bench["run_seconds"]))


def modules(cell: Cell):
    """The cell's runner module and its reference module (``run.py`` puts
    ``chipbench/`` on the path)."""
    return (importlib.import_module(cell.runner),
            importlib.import_module(f"reference.{cell.reference}"))


def all_cells(root: Path = ROOT) -> List[str]:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]]
