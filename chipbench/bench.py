"""The benchmark's definition, read from ``BENCHMARK.json`` and the files
it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by name:

* ``chipbench/configs/<config>.json``: a deployment (extents, kappa,
  backend, dtype, solver, tolerance);
* ``chipbench/traffic/<traffic>.json``: a traffic mix, read by the
  driver it names (``chipbench/drivers/<driver>.py``);
* ``chipbench/metrics/<metric>.py``: a per-layer reader, ``read(rec)``;
* ``chipbench/kernels/<kernel>.json`` and ``chipbench/peaks.json``: the
  work of each kernel and the peaks of each device (see ``work.py``).

A new cell, configuration, metric or kernel is therefore new files and
new entries in ``BENCHMARK.json``, never an edit of the harness.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` under ``root`` and the files it names."""

    def __init__(self, root: pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        self.dir = self.root / "chipbench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        """The workload ``name`` with its configuration and traffic."""
        by_name = {w["name"]: w for w in self.spec["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
        w = by_name[name]
        configs = {c["name"]: c for c in self.spec["configs"]}
        entry = configs[w["config"]]
        config = json.loads((self.root / entry["file"]).read_text())
        traffic = json.loads(
            (self.dir / "traffic" / f"{w['traffic']}.json").read_text())
        return {"name": name, "chips": int(w["chips"]), "config": config,
                "traffic": traffic, "workload": w}

    def _metrics(self, kind: str, cell: str) -> list:
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]

    def end_to_end(self, cell: str) -> list:
        return self._metrics("end_to_end", cell)

    def per_layer(self, cell: str) -> list:
        return self._metrics("per_layer", cell)

    def reader(self, metric: str):
        """``read(rec)`` of ``metrics/<metric>.py``."""
        return _load_module(self.dir / "metrics" / f"{metric}.py",
                            f"chipbench_metric_{metric}").read

    def driver(self, name: str):
        return _load_module(self.dir / "drivers" / f"{name}.py",
                            f"chipbench_driver_{name}")
