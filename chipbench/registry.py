"""Find a cell's pieces by name.

Everything that belongs to one configuration, traffic mix, training path,
reference, per-layer metric or set of limits is a file of its own under
``chipbench/``; ``BENCHMARK.json`` at the root names them:

* ``configs[].file``             the configuration (JSON)
* ``mixes/<traffic>.json``       the traffic mix; its ``path`` names
* ``paths/<path>.py``            the training path that drives the program
* ``references/<arch>.py``       the plain reference of the configuration's
                                 ``model.arch``
* ``metrics/<metric>.py``        one reader per per-layer metric; a metric
                                 split by cell kind (``mfu.minibatch``)
                                 falls back to the reader of its stem
                                 (``metrics/mfu.py``)
* ``limits/<cell>.json``         the limits of the numbers that decide
                                 ``correct`` in that cell

Adding a cell, a mix, a path or a metric adds files and entries; it edits
none of these modules.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = "chipbench"


def _load_module(path: str, name: str):
    key = f"chipbench_plugin.{name}"
    if key in sys.modules and sys.modules[key].__file__ == path:
        return sys.modules[key]
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Registry:
    """The benchmark as ``BENCHMARK.json`` under ``root`` describes it."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.bench = _read_json(os.path.join(self.root, "BENCHMARK.json"))
        self.dir = os.path.join(self.root, BENCH_DIR)

    def _file(self, *parts) -> str:
        return os.path.join(self.dir, *parts)

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                cfg = _read_json(os.path.join(self.root, c["file"]))
                if cfg.get("name") != name:
                    raise ValueError(f"{c['file']} names {cfg.get('name')!r}")
                return cfg
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def mix(self, traffic: str) -> dict:
        return _read_json(self._file("mixes", traffic + ".json"))

    def limits(self, cell: str) -> dict:
        return _read_json(self._file("limits", cell + ".json"))

    def path(self, name: str):
        return _load_module(self._file("paths", name + ".py"), "paths." + name)

    def reference(self, arch: str):
        return _load_module(self._file("references", arch + ".py"),
                            "references." + arch)

    def metric_reader(self, name: str):
        for stem in (name, name.split(".", 1)[0]):
            path = self._file("metrics", stem + ".py")
            if os.path.exists(path):
                return _load_module(path, "metrics." + stem)
        raise FileNotFoundError(self._file("metrics", name + ".py"))

    def end_to_end(self, cell: str) -> list:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.bench["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> list:
        """The per-layer metrics read in this cell: those that list it, and
        those without a list whose end-to-end metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.bench["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def resolve(self, cell_name: str) -> dict:
        """Every piece of one cell, loaded."""
        cell = self.cell(cell_name)
        cfg = self.config(cell["config"])
        mix = self.mix(cell["traffic"])
        return {"cell": cell, "config": cfg, "mix": mix,
                "path": self.path(mix["path"]),
                "reference": self.reference(cfg["model"]["arch"]),
                "limits": self.limits(cell_name),
                "end_to_end": self.end_to_end(cell_name),
                "per_layer": [(m, self.metric_reader(m["name"]))
                              for m in self.per_layer(cell_name)]}
