"""A throw-away benchmark root with tiny cells, for tests on the CPU.

``make_root(tmp)`` copies the benchmark's code (paths, references, metrics)
into ``tmp/chipbench`` beside tiny configurations, mixes and limits, and
writes a ``BENCHMARK.json`` that names them.  The tiny cells keep the real
cells' architectures, widths, paths, metrics and limits, on graphs and
batches a test can hold.
"""
from __future__ import annotations

import copy
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

SAGE_CELL, GCN_CELL = "sage-reddit.b1024-f25x10", "gcn-arxiv.fullbatch"
TINY = {"tiny-sage.b64-f3x2": (SAGE_CELL, "tiny-sage", "b64-f3x2"),
        "tiny-gcn.fullbatch": (GCN_CELL, "tiny-gcn", "fullbatch")}


def _read(path):
    with open(path) as f:
        return json.load(f)


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_config(name: str) -> dict:
    if name == "tiny-sage":
        cfg = _read(os.path.join(BENCH, "configs", "sage-reddit.json"))
        cfg["graph"].update(nodes=600, edges=600 * 24,
                            max_expected_degree=150, train_nodes=400)
    else:
        cfg = _read(os.path.join(BENCH, "configs", "gcn-arxiv.json"))
        cfg["graph"].update(nodes=20000, edges=20000 * 12,
                            max_expected_degree=2000, train_nodes=10000)
    cfg["name"] = name
    return cfg


def make_root(tmp: str, *, cells=tuple(TINY)) -> str:
    """Write the throw-away root under ``tmp`` and return its path."""
    root = os.path.join(str(tmp), "root")
    bench = os.path.join(root, "chipbench")
    for d in ("paths", "references", "metrics"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(bench, d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    real = _read(os.path.join(REPO, "BENCHMARK.json"))
    bench_json = copy.deepcopy(real)
    bench_json["configs"], bench_json["workloads"] = [], []
    rename = {}
    for cell in cells:
        real_cell, cfg_name, traffic = TINY[cell]
        rename[real_cell] = cell
        _write(os.path.join(bench, "configs", cfg_name + ".json"),
               tiny_config(cfg_name))
        bench_json["configs"].append(
            {"name": cfg_name, "source": "test", "reduced": [], "why": "test",
             "file": f"chipbench/configs/{cfg_name}.json"})
        bench_json["workloads"].append(
            {"name": cell, "config": cfg_name, "traffic": traffic,
             "chips": 1, "why": "test"})
        mix = _read(os.path.join(BENCH, "mixes", real_cell.split(".", 1)[1]
                                 + ".json"))
        if mix["path"] == "minibatch":
            mix.update(name=traffic, batch=64, fanouts=[2, 3])
        _write(os.path.join(bench, "mixes", traffic + ".json"), mix)
        _write(os.path.join(bench, "limits", cell + ".json"),
               _read(os.path.join(BENCH, "limits", real_cell + ".json")))
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]
                              if w in rename]
    bench_json["end_to_end"] = [m for m in bench_json["end_to_end"]
                                if m.get("workloads", True)]
    bench_json["per_layer"] = [m for m in bench_json["per_layer"]
                               if m.get("workloads", True)]
    _write(os.path.join(root, "BENCHMARK.json"), bench_json)
    return root
