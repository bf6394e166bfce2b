"""BENCHMARK.json keeps to its contract, and every piece of every cell is
found by name, also a new one added as files alone."""
import json
import os
import re
import textwrap

import pytest

from chipbench.registry import Registry
from chipbench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in json.load(open(os.path.join(
    tiny.REPO, "BENCHMARK.json")))["workloads"]]


def _line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_benchmark_json_keeps_to_the_contract(benchmark_json, repo_root):
    b = benchmark_json
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(repo_root, "BENCHMARK.json")) < 64 * 1024
    assert b["command"][0] == "python3" and len(b["command"]) <= 32
    for word in b["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in b["paths"])
    for p in b["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
    assert 1 <= b["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200

    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert len(c["reduced"]) <= 16
        names.add(c["name"])
    used = {w["config"] for w in b["workloads"]}
    assert used == names
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(b["workloads"])
    cells = {w["name"] for w in b["workloads"]}

    e2e = {}
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
        e2e[m["name"]] = m
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    layers = set()
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert _line(m["layer"]) and m["moves"] in e2e
        reporting = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= reporting
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
        layers.add(m["layer"])
    all_names = ([c["name"] for c in b["configs"]] + list(cells)
                 + list(e2e) + [m["name"] for m in b["per_layer"]])
    assert len(set(all_names)) == len(all_names)

    reg = Registry(repo_root)
    for cell in cells:
        reported = [m["name"] for m in reg.end_to_end(cell)]
        assert "setup_s" in reported and len(reported) >= 2
        assert reg.per_layer(cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(repo_root, benchmark_json, cell):
    parts = Registry(repo_root).resolve(cell)
    cfg, mix, path = parts["config"], parts["mix"], parts["path"]
    entry = next(c for c in benchmark_json["configs"]
                 if c["name"] == cfg["name"])
    assert cfg["reduced"] == entry["reduced"]
    for fn in ("Session", "check", "reference_run", "step_args"):
        assert callable(getattr(path, fn)), fn
    for fn in ("init", "loss", "work"):
        assert callable(getattr(parts["reference"], fn)), fn
    assert {"compiles_in_window", "grad_gap",
            "update_gap"} <= set(parts["limits"])
    assert all(callable(reader.read) for _, reader in parts["per_layer"])
    assert mix["name"] == parts["cell"]["traffic"]


def test_a_new_cell_is_added_by_files_alone(tmp_path):
    """A throw-away configuration, mix, training path, reference, metric and
    limits, written into a temporary root, resolve by name."""
    root = tiny.make_root(tmp_path, cells=("tiny-gcn.fullbatch",))
    bench = os.path.join(root, "chipbench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    cfg = tiny.tiny_config("tiny-gcn")
    cfg.update(name="echo-graph")
    cfg["model"]["arch"] = "echo"
    files = {
        "configs/echo-graph.json": json.dumps(cfg),
        "mixes/once.json": json.dumps({"name": "once", "path": "echo"}),
        "limits/echo-graph.once.json": json.dumps({"compiles_in_window": 0}),
        "paths/echo.py": "def check(ctx, first):\n    return {}\n",
        "references/echo.py": "def work(cfg, counts):\n    return {}\n",
        "metrics/answer_ms.py": textwrap.dedent("""
            def read(run):
                return 42.0
            """),
    }
    for rel, text in files.items():
        with open(os.path.join(bench, rel), "w") as f:
            f.write(text)
    b["configs"].append({"name": "echo-graph", "source": "test", "why": "t",
                         "reduced": [],
                         "file": "chipbench/configs/echo-graph.json"})
    b["workloads"].append({"name": "echo-graph.once", "config": "echo-graph",
                           "traffic": "once", "chips": 1, "why": "test"})
    b["end_to_end"].append({"name": "answers_per_s", "unit": "1/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["echo-graph.once"]})
    b["per_layer"].append({"name": "answer_ms", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "answers", "moves": "answers_per_s",
                           "workloads": ["echo-graph.once"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    parts = Registry(root).resolve("echo-graph.once")
    assert parts["path"].check(None, None) == {}
    assert parts["reference"].work(cfg, None) == {}
    assert [m["name"] for m, _ in parts["per_layer"]] == ["answer_ms"]
    assert parts["per_layer"][0][1].read(None) == 42.0
    assert sorted(m["name"] for m in parts["end_to_end"]) == ["answers_per_s",
                                                       "setup_s"]
    # the cells already there resolve as before
    assert Registry(root).resolve("tiny-gcn.fullbatch")["path"].Session


BANNED_IMPORTS = re.compile(
    r"^\s*(from|import)\s+repro\.(graph\.generators|kernels\.ref)\b"
    r"|^\s*from\s+repro\.(graph|kernels)\s+import\s+.*\b(generators|ref)\b",
    re.M)


def test_the_yardstick_imports_nothing_of_the_program_s_generators_or_refs():
    for dirpath, _, files in os.walk(tiny.BENCH):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name)) as f:
                    assert not BANNED_IMPORTS.search(f.read()), name


def test_a_metric_split_by_cell_kind_reads_with_its_stem_s_reader(tmp_path):
    """``mfu.minibatch`` and ``mfu.fullgraph`` share ``metrics/mfu.py``; a
    file of the whole name, where there is one, comes first."""
    root = tiny.make_root(tmp_path, cells=("tiny-gcn.fullbatch",))
    reg = Registry(root)
    metrics = os.path.join(reg.dir, "metrics")
    with open(os.path.join(metrics, "answer_ms.py"), "w") as f:
        f.write("def read(run):\n    return 1.0\n")
    with open(os.path.join(metrics, "answer_ms.special.py"), "w") as f:
        f.write("def read(run):\n    return 2.0\n")
    assert reg.metric_reader("answer_ms.plain").read(None) == 1.0
    assert reg.metric_reader("answer_ms.special").read(None) == 2.0
    assert reg.metric_reader("answer_ms").read(None) == 1.0
    with pytest.raises(FileNotFoundError):
        reg.metric_reader("no_such_metric.minibatch")
