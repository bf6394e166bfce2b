"""Fixtures for the benchmark's CPU tests: a throw-away root with tiny cells,
and a way to drive a whole run in this process without a chip."""
import json
import os

import pytest

from chipbench.tests import tiny


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("chipbench"))


@pytest.fixture
def run_cell(monkeypatch, capsys):
    """``run_cell(root, cell, trace=0)`` drives ``harness.main`` past the
    look for a chip, with the persistent compile cache left alone, and
    returns ``(exit code, result line or None, standard error)``."""
    from chipbench import harness, trace_reduce

    monkeypatch.setattr(harness, "enable_compile_cache", lambda d: "off")
    cpu_peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11,
                "hbm_bytes": 1e9}
    monkeypatch.setattr(trace_reduce, "load_peak", lambda kind: cpu_peak)

    def run(root, cell, *, trace=0, seed=3000000017, seconds=0.5):
        capsys.readouterr()
        rc = harness.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          root=root, require_chip=False)
        out, err = capsys.readouterr()
        lines = out.strip().splitlines()
        result = json.loads(lines[-1]) if lines else None
        return rc, result, err

    return run


@pytest.fixture(scope="session")
def repo_root():
    return tiny.REPO


@pytest.fixture(scope="session")
def benchmark_json(repo_root):
    with open(os.path.join(repo_root, "BENCHMARK.json")) as f:
        return json.load(f)
