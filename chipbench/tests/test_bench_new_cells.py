"""The GAT mini-batch cell and the four-partition SAGE cell, each run end to
end at a tiny size on the CPU (Pallas in interpret mode; the partitions on
four forced CPU devices, in a process of their own), with a planted fault
caught in each; and the readers of ``attention_roofline`` and
``collective_ms`` on synthetic runs."""
import copy
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from chipbench import faults
from chipbench import program_trace as P
from chipbench import trace_reduce as TR
from chipbench.tests import tiny

GAT_CELL, DIST_CELL = "gat-products.b512-f10x10x10", "sage-reddit.b1024x4-f25x10"
TINY_GAT, TINY_DIST = "tiny-gat.b32-f2x3x2", "tiny-sage.b16x4-f3x2"
METRICS = os.path.join(tiny.BENCH, "metrics")


def make_root(tmp) -> str:
    """``tiny.make_root``'s throw-away root with the tiny SAGE cell, plus a
    tiny GAT cell (the real cell's widths and mix, on a 600-node graph and
    batches of 32) and a tiny four-partition SAGE cell (16 seeds a
    partition), each with its real cell's limits and metrics."""
    root = tiny.make_root(tmp, cells=("tiny-sage.b64-f3x2",))
    bench = os.path.join(root, "chipbench")
    gat = tiny._read(os.path.join(tiny.BENCH, "configs", "gat-products.json"))
    gat["graph"].update(nodes=600, edges=600 * 24, max_expected_degree=150,
                        train_nodes=400)
    gat["name"] = "tiny-gat"
    tiny._write(os.path.join(bench, "configs", "tiny-gat.json"), gat)
    for cell, real, fanouts, batch in ((TINY_GAT, GAT_CELL, [2, 3, 2], 32),
                                       (TINY_DIST, DIST_CELL, [2, 3], 16)):
        traffic = cell.split(".", 1)[1]
        mix = tiny._read(os.path.join(tiny.BENCH, "mixes",
                                      real.split(".", 1)[1] + ".json"))
        mix.update(name=traffic, batch=batch, fanouts=fanouts)
        tiny._write(os.path.join(bench, "mixes", traffic + ".json"), mix)
        tiny._write(os.path.join(bench, "limits", cell + ".json"),
                    tiny._read(os.path.join(tiny.BENCH, "limits",
                                            real + ".json")))
    real = tiny._read(os.path.join(tiny.REPO, "BENCHMARK.json"))
    bench_json = tiny._read(os.path.join(root, "BENCHMARK.json"))
    bench_json["configs"].append(
        {"name": "tiny-gat", "source": "test", "reduced": [], "why": "test",
         "file": "chipbench/configs/tiny-gat.json"})
    bench_json["workloads"] += [
        {"name": TINY_GAT, "config": "tiny-gat", "traffic": "b32-f2x3x2",
         "chips": 1, "why": "test"},
        {"name": TINY_DIST, "config": "tiny-sage", "traffic": "b16x4-f3x2",
         "chips": 4, "why": "test"}]
    rename = {tiny.SAGE_CELL: "tiny-sage.b64-f3x2", GAT_CELL: TINY_GAT,
              DIST_CELL: TINY_DIST}
    for kind in ("end_to_end", "per_layer"):
        metrics = copy.deepcopy(real[kind])
        for m in metrics:
            if "workloads" in m:
                m["workloads"] = [rename[w] for w in m["workloads"]
                                  if w in rename]
        bench_json[kind] = [m for m in metrics if m.get("workloads", True)]
    tiny._write(os.path.join(root, "BENCHMARK.json"), bench_json)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("newcells"))


def test_tiny_gat_cell_runs_and_is_correct(root, run_cell):
    rc, result, err = run_cell(root, TINY_GAT)
    assert rc == 0, err
    assert result["correct"] is True, err
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_nodes_per_s", "setup_s"}
    assert result["checks"]["blocks_bad"]["value"] == 0


def test_tiny_gat_cell_traced(root, run_cell):
    rc, result, err = run_cell(root, TINY_GAT, trace=1)
    assert rc == 0, err
    assert result["correct"] is True, err
    # no TPU plane on the CPU: the device and program readers find nothing
    assert set(result["metrics"]) == {"input_wait_ms", "sample_ms",
                                      "fetch_ms", "fetch_mib_per_step",
                                      "mfu.minibatch"}


@pytest.mark.parametrize("fault", faults.TRAINING + faults.MINIBATCH)
def test_a_planted_fault_is_caught_in_the_gat_cell(root, run_cell, fault):
    with faults.planted(fault):
        rc, result, err = run_cell(root, TINY_GAT)
    assert rc == 0, err
    assert result["correct"] is False, err
    assert any(not c["value"] <= c["limit"]
               for c in result["checks"].values()), result["checks"]


DIST_RUNS = r"""
import contextlib, io, json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
root, repo = sys.argv[1], sys.argv[2]
sys.path[:0] = [repo, os.path.join(repo, "src")]
import jax
jax.config.update("jax_platforms", "cpu")
from chipbench import faults, harness, trace_reduce
import repro.distributed as D
harness.enable_compile_cache = lambda d: "off"
trace_reduce.load_peak = lambda kind: {"bf16_flops": 1e12,
                                       "hbm_bytes_per_s": 1e11,
                                       "hbm_bytes": 1e9}
from chipbench.registry import Registry
path = Registry(root).path("minibatch_dist")
windows = []
window = path.Session.window


def recorded(self, seconds):
    windows.append(window(self, seconds))
    return windows[-1]


path.Session.window = recorded
real = D.make_distributed_minibatch_step

def frozen(*a, **k):
    mesh, step = real(*a, **k)
    def same(params, ostate, arrays):
        return (params, ostate) + tuple(step(params, ostate, arrays)[2:])
    return mesh, same

for mode in ("clean", "traced", "fetched_row", "state_unchanged"):
    out = io.StringIO()
    with contextlib.ExitStack() as stack:
        if mode == "fetched_row":
            stack.enter_context(faults.planted("fetched_row"))
        if mode == "state_unchanged":
            D.make_distributed_minibatch_step = frozen
        with contextlib.redirect_stdout(out):
            rc = harness.main(["--workload", sys.argv[3], "--seed",
                               "3000000019", "--seconds", "0.5", "--trace",
                               str(int(mode == "traced"))], root=root,
                              require_chip=False)
        D.make_distributed_minibatch_step = real
    names = windows[-1]["op_names"]
    psum = names and any("dist.grad_psum" in v for m in names().values()
                         for v in m.values())
    print(json.dumps({"mode": mode, "rc": rc, "psum_scope": bool(psum),
                      "result": json.loads(out.getvalue().splitlines()[-1])}))
"""


def test_tiny_four_partition_cell(root):
    """Runs the tiny four-partition cell four times in one process on four
    CPU devices: as it is (``correct``), traced, with a fetched row
    altered, and with a step that returns the state it was given; the two
    faults each come out ``correct`` false."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", DIST_RUNS, root, tiny.REPO,
                        TINY_DIST], env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    runs = {r["mode"]: r for r in map(json.loads, p.stdout.splitlines())}
    assert all(r["rc"] == 0 for r in runs.values())
    # the step as it ran, compiled again, names its gradient psum; a step
    # that is a plain function cannot be lowered and gives nothing
    assert runs["clean"]["psum_scope"] and runs["traced"]["psum_scope"]
    assert not runs["state_unchanged"]["psum_scope"]
    clean = runs["clean"]["result"]
    assert clean["correct"] is True, p.stderr[-4000:]
    assert clean["device"]["count"] == 4
    assert set(clean["metrics"]) == {"train_nodes_per_s", "setup_s"}
    assert clean["checks"]["update_gap"]["value"] < 1e-3
    traced = runs["traced"]["result"]
    assert traced["correct"] is True
    # no TPU plane on the CPU: only the host-clock and counter readers read
    assert set(traced["metrics"]) == {"input_wait_ms", "sample_ms",
                                      "fetch_ms", "fetch_mib_per_step",
                                      "mfu.minibatch"}
    assert runs["fetched_row"]["result"]["checks"]["rows_bad"]["value"] > 0
    assert runs["fetched_row"]["result"]["correct"] is False
    frozen = runs["state_unchanged"]["result"]
    assert frozen["correct"] is False
    assert frozen["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Work:
    @staticmethod
    def work(cfg, counts):
        return {"model_flops": 1.0, "attention_flops": 2e9 * counts[0],
                "attention_bytes": 4e8 * counts[0]}


def _run(program, *, window=None, chips=1):
    run = TR.Run(cell={"name": "x"}, config={}, mix={}, reference=_Work,
                 window=dict({"t0": 0.0, "t1": 1.0, "steps": 2,
                              "counts": [[1], [1]]}, **(window or {})),
                 spans=None, trace={"planes": chips}, peak={
                     "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
                 chips=chips, notes=[])
    run.program_trace = program
    return run


def test_attention_roofline_reads_the_aggregate_scope():
    reader = _reader("attention_roofline")
    pt = P.ProgramTrace([], {"gnn.aggregate/jax_ops fwd": 0.05,
                             "gnn.aggregate/jax_ops bwd": 0.03,
                             "gnn.dense fwd": 0.5}, {})
    # 4e9 FLOP at 1e12/s is 4 ms; 8e8 bytes at 1e11/s is 8 ms: bytes bound,
    # 8 ms over the 80 ms under gnn.aggregate
    run = _run(pt)
    assert reader.read(run) == pytest.approx(10.0)
    assert "bound by bytes" in run.notes[-1]
    assert reader.read(_run(None)) is None
    assert reader.read(_run(P.ProgramTrace([], {"gnn.dense fwd": 1.0},
                                           {}))) is None


def _op(instr, s, e, module="jit_partition_step"):
    return P.DeviceOp(s, e, module, instr)


def test_collective_ms_reads_the_psum_scope_over_the_chips():
    mod = _reader("collective_ms")
    hlo = {"jit_partition_step": {
        "all-reduce.1": "jit(partition_step)/shard_map/dist.grad_psum/psum",
        "fusion.2": "jit(partition_step)/shard_map/optimizer/sub",
        "all-reduce.3": "jit(partition_step)/shard_map/psum"}}
    ops = {"/device:TPU:0": [_op("all-reduce.1", 100, 300),
                             _op("fusion.2", 300, 400),
                             _op("all-reduce.3", 400, 450),
                             _op("all-reduce.1", 900, 1100)],
           "/device:TPU:1": [_op("all-reduce.1", 150, 250),
                             _op("all-reduce.1", 100, 200, module="jit_other")]}
    host = [P.Span("harness.window", 0, 1000, "main", {})]
    # plane 0: 200 + 100 (clipped at the window's end) ns; plane 1: 100 ns;
    # over two planes and two steps
    assert mod.scope_ms(ops, host, hlo, 2) == pytest.approx(1e-6 * 400 / 4)
    assert mod.scope_ms(ops, host, {}, 2) is None
    assert mod.scope_ms(ops, [], hlo, 2) is None
    # a run whose path gives no compiled step reads nothing
    assert mod.read(_run(None, chips=4)) is None
