"""Each training path run end to end at a tiny size on the CPU (Pallas in
interpret mode), past the command line's look for a chip."""
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import harness

E2E = {"tiny-sage.b64-f3x2": "train_nodes_per_s",
       "tiny-gcn.fullbatch": "fullgraph_epoch_ms"}
HOST_METRICS = {"tiny-sage.b64-f3x2": {"input_wait_ms", "sample_ms",
                                        "fetch_ms", "fetch_mib_per_step",
                                        "mfu.minibatch"},
                "tiny-gcn.fullbatch": {"mfu.fullgraph"}}


def test_the_command_refuses_a_cpu(repo_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "gcn-arxiv.fullbatch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=repo_root, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("cell", sorted(E2E))
def test_tiny_cell_runs_and_is_correct(tiny_root, run_cell, cell):
    rc, result, err = run_cell(tiny_root, cell)
    assert rc == 0, err
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, err
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {E2E[cell], "setup_s"}
    assert result["metrics"][E2E[cell]]["value"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["checks"]["compiles_in_window"]["value"] == 0
    tail = err.strip().splitlines()[-len(result["checks"]) - 1:]
    assert tail[0].startswith("correct True")
    assert [l.split()[1] for l in tail[1:]] == list(result["checks"])


@pytest.mark.parametrize("cell", sorted(E2E))
def test_tiny_cell_traced(tiny_root, run_cell, cell):
    rc, result, err = run_cell(tiny_root, cell, trace=1)
    assert rc == 0, err
    assert result["correct"] is True, err
    # no TPU plane on the CPU: the trace's device metrics find nothing to
    # read and are left out; the host's are there
    assert set(result["metrics"]) == HOST_METRICS[cell]
    assert result["device"]["window_s"] > 0
    assert result["device"]["busy_s"] == 0.0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_pace_note_finds_the_longest_step_interval():
    spans = harness.Spans()
    spans.events = [("dispatch", 10.0, 10.1), ("dispatch", 10.5, 10.6),
                    ("sample", 10.0, 13.0), ("dispatch", 12.9, 13.0),
                    ("dispatch", 13.2, 13.3), ("dispatch", 20.0, 20.1)]
    note = harness.pace_note(spans, {"t0": 10.0, "t1": 14.0}, 4.0)
    assert note == ("pace: 4 dispatches, interval median 400.000 ms, "
                    "longest 2400.000 ms ending 3.000 s into the window; "
                    "window opened 6.000 s after the process started")


def test_batches_are_the_same_for_a_seed(tiny_root):
    """The harness's batch order and each worker's sampler come from the
    seed, so a batch is the same whichever thread samples it."""
    from chipbench.registry import Registry
    from repro.core.sampling import NeighborSampler

    parts = Registry(tiny_root).resolve("tiny-sage.b64-f3x2")
    ctx = harness.Context(Registry(tiny_root), parts, 7, harness.Spans())
    g = ctx.program_graph()
    pool = np.flatnonzero(ctx.graph_arrays()["train_mask"])
    path = parts["path"]

    def batches(seed):
        feed = path.Feed(NeighborSampler(g, [2, 3]), pool, 64, seed, 2,
                         ctx.spans)
        out = {}
        for _ in range(3):           # the first worker takes 0, 2, 4
            i, seeds, mb = feed()
            out[i] = (seeds, mb.blocks[0].edge_src.copy())
        return out

    a, b = batches(2**33 + 5), batches(2**33 + 5)
    assert sorted(a) == [0, 2, 4]
    for i in a:
        assert np.array_equal(a[i][0], b[i][0])
        assert np.array_equal(a[i][1], b[i][1])
    c = batches(11)
    assert not np.array_equal(a[0][0], c[0][0])
