"""``correct`` comes out false for the control and for every fault a cell
can have, in a whole run past the look for a chip, at a tiny size."""
import pytest

from chipbench import faults

CASES = ([("tiny-sage.b64-f3x2", f) for f in faults.TRAINING + faults.MINIBATCH]
         + [("tiny-gcn.fullbatch", f) for f in faults.TRAINING])


@pytest.mark.parametrize("cell,fault", CASES)
def test_a_planted_fault_is_caught(tiny_root, run_cell, cell, fault):
    with faults.planted(fault):
        rc, result, err = run_cell(tiny_root, cell)
    assert rc == 0, err
    assert result["correct"] is False, err
    failed = [k for k, c in result["checks"].items()
              if not c["value"] <= c["limit"]]
    assert failed, result["checks"]


@pytest.mark.parametrize("cell", ["tiny-gcn.fullbatch"])
def test_the_control_is_caught(tiny_root, run_cell, monkeypatch, cell):
    """The reference computed one precision below the configuration's
    (three bfloat16 passes for float32 at ``highest``), put in the
    program's place, fails the check.  The full-graph cell holds it on a
    20,000-node graph at the real widths; at the sizes a test holds, the
    mini-batch cell's three-pass gradients move too little to be caught on
    every seed (PERF.md, Findings, PR 12), and its control is read on the
    chip at the cell's size."""
    from chipbench.registry import Registry
    path = Registry(tiny_root).resolve(cell)["path"]
    start = path.Session.start

    def control_start(self, seed):
        first = start(self, seed)
        first.update(path.reference_run(self.ctx, first, "high"))
        return first

    monkeypatch.setattr(path.Session, "start", control_start)
    rc, result, err = run_cell(tiny_root, cell)
    assert rc == 0, err
    assert result["correct"] is False, err
    assert any(c["value"] > c["limit"] for k, c in result["checks"].items()
               if k.endswith("_gap"))
