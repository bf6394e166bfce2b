"""Device time per step of the operations under the program's
``dist.grad_psum`` scope (the partition-parallel step's gradient
all-reduce), in ms, the mean over the cell's chips.  The step's
``op_name`` of each instruction comes from the path's ``op_names`` (the
step as it ran, compiled again after the window).  Serves
``collective_ms.<cell kind>``."""
import glob
import os

from chipbench import program_trace as P
from chipbench import trace_reduce

SCOPE = "dist.grad_psum"


def scope_ms(ops: dict, host: list, hlo: dict, steps: int):
    """Device ms per step under :data:`SCOPE`, the mean over the planes of
    ``ops`` (``{plane: [DeviceOp]}``), inside the last ``harness.window``
    span of ``host``; ``None`` where no operation is under it."""
    windows = [(s.start, s.end) for s in host if s.name == "harness.window"]
    if not ops or not windows or not steps:
        return None
    t0, t1 = windows[-1]
    ns = sum(min(o.end, t1) - max(o.start, t0)
             for plane in ops.values() for o in plane
             if o.end > t0 and o.start < t1
             and SCOPE in hlo.get(o.module, {}).get(o.instr, ""))
    return 1e-6 * ns / len(ops) / steps if ns > 0 else None


def read(run):
    op_names = run.window.get("op_names")
    if op_names is None or not run.trace.get("planes"):
        return None
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = glob.glob(os.path.join(bench_dir, ".cache", "traces",
                                   run.cell["name"], "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    try:
        hlo = op_names()
    except Exception as e:          # noqa: BLE001 — a reader never fails a run
        run.notes.append(f"collective_ms: the step could not be compiled "
                         f"again ({type(e).__name__}: {e})")
        return None
    ops, host = P.read_trace(max(files, key=os.path.getmtime),
                             trace_reduce.load_table(), run.chips)
    return scope_ms(ops, host, hlo, run.window["steps"])
