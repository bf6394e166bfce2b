"""Device time per step of the operations under the program's
``gnn.aggregate`` scope, forward and backward, in ms.  Serves
``aggregation_ms.<cell kind>``."""
from chipbench import program_trace as P


def read(run):
    pt = P.program(run, __file__)
    seconds = pt.scope_s("gnn.aggregate") if pt else 0.0
    if seconds <= 0 or not run.window["steps"]:
        return None
    return 1e3 * seconds / run.window["steps"]
