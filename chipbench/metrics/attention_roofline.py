"""The edge attention's share of its roofline, in %: its essential
operations and bytes over the window (the reference's ``work``:
``attention_flops``, ``attention_bytes``) against the device time of the
operations under the program's ``gnn.aggregate`` scope, forward and
backward (``program_trace``).  Serves ``attention_roofline.<cell kind>``."""
from chipbench import program_trace as P
from chipbench import work as W
from chipbench.readers import window_work


def read(run):
    pt = P.program(run, __file__)
    seconds = pt.scope_s("gnn.aggregate") if pt else 0.0
    if seconds <= 0 or not run.window["steps"]:
        return None
    w = window_work(run)
    if "attention_flops" not in w:
        return None
    share, bound = W.roofline_share(w["attention_flops"],
                                    w["attention_bytes"], seconds, run.peak)
    run.notes.append(f"attention: {w['attention_flops']!r} FLOP, "
                     f"{w['attention_bytes']!r} bytes over {seconds!r} "
                     f"device s under gnn.aggregate; bound by {bound}")
    return share
