"""The aggregation's share of its roofline, in %: its essential operations
and bytes over the window (``work.py``) against the device time of the
operations ``op_layers.json`` gives to the aggregation layer.  Serves
``aggregation_roofline.<cell kind>``."""
from chipbench import work as W
from chipbench.readers import window_work


def read(run):
    seconds = run.trace["layer_s"].get("aggregation", 0.0)
    if seconds <= 0:
        return None
    w = window_work(run)
    share, bound = W.roofline_share(w["aggregation_flops"],
                                    w["aggregation_bytes"], seconds,
                                    run.peak)
    run.notes.append(f"aggregation: {w['aggregation_flops']!r} FLOP, "
                     f"{w['aggregation_bytes']!r} bytes over "
                     f"{seconds!r} device s; bound by {bound}")
    return share
