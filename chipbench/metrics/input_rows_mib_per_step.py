"""Bytes of the input-row matrices ``FeatureStore.fetch_masked`` returned in
the window, per step, in MiB: what the step's upload carries, pad rows
included (program span ``repro.store.fetch_masked``, attribute
``bytes``)."""
from chipbench import program_trace as P


def read(run):
    fetches = P.spans(run, __file__, "store.fetch_masked")
    steps = run.window["steps"]
    if not fetches or not steps:
        return None
    return sum(int(s.attrs["bytes"]) for s in fetches) / steps / 2**20
