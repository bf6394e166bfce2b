"""Share of the main thread's time in ``FeatureStore.fetch_masked`` in
which it was not running, in % (program span ``repro.store.fetch_masked``,
attribute ``cpu``)."""
from chipbench import program_trace as P


def read(run):
    return P.offcpu_share(P.spans(run, __file__, "store.fetch_masked"))
