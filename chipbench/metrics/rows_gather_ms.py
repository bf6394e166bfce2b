"""Mean time of ``FeatureStore.fetch_masked`` per step, the input rows'
gather into one host matrix (program span ``repro.store.fetch_masked``,
host clock)."""
import statistics

from chipbench import program_trace as P


def read(run):
    d = [1e-6 * (s.end - s.start)
         for s in P.spans(run, __file__, "store.fetch_masked")]
    return statistics.fmean(d) if d else None
