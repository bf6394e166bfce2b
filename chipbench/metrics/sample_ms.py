"""Mean time a loader thread spent in ``NeighborSampler.sample`` per batch
(host clock)."""
from chipbench.readers import mean_span_ms


def read(run):
    return mean_span_ms(run, "sample")
