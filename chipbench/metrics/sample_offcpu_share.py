"""Share of the loader threads' time in ``NeighborSampler.sample`` in which
they were not running, in %: one less their CPU seconds over their wall
seconds (program span ``repro.sampler.sample``, attribute ``cpu``).  For
these Python threads it is mostly time spent waiting for the GIL."""
from chipbench import program_trace as P


def read(run):
    return P.offcpu_share(P.spans(run, __file__, "sampler.sample"))
