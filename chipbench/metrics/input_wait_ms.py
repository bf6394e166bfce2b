"""Mean time the main thread waited on ``next(loader)`` per step (host clock)."""
from chipbench.readers import mean_span_ms


def read(run):
    return mean_span_ms(run, "loader_wait")
