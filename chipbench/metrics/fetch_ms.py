"""Mean time from the feature fetch call to the device arrays handed to the
step, per step (host clock)."""
from chipbench.readers import mean_span_ms


def read(run):
    return mean_span_ms(run, "fetch")
