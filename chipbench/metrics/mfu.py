"""Model FLOP/s over the window against the chips' bf16 peak, in %.
Serves ``mfu.<cell kind>``."""
from chipbench.readers import window_work


def read(run):
    if not run.window["steps"]:
        return None
    flops = window_work(run)["model_flops"]
    return 100.0 * flops / run.window_s() / (run.chips
                                             * run.peak["bf16_flops"])
