"""Full passes over the rows per tree, from the program's own counters."""
from lightgbm_tpu.obs import metrics as obs


def read(ctx, spec):
    if not obs.enabled():
        return None
    passes = obs.counter("train_hist_passes_total").value
    rounds = obs.counter("train_boost_rounds_total").value
    if not passes or not rounds:  # another grower counts no pass
        return None
    return passes / rounds
