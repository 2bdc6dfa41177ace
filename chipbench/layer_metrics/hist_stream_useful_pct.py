"""The share of the rows the histogram passes streamed that a tree needed,
from the program's own counters."""
from lightgbm_tpu.obs import metrics as obs


def read(ctx, spec):
    if not obs.enabled():
        return None
    needed = obs.counter("train_hist_rows_needed_total").value
    streamed = obs.counter("train_hist_rows_streamed_total").value
    if not needed or not streamed:  # another grower counts no pass
        return None
    return 100.0 * needed / streamed
