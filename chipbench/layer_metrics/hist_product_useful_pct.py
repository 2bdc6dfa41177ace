"""The share of the rows the histogram passes put through the one-hot
product that a tree needed, from the program's own counters."""
from lightgbm_tpu.obs import metrics as obs
from lightgbm_tpu.ops import hist_pallas


def read(ctx, spec):
    if not obs.enabled():
        return None
    needed = obs.counter("train_hist_rows_needed_total").value
    blocks = obs.counter("train_hist_blocks_multiplied_total").value
    multiplied = blocks * getattr(hist_pallas, "SUB_BLOCK", 0)
    if not multiplied:
        # no count of what the product took: a program before the kernel
        # packed its rows, or a route that runs no kernel (the einsum at 64
        # bins or fewer).  Both multiply every row they stream.
        multiplied = obs.counter("train_hist_rows_streamed_total").value
    if not needed or not multiplied:  # another grower counts no pass
        return None
    return 100.0 * needed / multiplied
