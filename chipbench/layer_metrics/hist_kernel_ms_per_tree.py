"""Device milliseconds of the histogram kernel per traced tree."""
from chipbench.harness import trace_reduce


def read(ctx, spec):
    s = trace_reduce.kernel_seconds(ctx, spec)
    return None if s is None else 1e3 * s / len(ctx["traced"])
