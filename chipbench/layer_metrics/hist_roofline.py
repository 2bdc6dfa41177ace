"""The histogram kernel's share of its roofline: the least time the chip
could take for the traced trees' histogram work, over the kernel's device
time in the trace."""
from chipbench.harness import trace_reduce


def read(ctx, spec):
    s = trace_reduce.kernel_seconds(ctx, spec)
    if s is None:
        return None
    return 100.0 * sum(ctx["least_s"][i] for i in ctx["traced"]) / s
