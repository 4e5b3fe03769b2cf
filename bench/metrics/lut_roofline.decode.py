"""The LUT kernels' share of their roofline inside decode forwards: the
least time of each kernel call's work (the rows it is given) over the
device time of its events in the profiler trace, summed."""

from harness.tracing import kernel_share


def read(ctx):
    return kernel_share(ctx, "decode")
