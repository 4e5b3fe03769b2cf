"""The LUT kernels' share of their roofline inside prefill forwards (as
lut_roofline.decode)."""

from harness.tracing import kernel_share


def read(ctx):
    return kernel_share(ctx, "prefill")
