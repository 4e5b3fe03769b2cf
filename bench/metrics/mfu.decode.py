"""The decode forwards' share of the chip's peak: the least time of the
work they require (valid rows only, `harness.work.forward_work`) over the
time the engine measured for them, summed over the window's decode
forwards."""

from harness.tracing import step_share


def read(ctx):
    return step_share(ctx, "decode")
