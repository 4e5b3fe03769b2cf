"""The prefill forwards' share of the chip's peak: the least time of the
work they require (valid rows and positions only, lm-head only where a
token is sampled) over the time the engine measured for them."""

from harness.tracing import step_share


def read(ctx):
    return step_share(ctx, "prefill")
