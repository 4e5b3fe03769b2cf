"""Share of the forwards' device time spent in attention: scores,
softmax and the weighted sum, less its projections and cache writes
(scope `attention`).

Read from the profiler trace by `harness.scopes`: leaf ops that start
inside a forward, each under the innermost listed scope on its op_name.
None where no op carries a scope."""

from harness.scopes import scope_share


def read(ctx):
    return scope_share(ctx, "attention")
