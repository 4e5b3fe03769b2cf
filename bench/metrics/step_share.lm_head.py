"""Share of the forwards' device time spent in the lm-head: the logits
over the vocabulary at every position of the forward (scope
`lm_head`).

Read from the profiler trace by `harness.scopes`: leaf ops that start
inside a forward, each under the innermost listed scope on its op_name.
None where no op carries a scope."""

from harness.scopes import scope_share


def read(ctx):
    return scope_share(ctx, "lm_head")
