"""Share of the forwards' device time spent writing the KV cache: the
scatter of each forward's new keys and values, layer by layer, into the
cache the step was given (scope `kv_write`).

Read from the profiler trace by `harness.scopes`: leaf ops that start
inside a forward, each under the innermost listed scope on its op_name.
None where no op carries a scope."""

from harness.scopes import scope_share


def read(ctx):
    return scope_share(ctx, "kv_write")
