"""Mean time of one prefill forward, dispatch to result on the host:
`prefill_s / prefill_forwards` of `stats()` over the window."""


def read(ctx):
    st = ctx.stats
    return 1e3 * st["prefill_s"] / st["prefill_forwards"] if st["prefill_forwards"] else None
