"""Mean time of one decode forward, dispatch to result on the host:
`decode_s / decode_forwards` of `stats()` over the window."""


def read(ctx):
    st = ctx.stats
    return 1e3 * st["decode_s"] / st["decode_forwards"] if st["decode_forwards"] else None
