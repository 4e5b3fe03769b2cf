"""Mean time from a request's submit to its admission into a slot:
`queue_wait_s / admitted` of the engine's `stats()` over the window. None
where the program keeps no such counter."""


def read(ctx):
    st = ctx.stats
    n = st.get("admitted")
    return 1e3 * st["queue_wait_s"] / n if n else None
