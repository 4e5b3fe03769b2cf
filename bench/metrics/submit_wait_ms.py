"""Mean time a request waits in `EnginePump.submit` for the pump's lock,
which a running engine step holds: `submit_wait_s / submits` of the
pump's `stats()` over the window. None where the program keeps no such
counter."""


def read(ctx):
    st = ctx.stats
    n = st.get("submits")
    return 1e3 * st["submit_wait_s"] / n if n else None
