"""`submit_wait_ms` on the chat cells, where the wait moves the gaps a
client sees rather than the tokens completed: mean time a request waits
in `EnginePump.submit` for the pump's lock, `submit_wait_s / submits` of
`stats()` over the window. None where the program keeps no such counter."""


def read(ctx):
    st = ctx.stats
    n = st.get("submits")
    return 1e3 * st["submit_wait_s"] / n if n else None
