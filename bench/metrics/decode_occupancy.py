"""Share of decode rows that carry a live request: decode tokens over
decode forwards times slots (`stats()` over the window). Every decode
forward runs all n_slots rows."""


def read(ctx):
    st = ctx.stats
    return 100.0 * st["decode_occupancy"] if st["decode_forwards"] else None
