"""Share of prefill rows that carry a prompt token: prefill tokens over
prefill forwards times slots times the chunk (`stats()` over the window).
Every prefill forward runs all n_slots x prefill_chunk rows."""


def read(ctx):
    st, eng = ctx.stats, ctx.settings["engine"]
    if not st["prefill_forwards"]:
        return None
    rows = st["prefill_forwards"] * eng["n_slots"] * eng["prefill_chunk"]
    return 100.0 * st["prefill_tokens"] / rows
