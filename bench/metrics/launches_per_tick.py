"""Backend: kernel launches per decode tick, the backend's launch count
over each ``decode_tick`` (its ``launches`` attribute)."""


def read(ctx):
    ticks = ctx.spans_named("decode_tick")
    if not ticks:
        return None
    return sum(e.attrs.get("launches", 0) for e in ticks) / len(ticks)
