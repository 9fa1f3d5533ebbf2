"""Backend: megabytes uploaded host to device per decode tick, the
``decode_tick`` span's ``h2d_bytes`` (the backend's byte counter over
the tick), mean over ticks."""


def read(ctx):
    counts = [e.attrs["h2d_bytes"] for e in ctx.spans_named("decode_tick")
              if "h2d_bytes" in e.attrs]
    return sum(counts) / len(counts) / 1e6 if counts else None
