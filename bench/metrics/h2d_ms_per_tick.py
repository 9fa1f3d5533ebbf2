"""Backend: host-to-device upload time per decode tick, the
``backend.put`` spans below each ``decode_tick`` (each ends when its
transfer is done), summed per tick, mean over ticks."""
from bench.tick_spans import ms_per_tick


def read(ctx):
    return ms_per_tick(ctx.spans, {"backend.put"})
