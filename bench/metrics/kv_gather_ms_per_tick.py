"""Scheduler: time gathering each request's K/V from the paged arena per
decode tick, the ``kv.gather`` spans below each ``decode_tick``, summed
per tick, mean over ticks."""
from bench.tick_spans import ms_per_tick


def read(ctx):
    return ms_per_tick(ctx.spans, {"kv.gather"})
