"""Executable: host glue per decode tick, the ``host.stage`` (stacking,
padding and adapting inputs and K/V) and ``host.act`` (activations on
the host) spans below each ``decode_tick``, summed per tick, mean over
ticks."""
from bench.tick_spans import ms_per_tick


def read(ctx):
    return ms_per_tick(ctx.spans, {"host.stage", "host.act"})
