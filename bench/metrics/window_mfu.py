"""Model step: the operations of every GEMM the window ran (true rows,
no padding) over the window times chips times the bf16 peak."""


def read(ctx):
    flops = sum(f for calls in ctx.launches.values() for f, _ in calls)
    if flops <= 0.0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks.bf16_flops)
