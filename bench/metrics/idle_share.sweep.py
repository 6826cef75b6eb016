"""Percent of the sweep window in which no operation ran on the device,
averaged over the cell's chips (profiler trace)."""


def read(ctx):
    TR = ctx.lib("trace")
    return TR.idle_share(ctx.events, ctx.planes, *ctx.window)
