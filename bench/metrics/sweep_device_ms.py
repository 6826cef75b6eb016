"""Device milliseconds per launch of the sweep executable inside the
window, on the busiest chip (profiler trace).  The executable's name is
the mix's ``sweep_module`` pattern."""


def read(ctx):
    TR = ctx.lib("trace")
    return TR.mean_launch_ms(ctx.events, ctx.planes,
                             ctx.cell.mix["sweep_module"], *ctx.window)
