"""Share of its roofline that the quantized-entropy stage reaches: the
least time the chip could take, one read of a field's stack at HBM
bandwidth, over the device time of the program's
``quantized_entropy_sweep`` jitted alone (``bench_qent_probe`` in the
trace).  The work is counted here, not taken from the compiled
program."""

PROBE = r"jit_bench_qent_probe"


def work(k: int, m: int, n: int) -> tuple:
    """(FLOP, bytes): no floating-point work is counted; one read of the
    (k, m, n) f32 stack."""
    return 0.0, 4.0 * k * m * n


def read(ctx):
    TR = ctx.lib("trace")
    ms = TR.mean_launch_ms(ctx.events, ctx.planes, PROBE)
    if not ms:
        return None
    c = ctx.cell.config
    flops, nbytes = work(c["slices"], c["edge"], c["edge"])
    return TR.roofline_share(flops, nbytes, ms / 1e3, ctx.peaks)[0]
