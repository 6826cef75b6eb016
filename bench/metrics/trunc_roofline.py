"""Share of its roofline that the truncation stage reaches: the least
time the chip could take for the batched Gram of a field's stack, over
the device time of the program's ``svd_trunc_batch`` jitted alone
(``bench_trunc_probe`` in the trace).  The work is counted here, not
taken from the compiled program, so it reads the same whatever
implements the stage; ``eigvalsh`` is not counted, so the share is a
lower bound.  The bf16 peak is used although the Gram runs f32 at
HIGHEST precision."""

PROBE = r"jit_bench_trunc_probe"


def work(k: int, m: int, n: int) -> tuple:
    """(FLOP, bytes) of k Gram matrices of (m, n) f32 slices: 2 q p^2
    multiply-adds each for p = min(m, n), q = max(m, n); one read of
    the stack."""
    p, q = min(m, n), max(m, n)
    return 2.0 * k * q * p * p, 4.0 * k * m * n


def read(ctx):
    TR = ctx.lib("trace")
    ms = TR.mean_launch_ms(ctx.events, ctx.planes, PROBE)
    if not ms:
        return None
    c = ctx.cell.config
    flops, nbytes = work(c["slices"], c["edge"], c["edge"])
    return TR.roofline_share(flops, nbytes, ms / 1e3, ctx.peaks)[0]
