"""Percent of the advisor window in which the device was idle while the
host trained the compressors' models: device idle time (the complement
of the union of ``XLA Ops``) inside the union of the window's
``repro.advise.train`` spans, shifted onto the trace's clock, over the
``bench.window`` length, averaged over the cell's chips.  At most
``idle_share.advise`` by construction; None without a device plane."""


def read(ctx):
    if not ctx.planes:
        return None
    PS = ctx.lib("program_spans")
    spans = PS.window_spans(ctx)
    if spans is None:
        return None
    TR = ctx.lib("trace")
    lo, hi = ctx.window
    train = TR.union([(max(s.start_ns, lo), min(s.end_ns, hi))
                      for s in PS.named(spans, "repro.advise.train")])
    idle = [PS.overlap_ns(PS.idle_intervals(TR, ctx.events, p, lo, hi),
                          train) for p in ctx.planes]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)
