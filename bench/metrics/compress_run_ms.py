"""Mean milliseconds of one training compressor run (``Compressor.cr``:
``encode`` plus the host byte count), over the ``repro.compress.run``
spans of the window's advisor variables (program spans)."""


def read(ctx):
    PS = ctx.lib("program_spans")
    spans = PS.window_spans(ctx)
    runs = [] if spans is None else PS.named(spans, "repro.compress.run")
    if not runs:
        return None
    return sum(s.dur_ns for s in runs) / len(runs) / 1e6
