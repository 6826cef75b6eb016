"""Percent of the rows the stream launched that were padding: over the
``repro.stream.launch`` spans of the window's advisor variables,
sum(rows_launched - rows) / sum(rows_launched) (program counters, the
spans' attributes)."""


def read(ctx):
    PS = ctx.lib("program_spans")
    spans = PS.window_spans(ctx)
    launches = [] if spans is None else PS.named(spans,
                                                 "repro.stream.launch")
    launched = sum(s.attrs["rows_launched"] for s in launches)
    if not launched:
        return None
    rows = sum(s.attrs["rows"] for s in launches)
    return 100.0 * (launched - rows) / launched
