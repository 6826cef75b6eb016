"""Percent of the window's advisor time spent training the compressors'
models: the summed ``repro.advise.train`` spans over the summed
``repro.advise.variable`` spans of the window's variables (program
spans, ``bench/program_spans.py``)."""


def read(ctx):
    PS = ctx.lib("program_spans")
    spans = PS.window_spans(ctx)
    if spans is None:
        return None
    total = PS.total_ns(spans, PS.VARIABLE)
    return 100.0 * PS.total_ns(spans, "repro.advise.train") / total
