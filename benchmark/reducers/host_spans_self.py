"""Reducer `host_spans_self`: seconds per product of the self time of
several host spans in the traced window, summed: each span's length
less what the program's and the benchmark's own spans nested in it
cover (`host_span_self` for a layer that owns more than one span).
Host time on the profiler's clock, never device time.
Spec: {"spans": [names]}."""


def reduce(spec, ctx):
    run = ctx.run
    n = len(run.records)
    if run.trace is None or not n:
        return None
    selfs = [s for name in spec["spans"]
             for s in ctx.xplane.span_self_ns(run.trace, name, ctx.family,
                                              run.trace_window)]
    if not selfs:
        return None
    return sum(selfs) * 1e-9 / n
