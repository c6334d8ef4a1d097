"""Reducer `host_span_self`: seconds per product of the self time of a
host span in the traced window: its length less what the program's and
the benchmark's own spans nested in it cover.  Host time on the
profiler's clock, never device time.  Spec: {"span": name}."""


def reduce(spec, ctx):
    run = ctx.run
    if run.trace is None:
        return None
    selfs = ctx.xplane.span_self_ns(run.trace, spec["span"], ctx.family,
                                    run.trace_window)
    n = len(run.records)
    if not selfs or not n:
        return None
    return sum(selfs) * 1e-9 / n
