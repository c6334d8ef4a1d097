"""Reducer `clock`: the median of one of the benchmark's own clocks
over the window's products (`dispatch_s`, `fence_wait_s`), or the one
reading of a set-up clock.  Spec: {"clock": name}."""


def reduce(spec, ctx):
    xs = ctx.run.samples.get(spec["clock"])
    if not xs:
        return None
    return ctx.arithmetic.median(xs)
