"""Reducer `generator_bytes_share`: scale * bytes / (seconds * peak),
a kernel's share of its memory roofline, where the bytes are the
generator's own count for the window's products (its method
`bytes_of`, from the patterns of its NumPy reference, each moved block
read and written once) and the seconds another per-layer metric's
device time per product.  A generator without that method, or a
window where that metric reads nothing, reports nothing.
Spec: {"bytes_of": method, "per_metric": metric, "peak": a key of
peaks.json (per second, in units of 1e9), "scale": number}."""


def reduce(spec, ctx):
    gen, run = ctx.gen, ctx.run
    count = getattr(gen, spec["bytes_of"], None)
    if count is None or not run.product_ids or spec["peak"] not in ctx.peaks:
        return None
    nbytes = sum(map(count, run.product_ids)) / len(run.product_ids)
    seconds = run.metrics.get(spec["per_metric"])
    if seconds is None:  # not reduced yet
        other_spec, other = ctx.cell.layer(spec["per_metric"])
        seconds = other.reduce(other_spec, ctx)
    if not seconds:
        return None
    peak = ctx.peaks[spec["peak"]] * 1e9
    ctx.log(spec["per_metric"] + "_roofline", {
        "bytes": nbytes, "seconds": seconds,
        "achieved_gbytes_per_s": nbytes / seconds * 1e-9,
        "bound": spec["peak"], "least_seconds": nbytes / peak})
    return float(spec.get("scale", 1)) * nbytes / (seconds * peak)
