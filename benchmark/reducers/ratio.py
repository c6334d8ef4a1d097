"""Reducer `ratio`: scale * quantity / (metric * peak).

Spec: {"quantity": a name below, "per_metric": another per-layer metric
(seconds per product), "peak": a key of peaks.json (per second, in
units of 1e9), "scale": number}.  The quantity is the benchmark's own
arithmetic on the real stacks of the window's products, per product:
  fused_stack_bytes   least bytes the stack engine must move
  true_flops          true flops of the product
An earlier line gives flops, bytes, intensity and which bound holds.
"""


def reduce(spec, ctx):
    gen, ar, run = ctx.gen, ctx.arithmetic, ctx.run
    if not run.product_ids or spec["peak"] not in ctx.peaks:
        return None
    per_product = [gen.stacks(p) for p in run.product_ids]
    flops = sum(map(ar.true_flops, per_product)) / len(per_product)
    nbytes = sum(ar.fused_stack_bytes(st, gen.itemsize())
                 for st in per_product) / len(per_product)
    quantity = {"fused_stack_bytes": nbytes, "true_flops": flops}[
        spec["quantity"]]
    seconds = run.metrics.get(spec["per_metric"])
    if seconds is None:  # not reduced yet, or not one of this cell's
        other_spec, other = ctx.cell.layer(spec["per_metric"])
        seconds = other.reduce(other_spec, ctx)
    if not seconds:
        return None
    peak = ctx.peaks[spec["peak"]] * 1e9
    ctx.log(spec["per_metric"] + "_roofline", {
        "flops": flops, "bytes": nbytes, "flop_per_byte": flops / nbytes,
        "seconds": seconds, "achieved_gflops": flops / seconds * 1e-9,
        "achieved_gbytes_per_s": nbytes / seconds * 1e-9,
        "bound": spec["peak"], "least_seconds": quantity / peak})
    return float(spec.get("scale", 1)) * quantity / (seconds * peak)
