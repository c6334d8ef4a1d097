"""Reducer `device_time_by_module`: seconds per product of device time
(self time of op events) in the XLA modules matching `modules`, of the
ops whose own name matches `ops` (shell patterns; both default to all),
on the device of the cell that spends most there.  With "log" the
seconds on every device go to an earlier line, and with "rate_of":
"dense_flops" the achieved TFLOP/s of the configuration's 2*m*n*k (no
ratio: no f32 or f64 peak is published).
Spec: {"modules": [...], "ops": [...], "log": tag, "rate_of": name}."""


def reduce(spec, ctx):
    run = ctx.run
    if run.trace is None:
        return None
    n = len(run.records)
    planes = ctx.xplane.device_planes(run.trace)[:len(ctx.devices)]
    per_device = [
        ctx.xplane.device_seconds(plane, run.trace_window,
                                  spec.get("modules", ["*"]),
                                  spec.get("ops", ["*"]))
        for _, plane in planes]
    if not n or not any(per_device):
        return None
    seconds = max(per_device) / n
    if "log" in spec:
        line = {"seconds_per_product_per_device": [s / n for s in per_device]}
        if spec.get("rate_of") == "dense_flops":
            cfg = ctx.cell.config
            flops = ctx.arithmetic.dense_cost(
                cfg["m"], cfg["n"], cfg["k"])["flops"]
            line.update(dense_flops=flops,
                        achieved_tflops=flops / seconds * 1e-12)
        ctx.log(spec["log"], line)
    return seconds
