"""Reducer `device_busy`: shares of the traced window in which an op
ran on the cell's devices.  Spec: {"which": "idle_mean"} gives 100 *
(1 - mean busy / window); {"which": "least_busy"} gives 100 * busy /
window of the least busy device."""


def reduce(spec, ctx):
    busy = getattr(ctx, "busy_per_device", None)
    if not busy:
        return None
    window = ctx.device_trace["window_s"]
    if spec["which"] == "idle_mean":
        return 100.0 * (1.0 - sum(busy) / len(busy) / window)
    if spec["which"] == "least_busy":
        return 100.0 * min(busy) / window
    raise ValueError(f"device_busy: unknown 'which' {spec['which']!r}")
