"""Reducer `device_time_by_scope`: seconds per product of device time
(self time of op events, so a `while` does not count its body twice) in
the XLA modules matching `modules`, of the ops whose scope path matches
one of `scopes` (shell patterns, e.g. "*/stk_dot/*"), on the device of
the cell that spends most there.

The scope path is the op's `tf_op`: JAX's `op_name`, which holds every
`dbcsr_tpu.core.timings.device_scope` the op was created under.
`opmeta.device_ops` reads it from the trace file itself (`xplane.load`
does not show it); times, the window and the module an op ran in are
`xplane`'s, as for `device_time_by_module`.  XLA fuses across scopes
and a fusion carries one `op_name`, so the split is the compiler's.

With "log" one earlier line gives, for the device that spends most in
the modules: seconds per product of every phase (the innermost `stk_*`
scope of an op, `unscoped` where it has none) and of every `span*`
scope (the module's name for ops of a per-span program), the five
largest `source` lines of the unscoped ops, and per phase XLA's
`bytes_accessed` over the seconds in GB/s.

Where the modules ran and no op of them carries a `stk_*` scope the
value is None, and a line in capitals says so: the program predates the
scopes, or its executable came from a compile cache written before
them (the cache keys a program without its metadata).

Spec: {"modules": [...], "scopes": [...], "log": tag}.
"""

import time

from benchmark import opmeta

PHASE, SPAN, UNSCOPED = "stk_", "span", "unscoped"


def _innermost(tf_op: str, prefix: str):
    found = None
    for part in tf_op.split("/"):
        if part.startswith(prefix):
            found = part.rstrip(":")
    return found


def _rows(ctx) -> list:
    """Per device of the cell, [(module, op event with its metadata,
    self ns)] of the window; read once per run."""
    rows = getattr(ctx, "scope_rows", None)
    if rows is not None:
        return rows
    xp, run = ctx.xplane, ctx.run
    t0 = time.perf_counter()
    path = xp.find_xplane(ctx.trace_dir)
    by_plane = opmeta.device_ops(path)
    rows = []
    for _, plane in xp.device_planes(run.trace)[:len(ctx.devices)]:
        ops = by_plane.get(plane["name"], [])
        joined = {"lines": [
            {"name": xp.MODULES_LINE,
             "events": xp.line_events(plane, xp.MODULES_LINE)},
            # an op is carried through the reductions by its position
            {"name": xp.OPS_LINE,
             "events": [[i, ev[1], ev[2]] for i, ev in enumerate(ops)]}]}
        rows.append([(mod, ops[i], self_ns) for mod, i, self_ns
                     in xp.ops_by_module(joined, run.trace_window)])
    ctx.scope_rows = rows
    ctx.log("opmeta", {"file": path, "read_s": time.perf_counter() - t0,
                       "op_events": [len(r) for r in rows]})
    return rows


def _log_phases(tag, ctx, rows, n) -> None:
    xp = ctx.xplane
    seconds, spans, nbytes, sources = {}, {}, {}, {}
    for mod, (name, _, _, tf_op, source, accessed), self_ns in rows:
        phase = _innermost(tf_op, PHASE) or UNSCOPED
        span = _innermost(tf_op, SPAN) or mod
        seconds[phase] = seconds.get(phase, 0.0) + self_ns
        spans[span] = spans.get(span, 0.0) + self_ns
        nbytes[phase] = nbytes.get(phase, 0) + accessed
        if phase == UNSCOPED:
            key = source or f"(no source) {xp.op_kind(name)} of {tf_op}"
            sources[key] = sources.get(key, 0.0) + self_ns
    ctx.log(tag, {
        "seconds_per_product": dict(xp.top(seconds, len(seconds), 1e-9 / n)),
        "sum": sum(seconds.values()) * 1e-9 / n,
        "by_span": dict(xp.top(spans, len(spans), 1e-9 / n)),
        "unscoped_top_sources": xp.top(sources, 5, 1e-9 / n),
        "xla_gbytes_per_s": {p: nbytes[p] / s for p, s in seconds.items()
                             if s},
        "note": "xla_gbytes_per_s is the compiler's bytes_accessed over "
                "measured self time: an estimate of traffic, not a reading"})


def reduce(spec, ctx):
    run = ctx.run
    n = len(run.records)
    if run.trace is None or not n:
        return None
    xp = ctx.xplane
    per_device = [[row for row in rows if xp.matches(row[0], spec["modules"])]
                  for rows in _rows(ctx)]
    ran = max(per_device, key=lambda rows: sum(r[2] for r in rows),
              default=[])
    if not ran:
        return None
    if not any(_innermost(r[1][3], PHASE) for rows in per_device
               for r in rows):
        if not getattr(ctx, "scope_warned", False):
            ctx.scope_warned = True
            ctx.log("stack_phases", {
                "NO_SCOPE": "NO OP OF " + " ".join(spec["modules"])
                + " CARRIES A stk_* SCOPE, SO NO PHASE METRIC IS REPORTED. "
                "LIKELY CAUSE: AN EXECUTABLE FROM A COMPILE CACHE WRITTEN "
                "BEFORE THE SCOPES (THE CACHE KEY LEAVES METADATA OUT), OR "
                "A PROGRAM THAT PREDATES THEM",
                "modules_seen": sorted({r[0] for r in ran})})
        return None
    if "log" in spec:
        _log_phases(spec["log"], ctx, ran, n)
    return 1e-9 / n * max(
        sum(r[2] for r in rows if xp.matches(r[1][3], spec["scopes"]))
        for rows in per_device)
