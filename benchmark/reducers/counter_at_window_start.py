"""Reducer `counter_at_window_start`: what a counter of the program had
reached when set-up ended, read from the snapshot the harness takes
just before the window's first product (`run.counters_before`).  All of
it was counted during set-up: the process is the cell's own.

Spec: {"counter": name, "labels": {k: v or [v, ...]} (only series whose
label k is v, or one of the list), "scale": number, "log_by": [label,
...]}.  A program without the counter, or without a matching series,
reads 0: a warm start has compiled nothing and says so.

With "log_by" an earlier `BENCH <metric>` line breaks the number down by
those labels: the 12 largest series and, with more than one label, the
12 largest values of each label alone.  A label that is both filtered and logged by is freed for
that line (logging by a label held to one value would say nothing), so
`{"labels": {"span": "x", "kind": "total"}, "log_by": ["span"]}` reads
x's total and logs every span's.
"""

LOG_TOP = 12


def _matches(lab, labels):
    return all(lab.get(k) in (want if isinstance(want, list) else [want])
               for k, want in labels.items())


def _top(by):
    return dict(sorted(by.items(), key=lambda kv: -kv[1])[:LOG_TOP])


def reduce(spec, ctx):
    items = ctx.run.counters_before.get(spec["counter"], [])
    labels = spec.get("labels", {})
    scale = float(spec.get("scale", 1))
    log_by = spec.get("log_by")
    if log_by:
        held = {k: v for k, v in labels.items() if k not in log_by}
        series, alone = {}, {k: {} for k in log_by}
        for lab, v in items:
            if not _matches(lab, held) or not v:
                continue
            key = ",".join(f"{k}={lab.get(k, '')}" for k in log_by)
            series[key] = series.get(key, 0.0) + scale * v
            for k in log_by:
                at = str(lab.get(k, ""))
                alone[k][at] = alone[k].get(at, 0.0) + scale * v
        # the metric's own name: the harness hands a reducer the layer
        # file's contents, and keeps them under the name it loaded them by
        tag = next((name for name, (s, _) in getattr(ctx, "layers",
                                                     {}).items()
                    if s is spec), spec["counter"])
        line = {"series": _top(series)}
        if len(log_by) > 1:
            line["by"] = {k: _top(v) for k, v in alone.items()}
        ctx.log(tag, line)
    return scale * sum(v for lab, v in items if _matches(lab, labels))
