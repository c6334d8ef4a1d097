"""Reducer `counter_share`: the share of what one counter of the program
moved by over the window that fell to some of its series.

Spec: {"counter": name, "labels": {k: v} (the part: only series with
these), "of_labels": {k: v} (the whole; all series where left out),
"scale": number, "log_by": label}.  The value is
scale * delta(part) / delta(whole); where the whole did not move (the
program has no such counter, or the window launched nothing it counts)
the reader finds nothing and says nothing, not 0.

One counter, because the harness snapshots a layer file's `counter`
and no other name: a share of two families would read the second
one's window from nothing.  A quantity that is a ratio of two counts
is therefore counted as two values of one label (`kind`) of one family.

With "log_by" an earlier `BENCH <metric>` line gives the part, the
whole and the share for every value of that label that moved.
"""


def _deltas(run, spec, labels) -> dict:
    """{value of the log_by label (None without one): what the series
    under ``labels`` moved by over the window}."""
    key, by = spec.get("log_by"), {}
    for sign, items in ((1.0, run.counters_after), (-1.0, run.counters_before)):
        for lab, v in items.get(spec["counter"], []):
            if all(lab.get(k) == want for k, want in labels.items()):
                at = lab.get(key) if key else None
                by[at] = by.get(at, 0.0) + sign * v
    return by


def reduce(spec, ctx):
    part = _deltas(ctx.run, spec, spec.get("labels", {}))
    whole = _deltas(ctx.run, spec, spec.get("of_labels", {}))
    total = sum(whole.values())
    if not total:
        return None
    scale = float(spec.get("scale", 1))
    if "log_by" in spec:
        # the metric's own name: the harness keeps a layer file's
        # contents under the name it loaded them by
        tag = next((name for name, (s, _) in getattr(ctx, "layers",
                                                     {}).items()
                    if s is spec), spec["counter"])
        ctx.log(tag, {
            "by": spec["log_by"],
            "series": {str(k): {"part": part.get(k, 0.0), "whole": w,
                                "share": scale * part.get(k, 0.0) / w}
                       for k, w in sorted(whole.items(), key=str) if w}})
    return scale * sum(part.values()) / total
