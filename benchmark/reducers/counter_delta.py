"""Reducer `counter_delta`: what a counter of the program (or of JAX)
moved by over the window.

Spec: {"counter": name, "labels": {k: v} (only series with these),
"per": "product" | "window", "scale": number,
"when_uncounted": {"algorithm": [names]}}.  Where the counter did not
move at all for any label and `when_uncounted` is given, the share of
the window's results whose `_mm_algorithm` is one of the names is
taken (the mesh path counts no format decision).  With "log_by" the
delta per value of that label goes to an earlier line.
"""


def _total(items, labels):
    return sum(v for lab, v in items
               if all(lab.get(k) == want for k, want in labels.items()))


def reduce(spec, ctx):
    run = ctx.run
    before = run.counters_before.get(spec["counter"], [])
    after = run.counters_after.get(spec["counter"], [])
    labels = spec.get("labels", {})
    delta = _total(after, labels) - _total(before, labels)
    n = len(run.records)
    if "log_by" in spec:
        key = spec["log_by"]
        by = {}
        for lab, v in after:
            by[lab.get(key)] = by.get(lab.get(key), 0.0) + v
        for lab, v in before:
            by[lab.get(key)] = by.get(lab.get(key), 0.0) - v
        ctx.log(spec["counter"], {str(k): v for k, v in by.items() if v})
    fallback = spec.get("when_uncounted")
    if fallback and _total(after, {}) == _total(before, {}):
        if not run.algorithms:
            return None
        hits = sum(a in fallback["algorithm"] for a in run.algorithms)
        ctx.log(spec["counter"], {"uncounted": True,
                                  "algorithms": run.algorithms})
        return float(spec.get("scale", 1)) * hits / len(run.algorithms)
    if spec.get("per", "window") == "product":
        if not n:
            return None
        delta /= n
    if spec.get("loud_if_nonzero") and delta:
        ctx.log("WARNING", {spec["counter"]: delta,
                            "why": spec["loud_if_nonzero"]})
    return float(spec.get("scale", 1)) * delta
