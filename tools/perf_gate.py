#!/usr/bin/env python
"""Noise-aware bench regression gate: candidate capture vs baseline.

The machine check behind the ROADMAP's "as fast as the hardware
allows": given two bench capture files, decide per case whether the
candidate regressed, with medians and noise-derived thresholds instead
of single-sample wall-clock comparisons, and exit nonzero so CI (or
the round driver) can block the PR.

Accepted capture formats (auto-detected, mixable):

* a raw ``bench.py`` output object (one JSON dict with ``metric`` /
  ``value``);
* the round artifacts ``BENCH_rNN.json`` (a wrapper whose ``parsed``
  field holds the bench dict);
* JSONL capture logs (one record per line, torn tail lines
  skipped);
* a JSON list of any of the above records.

Cases are keyed by the record's ``metric`` string (bench runs) or its
``kernel``/``dtype``/``stack_size`` signature (acc micro-benchmarks).
Multiple samples of one case (a JSONL log, repeated runs) are reduced
to their **median**; the regression threshold is
``max(--rel-tol, --noise-mult * MAD/median)`` of the baseline samples,
so a case that historically wobbles gets a proportionally wider gate.

The gate compares **efficiency, not raw wall-clock**, whenever it can:
with ``--gate-on auto`` (default) a case whose records carry the
cost-model block ``modeled.roofline_fraction`` (bench.py embeds it,
see `obs/costmodel.py`) is gated on that normalized fraction;
otherwise on the raw higher-is-better ``value``/``gflops``.

Apples-to-oranges refusal: a case whose baseline and candidate were
produced on different ``device_kind``s (or one on the real device and
one on the CPU fallback) is ``incomparable`` — reported, never
silently compared (``--force`` overrides).  Records produced before
the stamps existed compare on their ``device`` string.

Exit codes: 0 = pass (improvements and in-tolerance deltas), 1 = at
least one regression (or a baseline case missing from the candidate,
unless ``--allow-missing``), 2 = nothing regressed but at least one
case was incomparable.

Usage:
    python tools/perf_gate.py BASELINE.json CANDIDATE.json
        [--rel-tol 0.1] [--noise-mult 3] [--gate-on auto|value|
         roofline_fraction|gflops_modeled] [--json] [--report PATH]
        [--allow-missing] [--force]

No dbcsr_tpu import required: the capture JSON schema is the contract.
"""

from __future__ import annotations

import argparse
import json
import re
import sys


# ------------------------------------------------------------- loading

def _records_of(obj) -> list:
    """Flatten one parsed JSON document into capture records."""
    if isinstance(obj, list):
        out = []
        for o in obj:
            out.extend(_records_of(o))
        return out
    if isinstance(obj, dict):
        if isinstance(obj.get("parsed"), dict):  # BENCH_rNN.json wrapper
            return [obj["parsed"]]
        return [obj]
    return []


def load_records(path: str) -> list:
    """Parse a capture file (JSON object/list, wrapper, or JSONL)."""
    with open(path) as f:
        text = f.read()
    try:
        return _records_of(json.loads(text))
    except ValueError:
        pass
    records = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            records.extend(_records_of(json.loads(line)))
        except ValueError:
            continue  # torn tail line (writer killed mid-append)
    return records


# ------------------------------------------------------------- casing

def case_key(rec: dict) -> str | None:
    if rec.get("metric"):
        return str(rec["metric"])
    if rec.get("kernel"):
        return (f"acc_bench {rec['kernel']} {rec.get('dtype', '?')} "
                f"S={rec.get('stack_size', '?')}")
    return None


def comparability_key(rec: dict) -> str:
    """What must MATCH between baseline and candidate for a comparison
    to mean anything: the device kind (stamped by bench.py /
    acc/bench.py; pre-stamp records fall back to the device string
    with instance digits stripped) plus whether the run fell back to
    the CPU engine, plus — for workload rows that stamp it — which
    distributed tick scheduling (``cannon_mode``) the run used: a
    serial-mode baseline compared against a double-buffered candidate
    measures the scheduling change, not the code change under review.
    Rows whose ``unit`` is ``hidden-comm fraction`` are exempt: they
    ARE the cross-mode A/B (overlap/contract bench legs), where the
    mode is the experiment, not the environment."""
    kind = rec.get("device_kind")
    if not kind:
        kind = re.sub(r"[_\s]*\d+$", "", str(rec.get("device", "unknown")))
    kind = str(kind).strip().lower()
    if "cpu" in kind:
        # pre-stamp records say "TFRT_CPU_0", stamped ones "cpu": one
        # normalized bucket, so old baselines stay comparable
        kind = "cpu"
    fb = rec.get("device_fallback")
    key = f"{kind}|fallback={bool(fb)}"
    mode = rec.get("cannon_mode")
    if mode and rec.get("unit") != "hidden-comm fraction":
        key += f"|cannon_mode={mode}"
    return key


def environments_compatible(envs) -> bool:
    """True when the comparability keys describe one environment.
    Device kinds compare by PREFIX: a pre-stamp record whose device
    string only says "TPU" stays comparable with a stamped
    "tpu v5 lite" one, while "tpu v5 lite" vs "tpu v6 lite" (or a
    fallback-flag mix, or a cannon_mode mix on rows that stamp it)
    stays refused.  A pre-stamp row (no cannon_mode component) stays
    comparable with a stamped one — like the device-kind prefix rule,
    absent evidence never refuses."""
    envs = sorted(set(envs))
    if len(envs) <= 1:
        return True
    parts = [e.split("|") for e in envs]
    attrs = []
    for p in parts:
        d = {}
        for item in p[1:]:
            k, _, v = item.partition("=")
            d[k] = v
        attrs.append(d)
    for field in ("fallback", "cannon_mode"):
        seen = {d[field] for d in attrs if field in d}
        if len(seen) > 1:
            return False
    kinds = [p[0] for p in parts]
    return all(
        a.startswith(b) or b.startswith(a)
        for i, a in enumerate(kinds) for b in kinds[i + 1:]
    )


def gate_value(rec: dict, gate_on: str):
    """The higher-is-better number this record contributes, or None."""
    modeled = rec.get("modeled") or {}
    if gate_on == "roofline_fraction":
        return modeled.get("roofline_fraction")
    if gate_on == "gflops_modeled":
        return modeled.get("gflops_modeled")
    for field in ("value", "gflops"):
        if isinstance(rec.get(field), (int, float)):
            return float(rec[field])
    return None


def median(xs: list) -> float:
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def mad(xs: list) -> float:
    """Median absolute deviation (the robust noise scale)."""
    m = median(xs)
    return median([abs(x - m) for x in xs])


def collect_cases(records: list, gate_on: str) -> dict:
    """case -> {"samples": [...], "comparability": set, "metric": str}
    with per-case auto gate-metric resolution."""
    cases: dict = {}
    for rec in records:
        key = case_key(rec)
        if key is None:
            continue
        c = cases.setdefault(key, {"records": [], "comparability": set()})
        c["records"].append(rec)
        c["comparability"].add(comparability_key(rec))
    for c in cases.values():
        metric = gate_on
        if gate_on == "auto":
            metric = ("roofline_fraction"
                      if all((r.get("modeled") or {}).get(
                          "roofline_fraction") is not None
                          for r in c["records"])
                      else "value")
        c["metric"] = metric
        c["samples"] = [v for v in
                        (gate_value(r, metric) for r in c["records"])
                        if isinstance(v, (int, float))]
    return cases


# -------------------------------------------------------------- gating

def gate(base_records: list, cand_records: list, *, rel_tol: float = 0.1,
         noise_mult: float = 3.0, gate_on: str = "auto",
         allow_missing: bool = False, force: bool = False) -> dict:
    """Compare candidate against baseline; returns the report dict
    (with ``exit_code``)."""
    base = collect_cases(base_records, gate_on)
    cand = collect_cases(cand_records, gate_on)
    verdicts = []
    notes = []
    if not base:
        notes.append("empty baseline: nothing to gate against")
    for key in sorted(set(base) | set(cand)):
        b = base.get(key)
        c = cand.get(key)
        row = {"case": key}
        if b is None:
            row.update(verdict="new-case",
                       candidate_median=median(c["samples"])
                       if c["samples"] else None,
                       n_candidate=len(c["samples"]))
            verdicts.append(row)
            continue
        if c is None or not c["samples"]:
            row.update(verdict="missing-candidate",
                       baseline_median=median(b["samples"])
                       if b["samples"] else None,
                       n_baseline=len(b["samples"]))
            verdicts.append(row)
            continue
        if not b["samples"]:
            # the baseline has records for this case but none carries
            # the requested gate metric (e.g. --gate-on
            # roofline_fraction against a pre-modeled baseline):
            # comparing nothing must not pass the gate
            row.update(verdict="no-baseline-samples",
                       n_candidate=len(c["samples"]))
            verdicts.append(row)
            continue
        # resolve a common gate metric: auto may have picked
        # roofline_fraction on one side only (old baseline) — drop to
        # the raw value so both sides measure the same thing
        metric = b["metric"]
        if b["metric"] != c["metric"]:
            metric = "value"
        b_samples = [v for v in (gate_value(r, metric)
                                 for r in b["records"])
                     if isinstance(v, (int, float))]
        c_samples = [v for v in (gate_value(r, metric)
                                 for r in c["records"])
                     if isinstance(v, (int, float))]
        if not b_samples or not c_samples:
            row.update(verdict=("no-baseline-samples" if not b_samples
                                else "no-candidate-samples"),
                       metric=metric)
            verdicts.append(row)
            continue
        med_b = median(b_samples)
        med_c = median(c_samples)
        compat = b["comparability"] | c["comparability"]
        row.update(
            metric=metric,
            baseline_median=med_b,
            candidate_median=med_c,
            n_baseline=len(b_samples),
            n_candidate=len(c_samples),
        )
        if not environments_compatible(compat) and not force:
            row.update(verdict="incomparable",
                       environments=sorted(compat))
            verdicts.append(row)
            continue
        noise_tol = (noise_mult * mad(b_samples) / abs(med_b)
                     if med_b else 0.0)
        tol = max(rel_tol, noise_tol)
        delta = (med_c - med_b) / abs(med_b) if med_b else 0.0
        row.update(delta_rel=round(delta, 4), threshold=round(tol, 4))
        if delta < -tol:
            row["verdict"] = "regressed"
        elif delta > tol:
            row["verdict"] = "improved"
        else:
            row["verdict"] = "ok"
        verdicts.append(row)
    n_reg = sum(v["verdict"] == "regressed" for v in verdicts)
    # a candidate side with no usable samples is as bad as a missing
    # case; a baseline side with none means nothing was compared —
    # both must be visible in the exit code, never a vacuous pass
    n_missing = sum(v["verdict"] in ("missing-candidate",
                                     "no-candidate-samples")
                    for v in verdicts)
    n_incomp = sum(v["verdict"] in ("incomparable",
                                    "no-baseline-samples")
                   for v in verdicts)
    if n_reg or (n_missing and not allow_missing):
        exit_code = 1
    elif n_incomp:
        exit_code = 2
    else:
        exit_code = 0
    return {
        "gate_on": gate_on,
        "rel_tol": rel_tol,
        "noise_mult": noise_mult,
        "cases": verdicts,
        "regressed": n_reg,
        "improved": sum(v["verdict"] == "improved" for v in verdicts),
        "ok": sum(v["verdict"] == "ok" for v in verdicts),
        "missing": n_missing,
        "incomparable": n_incomp,
        "notes": notes,
        "exit_code": exit_code,
    }


# ------------------------------------------------------------- display

def print_report(report: dict, baseline: str, candidate: str,
                 out=print) -> None:
    out(f" perf gate: {candidate} vs baseline {baseline}")
    for note in report["notes"]:
        out(f"   note: {note}")
    out(" " + "-" * 76)
    out(f" {'VERDICT':<20} {'BASE med':>10} {'CAND med':>10} "
        f"{'DELTA':>8} {'TOL':>7}  CASE")
    def fmt(x, spec):
        return "" if x is None else format(x, spec)

    for v in report["cases"]:
        out(f" {v['verdict']:<20} "
            f"{fmt(v.get('baseline_median'), '.4g'):>10} "
            f"{fmt(v.get('candidate_median'), '.4g'):>10} "
            f"{fmt(v.get('delta_rel'), '+.1%'):>8} "
            f"{fmt(v.get('threshold'), '.1%'):>7}  "
            f"{v['case'][:70]}")
    out(" " + "-" * 76)
    out(f" {report['regressed']} regressed, {report['improved']} improved, "
        f"{report['ok']} ok, {report['missing']} missing, "
        f"{report['incomparable']} incomparable -> "
        f"{'PASS' if report['exit_code'] == 0 else 'FAIL'} "
        f"(exit {report['exit_code']})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Noise-aware bench regression gate "
                    "(candidate vs baseline capture JSON)")
    ap.add_argument("baseline", help="baseline capture JSON/JSONL")
    ap.add_argument("candidate", help="candidate capture JSON/JSONL")
    ap.add_argument("--rel-tol", type=float, default=0.1,
                    help="relative regression tolerance (default 0.10)")
    ap.add_argument("--noise-mult", type=float, default=3.0,
                    help="noise threshold = this * MAD/median of the "
                         "baseline samples (default 3)")
    ap.add_argument("--gate-on", default="auto",
                    choices=("auto", "value", "roofline_fraction",
                             "gflops_modeled"),
                    help="which higher-is-better number to gate on "
                         "(auto: roofline_fraction when every record "
                         "of a case embeds it, else value)")
    ap.add_argument("--allow-missing", action="store_true",
                    help="a baseline case missing from the candidate "
                         "does not fail the gate")
    ap.add_argument("--force", action="store_true",
                    help="compare across differing device_kind/"
                         "fallback environments anyway")
    ap.add_argument("--json", action="store_true",
                    help="print the machine-readable report to stdout")
    ap.add_argument("--report", default=None,
                    help="also write the JSON report to this path")
    args = ap.parse_args(argv)
    try:
        base_records = load_records(args.baseline)
        cand_records = load_records(args.candidate)
    except OSError as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 1
    report = gate(
        base_records, cand_records,
        rel_tol=args.rel_tol, noise_mult=args.noise_mult,
        gate_on=args.gate_on, allow_missing=args.allow_missing,
        force=args.force,
    )
    report["baseline"] = args.baseline
    report["candidate"] = args.candidate
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1)
    if args.json:
        print(json.dumps(report))
    else:
        print_report(report, args.baseline, args.candidate)
    return report["exit_code"]


if __name__ == "__main__":
    sys.exit(main())
