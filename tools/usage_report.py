"""dbcsr_tpu usage report: tenant cost rollup -> capacity estimate.

Reads the committed ``USAGE_ROLLUP.jsonl`` artifact or any file in
the same shape, and turns the attributed per-request device time plus
the serving SLO latency target into the number an on-call/capacity
planner actually wants: **sustainable requests/s per worker**.

    python tools/usage_report.py                       # ./USAGE_ROLLUP.jsonl
    python tools/usage_report.py --rollup path.jsonl --slo-ms 250
    python tools/usage_report.py --json

When a measured capacity certificate exists (``CAPACITY_CERT.json``,
written by ``tools/loadtest.py certify`` — override with ``--cert``),
the report cross-checks the analytic number against the measured one
side by side and **exits 3 when they diverge by more than 2×**: that
catches a stale analytic model (the workload changed under it) or a
broken replay (the measured number is nonsense) — either way a human
must look before trusting a capacity plan.  A degraded certificate is
reported but never cross-checked.

When a routed fleet certificate also exists (``FLEET_CERT.json``,
written by ``tools/loadtest.py fleet-certify``), the report adds the
fleet row: N-worker capacity vs the routed single-worker knee, the
scaling efficiency, and the failover leg's exactly-once verdict — and
**exits 3 when the fleet delivers under 1/MAX_DIVERGENCE of one routed
worker** (the router lost capacity outright) or claims more than
MAX_DIVERGENCE × N× it (the measurement is nonsense).

Artifact shape (one JSON object per line, ``kind`` discriminator):

    {"kind": "usage_meta",   "obs_schema": 5, "slo_target_ms": 500.0, ...}
    {"kind": "tenant_usage", "tenant": "alice", "device_seconds": ...,
     "flops": ..., "bytes_moved": ..., "saved_flops": ..., "requests": ...}
    {"kind": "usage_totals", "device_seconds": ..., "requests": ..., ...}

Capacity model (documented so the number is auditable, M/M/1 with an
exponential sojourn tail): mean service time ``s`` is the attributed
device-seconds per request; the p95 sojourn time of an M/M/1 queue is
``~ 3 s / (1 - rho)`` (``ln 20 ~= 3``), so holding p95 under the SLO
target ``T`` bounds utilization at ``rho = 1 - 3 s / T`` (clamped to
[0, 0.95]); the sustainable arrival rate per worker is then
``rho / s`` requests/s.  When the target cannot be met even unloaded
(``3 s >= T``) the report says so instead of printing a zero.

No dbcsr_tpu import — works on an artifact copied off another machine.
The SLO target falls back to ``DBCSR_TPU_SLO_SERVE_P95_MS`` (the same
knob the live SLO evaluator reads), default 500 ms.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_ROLLUP = "USAGE_ROLLUP.jsonl"
DEFAULT_CERT = "CAPACITY_CERT.json"
DEFAULT_FLEET_CERT = "FLEET_CERT.json"
DEFAULT_SLO_MS = 500.0
MAX_UTILIZATION = 0.95
P95_TAIL_FACTOR = 3.0  # ln(20): P(T > t) = exp(-t / E[T]) at p95
# analytic-vs-measured divergence past this factor exits non-zero:
# >2x apart means the model or the measurement is wrong, not noise
MAX_DIVERGENCE = 2.0


def read_rollup(path: str) -> dict:
    """{"meta": dict, "tenants": {name: row}, "totals": dict} from the
    typed-JSONL artifact; torn/unknown lines are skipped."""
    meta: dict = {}
    tenants: dict = {}
    totals: dict = {}
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            kind = rec.get("kind")
            if kind == "usage_meta":
                meta = rec
            elif kind == "tenant_usage":
                tenants[rec.get("tenant", "?")] = rec
            elif kind == "usage_totals":
                totals = rec
    return {"meta": meta, "tenants": tenants, "totals": totals}


def capacity(totals: dict, slo_ms: float) -> dict:
    """The capacity estimate from attributed totals + the SLO target
    (see the module docstring for the queueing model)."""
    requests = int(totals.get("requests") or 0)
    dev_s = float(totals.get("device_seconds") or 0.0)
    out: dict = {"slo_target_ms": slo_ms, "requests": requests,
                 "device_seconds": round(dev_s, 6)}
    if requests <= 0 or dev_s <= 0.0:
        out["feasible"] = False
        out["why"] = "no attributed requests in the rollup"
        return out
    service_s = dev_s / requests
    slo_s = slo_ms / 1e3
    out["mean_service_ms"] = round(service_s * 1e3, 4)
    rho = 1.0 - P95_TAIL_FACTOR * service_s / slo_s
    if rho <= 0.0:
        out["feasible"] = False
        out["why"] = (f"p95 target {slo_ms:g} ms is unreachable: even an "
                      f"unloaded worker's tail is ~"
                      f"{P95_TAIL_FACTOR * service_s * 1e3:.3f} ms")
        return out
    rho = min(rho, MAX_UTILIZATION)
    out["feasible"] = True
    out["utilization"] = round(rho, 4)
    out["req_per_s_per_worker"] = round(rho / service_s, 3)
    return out


def read_cert(path: str) -> dict | None:
    """The measured capacity certificate, or None when absent or not
    a certificate (the cross-check is strictly opt-in evidence)."""
    try:
        with open(path) as fh:
            cert = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(cert, dict) or cert.get("kind") != "capacity_cert":
        return None
    return cert


def cross_check(cap: dict, cert: dict) -> dict:
    """Analytic vs measured, side by side.  ``diverged`` is True when
    both numbers exist and sit more than MAX_DIVERGENCE apart."""
    measured = cert.get("value")
    analytic = cap.get("req_per_s_per_worker")
    out = {
        "measured_req_per_s": measured,
        "analytic_req_per_s": analytic,
        "certificate_degraded": bool(cert.get("degraded")),
        "device_kind": cert.get("device_kind"),
        "certified_rate_x": cert.get("certified_rate_x"),
        "diverged": False,
    }
    if cert.get("degraded") or not measured or not analytic:
        return out
    ratio = max(measured / analytic, analytic / measured)
    out["ratio"] = round(ratio, 3)
    out["diverged"] = ratio > MAX_DIVERGENCE
    return out


def fleet_check(cert: dict, fleet_cert: dict) -> dict:
    """Per-worker measured capacity vs the routed-fleet measurement
    (``tools/loadtest.py fleet-certify``).  The hard check uses the
    fleet certificate's OWN routed single-worker knee (same harness,
    same operating point): the fleet must deliver at least ``single /
    MAX_DIVERGENCE`` (co-located workers legitimately contend for the
    same cores, so N workers may not beat one — but losing more than
    half of one worker's capacity means the router itself is the
    bottleneck) and at most MAX_DIVERGENCE × workers × it (more means
    the measurement is nonsense).  The in-process per-worker certificate
    (``CAPACITY_CERT.json``) is reported alongside as the routing
    overhead — informational, the harnesses are not comparable
    enough to gate on.  A degraded fleet certificate (any leg
    unclean, including the failover leg's exactly-once audit) is
    reported, never cross-checked."""
    workers = int(fleet_cert.get("workers") or 0)
    single_routed = fleet_cert.get("single_worker_rps")
    fleet = fleet_cert.get("value")
    inproc = cert.get("value")
    out = {
        "workers": workers,
        "fleet_req_per_s": fleet,
        "single_routed_req_per_s": single_routed,
        "inproc_per_worker_req_per_s": inproc,
        "routing_overhead": (round(inproc / single_routed, 3)
                            if inproc and single_routed else None),
        "scaling_efficiency": fleet_cert.get("scaling_efficiency"),
        "failover_clean": (fleet_cert.get("failover_leg") or {}).get(
            "clean"),
        "certificate_degraded": bool(fleet_cert.get("degraded")),
        "diverged": False,
    }
    if (fleet_cert.get("degraded") or not fleet or not single_routed
            or not workers):
        return out
    out["diverged"] = (fleet < single_routed / MAX_DIVERGENCE
                       or fleet > MAX_DIVERGENCE * workers
                       * single_routed)
    return out


def report(rollup: dict, slo_ms: float, cert: dict | None = None,
           fleet_cert: dict | None = None) -> dict:
    totals = rollup["totals"]
    tenants = rollup["tenants"]
    cap = capacity(totals, slo_ms)
    total_dev = float(totals.get("device_seconds") or 0.0)
    rows = []
    for name, row in sorted(tenants.items(),
                            key=lambda kv: -float(
                                kv[1].get("device_seconds") or 0.0)):
        dev = float(row.get("device_seconds") or 0.0)
        rows.append({
            "tenant": name,
            "device_seconds": round(dev, 6),
            "share": round(dev / total_dev, 4) if total_dev else 0.0,
            "requests": int(row.get("requests") or 0),
            "flops": int(row.get("flops") or 0),
            "bytes_moved": int(row.get("bytes_moved") or 0),
            "saved_flops": int(row.get("saved_flops") or 0),
        })
    rep = {"meta": rollup["meta"], "tenants": rows, "totals": totals,
           "capacity": cap}
    if cert is not None:
        rep["cross_check"] = cross_check(cap, cert)
    if fleet_cert is not None:
        rep["fleet_check"] = fleet_check(cert or {}, fleet_cert)
    return rep


def render(rep: dict, out=print) -> None:
    meta = rep.get("meta") or {}
    out(" dbcsr_tpu usage report"
        + (f"  (rollup {meta['ts']})" if meta.get("ts") else ""))
    rows = rep["tenants"]
    if rows:
        out(f"   {'tenant':<20} {'dev_s':>12} {'share':>7} {'reqs':>6} "
            f"{'flops':>14} {'moved_MB':>9} {'saved_flops':>12}")
        for r in rows:
            out(f"   {r['tenant']:<20} {r['device_seconds']:>12.6f} "
                f"{r['share']:>6.1%} {r['requests']:>6} "
                f"{r['flops']:>14} {r['bytes_moved'] / 1e6:>9.2f} "
                f"{r['saved_flops']:>12}")
    else:
        out("   (no tenant rows in the rollup)")
    cap = rep["capacity"]
    out(f" slo target: p95 <= {cap['slo_target_ms']:g} ms")
    if cap.get("feasible"):
        out(f" capacity: ~{cap['req_per_s_per_worker']:g} req/s per worker "
            f"(mean attributed service {cap['mean_service_ms']:g} ms, "
            f"utilization cap {cap['utilization']:.0%})")
    else:
        out(f" capacity: n/a — {cap.get('why', '?')}")
    xc = rep.get("cross_check")
    if xc:
        line = (f" measured:  {xc['measured_req_per_s']:g} req/s per "
                f"worker (certificate"
                + (f", {xc['device_kind']}" if xc.get("device_kind")
                   else "") + ")")
        if xc["certificate_degraded"]:
            line += " DEGRADED — not cross-checked"
        elif xc.get("ratio") is not None:
            line += (f" — {xc['ratio']:g}x "
                     + ("apart: DIVERGED (model stale or replay "
                        "broken)" if xc["diverged"] else
                        "apart: consistent"))
        out(line)
    fc = rep.get("fleet_check")
    if fc:
        line = (f" fleet:     {fc['fleet_req_per_s']:g} req/s across "
                f"{fc['workers']} workers")
        if fc.get("scaling_efficiency") is not None:
            line += f" ({fc['scaling_efficiency']:.0%} of {fc['workers']}x)"
        if fc["certificate_degraded"]:
            line += " DEGRADED — not cross-checked"
        elif fc.get("single_routed_req_per_s"):
            line += (", DIVERGED (router bottleneck or stale cert)"
                     if fc["diverged"] else ", consistent")
        if fc.get("failover_clean") is False:
            line += "; failover leg UNCLEAN"
        out(line)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--rollup", default=DEFAULT_ROLLUP,
                    help="usage rollup JSONL (default USAGE_ROLLUP.jsonl)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="p95 latency target in ms (default: the "
                         "artifact's stamp, else DBCSR_TPU_SLO_SERVE_"
                         f"P95_MS, else {DEFAULT_SLO_MS:g})")
    ap.add_argument("--cert", default=DEFAULT_CERT,
                    help="measured capacity certificate "
                         "(tools/loadtest.py certify; skipped silently "
                         "when absent)")
    ap.add_argument("--fleet-cert", default=DEFAULT_FLEET_CERT,
                    help="routed fleet certificate (tools/loadtest.py "
                         "fleet-certify; skipped silently when absent)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable report")
    args = ap.parse_args(argv)
    try:
        rollup = read_rollup(args.rollup)
    except OSError as exc:
        print(f"usage_report: cannot read {args.rollup!r}: {exc}",
              file=sys.stderr)
        return 2
    if not rollup["totals"] and not rollup["tenants"]:
        print(f"usage_report: no usage records in {args.rollup!r}",
              file=sys.stderr)
        return 2
    slo_ms = args.slo_ms
    if slo_ms is None:
        slo_ms = rollup["meta"].get("slo_target_ms")
    if slo_ms is None:
        try:
            slo_ms = float(os.environ.get("DBCSR_TPU_SLO_SERVE_P95_MS",
                                          DEFAULT_SLO_MS))
        except ValueError:
            slo_ms = DEFAULT_SLO_MS
    cert = read_cert(args.cert)
    fleet_cert = read_cert(args.fleet_cert)
    rep = report(rollup, float(slo_ms), cert=cert,
                 fleet_cert=fleet_cert)
    if args.as_json:
        print(json.dumps(rep, default=str))
    else:
        render(rep)
    if (rep.get("cross_check") or {}).get("diverged"):
        print(f"usage_report: analytic and measured capacity diverge "
              f"by >{MAX_DIVERGENCE:g}x — capacity plan untrustworthy "
              f"until a human reconciles them", file=sys.stderr)
        return 3
    if (rep.get("fleet_check") or {}).get("diverged"):
        print("usage_report: routed fleet capacity is inconsistent "
              "with its per-worker measurement — router bottleneck "
              "or stale certificate; re-run tools/loadtest.py "
              "fleet-certify", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
