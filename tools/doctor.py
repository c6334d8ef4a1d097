"""dbcsr_tpu doctor: one diagnosis of a live job or its artifacts.

The CLI reader of the live ops plane (`dbcsr_tpu.obs`): points at a
running process's introspection endpoint (``DBCSR_TPU_OBS_PORT``) or
at the artifacts a finished/killed run left on disk, and prints what
an on-call engineer needs first — per-component health, breaker and
watchdog state, the multiplies that caused the recompile/fallback
churn, per-driver roofline fractions, and runbook pointers
(docs/resilience.md) for every active anomaly.

Live mode (reads ``/healthz``, ``/metrics``, ``/events``, ``/flight``):

    python tools/doctor.py --url http://127.0.0.1:9100
    python tools/doctor.py --port 9100          # localhost shorthand

Artifact mode (any subset; shard bases expand like DBCSR_TPU_TRACE):

    python tools/doctor.py --events events.jsonl --trace trace.jsonl \\
        --probe watchdog.jsonl --captures bench_rows.jsonl

Trend mode (``--trend``): sparkline history tables per telemetry cell
and the SLO burn summary, from a live endpoint's ``/timeseries`` +
``/slo`` routes or from committed time-series shard artifacts
(``--timeseries``, default ``timeseries.jsonl``; the committed
``TELEMETRY_ROLLUP.jsonl`` works too):

    python tools/doctor.py --port 9100 --trend
    python tools/doctor.py --trend --timeseries TELEMETRY_ROLLUP.jsonl

Diagnose mode (``--diagnose``): the causal diagnosis plane's ranked
root-cause reports — change-point, ranked candidate causes off the
change ledger, and the profile-baseline diff that localizes the
regressed phase.  Reads a live endpoint's ``/rca`` route, an incident
bundle's ``rca`` record (``--bundle``), or the committed
``RCA_CERT.json`` (``--rca-cert``):

    python tools/doctor.py --port 9100 --diagnose
    python tools/doctor.py --diagnose --rca-cert RCA_CERT.json

With no arguments the doctor looks for the default artifact names in
the current directory.  ``--json`` emits the report machine-readable;
``--selftest`` runs the full pipeline offline against synthetic events
plus the committed bench artifacts and exits 0 — the tier-1 CI smoke.

No dbcsr_tpu import in artifact mode (works on files copied off
another machine); live mode is stdlib urllib.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import sys
import time

RUNBOOK = "docs/resilience.md"
SERVE_RUNBOOK = "docs/serving.md"

# anomaly kind -> (one-line action, runbook anchor); anchors starting
# with "docs/" are full runbook paths (the serving plane's hints live
# in docs/serving.md, everything else in docs/resilience.md)
HINTS = {
    "recompile_storm": (
        "new shapes are arriving every multiply and XLA is recompiling "
        "for each; bucket/pad the block sizes or pin the workload's "
        "shape set", "#anomaly-recompile-storm"),
    "fallback_storm": (
        "a quarantined driver keeps being re-routed; check the open "
        "breakers below and the driver chain", "#anomaly-fallback-storm"),
    "dispatch_latency_spike": (
        "a multiply ran far over the rolling median; check for a "
        "recompile, host contention or a device stall",
        "#anomaly-dispatch-latency-spike"),
    "roofline_collapse": (
        "a driver's achieved fraction of roofline dropped below half "
        "its window median; the device is throttled or the host is "
        "starving it", "#anomaly-roofline-collapse"),
    "breaker_open": (
        "a (driver, shape) is quarantined; the chain re-routes it — "
        "fix the kernel or force a safe driver",
        "#driver-failover--circuit-breakers"),
    "wedge_streak": (
        "a guarded channel is not answering; backoff is "
        "exponential — find what it waits on before resetting anything",
        "#watchdog"),
    "checksum_corruption": (
        "a checksum retry classified deterministic/unstable: proven "
        "numeric corruption — quarantine the driver and capture the "
        "flight dump", "#checksum-gate-one-shot-safe-driver-retry"),
    "shed_storm": (
        "the serving plane is rejecting a large fraction of "
        "submissions; raise quotas/queue bound, add capacity, or check "
        "the health verdict driving admission",
        SERVE_RUNBOOK + "#shed-storms"),
    "serve_shed": (
        "submissions are being shed; the per-tenant reasons below say "
        "whether it is health-driven (critical), quota pressure, or a "
        "full queue", SERVE_RUNBOOK + "#admission-control"),
    "serve_deadline": (
        "queued requests are expiring before execution; shorten the "
        "coalescing window, raise worker capacity, or relax deadlines",
        SERVE_RUNBOOK + "#deadlines--the-watchdog-outcome-classes"),
    "incremental_degrade": (
        "the delta-aware incremental multiply breaker opened after "
        "repeated probe/fault failures and the plane degraded to full "
        "recompute; inspect the abft_mismatch events, then reset with "
        "DBCSR_TPU_INCREMENTAL=off->auto or a process restart",
        "#incremental-multiply--product-cache"),
    "abft_mismatch": (
        "an ABFT probe checksum disagreed: the device produced a wrong "
        "but FINITE answer (silent data corruption) — the engine "
        "recovered it, but repeated mismatches from one driver mean "
        "the corruption tracks that driver",
        "#abft-probe-checksums"),
    "sdc_critical": (
        "repeated SDC from one driver — deterministic corruption, not "
        "a particle strike; quarantine the driver (force a safe "
        "driver) and capture the flight dump",
        "#runbook-silent-data-corruption"),
    "chain_rollback": (
        "an iterative chain's per-step invariant failed; the iterate "
        "rolled back to its checkpoint and recomputed on the safe "
        "engine — check which driver the underlying multiplies used",
        "#chain-checkpoint-and-rollback"),
    "serve_drain": (
        "the serving plane drained: admission closed, queued requests "
        "journaled; restart the process with DBCSR_TPU_SERVE_JOURNAL "
        "pinned to the same path to replay them exactly once",
        SERVE_RUNBOOK + "#drain--restart"),
    "slo_burn": (
        "an objective is burning its error budget on BOTH the short "
        "and long windows — sustained, not a spike; shed load, raise "
        "capacity, or roll back the regressing change",
        "docs/observability.md#slo-objectives--error-budget-burn"),
    "lint_findings": (
        "the tree violates its own contracts (mutation-epoch, "
        "donation, lock, knob/site/metric registry invariants); run "
        "`python -m tools.lint` and fix or suppress-with-reason "
        "before trusting any capture from this tree",
        "docs/static_analysis.md#rule-catalog"),
    "tune_demotion": (
        "the online tuner demoted a promoted parameter row: its live "
        "roofline cell regressed after promotion (workload shift, "
        "device throttle, or a trial that measured an unrepresentative "
        "stack) — the displaced row is restored; check the ledger's "
        "trial stats before re-tuning the cell",
        "docs/autotuning.md#demotion-on-regression"),
    "tune_trial_failures": (
        "tuning trials keep failing; the tuner is deferring but "
        "burning cycles — check the trial watchdog channel "
        "(tune_trial) and the last_error in the tune health component",
        "docs/autotuning.md#runbook-failing-trials"),
    "tenant_hotspot": (
        "one tenant dominates the attributed device time; check its "
        "request mix and quotas (and `tools/usage_report.py` for the "
        "capacity math) before adding capacity for everyone",
        SERVE_RUNBOOK + "#usage-metering--capacity-planning"),
    "incident_captured": (
        "the process auto-captured incident bundle(s) on an "
        "anomaly/SLO rising edge; render one offline with "
        "`python tools/doctor.py --bundle incidents/<file>.jsonl`",
        "docs/observability.md#incident-bundles"),
    "worker_down": (
        "the fleet router declared a worker DOWN (missed heartbeats "
        "past the suspicion threshold or its process exited); its "
        "sessions fail over to a surviving peer — check the worker's "
        "own endpoint/journal before respawning",
        SERVE_RUNBOOK + "#runbook-worker-down"),
    "failover_replay": (
        "a dead/drained worker's journal was replayed on a peer; "
        "every request id lands exactly once fleet-wide (ledger-"
        "deduplicated) — audit the router's ledger if counts look off",
        SERVE_RUNBOOK + "#exactly-once-failover"),
    "capacity_regression": (
        "the committed capacity certificate is degraded or disagrees "
        "with the live usage meter by >2x; re-run `python tools/"
        "loadtest.py certify` against the committed trace and "
        "re-commit CAPACITY_CERT.json only if the change is real",
        "docs/loadtest.md#capacity-certification"),
}

# the telemetry cells --trend tables by default (history worth eyes:
# per-driver roofline, the autotune evidence cells, serve load/latency,
# breaker states, SLO burn, health status)
TREND_METRICS = (
    "dbcsr_tpu_roofline_fraction",
    "dbcsr_tpu_cell_flops_total",
    "dbcsr_tpu_precision_cell_demoted",
    "dbcsr_tpu_precision_promotions_total",
    "dbcsr_tpu_tune_promotions_total",
    "dbcsr_tpu_params_generation",
    "dbcsr_tpu_serve_queue_depth",
    "dbcsr_tpu_serve_latency_p95_ms",
    "dbcsr_tpu_serve_shed_total",
    "dbcsr_tpu_breaker_state",
    "dbcsr_tpu_abft_mismatches_total",
    "dbcsr_tpu_slo_burn_rate",
    "dbcsr_tpu_health_status",
)


# --------------------------------------------------------- prometheus

def parse_prometheus(text: str) -> dict:
    """{metric: [(labels dict, value)]} from text exposition."""
    out: dict = collections.defaultdict(list)
    pat = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")
    lab = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="([^"]*)"')
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = pat.match(line)
        if m is None:
            continue
        labels = dict(lab.findall(m.group(2) or ""))
        try:
            val = float(m.group(3))
        except ValueError:
            continue
        out[m.group(1)].append((labels, val))
    return dict(out)


# ------------------------------------------------------------- inputs

def _read_jsonl(path: str) -> list:
    recs = []
    try:
        with open(path) as fh:
            for line in fh:
                try:
                    recs.append(json.loads(line))
                except ValueError:
                    continue  # torn tail line
    except OSError:
        return []
    return recs


def expand_shards(base: str) -> list:
    """A shard base (``events.jsonl``) expands to its ``p*`` shards; a
    concrete file (or glob) stays itself.  Delegates to the ONE
    sharding-contract implementation (`tools/trace_merge.py` — skips
    unsettled ``.ptmp*`` shards, drops chrome exports)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_merge

    return trace_merge.expand_shards([base])


def fetch_live(url: str, timeout: float = 10.0) -> dict:
    """Pull /healthz /metrics /events /flight (+ /usage /rca when the
    endpoint is new enough) off a live endpoint."""
    import urllib.error
    import urllib.request

    def get(route):
        try:
            with urllib.request.urlopen(url.rstrip("/") + route,
                                        timeout=timeout) as r:
                return r.read().decode()
        except urllib.error.HTTPError as e:  # 503 CRITICAL still has a body
            return e.read().decode()

    live = {
        "health": json.loads(get("/healthz")),
        "metrics_text": get("/metrics"),
        "events": json.loads(get("/events")),
        "flight": json.loads(get("/flight")),
        "usage": None,
        "rca": None,
    }
    try:  # pre-v5 endpoints have no /usage route
        usage = json.loads(get("/usage"))
        if isinstance(usage, dict) and "tenants" in usage:
            live["usage"] = usage
    except ValueError:
        pass
    try:  # pre-v7 endpoints have no /rca route
        rca = json.loads(get("/rca?limit=8"))
        if isinstance(rca, dict) and "reports" in rca:
            live["rca"] = rca
    except ValueError:
        pass
    return live


def read_bundle(path: str) -> dict:
    """Parse an incident bundle (`dbcsr_tpu.obs.incidents`, typed JSONL
    with a ``rec`` discriminator) back into analyze()'s inputs."""
    out: dict = {"meta": {}, "health": None, "sample": None,
                 "usage": None, "rca": None, "events": [], "flight": []}
    for rec in _read_jsonl(path):
        kind = rec.get("rec")
        if kind == "meta":
            out["meta"] = rec
        elif kind in ("health", "sample", "usage", "rca"):
            out[kind] = rec.get(kind)
        elif kind == "event":
            out["events"].append(rec)
        elif kind == "flight":
            out["flight"].append(rec)
    return out


def usage_from_rollup(path: str) -> dict | None:
    """The committed USAGE_ROLLUP.jsonl artifact re-shaped into the
    `/usage` endpoint's dict (delegates to `tools/usage_report.py` —
    the ONE reader of that artifact)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import usage_report

    try:
        rollup = usage_report.read_rollup(path)
    except OSError:
        return None
    if not rollup["tenants"] and not rollup["totals"]:
        return None
    return {"tenants": rollup["tenants"], "totals": rollup["totals"]}


# ----------------------------------------------------------- analysis

def analyze(health: dict | None, prom: dict, events: list,
            flight: list, probe: list, captures: list,
            top: int = 5, usage: dict | None = None,
            capacity: dict | None = None) -> dict:
    """Fold every available signal into one report dict (the renderer
    and --json both consume this)."""
    report: dict = {"health": health, "hints": []}

    # breakers: live gauge wins; else reconstruct last state per
    # (driver, shape) from breaker_transition events
    breakers = {}
    for labels, v in prom.get("dbcsr_tpu_breaker_state", []):
        state = {0: "closed", 1: "half_open", 2: "open"}.get(int(v), "?")
        breakers[f"{labels.get('driver')}|{labels.get('shape')}"] = state
    if not breakers:
        for e in events:
            if e.get("event") == "breaker_transition":
                breakers[f"{e.get('driver')}|{e.get('shape')}"] = e.get("to")
    report["breakers"] = breakers
    open_breakers = {k: s for k, s in breakers.items()
                     if s in ("open", "half_open")}
    if open_breakers:
        report["hints"].append(_hint("breaker_open", detail=", ".join(
            sorted(open_breakers))))

    # watchdog: live gauge, else the LAST persisted record per
    # channel (a `Watchdog(state_path=...)` JSONL)
    watchdog = {}
    for labels, v in prom.get("dbcsr_tpu_watchdog_wedge_streak", []):
        watchdog[labels.get("name", "?")] = {"wedge_streak": int(v)}
    for rec in probe:
        name = rec.get("name", "?")
        watchdog[name] = {
            "wedge_streak": int(rec.get("wedge_streak", 0)),
            "streak": int(rec.get("streak", 0)),
            "outcome": rec.get("outcome"), "ts": rec.get("ts"),
        }
    report["watchdog"] = watchdog
    wedged = {n: w for n, w in watchdog.items()
              if w.get("wedge_streak", 0) >= 1}
    if wedged:
        report["hints"].append(_hint("wedge_streak", detail=", ".join(
            f"{n} (streak {w['wedge_streak']})"
            for n, w in sorted(wedged.items()))))

    # offenders: events grouped by product_id (the correlation payoff —
    # "which multiplies caused the churn")
    def offenders(kind):
        by_product: dict = collections.Counter()
        for e in events:
            if e.get("event") == kind:
                by_product[e.get("product_id") or "<no product>"] += 1
        return by_product.most_common(top)

    report["offenders"] = {
        "recompiles": offenders("jit_compile"),
        "fallbacks": offenders("driver_failover"),
        "failures": offenders("driver_failure"),
        "faults_injected": offenders("fault_injected"),
    }
    # name the offender products where the events carry the context
    names = {}
    for e in events:
        if e.get("event") in ("multiply_begin", "multiply_end") \
                and e.get("product_id"):
            ent = names.setdefault(e["product_id"], {})
            for f in ("name", "mnk", "dur_ms", "algorithm", "error"):
                if e.get(f) is not None:
                    ent[f] = e[f]
    for r in flight:
        if r.get("product_id"):
            names.setdefault(r["product_id"], {}).update(
                {f: r.get(f) for f in ("name", "mnk", "dur_ms", "error")
                 if r.get(f) is not None})
    report["products"] = names

    # roofline per driver: live gauges, else the latest capture rows'
    # embedded modeled block
    roofline = {}
    for labels, v in prom.get("dbcsr_tpu_roofline_fraction", []):
        roofline[labels.get("driver", "?")] = round(v, 5)
    if not roofline:
        for row in captures:
            modeled = row.get("modeled") or {}
            frac = modeled.get("roofline_fraction")
            if frac is not None:
                key = row.get("algorithm") or row.get("metric", "?")[:40]
                roofline[key] = round(float(frac), 5)
    report["roofline"] = roofline

    # memory pool: live counters/gauge (prometheus), else the health
    # verdict's perf-component pool block
    pool = {}
    for kind in ("hits", "misses", "returns", "evictions"):
        vals = prom.get(f"dbcsr_tpu_pool_{kind}_total")
        if vals:
            pool[kind] = int(sum(v for _, v in vals))
    held = prom.get("dbcsr_tpu_pool_bytes_held")
    if held:
        pool["bytes_held"] = int(held[-1][1])
    for kind in ("h2d", "d2h"):
        vals = prom.get(f"dbcsr_tpu_{kind}_bytes_total")
        if vals:
            pool[f"{kind}_bytes"] = int(sum(v for _, v in vals))
    if not pool and health:
        pool = ((health.get("components") or {}).get("perf") or {}) \
            .get("pool") or {}
    report["pool"] = pool

    # value reuse: the delta-aware incremental multiply plane and the
    # serve-layer content-addressed product cache
    reuse: dict = {}
    inc_outcomes = collections.Counter()
    for labels, v in prom.get("dbcsr_tpu_incremental_total", []):
        inc_outcomes[labels.get("result", "?")] += int(v)
    if inc_outcomes:
        reuse["incremental"] = dict(inc_outcomes)
    saved = prom.get("dbcsr_tpu_incremental_saved_flops_total")
    if saved:
        reuse["incremental_saved_flops"] = int(sum(v for _, v in saved))
    pc_outcomes = collections.Counter()
    for labels, v in prom.get("dbcsr_tpu_product_cache_total", []):
        pc_outcomes[labels.get("result", "?")] += int(v)
    if pc_outcomes:
        reuse["product_cache"] = dict(pc_outcomes)
    pcb = [v for labels, v in
           prom.get("dbcsr_tpu_product_cache_bytes", [])
           if not labels.get("tenant")]
    if pcb:
        reuse["product_cache_bytes"] = int(pcb[-1])
    if reuse:
        report["value_reuse"] = reuse
    degr = prom.get("dbcsr_tpu_incremental_degrade_total")
    if degr and sum(v for _, v in degr):
        report["hints"].append(_hint(
            "incremental_degrade",
            detail=f"{int(sum(v for _, v in degr))} breaker open(s)"))

    # serving plane: live counters/gauge first (prometheus), else the
    # serve_* bus events — queue depth, per-tenant shed/admit, and the
    # top deadline-miss offenders by tenant
    serving: dict = {"tenants": {}}
    depth = prom.get("dbcsr_tpu_serve_queue_depth")
    if depth:
        serving["queue_depth"] = int(depth[-1][1])
    for labels, v in prom.get("dbcsr_tpu_serve_requests_total", []):
        t = labels.get("tenant", "?")
        serving["tenants"].setdefault(t, collections.Counter())[
            labels.get("outcome", "?")] += int(v)
    for labels, v in prom.get("dbcsr_tpu_serve_shed_total", []):
        serving.setdefault("shed_reasons", collections.Counter())[
            labels.get("reason", "?")] += int(v)
    for labels, v in prom.get("dbcsr_tpu_serve_deadline_missed_total", []):
        serving["tenants"].setdefault(
            labels.get("tenant", "?"),
            collections.Counter())["deadline_missed"] += int(v)
    if not serving["tenants"]:
        ev_outcome = {"serve_admitted": "admitted", "serve_shed": "shed",
                      "serve_deadline_missed": "deadline_missed",
                      "serve_done": "done", "serve_failed": "failed"}
        for e in events:
            outcome = ev_outcome.get(e.get("event"))
            if outcome is None:
                continue
            t = e.get("tenant", "?")
            serving["tenants"].setdefault(t, collections.Counter())[
                outcome] += 1
            if outcome == "shed":
                serving.setdefault("shed_reasons", collections.Counter())[
                    e.get("reason", "?")] += 1
    serving["tenants"] = {t: dict(c) for t, c in
                          serving["tenants"].items() if c}
    if "shed_reasons" in serving:
        serving["shed_reasons"] = dict(serving["shed_reasons"])
    serving["deadline_offenders"] = sorted(
        ((t, c["deadline_missed"]) for t, c in serving["tenants"].items()
         if c.get("deadline_missed")),
        key=lambda kv: -kv[1])[:top]
    if serving["tenants"] or "queue_depth" in serving:
        report["serving"] = serving
        total_shed = sum(c.get("shed", 0)
                         for c in serving["tenants"].values())
        if total_shed:
            report["hints"].append(_hint("serve_shed", detail=", ".join(
                f"{k}={v}" for k, v in sorted(
                    (serving.get("shed_reasons") or {}).items()))))
        if serving["deadline_offenders"]:
            report["hints"].append(_hint("serve_deadline", detail=", ".join(
                f"{t} ({n})" for t, n in serving["deadline_offenders"])))

    # integrity plane: live ABFT/rollback counters first (prometheus),
    # else the abft_mismatch / chain_rollback / serve_drain bus events
    integrity: dict = {"mismatches": {}, "rollbacks": 0}
    checks = prom.get("dbcsr_tpu_abft_checks_total")
    if checks:
        integrity["checks"] = int(sum(v for _, v in checks))
    for labels, v in prom.get("dbcsr_tpu_abft_mismatches_total", []):
        d = labels.get("driver", "?")
        integrity["mismatches"][d] = \
            integrity["mismatches"].get(d, 0) + int(v)
    for labels, v in prom.get("dbcsr_tpu_abft_recoveries_total", []):
        integrity["recoveries"] = integrity.get("recoveries", 0) + int(v)
    rb = prom.get("dbcsr_tpu_chain_rollback_total")
    if rb:
        integrity["rollbacks"] = int(sum(v for _, v in rb))
    dr = prom.get("dbcsr_tpu_serve_drain_total")
    if dr:
        integrity["drains"] = int(sum(v for _, v in dr))
    rp = prom.get("dbcsr_tpu_serve_journal_replayed_total")
    if rp:
        integrity["replayed"] = int(sum(v for _, v in rp))
    if not integrity["mismatches"] and not integrity["rollbacks"]:
        for e in events:
            if e.get("event") == "abft_mismatch":
                d = e.get("driver", "?")
                integrity["mismatches"][d] = \
                    integrity["mismatches"].get(d, 0) + 1
            elif e.get("event") == "chain_rollback":
                integrity["rollbacks"] += 1
            elif e.get("event") == "serve_drain":
                integrity["drains"] = integrity.get("drains", 0) + 1
            elif e.get("event") == "serve_replayed":
                integrity["replayed"] = integrity.get("replayed", 0) + 1
    sdc_total = sum(integrity["mismatches"].values())
    if sdc_total or integrity["rollbacks"] or integrity.get("drains") \
            or "checks" in integrity:
        report["integrity"] = integrity
    if sdc_total:
        report["hints"].append(_hint("abft_mismatch", detail=", ".join(
            f"{d}={n}" for d, n in sorted(integrity["mismatches"].items()))))
    repeat = {d: n for d, n in integrity["mismatches"].items() if n >= 3}
    if repeat:
        report["hints"].append(_hint("sdc_critical", detail=", ".join(
            f"{d} ({n}x)" for d, n in sorted(repeat.items()))))
    if integrity["rollbacks"]:
        report["hints"].append(_hint(
            "chain_rollback", detail=f"{integrity['rollbacks']} rollback(s)"))
    if integrity.get("drains"):
        report["hints"].append(_hint("serve_drain", detail=(
            f"{integrity['drains']} drain(s), "
            f"{integrity.get('replayed', 0)} replayed")))

    # autotuner plane: live counters first (prometheus), else the
    # tune_promotion / tune_demotion / tune_trial bus events; the
    # health verdict's tune component carries queue depth and streaks
    tune: dict = {}
    tr = collections.Counter()
    for labels, v in prom.get("dbcsr_tpu_tune_trials_total", []):
        tr[labels.get("outcome", "?")] += int(v)
    for labels, v in prom.get("dbcsr_tpu_tune_promotions_total", []):
        tune["promotions"] = tune.get("promotions", 0) + int(v)
    dem = collections.Counter()
    for labels, v in prom.get("dbcsr_tpu_tune_demotions_total", []):
        dem[labels.get("reason", "?")] += int(v)
    if not tr and not tune and not dem:
        for e in events:
            if e.get("event") == "tune_trial":
                tr[e.get("outcome", "?")] += 1
            elif e.get("event") == "tune_promotion":
                tune["promotions"] = tune.get("promotions", 0) + 1
            elif e.get("event") == "tune_demotion":
                dem[e.get("reason", "?")] += 1
    if tr:
        tune["trials"] = dict(tr)
    if dem:
        tune["demotions"] = dict(dem)
    if health:
        tcomp = (health.get("components") or {}).get("tune") or {}
        for f in ("queue_depth", "params_generation", "running"):
            if tcomp.get(f) is not None:
                tune[f] = tcomp[f]
    if tune:
        report["tune"] = tune
    if dem:
        report["hints"].append(_hint("tune_demotion", detail=", ".join(
            f"{r}={n}" for r, n in sorted(dem.items()))))
    failed = sum(n for o, n in tr.items()
                 if o in ("failed", "faulted", "wedged"))
    if failed >= 3:
        report["hints"].append(_hint(
            "tune_trial_failures",
            detail=f"{failed} non-OK trial(s): " + ", ".join(
                f"{o}={n}" for o, n in sorted(tr.items()))))

    # storage-format planner plane: decision counters by (format, reason)
    decisions = collections.Counter()
    for labels, v in prom.get("dbcsr_tpu_format_decision_total", []):
        decisions[f"{labels.get('format', '?')}/"
                  f"{labels.get('reason', '?')}"] += int(v)
    if decisions:
        report["format_planner"] = {"decisions": dict(decisions)}

    # SLO burn: the live verdict's slo component first, else slo_burn
    # bus events (the telemetry history plane, obs/slo.py)
    slo_burning: dict = {}
    if health:
        slo_comp = (health.get("components") or {}).get("slo") or {}
        for name, row in (slo_comp.get("objectives") or {}).items():
            if row.get("status") == "BURNING":
                slo_burning[name] = row.get("burn")
    for e in events:
        if e.get("event") == "slo_burn":
            slo_burning.setdefault(e.get("objective", "?"), e.get("burn"))
    if slo_burning:
        report["slo_burning"] = slo_burning
        report["hints"].append(_hint("slo_burn", detail=", ".join(
            f"{n} ({b}x)" for n, b in sorted(slo_burning.items()))))

    # tenant cost attribution: the /usage dict (live), an incident
    # bundle's usage section, or the committed USAGE_ROLLUP.jsonl
    # re-shaped by usage_from_rollup — else the tenant meter counters
    if usage is None:
        meters: dict = {}
        meter_keys = (("dbcsr_tpu_tenant_device_seconds_total",
                       "device_seconds"),
                      ("dbcsr_tpu_tenant_flops_total", "flops"),
                      ("dbcsr_tpu_tenant_bytes_moved_total", "bytes_moved"),
                      ("dbcsr_tpu_tenant_saved_flops_total", "saved_flops"))
        for metric, field in meter_keys:
            for labels, v in prom.get(metric, []):
                meters.setdefault(labels.get("tenant", "?"), {})[field] = v
        if meters:
            usage = {"tenants": meters, "totals": {}}
    if usage and usage.get("tenants"):
        rows = {t: {
            "device_seconds": float(r.get("device_seconds") or 0.0),
            "flops": int(r.get("flops") or 0),
            "bytes_moved": int(r.get("bytes_moved") or 0),
            "saved_flops": int(r.get("saved_flops") or 0),
            "requests": int(r.get("requests") or 0),
        } for t, r in usage["tenants"].items()}
        report["usage"] = {"tenants": rows,
                           "totals": dict(usage.get("totals") or {})}
        total_dev = sum(r["device_seconds"] for r in rows.values())
        named = {t: r for t, r in rows.items() if t != "(evicted)"}
        if total_dev > 0 and len(named) >= 2:
            hot, row = max(named.items(),
                           key=lambda kv: kv[1]["device_seconds"])
            share = row["device_seconds"] / total_dev
            if share >= 0.6:
                report["hints"].append(_hint(
                    "tenant_hotspot",
                    detail=f"{hot} holds {share:.0%} of attributed "
                           f"device time"))

    # measured serve capacity: the committed CAPACITY_CERT.json
    # (tools/loadtest.py).  A degraded certificate, or one that
    # disagrees with the analytic M/M/1 number derived from the usage
    # totals by >2x, earns the capacity_regression hint — same
    # divergence bar as `tools/usage_report.py --cert`.
    if capacity and capacity.get("kind") == "capacity_cert":
        report["capacity"] = {k: capacity.get(k) for k in (
            "value", "unit", "certified_rate_x", "p50_ms_at_knee",
            "p95_ms_at_knee", "cache_hit_rate", "requests_per_dispatch",
            "device_kind", "degraded", "trace", "seed")}
        if capacity.get("degraded"):
            report["hints"].append(_hint(
                "capacity_regression",
                detail="certificate is marked degraded (built under "
                       "fault injection) — not publishable evidence"))
        else:
            totals = ((usage or {}).get("totals") or {})
            try:
                import usage_report as _ur

                cap = _ur.capacity(totals, slo_ms=500.0)
            except Exception:
                cap = None
            analytic = (cap or {}).get("req_per_s_per_worker")
            measured = capacity.get("value")
            if analytic and measured:
                ratio = max(measured / analytic, analytic / measured)
                report["capacity"]["analytic_req_per_s"] = round(
                    analytic, 4)
                if ratio > 2.0:
                    report["hints"].append(_hint(
                        "capacity_regression",
                        detail=f"measured {measured:g} vs analytic "
                               f"{analytic:g} req/s/worker "
                               f"({ratio:.1f}x apart)"))

    # fleet: the router's per-worker liveness gauge first
    # (prometheus), else the worker_down / fleet_failover bus events
    fleet_row: dict = {"workers": {}}
    for labels, v in prom.get("dbcsr_tpu_fleet_worker_up", []):
        fleet_row["workers"][labels.get("worker", "?")] = \
            "up" if v >= 1.0 else "down"
    routed = collections.Counter()
    for labels, v in prom.get("dbcsr_tpu_fleet_requests_total", []):
        routed[labels.get("outcome", "?")] += int(v)
    if routed:
        fleet_row["routed"] = dict(routed)
    fo = prom.get("dbcsr_tpu_fleet_failovers_total")
    if fo:
        fleet_row["failovers"] = int(sum(v for _, v in fo))
    rp2 = prom.get("dbcsr_tpu_fleet_replayed_total")
    if rp2:
        fleet_row["replayed"] = int(sum(v for _, v in rp2))
    if not fleet_row["workers"]:
        for e in events:
            if e.get("event") == "worker_down":
                fleet_row["workers"][e.get("worker", "?")] = "down"
            elif e.get("event") == "worker_up":
                fleet_row["workers"][e.get("worker", "?")] = "up"
            elif e.get("event") == "fleet_failover":
                fleet_row["failovers"] = \
                    fleet_row.get("failovers", 0) + 1
                fleet_row["replayed"] = \
                    fleet_row.get("replayed", 0) + int(
                        e.get("replayed") or 0)
    if fleet_row["workers"] or fleet_row.get("failovers"):
        report["fleet"] = fleet_row
        dead = sorted(w for w, st in fleet_row["workers"].items()
                      if st == "down")
        if dead:
            report["hints"].append(_hint(
                "worker_down", detail=", ".join(dead)))
        if fleet_row.get("failovers"):
            report["hints"].append(_hint(
                "failover_replay",
                detail=f"{fleet_row['failovers']} failover(s), "
                       f"{fleet_row.get('replayed', 0)} request(s) "
                       f"replayed"))

    # incident bundles: the capture counter, else the bus event
    incidents = 0.0
    for labels, v in prom.get("dbcsr_tpu_incident_bundles_total", []):
        if labels.get("result") == "captured":
            incidents += v
    if not incidents:
        incidents = sum(1 for e in events
                        if e.get("event") == "incident_captured")
    if incidents:
        report["incidents"] = int(incidents)
        report["hints"].append(_hint(
            "incident_captured", detail=f"{int(incidents)} bundle(s)"))

    # anomalies: live health verdict first, else anomaly events
    anomalies: dict = collections.Counter()
    if health:
        for kind, n in (health.get("anomaly_counts") or {}).items():
            anomalies[kind] += int(n)
    for e in events:
        if e.get("event") == "anomaly" and not health:
            anomalies[e.get("kind", "?")] += 1
    report["anomalies"] = dict(anomalies)
    for kind in anomalies:
        if kind in HINTS:
            report["hints"].append(_hint(kind))

    # corruption verdicts ride the checksum_retry counter/events
    corrupt = 0.0
    for labels, v in prom.get("dbcsr_tpu_checksum_retry_total", []):
        if labels.get("outcome") in ("deterministic", "unstable"):
            corrupt += v
    corrupt += sum(1 for e in events
                   if e.get("event") == "checksum_retry"
                   and e.get("outcome") in ("deterministic", "unstable"))
    if corrupt:
        report["hints"].append(_hint("checksum_corruption",
                                     detail=f"{int(corrupt)} verdict(s)"))

    # synthesize a health verdict from artifacts when no live one exists
    if health is None:
        status = "OK"
        if open_breakers or wedged or anomalies or sdc_total \
                or integrity["rollbacks"] or slo_burning:
            status = "DEGRADED"
        if corrupt or repeat or any(w.get("wedge_streak", 0) >= 3
                                    for w in watchdog.values()):
            status = "CRITICAL"
        report["health"] = {"status": status, "source": "artifacts"}
    return report


def _hint(kind: str, detail: str = "") -> dict:
    action, anchor = HINTS[kind]
    runbook = anchor if anchor.startswith("docs/") else RUNBOOK + anchor
    return {"kind": kind, "detail": detail, "action": action,
            "runbook": runbook}


# ----------------------------------------------------------- renderer

def render(report: dict, out=print) -> None:
    h = report.get("health") or {}
    out(f" dbcsr_tpu doctor — overall: {h.get('status', '?')}"
        + (f"  (source: {h['source']})" if h.get("source") else ""))
    comps = (h.get("components") or {})
    if comps:
        out(f"   {'component':<12} {'status':<10} reasons")
        for name, c in sorted(comps.items()):
            reasons = "; ".join(c.get("reasons") or []) or "-"
            out(f"   {name:<12} {c.get('status', '?'):<10} {reasons}")
    if report.get("breakers"):
        openish = {k: s for k, s in report["breakers"].items()
                   if s != "closed"}
        out(f" breakers: {len(report['breakers'])} tracked, "
            f"{len(openish)} not closed"
            + (": " + ", ".join(f"{k}={s}"
                                for k, s in sorted(openish.items()))
               if openish else ""))
    if report.get("watchdog"):
        for name, w in sorted(report["watchdog"].items()):
            extra = f" last={w['outcome']}" if w.get("outcome") else ""
            out(f" watchdog {name}: wedge_streak={w.get('wedge_streak', 0)}"
                f"{extra}")
    for label, key in (("recompile offenders", "recompiles"),
                       ("fallback offenders", "fallbacks"),
                       ("failure offenders", "failures")):
        offs = report.get("offenders", {}).get(key) or []
        if not offs:
            continue
        out(f" top {label} (by product):")
        for pid, n in offs:
            ctx = report.get("products", {}).get(pid, {})
            mnk = ctx.get("mnk")
            desc = f" {ctx.get('name', '')}" \
                   + (f" {tuple(mnk)}" if mnk else "")
            out(f"   {n:>6}x  {pid}{desc}")
    if report.get("roofline"):
        out(" roofline fraction per driver:")
        for drv, frac in sorted(report["roofline"].items()):
            out(f"   {drv:<40} {frac}")
    if report.get("pool"):
        p = report["pool"]
        parts = [f"{k}={p[k]}" for k in
                 ("hits", "misses", "returns", "evictions") if k in p]
        if "bytes_held" in p:
            parts.append(f"held={p['bytes_held'] / 1e6:.1f}MB")
        for k in ("h2d_bytes", "d2h_bytes"):
            if k in p:
                parts.append(f"{k.split('_')[0]}={p[k] / 1e6:.1f}MB")
        out(" memory pool: " + ", ".join(parts))
    if report.get("value_reuse"):
        vr = report["value_reuse"]
        parts = []
        if vr.get("incremental"):
            parts.append("incremental[" + ", ".join(
                f"{k}={v}" for k, v in sorted(vr["incremental"].items()))
                + "]")
        if "incremental_saved_flops" in vr:
            parts.append(
                f"saved_gflop={vr['incremental_saved_flops'] / 1e9:.2f}")
        if vr.get("product_cache"):
            parts.append("product_cache[" + ", ".join(
                f"{k}={v}" for k, v in sorted(vr["product_cache"].items()))
                + "]")
        if "product_cache_bytes" in vr:
            parts.append(
                f"cache_held={vr['product_cache_bytes'] / 1e6:.1f}MB")
        out(" value reuse: " + ", ".join(parts))
    if report.get("serving"):
        sv = report["serving"]
        head = " serving:"
        if "queue_depth" in sv:
            head += f" queue_depth={sv['queue_depth']}"
        if sv.get("shed_reasons"):
            head += " shed[" + ", ".join(
                f"{k}={v}" for k, v in sorted(sv["shed_reasons"].items())
            ) + "]"
        out(head if head != " serving:" else " serving: (per-tenant)")
        for t, c in sorted(sv.get("tenants", {}).items()):
            out(f"   {t:<20} " + ", ".join(
                f"{k}={v}" for k, v in sorted(c.items())))
        if sv.get("deadline_offenders"):
            out("   top deadline-miss offenders: " + ", ".join(
                f"{t} ({n})" for t, n in sv["deadline_offenders"]))
    if report.get("usage"):
        ug = report["usage"]
        totals = ug.get("totals") or {}
        head = " tenant usage:"
        if totals.get("device_seconds") is not None:
            head += f" total_dev_s={float(totals['device_seconds']):.6f}"
        if totals.get("requests"):
            head += f" requests={int(totals['requests'])}"
        out(head)
        ranked = sorted(ug["tenants"].items(),
                        key=lambda kv: -kv[1]["device_seconds"])
        for t, r in ranked:
            parts = [f"dev_s={r['device_seconds']:.6f}",
                     f"flops={r['flops']}"]
            if r.get("requests"):
                parts.append(f"reqs={r['requests']}")
            if r.get("saved_flops"):
                parts.append(f"saved_flops={r['saved_flops']}")
            out(f"   {t:<20} " + ", ".join(parts))
    if report.get("capacity"):
        cp = report["capacity"]
        head = (f" capacity: certified {cp.get('value')} "
                f"{cp.get('unit', 'req/s/worker')}")
        if cp.get("certified_rate_x") is not None:
            head += f" at x{cp['certified_rate_x']:g}"
        if cp.get("p95_ms_at_knee") is not None:
            head += f", p95={cp['p95_ms_at_knee']}ms"
        if cp.get("analytic_req_per_s") is not None:
            head += f" (analytic {cp['analytic_req_per_s']:g})"
        if cp.get("device_kind"):
            head += f" [{cp['device_kind']}]"
        if cp.get("degraded"):
            head += " DEGRADED"
        out(head)
    if report.get("fleet"):
        fl = report["fleet"]
        parts = [f"{w}={st}" for w, st in sorted(fl["workers"].items())]
        if fl.get("routed"):
            parts.append("routed[" + ", ".join(
                f"{k}={v}" for k, v in sorted(fl["routed"].items()))
                + "]")
        if fl.get("failovers"):
            parts.append(f"failovers={fl['failovers']}")
        if fl.get("replayed"):
            parts.append(f"replayed={fl['replayed']}")
        out(" fleet: " + ", ".join(parts))
    if report.get("incidents"):
        out(f" incident bundles captured: {report['incidents']}")
    if report.get("integrity"):
        ig = report["integrity"]
        parts = []
        if "checks" in ig:
            parts.append(f"checks={ig['checks']}")
        if ig.get("mismatches"):
            parts.append("sdc[" + ", ".join(
                f"{d}={n}" for d, n in sorted(ig["mismatches"].items()))
                + "]")
        if "recoveries" in ig:
            parts.append(f"recoveries={ig['recoveries']}")
        if ig.get("rollbacks"):
            parts.append(f"chain_rollbacks={ig['rollbacks']}")
        if ig.get("drains"):
            parts.append(f"drains={ig['drains']}")
        if ig.get("replayed"):
            parts.append(f"replayed={ig['replayed']}")
        out(" integrity: " + ", ".join(parts))
    if report.get("tune"):
        tn = report["tune"]
        parts = []
        if tn.get("trials"):
            parts.append("trials[" + ", ".join(
                f"{k}={v}" for k, v in sorted(tn["trials"].items()))
                + "]")
        if tn.get("promotions"):
            parts.append(f"promotions={tn['promotions']}")
        if tn.get("demotions"):
            parts.append("demotions[" + ", ".join(
                f"{k}={v}" for k, v in sorted(tn["demotions"].items()))
                + "]")
        for f in ("queue_depth", "params_generation"):
            if tn.get(f) is not None:
                parts.append(f"{f}={tn[f]}")
        out(" autotuner: " + (", ".join(parts) or "idle"))
    if report.get("format_planner"):
        fpn = report["format_planner"]
        parts = []
        if fpn.get("decisions"):
            parts.append("decisions[" + ", ".join(
                f"{k}={v}" for k, v in sorted(fpn["decisions"].items()))
                + "]")
        out(" format planner: " + (", ".join(parts) or "idle"))
    if report.get("slo_burning"):
        out(" slo burning: " + ", ".join(
            f"{n} ({b}x)" for n, b in
            sorted(report["slo_burning"].items())))
    if report.get("anomalies"):
        out(" anomalies: " + ", ".join(
            f"{k}={v}" for k, v in sorted(report["anomalies"].items())))
    if report.get("hints"):
        out(" hints:")
        for hint in report["hints"]:
            det = f" [{hint['detail']}]" if hint.get("detail") else ""
            out(f"   - {hint['kind']}{det}: {hint['action']}")
            out(f"     runbook: {hint['runbook']}")
    if not any(report.get(k) for k in
               ("breakers", "watchdog", "anomalies", "roofline")) \
            and not (report.get("offenders") or {}).get("recompiles"):
        out(" (no signals found — is the job instrumented / are the "
            "artifact paths right?)")


# -------------------------------------------------------------- trend

def _fleet_mod():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import fleet

    return fleet


def fetch_trend_live(url: str, timeout: float = 10.0) -> dict:
    """Trend report off a live endpoint: per-cell history from
    ``/timeseries`` (one query per `TREND_METRICS` family) + the SLO
    evaluation from ``/slo``."""
    import urllib.error
    import urllib.request

    def get(route):
        try:
            with urllib.request.urlopen(url.rstrip("/") + route,
                                        timeout=timeout) as r:
                return r.read().decode()
        except urllib.error.HTTPError as e:
            return e.read().decode()

    series = []
    reached = 0
    last_exc = None
    for metric in TREND_METRICS:
        try:
            resp = json.loads(get(f"/timeseries?metric={metric}"))
        except ValueError:
            reached += 1  # endpoint answered, payload unusable
            continue
        except Exception as exc:
            # endpoint restarting/dying mid-loop: keep what the other
            # queries already fetched instead of discarding everything
            last_exc = exc
            continue
        reached += 1
        if isinstance(resp, list):  # a pre-v4 endpoint 404s with a dict
            series.extend(r for r in resp if isinstance(r, dict))
    slo: dict = {}
    try:
        resp = json.loads(get("/slo"))
        reached += 1
        if isinstance(resp, dict):
            slo = resp.get("objectives") or {}
    except Exception:
        pass
    if not reached and last_exc is not None:
        raise last_exc  # fully unreachable: main's exit-2 path
    return {"source": "live", "processes": {"live": series}, "slo": slo}


def trend_from_artifacts(ts_base: str) -> dict:
    """Trend report from committed time-series shard artifacts (the
    `tools/fleet.py` data model; no dbcsr_tpu import).  The SLO burn
    summary replays the persisted ``dbcsr_tpu_slo_burn_rate`` points —
    burn history travels WITH the shard, so an offline diagnosis sees
    the same objectives the live process alerted on."""
    fleet = _fleet_mod()
    merged = fleet.merge_shards(ts_base)
    processes: dict = {}
    slo: dict = {}
    for proc, series in merged.items():
        rows = []
        for (metric, _), ent in sorted(series.items()):
            if metric not in TREND_METRICS:
                continue
            rows.append({"metric": metric, "labels": ent["labels"],
                         "points": [[t, v] for t, v in ent["points"]]})
            if metric == "dbcsr_tpu_slo_burn_rate" and ent["points"]:
                name = ent["labels"].get("objective", "?")
                burn = ent["points"][-1][1]
                peak = max(v for _, v in ent["points"])
                row = slo.setdefault(name, {"burn": burn, "peak": peak})
                row["burn"] = max(row["burn"], burn)
                row["peak"] = max(row["peak"], peak)
                # BURNING = still over budget at the shard's tail;
                # BURNED = a burn is in the history but it recovered
                row["status"] = ("BURNING" if row["burn"] > 1.0 else
                                 "BURNED" if row["peak"] > 1.0 else "OK")
        processes[proc] = rows
    return {"source": "artifacts", "processes": processes, "slo": slo}


def render_trend(report: dict, out=print) -> None:
    fleet = _fleet_mod()
    out(f" dbcsr_tpu doctor --trend  (source: {report['source']})")
    for proc, rows in sorted(report["processes"].items()):
        if not rows:
            continue
        out(f" process {proc}:")
        by_metric: dict = collections.defaultdict(list)
        for row in rows:
            by_metric[row["metric"]].append(row)
        for metric in TREND_METRICS:
            if metric not in by_metric:
                continue
            out(f"   {metric}")
            for row in by_metric[metric]:
                pts = row["points"]
                if not pts:
                    continue
                lab = ",".join(f"{k}={v}" for k, v in
                               sorted(row["labels"].items())) or "-"
                spark = fleet.sparkline([v for _, v in pts]) \
                    if len(pts) > 1 else ""
                out(f"     {lab:<44} last={pts[-1][1]:<12.6g} "
                    f"n={len(pts):<4} {spark}")
        # executed-precision occupancy: share of each (m,n,k) cell's
        # flops by the dtype its launches actually EXECUTED at (the
        # cell_flops dtype label records the executed compute dtype,
        # so a demoted cell splits across float64/float32/bfloat16)
        occ: dict = {}
        for row in by_metric.get("dbcsr_tpu_cell_flops_total", []):
            pts = row["points"]
            if not pts:
                continue
            d = occ.setdefault(row["labels"].get("mnk", "?"), {})
            dt = row["labels"].get("dtype", "?") or "?"
            d[dt] = d.get(dt, 0.0) + pts[-1][1]
        if occ:
            out("   executed-precision occupancy "
                "(share of cell flops by executed dtype)")
            for mnk, by_dt in sorted(occ.items()):
                tot = sum(by_dt.values()) or 1.0
                share = "  ".join(f"{dt}={v / tot:.0%}"
                                  for dt, v in sorted(by_dt.items()))
                out(f"     {mnk:<20} {share}")
    slo = report.get("slo") or {}
    if slo:
        out(" slo burn summary:")
        for name, row in sorted(slo.items()):
            extra = f" peak={row['peak']:.2f}x" if "peak" in row else ""
            out(f"   {name:<22} {row.get('status', '?'):<8} "
                f"burn={row.get('burn', 0):.2f}x{extra}")
    else:
        out(" slo burn summary: (no slo series found)")


# ---------------------------------------------------------- diagnose

# Mirror of dbcsr_tpu.obs.OBS_SCHEMA_VERSION — a literal on purpose:
# the doctor must diagnose artifacts copied off another machine with
# no dbcsr_tpu import.  Bump together with the obs package.
_DIAG_SCHEMA = 7


def fetch_diagnose_live(url: str, timeout: float = 10.0) -> dict:
    """Pull the ``/rca`` route off a live endpoint into the
    ``--diagnose`` report shape."""
    import urllib.request

    with urllib.request.urlopen(url.rstrip("/") + "/rca?limit=8",
                                timeout=timeout) as r:
        doc = json.loads(r.read().decode())
    return {"schema": doc.get("schema", _DIAG_SCHEMA), "source": url,
            "reports": doc.get("reports") or [],
            "changepoints": doc.get("changepoints") or [],
            "ledger": doc.get("ledger") or []}


def diagnose_from_bundle(bundle: dict, path: str) -> dict:
    """An incident bundle's ``rca`` record (the freshest causal report
    at capture time) re-shaped into the ``--diagnose`` report."""
    rep = bundle.get("rca")
    meta = bundle.get("meta") or {}
    return {"schema": meta.get("schema", _DIAG_SCHEMA), "source": path,
            "reports": [rep] if rep else [],
            "changepoints": [rep["changepoint"]]
            if rep and rep.get("changepoint") else [],
            "ledger": []}


def diagnose_from_cert(path: str) -> dict | None:
    """The committed RCA_CERT.json (tools/rca_bench.py) re-shaped into
    the ``--diagnose`` report: each injection's full causal report."""
    try:
        with open(path) as fh:
            cert = json.load(fh)
    except (OSError, ValueError):
        return None
    reports = [inj["report"] for inj in cert.get("injections") or []
               if inj.get("report")]
    if not reports:
        return None
    return {"schema": cert.get("schema", _DIAG_SCHEMA), "source": path,
            "reports": reports,
            "changepoints": [r["changepoint"] for r in reports
                             if r.get("changepoint")],
            "ledger": []}


def _cause_detail(ent: dict) -> str:
    """One-line identity for a ranked cause: the payload fields that
    name WHAT changed (row identity, knob name, generation), minus the
    bookkeeping the table already shows."""
    skip = {"kind", "event", "t", "rank", "score", "seq", "pid"}
    parts = [f"{k}={v}" for k, v in sorted(ent.items())
             if k not in skip and v is not None]
    return " ".join(parts) or "-"


def render_diagnose(report: dict, out=print) -> None:
    out(f" dbcsr_tpu doctor --diagnose  (source: {report['source']}, "
        f"schema v{report.get('schema', '?')})")
    reports = report.get("reports") or []
    if not reports:
        out(" no causal reports: no regression change-point has fired"
            " (steady state, or the diagnosis plane is disabled)")
        return
    out(f" {len(reports)} causal report(s), newest first:")
    for rep in reversed(reports):
        cp = rep.get("changepoint") or {}
        sig = cp.get("sigma") or 0.0
        z = abs(cp.get("magnitude", 0.0)) / sig if sig else 0.0
        out(f"   change-point: {cp.get('series', '?')} "
            f"{cp.get('direction', '?')} "
            f"{cp.get('baseline', 0):.4g} -> {cp.get('level', 0):.4g} "
            f"(shift {cp.get('magnitude', 0):+.4g} = {z:.0f} sigma) "
            f"at t={cp.get('t_shift')}")
        causes = rep.get("causes") or []
        if causes:
            out("   ranked causes:")
            for ent in causes:
                out(f"     {ent.get('rank', '?')}. "
                    f"{ent.get('kind', '?'):<24} "
                    f"score={ent.get('score', 0):<9.3g} "
                    f"{_cause_detail(ent)}")
        else:
            out("   ranked causes: (change ledger empty in window)")
        diff = rep.get("profile_diff") or {}
        rows = (diff.get("phases") or []) if diff.get("ok") else []
        if rows:
            out("   profile diff (top phase deltas, baseline -> after):")
            for row in rows[:5]:
                ratio = row.get("ratio")
                # a phase absent on one side has no ratio: the driver
                # swap itself (new phase appears, old disappears)
                xr = f"x{ratio:.2f}" if isinstance(ratio, (int, float)) \
                    else "new" if not row.get("count_a") else "gone"
                key = f"{row['driver']}|{row['cell']}|{row['phase']}"
                out(f"     {key:<44} "
                    f"{row['mean_ms_a'] or 0:.4g}ms -> "
                    f"{row['mean_ms_b'] or 0:.4g}ms "
                    f"({xr}, n={row['count_a']}->{row['count_b']})")
        elif diff:
            out(f"   profile diff: unavailable "
                f"({diff.get('reason', 'no epochs straddle the shift')})")
        out("")


# ----------------------------------------------------------- selftest

def _selftest(repo_root: str) -> int:
    """Offline smoke: synthetic correlated events + the committed bench
    artifacts through the full analyze/render pipeline.  Exit 0 iff
    every expected section materializes."""
    pid = "self-1"
    events = [
        {"event": "multiply_begin", "product_id": pid, "name": "C",
         "mnk": [184, 184, 184]},
        {"event": "fault_injected", "product_id": pid,
         "site": "execute_stack", "kind": "raise", "target": "pallas"},
        {"event": "driver_failure", "product_id": pid, "driver": "pallas",
         "kind": "runtime", "shape": "23x23x23xfloat64"},
        {"event": "breaker_transition", "product_id": pid,
         "driver": "pallas", "shape": "23x23x23xfloat64", "to": "open",
         "transition": "threshold"},
        {"event": "driver_failover", "product_id": pid, "from": "pallas",
         "to": "xla_group", "shape": "23x23x23xfloat64"},
        {"event": "jit_compile", "product_id": pid,
         "fn": "acc.smm._process_stack_xla", "key": "(23, 23, 23)"},
        {"event": "anomaly", "kind": "fallback_storm",
         "rate_per_multiply": 1.0, "product_id": None},
        {"event": "multiply_end", "product_id": pid, "dur_ms": 12.5,
         "algorithm": "stack"},
        # serving-plane artifacts: one tenant being shed on quota, one
        # missing deadlines — both rows + hints must materialize
        {"event": "serve_admitted", "request_id": "req-1",
         "tenant": "alice", "op": "multiply", "outcome": "admitted"},
        {"event": "serve_done", "request_id": "req-1", "tenant": "alice",
         "outcome": "OK", "latency_ms": 40.0},
        {"event": "serve_shed", "request_id": "req-2", "tenant": "bob",
         "op": "multiply", "reason": "quota_inflight"},
        {"event": "serve_deadline_missed", "request_id": "req-3",
         "tenant": "bob", "op": "multiply", "waited_ms": 900.0},
        # integrity plane: one detected-SDC probe mismatch, one chain
        # rollback, one drain/replay pair — the integrity section and
        # its hints must materialize from events alone
        {"event": "abft_mismatch", "product_id": pid, "driver": "pallas",
         "site": "stack", "rel_err": 1.2e-3, "tolerance": 3.1e-11},
        {"event": "chain_rollback", "model": "purify", "step": 2,
         "reason": "invariant"},
        {"event": "serve_drain", "journal": "serve_journal-1.jsonl",
         "journaled": 1, "completed_inflight": True},
        {"event": "serve_replayed", "request_id": "req-4",
         "tenant": "alice", "journal": "serve_journal-1.jsonl"},
        # SLO plane: one objective burning its error budget — the
        # slo_burn hint must materialize from events alone
        {"event": "slo_burn", "objective": "serve_p95_latency",
         "burn": 3.2, "burn_short": 4.0, "burn_long": 3.2,
         "budget": 0.1},
    ]
    probe = [{"ts": "2026-01-01T00:00:00", "name": "tpu_probe",
              "outcome": "WEDGED", "streak": 4, "wedge_streak": 2,
              "elapsed_s": 120.0, "error": "DeadlineExceeded"}]
    captures = []
    for path in sorted(glob.glob(os.path.join(repo_root, "BENCH_r0*.json"))):
        try:
            doc = json.load(open(path))
        except ValueError:
            continue
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            captures.append(parsed)
    report = analyze(None, {}, events, [], probe, captures)
    render(report)

    # --bundle offline: a synthetic incident bundle (the JSONL shape
    # dbcsr_tpu.obs.incidents persists) through read_bundle + analyze —
    # the usage section, the hotspot hint and the incident marker must
    # all materialize from the file alone
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".jsonl",
                                     delete=False) as fh:
        bundle_path = fh.name
        fh.write(json.dumps({"rec": "meta", "kind": "incident",
                             "reason": "slo_burn:serve_p95_latency",
                             "t_unix": 1.0, "pid": 42}) + "\n")
        fh.write(json.dumps({"rec": "health", "health": {
            "status": "DEGRADED", "components": {}}}) + "\n")
        fh.write(json.dumps({"rec": "usage", "usage": {"tenants": {
            "alice": {"device_seconds": 0.9, "flops": 900,
                      "bytes_moved": 9000, "saved_flops": 0,
                      "requests": 9},
            "bob": {"device_seconds": 0.1, "flops": 100,
                    "bytes_moved": 1000, "saved_flops": 50,
                    "requests": 1},
        }, "totals": {"device_seconds": 1.0, "requests": 10}}}) + "\n")
        fh.write(json.dumps({"rec": "event", "event": "incident_captured",
                             "reason": "slo_burn:serve_p95_latency"})
                 + "\n")
    try:
        bundle = read_bundle(bundle_path)
        breport = analyze(bundle["health"], {}, bundle["events"],
                          bundle["flight"], [], [],
                          usage=bundle["usage"])
        render(breport)
    finally:
        os.unlink(bundle_path)
    bundle_ok = (
        bundle["meta"].get("reason") == "slo_burn:serve_p95_latency"
        and breport["usage"]["tenants"]["alice"]["device_seconds"] == 0.9
        and breport["usage"]["totals"]["requests"] == 10
        and breport["incidents"] == 1
        and any(h["kind"] == "tenant_hotspot" for h in breport["hints"])
        and any(h["kind"] == "incident_captured"
                for h in breport["hints"])
    )

    # --capacity offline: a synthetic certificate through analyze —
    # the capacity row must render, a degraded cert must hint, and a
    # clean cert that disagrees with the usage-derived analytic
    # number by >2x must hint with the divergence
    cert = {"kind": "capacity_cert", "value": 120.0,
            "unit": "req/s/worker", "certified_rate_x": 8.0,
            "p50_ms_at_knee": 12.0, "p95_ms_at_knee": 45.0,
            "cache_hit_rate": 0.5, "requests_per_dispatch": 3.4,
            "device_kind": "cpu", "degraded": True,
            "trace": "WORKLOAD_TRACE.jsonl", "seed": 0}
    creport = analyze(None, {}, [], [], [], [], capacity=cert)
    cap_lines: list = []
    render(creport, out=cap_lines.append)
    creport2 = analyze(
        None, {}, [], [], [], [],
        usage={"tenants": {}, "totals": {"device_seconds": 1.0,
                                         "requests": 10}},
        capacity=dict(cert, degraded=False))
    capacity_ok = (
        creport["capacity"]["value"] == 120.0
        and any(h["kind"] == "capacity_regression"
                and "degraded" in h["detail"] for h in creport["hints"])
        and any(ln.startswith(" capacity:") for ln in cap_lines)
        and any(h["kind"] == "capacity_regression"
                and "apart" in h["detail"] for h in creport2["hints"])
        and all(h["runbook"].startswith("docs/loadtest.md")
                for h in creport["hints"] + creport2["hints"]
                if h["kind"] == "capacity_regression")
    )

    # fleet offline: the router's liveness gauge + failover counters
    # through analyze — the fleet row must render, a down worker must
    # earn the worker_down hint (naming the worker) and a failover
    # must earn the failover_replay hint, both anchored in the
    # serving runbook
    fleet_prom = {
        "dbcsr_tpu_fleet_worker_up": [({"worker": "w0"}, 0.0),
                                      ({"worker": "w1"}, 1.0)],
        "dbcsr_tpu_fleet_requests_total": [
            ({"worker": "w0", "outcome": "routed"}, 5.0),
            ({"worker": "w0", "outcome": "retried"}, 2.0)],
        "dbcsr_tpu_fleet_failovers_total": [
            ({"worker": "w0", "target": "w1"}, 1.0)],
        "dbcsr_tpu_fleet_replayed_total": [({"worker": "w1"}, 4.0)],
    }
    freport = analyze(None, fleet_prom, [], [], [], [])
    fleet_lines: list = []
    render(freport, out=fleet_lines.append)
    # events-only fallback (a dead process's artifacts)
    freport2 = analyze(None, {}, [
        {"event": "worker_down", "worker": "w2", "misses": 3},
        {"event": "fleet_failover", "worker": "w2", "target": "w3",
         "replayed": 2},
    ], [], [], [])
    fleet_ok = (
        freport["fleet"]["workers"] == {"w0": "down", "w1": "up"}
        and freport["fleet"]["failovers"] == 1
        and freport["fleet"]["replayed"] == 4
        and any(h["kind"] == "worker_down" and "w0" in h["detail"]
                for h in freport["hints"])
        and any(h["kind"] == "failover_replay"
                for h in freport["hints"])
        and any(ln.startswith(" fleet:") for ln in fleet_lines)
        and all(h["runbook"].startswith("docs/serving.md")
                for h in freport["hints"]
                if h["kind"] in ("worker_down", "failover_replay"))
        and freport2["fleet"]["workers"] == {"w2": "down"}
        and freport2["fleet"]["replayed"] == 2
        and any(h["kind"] == "worker_down" and "w2" in h["detail"]
                for h in freport2["hints"])
    )

    # --trend offline: a synthetic 2-process shard family (one rank
    # healthy, one with a burning serve-latency SLO) through the full
    # trend pipeline — per-cell sparklines + the burn summary
    import tempfile

    trend_lines = []
    with tempfile.TemporaryDirectory() as td:
        for proc, burns in (("0", [0.0, 0.2, 0.1]), ("1", [0.5, 2.0, 3.2])):
            with open(os.path.join(td, f"ts.p{proc}.jsonl"), "w") as fh:
                for i, burn in enumerate(burns):
                    fh.write(json.dumps({
                        "seq": i + 1, "t": 1000.0 + 10 * i,
                        "reason": "interval",
                        "points": [
                            ["dbcsr_tpu_roofline_fraction",
                             {"driver": "xla"}, 0.4 - 0.1 * i, "gauge"],
                            ["dbcsr_tpu_serve_latency_p95_ms",
                             {"tenant": "alice"}, 40.0 + 400 * i,
                             "gauge"],
                            ["dbcsr_tpu_slo_burn_rate",
                             {"objective": "serve_p95_latency"}, burn,
                             "gauge"],
                        ]}) + "\n")
        trend = trend_from_artifacts(os.path.join(td, "ts.jsonl"))
        render_trend(trend, out=trend_lines.append)
    for ln in trend_lines:
        print(ln)
    trend_ok = (
        set(trend["processes"]) == {"0", "1"}
        and trend["slo"]["serve_p95_latency"]["status"] == "BURNING"
        and trend["slo"]["serve_p95_latency"]["burn"] == 3.2
        and any("driver=xla" in ln for ln in trend_lines)
        and any("slo burn summary" in ln for ln in trend_lines)
    )

    ok = trend_ok and bundle_ok and capacity_ok and fleet_ok and (
        report["health"]["status"] in ("DEGRADED", "CRITICAL")
        and report["breakers"].get("pallas|23x23x23xfloat64") == "open"
        and report["watchdog"].get("tpu_probe", {}).get("wedge_streak") == 2
        and report["offenders"]["fallbacks"][0][0] == pid
        and report["anomalies"].get("fallback_storm") == 1
        and any(h["kind"] == "wedge_streak" for h in report["hints"])
        and any(h["kind"] == "breaker_open" for h in report["hints"])
        and report["serving"]["tenants"]["bob"]["shed"] == 1
        and report["serving"]["deadline_offenders"] == [("bob", 1)]
        and any(h["kind"] == "serve_shed" for h in report["hints"])
        and any(h["kind"] == "serve_deadline" for h in report["hints"])
        and report["integrity"]["mismatches"] == {"pallas": 1}
        and report["integrity"]["rollbacks"] == 1
        and report["integrity"]["drains"] == 1
        and report["integrity"]["replayed"] == 1
        and any(h["kind"] == "abft_mismatch" for h in report["hints"])
        and any(h["kind"] == "chain_rollback" for h in report["hints"])
        and any(h["kind"] == "serve_drain" for h in report["hints"])
        and report["slo_burning"] == {"serve_p95_latency": 3.2}
        and any(h["kind"] == "slo_burn" for h in report["hints"])
    )
    print(f" selftest: {'OK' if ok else 'FAILED'} "
          f"(captures read: {len(captures)})")
    return 0 if ok else 1


# --------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--url", help="live endpoint base URL")
    ap.add_argument("--port", type=int,
                    help="live endpoint on localhost:<port>")
    ap.add_argument("--events", default="events.jsonl",
                    help="event-bus JSONL (shard base or file)")
    ap.add_argument("--trace", default="trace.jsonl",
                    help="trace JSONL (shard base or file) — instants "
                         "feed the offender tables when no events exist")
    ap.add_argument("--probe", default="watchdog.jsonl",
                    help="watchdog outcome JSONL (Watchdog state_path)")
    ap.add_argument("--captures", default="bench_rows.jsonl",
                    help="bench.py output rows, JSONL (roofline "
                         "fractions)")
    ap.add_argument("--bundle",
                    help="incident bundle JSONL (dbcsr_tpu.obs."
                         "incidents, incidents/incident-*.jsonl): "
                         "diagnose the captured moment offline")
    ap.add_argument("--usage", default="USAGE_ROLLUP.jsonl",
                    help="tenant usage rollup JSONL (the capture "
                         "loop's committed USAGE_ROLLUP.jsonl) for "
                         "the tenant-cost section in artifact mode")
    ap.add_argument("--capacity", default="CAPACITY_CERT.json",
                    help="measured capacity certificate JSON "
                         "(tools/loadtest.py certify) for the "
                         "capacity row + regression hint")
    ap.add_argument("--timeseries", default="timeseries.jsonl",
                    help="telemetry time-series shard base or file "
                         "(--trend artifact mode; the committed "
                         "TELEMETRY_ROLLUP.jsonl works too)")
    ap.add_argument("--rca-cert", default="RCA_CERT.json",
                    help="committed causal-diagnosis certificate "
                         "(tools/rca_bench.py) for --diagnose in "
                         "artifact mode")
    ap.add_argument("--diagnose", action="store_true",
                    help="ranked root-cause reports: change-point + "
                         "candidate causes + profile diff, from /rca "
                         "(live), an incident bundle's rca record "
                         "(--bundle), or --rca-cert")
    ap.add_argument("--trend", action="store_true",
                    help="sparkline history tables per telemetry cell "
                         "+ SLO burn summary, from /timeseries + /slo "
                         "(live) or the --timeseries shards")
    ap.add_argument("--top", type=int, default=5,
                    help="offender table size (default 5)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable report")
    ap.add_argument("--selftest", action="store_true",
                    help="offline smoke against synthetic events + the "
                         "committed bench artifacts; exit 0 on success")
    args = ap.parse_args(argv)

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if args.selftest:
        return _selftest(repo_root)

    if args.diagnose:
        if args.url or args.port:
            url = args.url or f"http://127.0.0.1:{args.port}"
            try:
                report = fetch_diagnose_live(url)
            except Exception as exc:
                print(f"doctor: cannot reach {url}: "
                      f"{type(exc).__name__}: {exc}", file=sys.stderr)
                return 2
        elif args.bundle:
            bundle = read_bundle(args.bundle)
            if not bundle["meta"] and bundle["rca"] is None:
                print(f"doctor: no bundle records in {args.bundle!r}",
                      file=sys.stderr)
                return 2
            report = diagnose_from_bundle(bundle, args.bundle)
        else:
            maybe = diagnose_from_cert(args.rca_cert)
            if maybe is None:
                print(f"doctor: no causal reports at {args.rca_cert!r} "
                      f"(run tools/rca_bench.py, or point --url/--port "
                      f"at a live endpoint)", file=sys.stderr)
                return 2
            report = maybe
        if args.as_json:
            print(json.dumps(report, default=str))
        else:
            render_diagnose(report)
        return 0

    if args.trend:
        if args.url or args.port:
            url = args.url or f"http://127.0.0.1:{args.port}"
            try:
                report = fetch_trend_live(url)
            except Exception as exc:
                print(f"doctor: cannot reach {url}: "
                      f"{type(exc).__name__}: {exc}", file=sys.stderr)
                return 2
            if not any(report["processes"].values()) \
                    and not report.get("slo"):
                # something answered but nothing was telemetry (a
                # typo'd port hitting another service must not read
                # as "fleet healthy, no burn")
                print(f"doctor: {url} returned no timeseries/slo data "
                      f"(is this an obs endpoint?)", file=sys.stderr)
                return 2
        else:
            report = trend_from_artifacts(args.timeseries)
            if not any(report["processes"].values()):
                print(f"doctor: no timeseries data at "
                      f"{args.timeseries!r}", file=sys.stderr)
                return 2
        if args.as_json:
            print(json.dumps(report, default=str))
        else:
            render_trend(report)
        return 0

    if args.bundle:
        bundle = read_bundle(args.bundle)
        if not bundle["meta"] and not bundle["events"] \
                and bundle["health"] is None:
            print(f"doctor: no bundle records in {args.bundle!r}",
                  file=sys.stderr)
            return 2
        report = analyze(bundle["health"], {}, bundle["events"],
                         bundle["flight"], [], [], top=args.top,
                         usage=bundle["usage"])
        report["incident"] = {k: bundle["meta"].get(k)
                              for k in ("reason", "ts", "t_unix", "pid")
                              if bundle["meta"].get(k) is not None}
        if args.as_json:
            print(json.dumps(report, default=str))
        else:
            meta = report["incident"]
            print(f" incident bundle: reason={meta.get('reason', '?')}"
                  + (f" ts={meta['ts']}" if meta.get("ts") else "")
                  + (f" pid={meta['pid']}" if meta.get("pid") else ""))
            render(report)
        return 0

    health = None
    prom: dict = {}
    events: list = []
    flight: list = []
    usage = None
    if args.url or args.port:
        url = args.url or f"http://127.0.0.1:{args.port}"
        try:
            live = fetch_live(url)
        except Exception as exc:
            print(f"doctor: cannot reach {url}: "
                  f"{type(exc).__name__}: {exc}", file=sys.stderr)
            return 2
        health = live["health"]
        prom = parse_prometheus(live["metrics_text"])
        events = live["events"]
        flight = live["flight"]
        usage = live.get("usage")
    else:
        for shard in expand_shards(args.events):
            events.extend(_read_jsonl(shard))
        if os.path.exists(args.usage):
            usage = usage_from_rollup(args.usage)
        if not events:
            # fall back to trace instants: same event names, no ring
            for shard in expand_shards(args.trace):
                for rec in _read_jsonl(shard):
                    if rec.get("ev") == "instant":
                        events.append(dict(rec.get("args") or {},
                                           event=rec.get("name")))
    probe = _read_jsonl(args.probe)
    captures = _read_jsonl(args.captures)
    capacity = None
    if os.path.exists(args.capacity):
        try:
            with open(args.capacity) as fh:
                capacity = json.load(fh)
        except (ValueError, OSError):
            capacity = None

    report = analyze(health, prom, events, flight, probe, captures,
                     top=args.top, usage=usage, capacity=capacity)
    # lint artifact (`python -m tools.lint --json > LINT.json`):
    # a tree that fails its own invariant analyzer taints every other
    # number this report vouches for
    lint_path = os.path.join(repo_root, "LINT.json")
    if os.path.exists(lint_path):
        try:
            age_h = (time.time() - os.path.getmtime(lint_path)) / 3600.0
            with open(lint_path) as fh:
                lint = json.load(fh)
            n = int(lint.get("counts", {}).get("new", 0))
        except (ValueError, OSError):
            n = 0
            age_h = 0.0
        # a day-old report says nothing about TODAY's tree — the next
        # capture window re-banks it; don't nag off stale evidence
        if n and age_h <= 24.0:
            report["hints"].append(_hint(
                "lint_findings",
                detail=f"{n} new finding(s), report {age_h:.1f}h old"))
    if args.as_json:
        print(json.dumps(report, default=str))
    else:
        render(report)
    status = (report.get("health") or {}).get("status", "OK")
    return 1 if status == "CRITICAL" else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # `doctor ... | head` closing the pipe
        sys.exit(0)
