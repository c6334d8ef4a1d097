"""Cost-attribution plane tests: the conservation invariant, exact
coalesced splits, zero-bill cache hits, no double-billing across
degrades, bounded tenant maps, anomaly-triggered incident bundles and
the offline usage artifacts.

The load-bearing pin is `test_conservation_exact_across_tenants`: with
every operand uploaded BEFORE the attribution baseline, the per-tenant
billings must sum EXACTLY (integer arithmetic) to the grand totals,
and the grand flops/bytes must equal the engine's own rollup
bit-for-bit — dollars out == dollars in, whatever coalesced, hit the
cache, faulted or replayed.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from dbcsr_tpu import serve
from dbcsr_tpu.core.config import get_config, set_config
from dbcsr_tpu.obs import attribution, events, health, incidents, metrics
from dbcsr_tpu.obs import timeseries as ts
from dbcsr_tpu.ops.test_methods import make_random_matrix
from dbcsr_tpu.resilience import faults

BS = [5, 3, 4, 5, 2, 5]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, os.path.join(REPO, "tools"))
import doctor  # noqa: E402
import usage_report  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_slate():
    """Fresh obs/serve state per test (the test_serve.py convention);
    `metrics.reset()` also re-baselines the attribution ledger and the
    incident capture budget."""
    prev = {k: getattr(get_config(), k) for k in
            ("serve_queue_max", "serve_window_ms", "serve_coalesce",
             "serve_coalesce_max", "serve_tenant_inflight",
             "serve_tenant_bytes", "serve_degraded_deadline_s")}
    events.set_enabled(True)
    metrics.reset()
    health.reset()
    events.clear()
    yield
    serve.shutdown()
    set_config(**prev)
    metrics.reset()
    health.reset()
    events.clear()


def _inputs(tenant: int, pattern_seed: int = 7, occ: float = 0.5):
    a = make_random_matrix("A", BS, BS, occupation=occ,
                           rng=np.random.default_rng(pattern_seed))
    b = make_random_matrix("B", BS, BS, occupation=0.6,
                           rng=np.random.default_rng(pattern_seed + 1))
    c = make_random_matrix("C", BS, BS, occupation=0.3,
                           rng=np.random.default_rng(pattern_seed + 2))
    a.map_bin_data(lambda d: d * (1.0 + tenant))
    b.map_bin_data(lambda d: d * (2.0 - 0.3 * tenant))
    c.map_bin_data(lambda d: d * (0.5 + 0.1 * tenant))
    return a, b, c


def _assert_conserved(cons: dict, exact_rollup: bool = True) -> None:
    for k, v in cons["tenant_sum"].items():
        assert v == cons["grand"][k], (k, cons)
    if exact_rollup:
        assert cons["grand"]["flops"] == cons["rollup"]["flops"], cons
        assert cons["grand"]["bytes_moved"] \
            == cons["rollup"]["bytes_moved"], cons
        assert abs(cons["grand"]["device_ns"] / 1e9
                   - cons["rollup"]["device_seconds"]) < 1e-6, cons


def _prebuilt_workload(n_tenants=3, n_req=2, window_ms=30.0):
    """Engine + sessions with every operand uploaded, attribution
    re-baselined AFTER the uploads (client-side H2D outside billing
    windows is not serve cost), requests submitted but the worker not
    yet started."""
    set_config(serve_coalesce=True, serve_window_ms=window_ms)
    eng = serve.ServeEngine(start=False)
    sessions = []
    for i in range(n_tenants):
        s = eng.open_session(f"tenant{i}")
        for rep in range(n_req):
            a, b, c = _inputs(i, pattern_seed=7 + 3 * rep)
            s.put(f"A{rep}", a)
            s.put(f"B{rep}", b)
            s.put(f"C{rep}", c)
        sessions.append(s)
    metrics.reset()  # baseline AFTER the uploads
    reqs = [eng.submit(s, a=f"A{rep}", b=f"B{rep}", c=f"C{rep}",
                       alpha=1.0, beta=0.0)
            for s in sessions for rep in range(n_req)]
    return eng, sessions, reqs


# ----------------------------------------------- the hard invariant

def test_conservation_exact_across_tenants():
    """Sum(tenant billings) == grand totals == engine rollup, exactly:
    integer flops/bytes bit-for-bit, device time to the per-window ns
    quantization."""
    eng, sessions, reqs = _prebuilt_workload()
    eng.start()
    for r in reqs:
        assert r.wait(120) and r.state == "done", r.info()
    eng.shutdown()
    cons = attribution.conservation()
    _assert_conserved(cons)
    assert cons["grand"]["requests"] == len(reqs)
    assert cons["grand"]["device_ns"] > 0
    assert cons["grand"]["flops"] > 0
    for s in sessions:
        s.close()


def test_coalesced_split_sums_exactly():
    """A coalesced composite's measured cost splits across its members
    by FLOP share with largest-remainder rounding: the integer member
    billings sum EXACTLY to the composite's windows — no lost or
    invented nanosecond/flop."""
    set_config(serve_coalesce=True, serve_window_ms=100.0)
    eng = serve.ServeEngine(start=False)
    sessions = []
    for i in range(3):
        s = eng.open_session(f"tenant{i}")
        a, b, c = _inputs(i)  # same structure -> one composite
        s.put("A", a), s.put("B", b), s.put("C", c)
        sessions.append(s)
    metrics.reset()
    reqs = [eng.submit(s, a="A", b="B", c="C", alpha=1.0, beta=0.5)
            for s in sessions]
    eng.start()
    for r in reqs:
        assert r.wait(120) and r.state == "done", r.info()
    assert all(r.result["coalesced"] == 3 for r in reqs)
    eng.shutdown()
    infos = [attribution.request_info(r.request_id) for r in reqs]
    totals = attribution.usage()["totals"]
    assert sum(i["billed"]["flops"] for i in infos) == totals["flops"]
    assert all(i["billed"]["flops"] > 0 for i in infos)
    assert sum(round(i["billed"]["device_seconds"] * 1e9)
               for i in infos) == totals["device_ns"]
    for info in infos:
        for phase in ("queued", "coalesce_wait", "execute", "carve"):
            assert phase in info["phases_ms"], info
    _assert_conserved(attribution.conservation())
    for s in sessions:
        s.close()


def test_cache_hit_bills_zero_and_credits_saved():
    """A product-cache hit bills ZERO device time/flops to the tenant
    and credits the saved work instead."""
    import dbcsr_tpu as dt
    from dbcsr_tpu.serve import product_cache as pc

    pc.clear()
    set_config(serve_coalesce=False)
    eng = serve.ServeEngine(start=True)
    s = eng.open_session("cache-tenant")
    a, b, _ = _inputs(0)
    s.put("A", a, adopt=False)
    s.put("B", b, adopt=False)
    s.put("C1", dt.create("C1", BS, BS))
    s.put("C2", dt.create("C2", BS, BS))
    metrics.reset()
    r1 = eng.submit(s, a="A", b="B", c="C1", beta=0.0)
    assert r1.wait(60) and r1.state == "done", r1.info()
    r2 = eng.submit(s, a="A", b="B", c="C2", beta=0.0)
    assert r2.wait(60) and r2.state == "done", r2.info()
    assert r2.result.get("cached") == 1
    eng.shutdown()
    miss = attribution.request_info(r1.request_id)
    hit = attribution.request_info(r2.request_id)
    assert miss["billed"]["flops"] > 0 and miss["cached"] == 0
    assert hit["billed"]["flops"] == 0
    assert hit["billed"]["device_seconds"] == 0.0
    assert hit["cached"] == 1
    assert hit["saved"]["flops"] == miss["billed"]["flops"]
    # the saved credit reaches the tenant meter, not just the ledger
    saved = dict((tuple(sorted(lab.items())), v) for lab, v in
                 metrics.counter_items(
                     "dbcsr_tpu_tenant_saved_flops_total"))
    assert saved.get((("tenant", "cache-tenant"),), 0) \
        == hit["saved"]["flops"]
    _assert_conserved(attribution.conservation())
    s.close()
    pc.clear()


def test_degraded_group_bills_once_per_request():
    """A serve_execute fault degrades the coalesced group to
    serialized replays: every member still gets exactly ONE terminal
    attribution (failed-window cost + its serialize replay), requests
    are never double-counted, and the books still balance against the
    rollup — replayed work costs device time on both sides."""
    eng, sessions, reqs = _prebuilt_workload(n_tenants=3, n_req=1,
                                             window_ms=100.0)
    with faults.inject_faults("serve_execute:raise,times=1"):
        eng.start()
        for r in reqs:
            assert r.wait(120) and r.state == "done", r.info()
    eng.shutdown()
    cons = attribution.conservation()
    _assert_conserved(cons)
    assert cons["grand"]["requests"] == len(reqs)
    infos = [attribution.request_info(r.request_id) for r in reqs]
    assert all(i["terminal"] == "done" for i in infos)
    # the degraded members replayed through the serialize phase
    assert any("serialize" in i["phases_ms"] for i in infos), infos
    for s in sessions:
        s.close()


def test_attribution_fault_swallowed_books_stay_balanced():
    """The `attribution` fault site fires INSIDE bill_window but is
    always swallowed before any ledger mutation: billing completes,
    conservation holds, and the fault is visible on the bus."""
    eng, sessions, reqs = _prebuilt_workload(n_tenants=2, n_req=1)
    with faults.inject_faults("attribution:raise"):
        eng.start()
        for r in reqs:
            assert r.wait(120) and r.state == "done", r.info()
    eng.shutdown()
    _assert_conserved(attribution.conservation())
    assert attribution.usage()["totals"]["requests"] == len(reqs)
    fired = [e for e in events.records(kind="fault_injected")
             if e.get("site") == "attribution"]
    assert fired, "attribution fault never fired on the bus"
    for s in sessions:
        s.close()


# --------------------------------------------------- bounded memory

def test_tenant_maps_bounded_many_tenants(monkeypatch):
    """A tenant churn storm must not grow any per-tenant map without
    bound: the queue's accounting rows pop at zero, the engine's
    latency/outcome windows expire past the cap, and the attribution
    rollup folds evicted tenants into one row WITHOUT breaking
    conservation."""
    monkeypatch.setenv("DBCSR_TPU_ATTRIBUTION_TENANTS", "4")
    monkeypatch.setenv("DBCSR_TPU_SERVE_TENANT_MAX", "4")
    set_config(serve_coalesce=False)
    eng = serve.ServeEngine(start=False)
    sessions = []
    n_tenants = 10
    for i in range(n_tenants):
        s = eng.open_session(f"churn{i}")
        a, b, c = _inputs(i % 3)
        s.put("A", a), s.put("B", b), s.put("C", c)
        sessions.append(s)
    metrics.reset()
    reqs = [eng.submit(s, a="A", b="B", c="C", beta=0.0)
            for s in sessions]
    eng.start()
    for r in reqs:
        assert r.wait(120) and r.state == "done", r.info()
    eng.shutdown()
    # queue accounting: pop-at-zero leaves no idle-tenant residue
    assert eng.queue.tenant_load() == {}
    assert eng.queue._tenant_count == {}
    assert eng.queue._tenant_bytes == {}
    # engine latency/outcome windows: capped, oldest expired
    assert len(eng._lat) <= 4
    assert len(eng._counts) <= 4
    # attribution: capped rows + the evicted fold, books still balanced
    assert attribution.tenant_rows() <= 4
    usage = attribution.usage(top=3)
    assert attribution.EVICTED in usage["tenants"]
    assert usage["totals"]["requests"] == n_tenants
    _assert_conserved(attribution.conservation())
    assert len(usage["top"]) == 3
    for s in sessions:
        s.close()


# ------------------------------------------------- incident bundles

def _one_request(tag="inc-tenant"):
    from dbcsr_tpu.serve import product_cache as pc

    pc.clear()  # a content-addressed hit would (correctly) bill zero
    set_config(serve_coalesce=False)
    eng = serve.ServeEngine(start=True)
    s = eng.open_session(tag)
    a, b, c = _inputs(0)
    s.put("A", a), s.put("B", b), s.put("C", c)
    r = eng.submit(s, a="A", b="B", c="C", beta=0.0)
    assert r.wait(60) and r.state == "done", r.info()
    eng.shutdown()
    s.close()
    return r


def test_incident_bundle_rising_edge_once_and_doctor_renders(
        tmp_path, monkeypatch):
    """A health rising edge arms ONE incident bundle, assembled at the
    next timeseries boundary; an immediate second edge is rate-limited
    (suppressed, counted); the persisted JSONL replays through
    `doctor --bundle` with the health/usage/events sections intact."""
    monkeypatch.setenv("DBCSR_TPU_INCIDENTS", str(tmp_path))
    monkeypatch.setenv("DBCSR_TPU_TS_INTERVAL_S", "0")
    _one_request()
    # a REAL anomaly may have fired during the request (compile storms
    # from cold XLA caches, depending on what ran before this test) and
    # consumed the rate-limit interval — re-arm the incident budget
    # without touching the usage ledger the bundle must carry
    incidents.reset()

    def _counts():
        return dict((lab.get("result"), v) for lab, v in
                    metrics.counter_items(
                        "dbcsr_tpu_incident_bundles_total"))

    base = _counts()
    # the rising edge: health._fire is the one chokepoint every
    # detector funnels through — it must arm (not capture) the bundle
    health._fire("test_storm", "test_storm", {"rate": 9.9})
    assert incidents.pending() == "anomaly:test_storm"
    rec = ts.sample(reason="test_boundary")
    assert rec is not None
    assert incidents.pending() is None
    bundles = incidents.bundles()
    assert len(bundles) == 1
    path = bundles[0]["path"]
    assert path and os.path.exists(path)
    # an immediate second edge is inside the rate-limit interval
    health._fire("test_storm2", "test_storm2", {})
    assert incidents.pending() is None  # suppressed, not armed
    ts.sample(reason="test_boundary2")
    assert len(incidents.bundles()) == 1
    counts = _counts()
    assert counts.get("captured", 0) - base.get("captured", 0) == 1
    assert counts.get("suppressed", 0) - base.get("suppressed", 0) >= 1
    assert any(e.get("reason") == "anomaly:test_storm"
               for e in events.records(kind="incident_captured"))
    # offline replay: the typed JSONL through the doctor pipeline
    bundle = doctor.read_bundle(path)
    assert bundle["meta"]["reason"] == "anomaly:test_storm"
    assert bundle["health"]["status"] in ("OK", "DEGRADED", "CRITICAL")
    assert bundle["usage"]["totals"]["requests"] >= 1
    assert any(e.get("event") == "anomaly" for e in bundle["events"])
    report = doctor.analyze(bundle["health"], {}, bundle["events"],
                            bundle["flight"], [], [],
                            usage=bundle["usage"])
    assert report["usage"]["tenants"]["inc-tenant"]["requests"] == 1
    lines = []
    doctor.render(report, out=lines.append)
    assert any("tenant usage:" in ln for ln in lines)
    # the CLI path end to end
    rc = doctor.main(["--bundle", path, "--json"])
    assert rc == 0


def test_incident_memory_only_mode(monkeypatch):
    """DBCSR_TPU_INCIDENTS=0 keeps bundles in memory: no directory is
    created, the ring still fills."""
    monkeypatch.setenv("DBCSR_TPU_INCIDENTS", "0")
    monkeypatch.setenv("DBCSR_TPU_TS_INTERVAL_S", "0")
    incidents.trigger("anomaly:mem_only", {})
    ts.sample(reason="mem_boundary")
    bundles = incidents.bundles()
    assert len(bundles) == 1
    assert bundles[0]["path"] is None
    assert bundles[0]["bundle"]["meta"]["reason"] == "anomaly:mem_only"


# ------------------------------------------------- surfacing layers

def test_usage_endpoint_and_status_phase_breakdown():
    from dbcsr_tpu.obs import server
    from dbcsr_tpu.serve import product_cache as pc

    pc.clear()  # earlier tests may have cached these exact operands
    set_config(serve_coalesce=False)
    # /serve/status only sees the process-default engine
    eng = serve.get_engine()
    s = eng.open_session("http-usage")
    a, b, c = _inputs(0)
    s.put("A", a), s.put("B", b), s.put("C", c)
    metrics.reset()
    r = eng.submit(s, a="A", b="B", c="C", beta=0.0)
    assert r.wait(60) and r.state == "done", r.info()
    server.start(port=0)
    try:
        base = server.url()

        def get(route):
            with urllib.request.urlopen(base + route, timeout=10) as h:
                return json.loads(h.read().decode())

        usage = get("/usage?top=2")
        assert "http-usage" in usage["tenants"]
        row = usage["tenants"]["http-usage"]
        assert row["requests"] == 1 and row["flops"] > 0
        assert usage["top"][0]["tenant"] == "http-usage"
        assert usage["totals"]["device_seconds"] > 0
        status = get(f"/serve/status?request_id={r.request_id}")
        attr = status["attribution"]
        assert attr["tenant"] == "http-usage"
        assert "execute" in attr["phases_ms"]
        assert "queued" in attr["phases_ms"]
        assert attr["billed"]["flops"] == row["flops"]
        assert attr["terminal"] == "done"
    finally:
        server.stop()
        serve.shutdown()
        s.close()


def test_timeseries_collects_tenant_meters(monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_TS_INTERVAL_S", "0")
    _one_request(tag="ts-tenant")
    rec = ts.sample(reason="test_usage")
    assert rec is not None
    pts = [p for p in rec["points"]
           if p[0] == "dbcsr_tpu_tenant_device_seconds_total"]
    assert any(p[1].get("tenant") == "ts-tenant" and p[2] > 0
               for p in pts), rec["points"]


def test_metrics_reset_clears_attribution_layer():
    """`metrics.reset()` (include_stats=True) zeroes the ledger, the
    tenant rollups and the incident budget — same contract as the
    roofline/pool layers; include_stats=False keeps them."""
    r = _one_request(tag="reset-tenant")
    assert attribution.usage()["totals"]["requests"] == 1
    metrics.reset(include_stats=False)
    assert attribution.usage()["totals"]["requests"] == 1
    metrics.reset()
    u = attribution.usage()
    assert u["tenants"] == {} and u["totals"]["requests"] == 0
    assert attribution.request_info(r.request_id) is None
    assert attribution.ledger_size() == 0
    cons = attribution.conservation()
    assert cons["rollup"]["flops"] == 0  # re-baselined, not stale


def test_attribution_disabled_by_knob(monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_ATTRIBUTION", "0")
    _one_request(tag="off-tenant")
    u = attribution.usage()
    assert u["tenants"] == {} and u["totals"]["requests"] == 0


# ------------------------------------------------ offline artifacts

def test_committed_usage_rollup_feeds_report_and_doctor():
    """The committed USAGE_ROLLUP.jsonl must stay
    readable by `tools/usage_report.py` (req/s-per-worker emitted) and
    by the doctor's usage section — the artifact IS the interface."""
    path = os.path.join(REPO, "USAGE_ROLLUP.jsonl")
    assert os.path.exists(path), "USAGE_ROLLUP.jsonl not committed"
    rollup = usage_report.read_rollup(path)
    assert rollup["meta"].get("obs_schema", 0) >= 5
    assert rollup["tenants"] and rollup["totals"]
    assert int(rollup["totals"]["requests"]) > 0
    rep = usage_report.report(rollup, slo_ms=500.0)
    cap = rep["capacity"]
    assert cap["feasible"] and cap["req_per_s_per_worker"] > 0
    assert abs(sum(r["share"] for r in rep["tenants"]) - 1.0) < 0.01
    # the CLI end to end, machine-readable
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "usage_report.py"),
         "--rollup", path, "--json"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["capacity"]["req_per_s_per_worker"] > 0
    # the doctor reads the same artifact into its usage section
    usage = doctor.usage_from_rollup(path)
    report = doctor.analyze(None, {}, [], [], [], [], usage=usage)
    assert set(report["usage"]["tenants"]) == set(rollup["tenants"])


def test_usage_report_infeasible_slo(tmp_path):
    p = tmp_path / "roll.jsonl"
    p.write_text(
        json.dumps({"kind": "usage_meta", "obs_schema": 5}) + "\n"
        + json.dumps({"kind": "tenant_usage", "tenant": "a",
                      "device_seconds": 10.0, "requests": 1}) + "\n"
        + json.dumps({"kind": "usage_totals", "device_seconds": 10.0,
                      "requests": 1}) + "\n")
    rep = usage_report.report(usage_report.read_rollup(str(p)),
                              slo_ms=100.0)
    assert rep["capacity"]["feasible"] is False


def test_doctor_selftest_still_green():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "doctor.py"),
         "--selftest"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr


# ------------------------------------------------------ chaos entry

@pytest.mark.chaos
@pytest.mark.slow
def test_usage_storm_conserves_under_faults():
    """Tier-2 entry for the chaos corpus' usage_storm case: concurrent
    tenants under injected serve_admit/serve_execute/attribution
    faults — the case itself asserts exact conservation after the
    storm, and the checksum must match the clean leg."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chaos_suite

    entry = dict(chaos_suite.corpus())["usage_storm"]
    ref = chaos_suite._one_product(entry, seed=1234)
    from dbcsr_tpu.resilience import breaker

    breaker.reset_board()
    with faults.inject_faults(
            "serve_execute:raise,times=2;serve_admit:raise,times=2;"
            "attribution:raise,times=3"):
        out = chaos_suite._one_product(entry, seed=1234)
    assert abs(out - ref) <= 1e-11 * max(1.0, abs(ref))
