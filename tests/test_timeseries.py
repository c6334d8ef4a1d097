"""Telemetry history plane tests (`obs.timeseries` + `obs.slo` +
fleet federation): retention/downsampling determinism, live-vs-replay
query consistency, SLO burn on an injected serve latency regression
(surfaced by `doctor --trend` from the committed artifact), the shared
quantile/window and shard helpers, and a REAL 2-process world whose
``/cluster`` route and `tools/fleet.py` merge per-process telemetry
with correct provenance labels (mirroring `test_trace_multihost.py`).

All runnable under JAX_PLATFORMS=cpu (conftest forces it)."""

import json
import os
import socket
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import dbcsr_tpu as dt
from dbcsr_tpu.core import stats
from dbcsr_tpu.obs import (events, health, metrics, server, shard, slo,
                           timeseries as ts, windows)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))
import doctor  # noqa: E402
import fleet  # noqa: E402


def setup_function(_):
    metrics.reset()
    health.reset()
    events.clear()
    events.set_enabled(True)
    ts.reset()
    ts.set_enabled(True)
    slo.reset()


def _small_multiply(seed=0):
    rng = np.random.default_rng(seed)
    rbs = [4] * 6
    a = dt.make_random_matrix("A", rbs, rbs, occupation=0.5, rng=rng)
    b = dt.make_random_matrix("B", rbs, rbs, occupation=0.5, rng=rng)
    c = dt.create("C", rbs, rbs)
    dt.multiply("N", "N", 1.0, a, b, 0.0, c)
    return c


# ------------------------------------------------- retention/downsample

def test_downsample_tiers_deterministic():
    """Raw -> 1-min -> 10-min tiers bucket deterministically in the
    sample timestamps; gauge buckets carry min/max/mean, counter
    buckets the max-merged last."""
    t0 = 12_000.0  # bucket-aligned for readability
    for i in range(40):
        ts.ingest_points(t0 + 30 * i, [
            ("ctr", {"cell": "a"}, 10 * i, ts.COUNTER),
            ("g", {}, float(i % 5), ts.GAUGE),
        ])
    raw = ts.query("ctr")[0]
    assert raw["tier"] == "raw" and len(raw["points"]) == 40
    one_min = ts.query("ctr", tier=60)[0]
    # 40 samples at 30 s cadence = 20 one-minute buckets, two samples
    # each; the counter bucket surfaces the larger (later) value
    assert len(one_min["points"]) == 20
    assert one_min["points"][0] == [12_000.0, 10.0]
    assert one_min["points"][1] == [12_060.0, 30.0]
    ten_min = ts.query("ctr", tier=600)[0]
    assert len(ten_min["points"]) == 2
    assert ten_min["points"][0] == [12_000.0, 190.0]  # samples 0..19
    assert ten_min["points"][1] == [12_600.0, 390.0]
    # gauge tier points surface the bucket's last value; agg then
    # reduces across buckets (i=19 -> 19%5=4, i=39 -> 39%5=4)
    g600 = ts.query("g", tier=600, agg="mean")[0]
    assert g600["points"] == [[12_000.0, 4.0], [12_600.0, 4.0]]
    assert g600["value"] == 4.0


def test_monotone_counter_never_decreases_across_downsample():
    """The downsample invariant the autotuner's delta mining relies
    on: a nondecreasing raw counter yields nondecreasing 1-min and
    10-min series — even when a scrape lands out of order."""
    t0 = 50_000.0
    vals = [0, 5, 5, 12, 40, 40, 41, 90, 90, 130, 200, 201]
    times = [t0 + 25 * i for i in range(len(vals))]
    # one out-of-order pair inside a bucket (t arrives late)
    times[5], times[6] = times[6], times[5]
    for t, v in zip(times, vals):
        ts.ingest_points(t, [("mono", {}, v, ts.COUNTER)])
    for tier in (60, 600):
        pts = [v for _, v in ts.query("mono", tier=tier)[0]["points"]]
        assert pts == sorted(pts), (tier, pts)


def test_raw_retention_bounded(monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_TS_RAW_N", "16")
    ts.reset()  # new store picks up the env-sized rings
    for i in range(50):
        ts.ingest_points(1000.0 + i, [("b", {}, float(i), ts.GAUGE)])
    pts = ts.query("b")[0]["points"]
    assert len(pts) == 16
    assert pts[0] == [1034.0, 34.0] and pts[-1] == [1049.0, 49.0]
    # raw evicted, but the 1-min tier never did: an auto query whose
    # window predates the retained raw points must use the finest
    # COMPLETE tier, not fall to the coarsest (SLO windows would
    # otherwise starve to NO_DATA in young high-rate processes)
    q = ts.query("b", since=900.0, tier="auto")[0]
    assert q["tier"] == "60"
    assert [v for _, v in q["points"]] == [19.0, 49.0]  # 960/1020 buckets


def test_auto_tier_prefers_dense_raw_when_nothing_covers(monkeypatch):
    """High-rate store (raw ring spans less than the window): no tier
    fully covers `since`, and the fallback must pick the DENSEST
    candidate — hundreds of raw points beat one coarse bucket (the SLO
    windows would otherwise starve to NO_DATA)."""
    monkeypatch.setenv("DBCSR_TPU_TS_RAW_N", "64")
    ts.reset()
    t0 = 700_000.0
    for i in range(200):  # 0.5 s cadence; raw ring spans only ~32 s
        ts.ingest_points(t0 + 0.5 * i, [("hr", {}, float(i), ts.GAUGE)])
    q = ts.query("hr", since=t0 + 65)  # predates the retained raw
    assert q[0]["tier"] == "raw"
    assert len(q[0]["points"]) >= 50  # not one coarse bucket


# ------------------------------------------------- query live vs replay

def test_query_live_matches_shard_replay(tmp_path):
    """The interchangeability contract: a query over the live rings
    and over the persisted shard family answer identically — raw
    points, downsample tiers, label matching and aggregation."""
    base = str(tmp_path / "timeseries.jsonl")
    ts.enable_persist(base)
    try:
        t0 = 30_000.0
        for i in range(25):
            ts.ingest_points(t0 + 13 * i, [
                ("cell", {"driver": "xla", "dtype": "float64"},
                 3 * i, ts.COUNTER),
                ("cell", {"driver": "host", "dtype": "float32"},
                 7 * i, ts.COUNTER),
                ("lat", {"tenant": "a"}, 10.0 + (i % 3), ts.GAUGE),
            ])
    finally:
        ts.disable_persist()
    assert (tmp_path / "timeseries.p0.jsonl").exists()
    for kwargs in (
        dict(metric="cell"),
        dict(metric="cell", labels={"driver": "xla"}),
        dict(metric="cell", tier=60),
        dict(metric="cell", tier=600, agg="last"),
        dict(metric="lat", agg="mean"),
        dict(metric="lat", since=30_100.0, agg="rate"),
    ):
        live = ts.query(**kwargs)
        replay = ts.query(path=base, **kwargs)
        assert live == replay, kwargs
    assert len(ts.query("cell", path=base)) == 2
    only_xla = ts.query("cell", labels={"driver": "xla"}, path=base)
    assert len(only_xla) == 1
    assert only_xla[0]["labels"]["driver"] == "xla"


def test_query_relative_since_and_agg_errors():
    import time as _time

    now = _time.time()
    for i in range(10):
        ts.ingest_points(now - 100 + 10 * i, [("m", {}, i, ts.GAUGE)])
    recent = ts.query("m", since=-35)[0]["points"]
    assert len(recent) in (3, 4)  # the last ~35 s of a 10 s cadence
    with pytest.raises(ValueError):
        ts.query("m", agg="nope")
    with pytest.raises(ValueError):
        ts.query("m", tier=77)


# --------------------------------------------------- engine integration

def test_real_multiply_samples_cells(monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_TS_INTERVAL_S", "0")
    _small_multiply()
    names = {s["metric"] for s in ts.series_list()}
    assert {"dbcsr_tpu_cell_flops_total", "dbcsr_tpu_multiplies_total",
            "dbcsr_tpu_health_status",
            "dbcsr_tpu_slo_burn_rate"} <= names
    cells = ts.query("dbcsr_tpu_cell_flops_total")
    assert cells, "no (mnk, driver, dtype) cell sampled"
    lbl = cells[0]["labels"]
    assert set(lbl) == {"mnk", "driver", "dtype"}
    assert lbl["mnk"].count("x") == 2
    # health status series covers every component incl. the new slo
    comps = {s["labels"]["component"]
             for s in ts.query("dbcsr_tpu_health_status")}
    assert {"overall", "drivers", "engine", "perf", "integrity",
            "slo", "watchdog"} <= comps


def test_cadence_gates_sampling(monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_TS_INTERVAL_S", "3600")
    _small_multiply(seed=1)  # first boundary always samples
    n1 = ts.query("dbcsr_tpu_multiplies_total")[0]["points"]
    _small_multiply(seed=2)  # inside the hour: gated
    n2 = ts.query("dbcsr_tpu_multiplies_total")[0]["points"]
    assert len(n2) == len(n1) == 1


def test_health_transition_forces_sample(monkeypatch, tmp_path):
    """An anomaly rising edge requests a forced sample; the next
    product boundary takes it despite the cadence, and the persisted
    record names the transition as its reason."""
    monkeypatch.setenv("DBCSR_TPU_TS_INTERVAL_S", "3600")
    base = str(tmp_path / "timeseries.jsonl")
    ts.enable_persist(base)
    try:
        _small_multiply(seed=1)  # first boundary: the interval sample
        for i in range(12):  # recompile storm -> _fire -> request_sample
            metrics.record_jit("fn", ("shape", i))
            health.observe_multiply(dur_ms=1.0)
        _small_multiply(seed=2)  # gated by cadence, taken by the force
    finally:
        ts.disable_persist()
    recs = [json.loads(ln) for ln in
            open(str(tmp_path / "timeseries.p0.jsonl"))]
    reasons = [r["reason"] for r in recs]
    # a forced sample was taken at the health transition (the reason
    # keeps the LATEST transition when several fire before a boundary
    # — the real multiply's own latency spike may overwrite the storm)
    assert any(r.startswith("anomaly:") for r in reasons), reasons


def test_broken_registered_collector_never_drops_the_sample(monkeypatch):
    """A registered collector returning a malformed point (or raising)
    must cost only its own points — the built-in collectors' output
    still lands in the rings and the shard."""
    monkeypatch.setenv("DBCSR_TPU_TS_INTERVAL_S", "0")
    ts.register_collector(lambda: [("bad", {}, None, ts.GAUGE),
                                   ("bad_labels", None, 2.0, ts.GAUGE),
                                   ("bad_labels2", 3, 2.0, ts.GAUGE),
                                   ("good_extra", {}, 7.0, ts.GAUGE)])
    ts.register_collector(lambda: (_ for _ in ()).throw(RuntimeError()))
    rec = ts.sample(reason="test")
    assert rec is not None
    names = {s["metric"] for s in ts.series_list()}
    assert "good_extra" in names and "bad" not in names
    assert "bad_labels2" not in names  # non-dict labels dropped
    assert "bad_labels" in names       # None labels coerce to {}
    assert "dbcsr_tpu_health_status" in names  # built-ins survived
    assert all(isinstance(p[2], float) for p in rec["points"])


def test_disabled_store_is_noop(monkeypatch):
    ts.set_enabled(False)
    try:
        _small_multiply()
        assert ts.series_list() == []
        assert ts.maybe_sample() is None and ts.sample() is None
        v = health.verdict()
        assert v["components"]["slo"]["status"] == "OK"
        assert any("DBCSR_TPU_TS=0" in r
                   for r in v["components"]["slo"]["reasons"])
    finally:
        ts.set_enabled(True)


# ----------------------------------------------------------------- SLO

def _ingest_latency(t0, n, p95_ms, step=15.0):
    for i in range(n):
        ts.ingest_points(t0 + step * i, [
            ("dbcsr_tpu_serve_latency_p95_ms", {"tenant": "alice"},
             p95_ms, ts.GAUGE)])


def test_slo_burn_rises_and_rearms(monkeypatch):
    import time as _time

    monkeypatch.setenv("DBCSR_TPU_SLO_SERVE_P95_MS", "100")
    # wall-anchored synthetic times: health's slo component treats a
    # cache older than the long window (wall clock) as stale and
    # re-evaluates — far-past timestamps would read as drained windows
    t0 = _time.time()
    _ingest_latency(t0, 45, p95_ms=500.0)  # 45*15s = both windows bad
    now = t0 + 45 * 15
    pts = slo.collect(now=now)
    burn = {lb["objective"]: v for _, lb, v, _ in pts}
    assert burn["serve_p95_latency"] > 1.0
    ev = events.records(kind="slo_burn")
    assert len(ev) == 1 and ev[0]["objective"] == "serve_p95_latency"
    assert metrics.counter("dbcsr_tpu_slo_burn_total").value(
        objective="serve_p95_latency") == 1
    # rising edge only: still burning -> no second event
    _ingest_latency(now, 5, p95_ms=500.0)
    slo.collect(now=now + 5 * 15)
    assert len(events.records(kind="slo_burn")) == 1
    # health: every sample bad = burn 10x, past the 8x sustained-burn
    # escalation -> the slo component goes CRITICAL with both reasons
    v = health.verdict()
    assert v["components"]["slo"]["status"] == "CRITICAL"
    assert any("serve_p95_latency" in r
               for r in v["components"]["slo"]["reasons"])
    assert any("sustained burn" in r
               for r in v["components"]["slo"]["reasons"])
    # recovery over both windows re-arms the edge, then re-fires
    t1 = now + 5 * 15
    _ingest_latency(t1, 45, p95_ms=10.0)
    slo.collect(now=t1 + 45 * 15)
    assert health.verdict()["components"]["slo"]["status"] == "OK"
    t2 = t1 + 45 * 15
    _ingest_latency(t2, 45, p95_ms=900.0)
    slo.collect(now=t2 + 45 * 15)
    assert len(events.records(kind="slo_burn")) == 2


def test_slo_short_spike_does_not_burn(monkeypatch):
    """The multi-window contract: a burst that breaches only the short
    window never alerts."""
    monkeypatch.setenv("DBCSR_TPU_SLO_SERVE_P95_MS", "100")
    t0 = 200_000.0
    _ingest_latency(t0, 40, p95_ms=10.0)           # long window healthy
    t1 = t0 + 40 * 15
    _ingest_latency(t1, 4, p95_ms=900.0, step=10)  # 40 s spike
    ev = slo.evaluate(now=t1 + 40)
    row = ev["serve_p95_latency"]
    assert row["burn_short"] > 1.0 and row["burn_long"] <= 1.0
    assert row["status"] == "OK"
    slo.collect(now=t1 + 40)
    assert events.records(kind="slo_burn") == []


def test_slo_counter_ratio_objective():
    t0 = 300_000.0
    for i in range(45):
        ts.ingest_points(t0 + 15 * i, [
            ("dbcsr_tpu_serve_requests_total",
             {"tenant": "a", "outcome": "admitted"}, 10 * i, ts.COUNTER),
            # terminal outcomes re-count the same requests: the
            # denominator must NOT include them (a completed request
            # would otherwise count twice and halve the burn)
            ("dbcsr_tpu_serve_requests_total",
             {"tenant": "a", "outcome": "done"}, 8 * i, ts.COUNTER),
            ("dbcsr_tpu_serve_requests_total",
             {"tenant": "a", "outcome": "shed"}, 2 * i, ts.COUNTER),
            ("dbcsr_tpu_serve_shed_total",
             {"tenant": "a", "reason": "quota_inflight"}, 2 * i,
             ts.COUNTER)])
    ev = slo.evaluate(now=t0 + 45 * 15)
    row = ev["serve_errors"]
    # 2 sheds per 12 submissions (10 admitted + 2 shed; the 8 "done"
    # re-counts are excluded) = 1/6 bad >> the 5% budget
    assert row["detail"]["total"] == pytest.approx(
        row["detail"]["bad"] * 6)
    assert row["status"] == "BURNING" and row["burn"] > 1.0


def test_slo_no_data_is_ok():
    ev = slo.evaluate(now=1_000.0)
    assert all(row["status"] == "NO_DATA" for row in ev.values())
    slo.collect(now=1_000.0)
    assert health.verdict()["components"]["slo"]["status"] == "OK"


def test_injected_latency_regression_end_to_end(monkeypatch, tmp_path):
    """The acceptance pin: a REAL serve workload whose latency breaches
    the objective drives an ``slo_burn`` event + ``slo`` health
    DEGRADED, and ``doctor --trend`` surfaces the burn from the
    committed shard artifact alone."""
    from dbcsr_tpu import serve

    monkeypatch.setenv("DBCSR_TPU_SLO_SERVE_P95_MS", "0.0001")
    # every sample violates -> bad fraction 1.0; budget 0.5 keeps the
    # burn at 2x: the acceptance pin is DEGRADED, not the 8x CRITICAL
    # escalation the default 10% budget would produce
    monkeypatch.setenv("DBCSR_TPU_SLO_SERVE_P95_BUDGET", "0.5")
    monkeypatch.setenv("DBCSR_TPU_TS_INTERVAL_S", "3600")
    base = str(tmp_path / "timeseries.jsonl")
    ts.enable_persist(base)
    eng = serve.get_engine()
    sess = eng.open_session("reg-tenant")
    try:
        rng = np.random.default_rng(3)
        rbs = [4] * 6
        sess.put("A", dt.make_random_matrix("A", rbs, rbs,
                                            occupation=0.5, rng=rng),
                 adopt=False)
        sess.put("B", dt.make_random_matrix("B", rbs, rbs,
                                            occupation=0.5, rng=rng),
                 adopt=False)
        sess.put("C", dt.create("C", rbs, rbs))
        for _ in range(4):  # real requests; any latency > 0.0001 ms
            req = eng.submit(sess, a="A", b="B", c="C", beta=0.0)
            assert req.wait(timeout=60) and req.state == "done"
        # sample the real store across both SLO windows with explicit
        # ascending timestamps (anchored at wall clock: the request
        # boundaries may already have taken a sample "now", and the
        # downsample tiers drop points older than their open bucket)
        import time as _time

        t0 = _time.time()
        for i in range(45):
            ts.sample(now=t0 + 15 * i, reason="test")
    finally:
        sess.close()
        serve.shutdown()
        ts.disable_persist()
    ev = events.records(kind="slo_burn")
    assert any(e["objective"] == "serve_p95_latency" for e in ev)
    v = health.verdict()
    assert v["components"]["slo"]["status"] == "DEGRADED"
    assert v["status"] in ("DEGRADED", "CRITICAL")
    # ...and the committed artifact alone surfaces it
    trend = doctor.trend_from_artifacts(base)
    row = trend["slo"]["serve_p95_latency"]
    assert row["status"] == "BURNING" and row["burn"] > 1.0
    lines = []
    doctor.render_trend(trend, out=lines.append)
    assert any("serve_p95_latency" in ln and "BURNING" in ln
               for ln in lines)
    # the full doctor report carries the slo hint from the bus events
    report = doctor.analyze(v, {}, events.records(), [], [], [])
    assert "serve_p95_latency" in report["slo_burning"]
    assert any(h["kind"] == "slo_burn" for h in report["hints"])


def test_slo_stale_cache_ages_out(monkeypatch):
    """An idle process must not serve a past burn as CRITICAL forever:
    sampling is boundary-driven, so `component()` re-evaluates a cache
    older than the long window instead of pinning /healthz at 503."""
    import time as _time

    monkeypatch.setenv("DBCSR_TPU_SLO_SERVE_P95_MS", "100")
    t0 = _time.time() - 2000  # the whole burn lies in the past
    _ingest_latency(t0, 45, p95_ms=900.0)
    slo.collect(now=t0 + 45 * 15)  # caches a CRITICAL-grade burn
    assert slo.burning()
    comp = slo.component()  # cache is >long-window old: re-evaluated
    assert comp["status"] == "OK"
    assert health.verdict()["components"]["slo"]["status"] == "OK"


def test_slo_burn_never_closes_admission(monkeypatch):
    """The feedback-loop pin: an SLO-burn CRITICAL pages (/healthz
    503s, fleet routing reacts) but must NOT shed new submissions —
    for the serve error budget a shed IS the bad event, so a
    burn-driven shed would lock the plane shut with no exit."""
    from dbcsr_tpu import serve

    import time as _time

    monkeypatch.setenv("DBCSR_TPU_SLO_SERVE_P95_MS", "100")
    t0 = _time.time()  # wall-anchored (see test_slo_burn_rises_and_rearms)
    _ingest_latency(t0, 45, p95_ms=900.0)  # burn 10x >= 8x critical
    slo.collect(now=t0 + 45 * 15)
    v = health.verdict()
    assert v["components"]["slo"]["status"] == "CRITICAL"
    assert v["status"] == "CRITICAL"
    # ...but admission keys on the non-slo components only
    assert health.admission_status() == "OK"
    eng = serve.get_engine()
    sess = eng.open_session("burning-tenant")
    try:
        rng = np.random.default_rng(5)
        rbs = [4] * 4
        sess.put("A", dt.make_random_matrix("A", rbs, rbs,
                                            occupation=0.5, rng=rng),
                 adopt=False)
        sess.put("B", dt.make_random_matrix("B", rbs, rbs,
                                            occupation=0.5, rng=rng),
                 adopt=False)
        sess.put("C", dt.create("C", rbs, rbs))
        req = eng.submit(sess, a="A", b="B", c="C", beta=0.0)
        assert req.wait(timeout=60) and req.state == "done", req.info()
    finally:
        sess.close()
        serve.shutdown()


# ----------------------------------------------------- shared utilities

def test_windows_quantiles_pin_serve_convention():
    rng = np.random.default_rng(7)
    for n in (1, 2, 5, 19, 100, 512):
        xs = sorted(rng.uniform(0, 100, n).tolist())
        # the exact historical /serve/tenants formulas
        assert windows.rank_quantile(xs, 0.5) == xs[len(xs) // 2]
        assert windows.rank_quantile(xs, 0.95) == \
            xs[min(len(xs) - 1, int(len(xs) * 0.95))]
        p50, p95 = windows.p50_p95(list(reversed(xs)))
        assert (p50, p95) == (xs[len(xs) // 2],
                              xs[min(len(xs) - 1, int(len(xs) * 0.95))])
    # health re-exports the one median/MAD implementation
    assert health.median is windows.median
    assert health.mad is windows.mad
    assert windows.median([1, 2, 3, 4]) == 2.5
    assert windows.mad([1, 1, 4]) == 0.0 or True  # convention smoke
    assert windows.mad([1, 2, 9]) == 1.0


def test_serve_tenants_p50_p95_unchanged():
    """The dedup pin: /serve/tenants reports the same quantiles the
    engine's private sorted-index logic always produced."""
    from dbcsr_tpu import serve

    eng = serve.get_engine()
    sess = eng.open_session("quant-tenant")
    try:
        import collections as _c

        lats = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0]
        with eng._slock:
            eng._lat["quant-tenant"] = _c.deque(lats, maxlen=512)
            eng._counts.setdefault("quant-tenant", _c.Counter())["done"] = 1
        metrics.counter(
            "dbcsr_tpu_serve_requests_total",
            "").inc(tenant="quant-tenant", outcome="done")
        tenants = eng.tenants()
        xs = sorted(lats)
        assert tenants["quant-tenant"]["p50_ms"] == round(
            xs[len(xs) // 2], 3)
        assert tenants["quant-tenant"]["p95_ms"] == round(
            xs[min(len(xs) - 1, int(len(xs) * 0.95))], 3)
    finally:
        sess.close()
        serve.shutdown()


def test_one_shard_contract_implementation():
    """Satellite pin: tracer, events and timeseries share obs.shard
    instead of three private copies."""
    from dbcsr_tpu.obs import tracer

    assert tracer.shard_path is shard.shard_path
    assert tracer._process_index is shard.process_index
    assert shard.shard_path("t.jsonl", 3) == "t.p3.jsonl"
    tag = shard.provisional_tag()
    assert tag.startswith("tmp") and str(os.getpid()) in tag


def test_shard_settle_appends_not_clobbers(tmp_path):
    base = str(tmp_path / "x.jsonl")
    final = tmp_path / "x.p0.jsonl"
    final.write_text("existing\n")
    prov = tmp_path / "x.ptmphost-1.jsonl"
    prov.write_text("fresh\n")
    fh = open(prov, "a")
    new_path, new_fh = shard.settle(base, str(prov), fh, 0)
    new_fh.close()
    assert new_path == str(final)
    assert final.read_text() == "existing\nfresh\n"
    assert not prov.exists()


# ------------------------------------------------------------ endpoint

@pytest.fixture
def endpoint():
    s = server.start(port=0)
    assert s is not None
    yield server.url()
    server.stop()


def _get(url, route):
    try:
        with urllib.request.urlopen(url + route, timeout=30) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_endpoint_timeseries_and_slo(endpoint, monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_TS_INTERVAL_S", "0")
    _small_multiply()
    code, body = _get(endpoint, "/timeseries")
    assert code == 200
    names = {s["metric"] for s in json.loads(body)}
    assert "dbcsr_tpu_cell_flops_total" in names
    code, body = _get(
        endpoint, "/timeseries?metric=dbcsr_tpu_cell_flops_total"
                  "&agg=last&dtype=float64")
    assert code == 200
    sers = json.loads(body)
    assert sers and all(s["labels"]["dtype"] == "float64" for s in sers)
    assert all(s["value"] > 0 for s in sers)
    code, body = _get(endpoint, "/slo")
    assert code == 200
    doc = json.loads(body)
    assert set(doc["objectives"]) >= {
        "serve_p95_latency", "serve_errors", "roofline_floor",
        "abft_unrecovered"}
    assert doc["component"]["status"] in ("OK", "DEGRADED", "CRITICAL")


def test_endpoint_cluster_single_process(endpoint, monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_TS_INTERVAL_S", "0")
    _small_multiply()
    port = server.get().port
    code, text = _get(endpoint, f"/cluster?ports={port}")
    assert code == 200
    assert f'dbcsr_tpu_cluster_peer_up{{process="0",' \
           f'endpoint="http://127.0.0.1:{port}"}} 1' in text
    mult = [ln for ln in text.splitlines()
            if ln.startswith("dbcsr_tpu_multiplies_total{")]
    assert mult and all('process="0"' in ln for ln in mult)
    # every sample line got the provenance labels
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert 'process="' in line, line
    code, body = _get(endpoint, f"/cluster?ports={port}&format=json")
    doc = json.loads(body)
    assert doc["reachable"] == 1
    assert doc["processes"]["0"]["components"]["slo"] in (
        "OK", "DEGRADED")
    # an unreachable peer shows up as down instead of vanishing
    code, text = _get(endpoint, f"/cluster?ports={port},1")
    assert 'dbcsr_tpu_cluster_peer_up{process="1",' \
           'endpoint="http://127.0.0.1:1"} 0' in text


# ----------------------------------------------- 2-process federation

_WORKER = r'''
import json, sys, time, urllib.request
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
port, pid, obs_base = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
import numpy as np
import dbcsr_tpu as dt
from dbcsr_tpu.obs import server, timeseries as ts
from dbcsr_tpu.parallel import multihost
# env activation (DBCSR_TPU_TS is in the environment) opened a
# provisional shard at import; init_multihost must rebind it
assert ts.persist_active(), "DBCSR_TPU_TS did not activate the sink"
ok = multihost.init_multihost(f"localhost:{{port}}", 2, pid)
assert ok and multihost.process_count() == 2
assert ts.persist_path().endswith(f".p{{pid}}.jsonl"), ts.persist_path()
s = server.start(port=obs_base)  # binds obs_base + process_index
assert s is not None and s.port == obs_base + pid, (s and s.port)
rng = np.random.default_rng(pid)
rbs = [4] * 4
a = dt.make_random_matrix("A", rbs, rbs, occupation=0.6, rng=rng)
b = dt.make_random_matrix("B", rbs, rbs, occupation=0.6, rng=rng)
c = dt.create("C", rbs, rbs)
dt.multiply("N", "N", 1.0, a, b, 0.0, c)
ts.sample(reason="worker")

from jax._src import distributed
client = distributed.global_state.client
client.wait_at_barrier("ts_sampled", 60_000)  # both endpoints live+sampled
if pid == 0:
    ports = f"{{obs_base}},{{obs_base + 1}}"
    text = ""
    for _ in range(60):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{{obs_base}}/cluster?ports={{ports}}",
                timeout=10) as r:
            text = r.read().decode()
        ups = [ln for ln in text.splitlines()
               if ln.startswith("dbcsr_tpu_cluster_peer_up{{") and
               ln.endswith(" 1")]
        if len(ups) == 2:
            break
        time.sleep(0.5)
    assert len(ups) == 2, text[:2000]
    mult = [ln for ln in text.splitlines()
            if ln.startswith("dbcsr_tpu_multiplies_total{{")]
    assert any('process="0"' in ln for ln in mult), mult
    assert any('process="1"' in ln for ln in mult), mult
    with urllib.request.urlopen(
            f"http://127.0.0.1:{{obs_base}}/cluster?ports={{ports}}"
            f"&format=json", timeout=10) as r:
        doc = json.loads(r.read().decode())
    assert doc["reachable"] == 2, doc
    print("CLUSTER OK")
client.wait_at_barrier("cluster_checked", 60_000)
ts.disable_persist()
server.stop()
print(f"WORKER{{pid}} OK shard={{ts.persist_path()}}")
multihost.shutdown_multihost()
'''


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_world(worker, ts_base, attempt_timeout):
    port = _free_port()
    obs_base = _free_port()
    env = dict(os.environ, DBCSR_TPU_TS=ts_base,
               DBCSR_TPU_TS_INTERVAL_S="0")
    env.pop("JAX_PLATFORMS", None)  # worker sets the platform itself
    env.pop("DBCSR_TPU_OBS_PORT", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(port), str(i),
             str(obs_base)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env=env, text=True,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=attempt_timeout)[0])
    except subprocess.TimeoutExpired:
        outs = None  # port race / hung join: caller may retry
    finally:
        for p in procs:
            p.kill()
        for p in procs:
            try:
                p.communicate(timeout=10)
            except Exception:
                pass
    return procs, outs


def test_two_process_cluster_and_fleet_merge(tmp_path):
    """A REAL 2-process world: each rank persists its own timeseries
    shard (rebinding at init_multihost), serves its own endpoint on
    the port-offset scheme, and rank 0's ``/cluster`` merges both
    ranks' metrics into one exposition with per-process provenance;
    afterwards `tools/fleet.py` merges the committed shards offline
    with the same labels."""
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER.format(repo=_REPO))
    base = str(tmp_path / "timeseries.jsonl")
    procs, outs = _run_world(worker, base, attempt_timeout=120)
    if outs is None:
        procs, outs = _run_world(worker, base, attempt_timeout=240)
    assert outs is not None, "world never formed (twice)"
    for i, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{o[-3000:]}"
    assert "CLUSTER OK" in outs[0]

    shard0 = tmp_path / "timeseries.p0.jsonl"
    shard1 = tmp_path / "timeseries.p1.jsonl"
    assert shard0.exists() and shard1.exists(), sorted(
        p.name for p in tmp_path.iterdir())
    # no provisional leftovers: every shard settled on its final name
    assert not [p.name for p in tmp_path.iterdir() if ".ptmp" in p.name]

    # offline federation: fleet.py merges the shard family with
    # per-process provenance
    merged = fleet.merge_shards(base)
    assert set(merged) == {"0", "1"}
    for proc, series in merged.items():
        mets = {m for m, _ in series}
        assert "dbcsr_tpu_multiplies_total" in mets, (proc, mets)
        assert "dbcsr_tpu_cell_flops_total" in mets
    # the query API reads the same family (per-process series merged
    # by labels — both ranks' multiply counters are present)
    assert ts.query("dbcsr_tpu_multiplies_total", path=base)
    # the fleet CLI smoke: table + json modes
    rc = fleet.main(["--timeseries", base])
    assert rc == 0
    rc = fleet.main(["--timeseries", base, "--json"])
    assert rc == 0
    # doctor --trend reads the same artifacts
    trend = doctor.trend_from_artifacts(base)
    assert set(trend["processes"]) == {"0", "1"}


# --------------------------------------------------------------- tools

def test_fleet_sparkline_and_relabel():
    assert fleet.sparkline([]) == ""
    assert fleet.sparkline([1.0]) == "▁"
    sp = fleet.sparkline([0, 5, 10])
    assert sp[0] == "▁" and sp[-1] == "█" and len(sp) == 3
    assert len(fleet.sparkline(list(range(200)))) == 24
    lines = fleet.relabel_prometheus(
        'a_total{x="1"} 5\nb_gauge 2\n# HELP a_total h',
        {"process": "3"})
    assert 'a_total{x="1",process="3"} 5' in lines
    assert 'b_gauge{process="3"} 2' in lines
    assert "# HELP a_total h" in lines


def test_doctor_trend_cli_offline(tmp_path, capsys):
    with open(tmp_path / "ts.p0.jsonl", "w") as fh:
        for i in range(5):
            fh.write(json.dumps({
                "seq": i + 1, "t": 1000.0 + i,
                "reason": "interval",
                "points": [["dbcsr_tpu_roofline_fraction",
                            {"driver": "xla"}, 0.1 * i, "gauge"]],
            }) + "\n")
    rc = doctor.main(["--trend", "--timeseries",
                      str(tmp_path / "ts.jsonl")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "driver=xla" in out and "dbcsr_tpu_roofline_fraction" in out
    rc = doctor.main(["--trend", "--timeseries",
                      str(tmp_path / "nothing.jsonl")])
    assert rc == 2


def test_doctor_trend_committed_rollup_artifact():
    """The committed TELEMETRY_ROLLUP.jsonl artifact stays readable:
    doctor --trend must surface real per-cell history and the SLO
    summary from it."""
    path = os.path.join(_REPO, "TELEMETRY_ROLLUP.jsonl")
    assert os.path.exists(path), "committed telemetry rollup missing"
    meta = json.loads(open(path).readline())
    assert meta["obs_schema"] >= 4
    trend = doctor.trend_from_artifacts(path)
    rows = trend["processes"]["0"]
    mets = {r["metric"] for r in rows}
    assert "dbcsr_tpu_cell_flops_total" in mets
    assert "dbcsr_tpu_serve_latency_p95_ms" in mets
    assert trend["slo"], "no slo burn series in the committed artifact"
    lines = []
    doctor.render_trend(trend, out=lines.append)
    assert any("slo burn summary" in ln for ln in lines)
