"""Observability subsystem tests (`dbcsr_tpu.obs`): span tracer
(JSONL + Chrome-trace export), metrics registry (snapshot / Prometheus
text / JIT-recompile counters), flight recorder (ring bound,
error-dump), and the `tools/trace_summary.py` smoke path.

All runnable under JAX_PLATFORMS=cpu (conftest forces it)."""

import json
import os
import sys

import numpy as np
import pytest

import dbcsr_tpu as dt
from dbcsr_tpu import obs
from dbcsr_tpu.core import stats, timings
from dbcsr_tpu.core.config import set_config
from dbcsr_tpu.obs import flight, metrics

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import trace_summary  # noqa: E402


@pytest.fixture
def trace(tmp_path):
    """An enabled trace session; always disabled afterwards.  Yields
    the FINAL shard path (the base path sharded to process index 0 —
    obs.tracer writes per-process shards since PR 2)."""
    base = str(tmp_path / "trace.jsonl")
    obs.enable_trace(base)
    yield obs.shard_path(base, 0)
    obs.disable_trace()


def setup_function(_):
    timings.reset()
    stats.reset()


def _read_jsonl(path):
    return [json.loads(ln) for ln in open(path) if ln.strip()]


def _small_multiply(seed=0, occ=0.5, **kwargs):
    rng = np.random.default_rng(seed)
    rbs = [4] * 6
    a = dt.make_random_matrix("A", rbs, rbs, occupation=occ, rng=rng)
    b = dt.make_random_matrix("B", rbs, rbs, occupation=occ, rng=rng)
    c = dt.create("C", rbs, rbs)
    dt.multiply("N", "N", 1.0, a, b, 0.0, c, **kwargs)
    return c


# ---------------------------------------------------------------- tracer

def test_span_nesting_and_attributes(trace):
    with timings.timed("outer"):
        obs.annotate(role="outer-attr", n=3)
        with timings.timed("inner"):
            obs.annotate(role="inner-attr")
        obs.trace_add("bytes", 10)
        obs.trace_add("bytes", 32)
    obs.disable_trace()
    spans = {r["name"]: r for r in _read_jsonl(trace) if r["ev"] == "span"}
    assert spans["inner"]["depth"] == 1 and spans["outer"]["depth"] == 0
    # inner completes first (JSONL order is completion order)
    names = [r["name"] for r in _read_jsonl(trace) if r["ev"] == "span"]
    assert names.index("inner") < names.index("outer")
    assert spans["outer"]["attrs"] == {"role": "outer-attr", "n": 3,
                                       "bytes": 42}
    assert spans["inner"]["attrs"] == {"role": "inner-attr"}
    # nesting containment in time
    o, i = spans["outer"], spans["inner"]
    assert o["ts_us"] <= i["ts_us"]
    assert i["ts_us"] + i["dur_us"] <= o["ts_us"] + o["dur_us"] + 1.0


def test_trace_off_is_noop(tmp_path):
    """With no tracer, timed()/annotate cost one attribute check and
    record nothing (the <2% off-path overhead contract)."""
    assert not obs.trace_enabled()
    with timings.timed("untraced"):
        obs.annotate(ignored=1)
        obs.instant("ignored")
    assert timings._stats["untraced"].calls == 1  # timer still works


def test_jsonl_and_chrome_trace_roundtrip(trace):
    _small_multiply()
    obs.disable_trace()
    recs = _read_jsonl(trace)
    assert recs[0]["ev"] == "meta"
    spans = [r for r in recs if r["ev"] == "span"]
    assert {"multiply", "multiply_stacks"} <= {s["name"] for s in spans}
    # chrome trace: valid trace_event schema Perfetto accepts
    doc = json.load(open(trace + ".chrome.json"))
    evs = doc["traceEvents"]
    assert evs, "empty chrome trace"
    for e in evs:
        assert e["ph"] in ("X", "i")
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["name"], str) and "pid" in e and "tid" in e
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        else:
            assert e["s"] in ("t", "p", "g")
    # the span attrs (mnk) made it into the chrome args
    mult = [e for e in evs if e["name"] == "multiply" and e["ph"] == "X"]
    assert mult and mult[0]["args"]["m"] == 24


def test_stack_and_comm_instants_in_trace(trace):
    stats.record_stack(4, 4, 4, 7, driver="xla")
    stats.record_comm("ppermute", 2, 4096)
    obs.disable_trace()
    inst = {r["name"]: r for r in _read_jsonl(trace) if r["ev"] == "instant"}
    assert inst["stack"]["args"] == {"mnk": "4x4x4", "entries": 7,
                                     "driver": "xla"}
    assert inst["comm:ppermute"]["args"] == {"messages": 2, "bytes": 4096}


def test_perf_input_run_produces_valid_chrome_trace(trace):
    """Acceptance: a tests/inputs/*.perf run under DBCSR_TPU_TRACE
    yields a Perfetto-loadable trace and a metrics snapshot with
    per-driver flops, comm bytes, and >= 1 recompile counter."""
    from dbcsr_tpu.perf.driver import parse_perf_file, run_perf

    metrics.reset()
    cfg = parse_perf_file(os.path.join(
        os.path.dirname(__file__), "inputs", "test_square_sparse.perf"))
    cfg.nrep = 1
    # force the XLA stack driver: the tuned CPU table routes these
    # blocks to the native host driver, which has no XLA jit cache to
    # count — the recompile-counter assertion needs a jitted driver
    set_config(mm_driver="xla")
    try:
        run_perf(cfg, verbose=False, n_devices=1)
    finally:
        set_config(mm_driver="auto")
    # run_perf flushes the tracer without needing disable/atexit
    doc = json.load(open(trace + ".chrome.json"))
    assert any(e["name"] == "multiply" for e in doc["traceEvents"])
    assert all("ph" in e and "ts" in e for e in doc["traceEvents"])
    snap = metrics.snapshot()
    assert snap["flops_by_driver"], "no per-driver flops in snapshot"
    assert "comm" in snap  # comm bytes dict (empty on single-chip)
    assert sum(d["compiles"] for d in snap["jit"].values()) >= 1


# --------------------------------------------------------------- metrics

def test_metrics_counter_gauge_histogram():
    metrics.reset()
    metrics.counter("t_total", "help").inc(driver="xla")
    metrics.counter("t_total").inc(3, driver="xla")
    metrics.gauge("t_gauge").set(1.5, kind="x")
    h = metrics.histogram("t_hist", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    assert metrics.counter("t_total").value(driver="xla") == 4
    assert metrics.gauge("t_gauge").value(kind="x") == 1.5
    text = metrics.prometheus_text()
    assert 't_total{driver="xla"} 4' in text
    assert 't_gauge{kind="x"} 1.5' in text
    assert 't_hist_bucket{le="1.0"} 1' in text
    assert 't_hist_bucket{le="+Inf"} 3' in text
    assert "t_hist_sum 55.5" in text and "t_hist_count 3" in text
    # TYPE lines for scrapers
    assert "# TYPE t_total counter" in text
    assert "# TYPE t_hist histogram" in text


def test_histogram_prometheus_exposition_cumulative():
    """Histogram exposition follows the Prometheus contract: bucket
    counts are CUMULATIVE over increasing ``le`` bounds, the +Inf
    bucket equals _count, and _sum/_count close each labeled series."""
    metrics.reset()
    h = metrics.histogram("t_lat_seconds", "latencies",
                          buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v, op="mm")
    h.observe(0.01, op="tr")
    text = metrics.prometheus_text()
    lines = [ln for ln in text.splitlines() if ln.startswith("t_lat_")]

    def bucket(op, le):
        (hit,) = [ln for ln in lines
                  if f'le="{le}"' in ln and f'op="{op}"' in ln]
        return int(hit.rsplit(" ", 1)[1])

    assert [bucket("mm", le) for le in ("0.1", "1.0", "10.0", "+Inf")] \
        == [1, 3, 4, 5]  # monotone cumulative counts
    assert [bucket("tr", le) for le in ("0.1", "1.0", "10.0", "+Inf")] \
        == [1, 1, 1, 1]
    assert 't_lat_seconds_count{op="mm"} 5' in text
    (s,) = [ln for ln in lines if ln.startswith('t_lat_seconds_sum{op="mm"}')]
    assert float(s.rsplit(" ", 1)[1]) == pytest.approx(56.05)
    # snapshot mirrors the same cumulative structure
    snap = metrics.snapshot()["histograms"]["t_lat_seconds"]
    mm = snap['{"op": "mm"}']
    assert mm["count"] == 5 and mm["buckets"]["+Inf"] == 5
    assert mm["buckets"]["0.1"] <= mm["buckets"]["1.0"] <= \
        mm["buckets"]["10.0"] <= mm["buckets"]["+Inf"]


def test_metrics_snapshot_layers_core_stats():
    metrics.reset()
    stats.record_stack(23, 23, 23, 100, driver="xla_group")
    stats.record_stack(5, 5, 5, 10, driver="pallas")
    stats.record_comm("psum", 4, 12345)
    snap = metrics.snapshot()
    assert snap["flops_by_driver"]["xla_group"] == 2 * 23**3 * 100
    assert snap["flops_by_driver"]["pallas"] == 2 * 5**3 * 10
    assert snap["by_mnk"]["23x23x23"]["entries"] == 100
    assert snap["comm"]["psum"] == {"messages": 4, "bytes": 12345}
    assert "memory" in snap and "totals" in snap
    text = metrics.prometheus_text()
    assert 'dbcsr_tpu_flops_total{driver="xla_group"}' in text
    assert 'dbcsr_tpu_comm_bytes_total{kind="psum"} 12345' in text


def test_recompile_counter_increments_on_fresh_mnk_bin():
    """A fresh (m,n,k) bin = a new XLA specialization = one compile;
    re-running the same shapes = cache hits only (stack-plan cache
    misses in acc/smm become visible, ISSUE tentpole)."""
    metrics.reset()
    set_config(mm_driver="xla")
    try:
        _small_multiply(seed=1)
        snap1 = metrics.jit_stats()["acc.smm._process_stack_xla"]
        assert snap1["compiles"] >= 1
        c0 = snap1["compiles"]
        # same patterns again -> no new specialization, only hits
        _small_multiply(seed=1)
        snap2 = metrics.jit_stats()["acc.smm._process_stack_xla"]
        assert snap2["compiles"] == c0
        assert snap2["cache_hits"] >= 1
        # a genuinely fresh block shape -> a new compile (occupancy low
        # enough that the dense-mode occupancy gate cannot divert it)
        rng = np.random.default_rng(2)
        rbs = [7] * 4
        a = dt.make_random_matrix("A", rbs, rbs, occupation=0.5, rng=rng)
        b = dt.make_random_matrix("B", rbs, rbs, occupation=0.5, rng=rng)
        c = dt.create("C", rbs, rbs)
        dt.multiply("N", "N", 1.0, a, b, 0.0, c)
        snap3 = metrics.jit_stats()["acc.smm._process_stack_xla"]
        assert snap3["compiles"] > c0
    finally:
        set_config(mm_driver="auto")


def test_plan_cache_counter():
    metrics.reset()
    _small_multiply(seed=3)
    assert metrics.counter("dbcsr_tpu_plan_cache_total").values, (
        "plan cache outcomes not counted")


# ---------------------------------------------------------------- flight

def test_flight_ring_is_bounded():
    flight.clear()
    cap = flight.ring_capacity()
    for i in range(cap + 8):
        flight.begin(op="multiply", name=f"M{i}", mnk=(4, 4, 4))
        flight.commit()
    recs = flight.records()
    assert len(recs) == cap
    # oldest dropped, newest kept, order preserved
    assert recs[-1]["name"] == f"M{cap + 7}"
    assert recs[0]["name"] == "M8"
    flight.clear()


def test_flight_records_real_multiply():
    flight.clear()
    _small_multiply(seed=4, filter_eps=1e-9)
    recs = flight.records()
    assert len(recs) == 1
    r = recs[0]
    assert r["mnk"] == (24, 24, 24)
    assert r["algorithm"] == "stack"
    assert r["drivers"], "no driver decisions recorded"
    for d in r["drivers"].values():
        assert d["stacks"] >= 1 and d["why"]
    assert r["filter_eps"] == 1e-9 and "kept_blocks" in r
    assert r["dur_ms"] > 0 and "multiply_stacks" in r["phases_ms"]
    assert r["memory"]["host_peak"] > 0
    flight.clear()


def test_flight_error_dump_path(tmp_path, monkeypatch):
    """An engine error commits the in-flight record with the error
    attached, and dump() writes the JSON artifact."""
    from dbcsr_tpu.mm import multiply as mm_mod

    flight.clear()

    def boom(*a, **k):
        raise RuntimeError("injected stack failure")

    monkeypatch.setattr(mm_mod, "_run_stacks", boom)
    with pytest.raises(RuntimeError, match="injected"):
        _small_multiply(seed=5)
    recs = flight.records()
    assert recs and "injected stack failure" in recs[-1]["error"]
    out_path = str(tmp_path / "flight.json")
    lines = []
    flight.dump(out=lines.append, path=out_path)
    assert any("ERROR" in ln for ln in lines)
    dumped = json.loads(open(out_path).read())
    assert dumped[-1]["error"].endswith("injected stack failure")
    flight.clear()


def test_flight_nested_multiplies_each_get_a_record():
    """TAS group loops nest multiply() calls; every one commits its own
    record (reentrancy contract)."""
    from dbcsr_tpu.tas.mm import tas_multiply

    flight.clear()
    rng = np.random.default_rng(6)
    rbs = [4] * 12
    kbs = [4] * 3
    a = dt.make_random_matrix("A", rbs, kbs, occupation=0.6, rng=rng)
    b = dt.make_random_matrix("B", kbs, kbs, occupation=0.8, rng=rng)
    c = dt.create("C", rbs, kbs)
    tas_multiply("N", "N", 1.0, a, b, 0.0, c, nsplit=3)
    assert len(flight.records()) == 3  # one per group
    flight.clear()


# ------------------------------------------------- tracer shards (PR 2)

def test_shard_path_naming():
    assert obs.shard_path("/x/trace.jsonl", 0) == "/x/trace.p0.jsonl"
    assert obs.shard_path("/x/trace.jsonl", 3) == "/x/trace.p3.jsonl"
    assert obs.shard_path("/x/trace", 1) == "/x/trace.p1"


def test_provisional_shard_rebinds_to_process_index(tmp_path, monkeypatch):
    """Two processes pointed at one DBCSR_TPU_TRACE path must never
    co-write a file: before the process index resolves the shard opens
    under a collision-proof provisional name, and `rebind` renames it
    atomically to its final p{index} shard."""
    from dbcsr_tpu.obs import tracer as tr

    monkeypatch.setattr(tr, "_process_index", lambda: None)
    base = str(tmp_path / "t.jsonl")
    t = obs.enable_trace(base)
    # collision-proof across hosts sharing a filesystem: host + OS pid
    assert f"-{os.getpid()}." in t.path and ".ptmp" in t.path
    with timings.timed("early"):
        pass
    tr.rebind(2)  # init_multihost passes the joined world's index
    assert t.path == obs.shard_path(base, 2)
    assert t.process_index == 2
    with timings.timed("late"):
        pass
    obs.disable_trace()
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "t.p2.jsonl", "t.p2.jsonl.chrome.json"]
    recs = _read_jsonl(str(tmp_path / "t.p2.jsonl"))
    names = [r["name"] for r in recs if r["ev"] == "span"]
    assert names == ["early", "late"]  # both sides of the rename kept
    # the chrome export puts the WHOLE shard on the final track
    doc = json.load(open(str(tmp_path / "t.p2.jsonl.chrome.json")))
    assert {e["pid"] for e in doc["traceEvents"]} == {2}


def test_shard_rename_appends_instead_of_clobbering(tmp_path, monkeypatch):
    """A second session whose rename lands on an existing shard (an
    earlier run's, or another process's) must APPEND its events, never
    os.replace over them."""
    from dbcsr_tpu.obs import tracer as tr

    monkeypatch.setattr(tr, "_process_index", lambda: None)
    base = str(tmp_path / "t.jsonl")
    for span in ("first_run", "second_run"):
        obs.enable_trace(base)
        with timings.timed(span):
            pass
        obs.disable_trace()  # both settle on p0
    recs = _read_jsonl(obs.shard_path(base, 0))
    names = [r["name"] for r in recs if r["ev"] == "span"]
    assert names == ["first_run", "second_run"]


def test_single_process_close_settles_on_p0(tmp_path, monkeypatch):
    """A session whose index never resolves (no jax work at all)
    settles on p0 at close — deterministic artifact names for the
    common single-process flow."""
    from dbcsr_tpu.obs import tracer as tr

    monkeypatch.setattr(tr, "_process_index", lambda: None)
    base = str(tmp_path / "t.jsonl")
    obs.enable_trace(base)
    obs.instant("ping")
    obs.disable_trace()
    assert (tmp_path / "t.p0.jsonl").exists()


def test_trace_merge_two_shards(tmp_path, monkeypatch):
    """trace_merge puts per-process shards on one timeline with one
    track per process, aligned on the clock_align instants."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import trace_merge
    from dbcsr_tpu.obs import tracer as tr

    monkeypatch.setattr(tr, "_process_index", lambda: None)
    base = str(tmp_path / "t.jsonl")
    for pid in (0, 1):
        t = obs.enable_trace(base)
        tr.rebind(pid)
        obs.instant("clock_align", {"t_unix": 1000.0 + pid,
                                    "process": pid})
        with timings.timed(f"work_p{pid}"):
            pass
        obs.disable_trace()
    res = trace_merge.merge([obs.shard_path(base, 0),
                             obs.shard_path(base, 1)])
    assert res["mode"] == "clock_align"
    evs = res["doc"]["traceEvents"]
    assert {e["pid"] for e in evs} == {0, 1}
    names = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert len(names) == 2
    # the two clock_align instants coincide on the merged timeline
    aligns = [e["ts"] for e in evs if e.get("name") == "clock_align"]
    assert len(aligns) == 2 and abs(aligns[0] - aligns[1]) < 1e-6
    assert os.path.exists(res["out_path"])


def test_trace_merge_skips_stale_provisional_and_disambiguates_pids(
        tmp_path, monkeypatch):
    """Base-path expansion ignores crashed runs' unsettled .ptmp*
    shards, and two shards claiming one pid land on distinct tracks."""
    import trace_merge
    from dbcsr_tpu.obs import tracer as tr

    monkeypatch.setattr(tr, "_process_index", lambda: None)
    base = str(tmp_path / "t.jsonl")
    obs.enable_trace(base)
    with timings.timed("good_run"):
        pass
    obs.disable_trace()  # settles on p0
    # a crashed earlier run left an unsettled provisional shard
    stale = tmp_path / "t.ptmphost-999.jsonl"
    stale.write_text(json.dumps({"ev": "meta", "pid": 0,
                                 "t0_unix": 1.0}) + "\n")
    paths = trace_merge.expand_shards([base])
    assert [os.path.basename(p) for p in paths] == ["t.p0.jsonl"]
    # passed EXPLICITLY, the stale shard merges onto its own track
    res = trace_merge.merge([obs.shard_path(base, 0), str(stale)])
    assert [s["pid"] for s in res["shards"]] == [0, 1]


def test_trace_merge_mixed_alignment(tmp_path, monkeypatch):
    """A shard that never reached the barrier (crashed pre-join) falls
    back to wall-clock alignment PER SHARD — the barrier-aligned
    shards keep coinciding exactly."""
    import trace_merge
    from dbcsr_tpu.obs import tracer as tr

    monkeypatch.setattr(tr, "_process_index", lambda: None)
    base = str(tmp_path / "t.jsonl")
    for pid in (0, 1, 2):
        obs.enable_trace(base)
        tr.rebind(pid)
        if pid < 2:  # rank 2 "crashed" before init_multihost
            obs.instant("clock_align", {"t_unix": 2000.0 + 0.001 * pid,
                                        "process": pid})
        with timings.timed(f"work_p{pid}"):
            pass
        obs.disable_trace()
    res = trace_merge.merge([obs.shard_path(base, i) for i in (0, 1, 2)])
    assert res["mode"] == "mixed"
    evs = res["doc"]["traceEvents"]
    assert {e["pid"] for e in evs} == {0, 1, 2}
    aligns = [e["ts"] for e in evs if e.get("name") == "clock_align"]
    assert len(aligns) == 2 and abs(aligns[0] - aligns[1]) < 1e-6
    assert all(e["ts"] >= 0 for e in evs if "ts" in e)


def test_trace_summary_multi_shard(tmp_path, monkeypatch):
    """A glob / base path of shards aggregates across processes while
    the single-file summary shape stays unchanged."""
    from dbcsr_tpu.obs import tracer as tr

    monkeypatch.setattr(tr, "_process_index", lambda: None)
    base = str(tmp_path / "t.jsonl")
    for pid in (0, 1):
        obs.enable_trace(base)
        tr.rebind(pid)
        with timings.timed("shared_phase"):
            pass
        obs.disable_trace()
    s = trace_summary.summarize_many(
        trace_summary.expand_paths([base]))
    assert s["phases"]["shared_phase"]["calls"] == 2
    assert len(s["per_process"]) == 2
    single = trace_summary.summarize(obs.shard_path(base, 0))
    assert "per_process" not in single
    assert single["phases"]["shared_phase"]["calls"] == 1


# -------------------------------------------- cost model + roofline (PR 2)

def test_metrics_reset_include_stats_semantics():
    """reset() clears the core.stats layers it snapshots (the stale-
    flops footgun); reset(include_stats=False) keeps them."""
    metrics.reset()
    stats.record_stack(4, 4, 4, 10, driver="xla")
    metrics.counter("t_reset_probe").inc()
    metrics.reset(include_stats=False)
    snap = metrics.snapshot()
    assert snap["flops_by_driver"]["xla"] == 2 * 4**3 * 10  # stats kept
    assert not metrics.counter("t_reset_probe").values  # registry cleared
    metrics.reset()  # default: stats go too
    snap = metrics.snapshot()
    assert snap["flops_by_driver"] == {}


def test_roofline_fraction_reported_per_driver():
    """Acceptance: snapshot() reports roofline_fraction for every
    driver that executed."""
    metrics.reset()
    _small_multiply(seed=11)
    snap = metrics.snapshot()
    assert snap["roofline"], "no drivers in the roofline rollup"
    for driver, fb in snap["flops_by_driver"].items():
        rl = snap["roofline"][driver]
        assert "roofline_fraction" in rl and "achieved_gflops" in rl
        assert rl["flops"] == fb
        assert rl["achieved_gflops"] > 0  # dispatch seconds were recorded
        assert 0 <= rl["roofline_fraction"]
        assert rl["bytes_moved"] > 0 and rl["arithmetic_intensity"] > 0
    # the same numbers are exported as labeled gauges
    text = metrics.prometheus_text()
    assert "dbcsr_tpu_roofline_fraction{" in text
    assert "dbcsr_tpu_achieved_gflops{" in text


def test_costmodel_stack_and_dense_models():
    from dbcsr_tpu.obs import costmodel

    assert costmodel.stack_flops(23, 23, 23, 100) == 2 * 23**3 * 100
    b = costmodel.stack_bytes(23, 23, 23, 100, nseg=40, itemsize=8)
    assert b == 8 * (100 * 2 * 23 * 23 + 2 * 40 * 23 * 23)
    d = costmodel.dense_cost(64, 32, 16, itemsize=4)
    assert d["flops"] == 2 * 64 * 32 * 16
    assert d["bytes"] == 4 * (64 * 16 + 16 * 32 + 2 * 64 * 32)


def test_roofline_peak_table_env_override(monkeypatch):
    from dbcsr_tpu.obs import costmodel

    monkeypatch.setenv("DBCSR_TPU_ROOFLINE",
                       json.dumps({"weird accel": {
                           "gflops": {"float64": 1234.0}, "gbs": 10.0}}))
    monkeypatch.setattr(costmodel, "_env_table", None)  # drop the cache
    assert costmodel.peak_gflops("Weird Accel v9", "float64") == 1234.0
    # high intensity -> compute-bound: attainable == peak
    rl = costmodel.roofline(2e9, 1e6, 1.0, kind="weird accel",
                            dtype="float64")
    assert rl["attainable_gflops"] == 1234.0
    assert rl["achieved_gflops"] == pytest.approx(2.0)
    assert rl["roofline_fraction"] == pytest.approx(2.0 / 1234.0)
    # low intensity -> bandwidth-bound: attainable = intensity * gbs
    rl = costmodel.roofline(1e6, 1e9, 1.0, kind="weird accel",
                            dtype="float64")
    assert rl["attainable_gflops"] == pytest.approx(1e-3 * 10.0)
    # a TPU kind no row names is an error, never the generic default
    assert costmodel.peaks_key("TPU v5 lite") == "tpu v5 lite"
    assert costmodel.peaks_key("TPU v99") is None
    with pytest.raises(KeyError, match="tpu v99"):
        costmodel.peaks_for("TPU v99")
    monkeypatch.setattr(costmodel, "_env_table", None)


def test_cannon_tick_overlap_model():
    from dbcsr_tpu.obs import costmodel

    tick = costmodel.cannon_tick_model(
        1024, 1024, 1024, kl=1, s=2, itemsize=8, dtype="float64",
        kind="cpu")
    # per device/tick: (512x512)@(512x512) dot, one A + one B shard move
    assert tick["tick_flops"] == 2 * 512 * 512 * 512
    assert tick["tick_comm_bytes"] == 2 * 512 * 512 * 8
    assert tick["overlap_ratio"] == pytest.approx(
        tick["t_comm_s"] / tick["t_compute_s"])


def test_costmodel_agrees_with_xla_cost_analysis():
    """Satellite acceptance: the analytic model and XLA's own
    cost_analysis agree on a small stack.  The stack is sized to a jit
    bucket so model and device work count the same entries; XLA adds
    the segment-sum/accumulate flops on top of the dot, so the ratio
    must sit just above 1."""
    from dbcsr_tpu.acc.smm import process_stack
    from dbcsr_tpu.obs import costmodel
    import jax.numpy as jnp

    metrics.reset()
    costmodel.enable_xla_capture(True)
    set_config(mm_driver="xla")
    try:
        m = n = k = 8
        s_entries = 512  # == bucket_size(512): no padding
        rng = np.random.default_rng(13)
        na, nc = 32, 64
        a = jnp.asarray(rng.standard_normal((na, m, k)))
        b = jnp.asarray(rng.standard_normal((na, k, n)))
        c = jnp.zeros((nc, m, n))
        ai = rng.integers(0, na, s_entries).astype(np.int32)
        bi = rng.integers(0, na, s_entries).astype(np.int32)
        ci = np.sort(rng.integers(0, nc, s_entries)).astype(np.int32)
        process_stack(c, a, b, ai, bi, ci)
        xc = costmodel.xla_costs()["acc.smm._process_stack_xla"]
        (rec,) = xc.values()
        assert rec["model"]["flops"] == 2 * m * n * k * s_entries
        assert rec["xla_flops"] > 0
        # dot flops dominate; segment-sum adds ~1/(2k) on top
        assert 1.0 <= rec["flops_ratio"] < 1.5, rec
        assert rec["xla_bytes_accessed"] > 0
        # the capture also lands in the metrics snapshot
        assert "acc.smm._process_stack_xla" in \
            metrics.snapshot()["xla_cost"]
    finally:
        costmodel.enable_xla_capture(False)
        set_config(mm_driver="auto")


# ---------------------------------------------------- trace_summary tool

def test_trace_summary_smoke(trace, capsys):
    set_config(mm_driver="xla")
    try:
        metrics.reset()
        _small_multiply(seed=7)
    finally:
        set_config(mm_driver="auto")
    obs.disable_trace()
    rc = trace_summary.main([trace])
    assert rc == 0
    out = capsys.readouterr().out
    assert "multiply_stacks" in out and "PHASE" in out
    assert "RECOMPILE OFFENDERS" in out
    assert "acc.smm._process_stack_xla" in out
    # machine-readable mode
    rc = trace_summary.main([trace, "--json"])
    assert rc == 0
    s = json.loads(capsys.readouterr().out)
    assert s["phases"]["multiply"]["calls"] == 1
    assert s["jit_compiles"].get("acc.smm._process_stack_xla", 0) >= 1
    assert s["bad_lines"] == 0
