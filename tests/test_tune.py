"""The online autotuning service (`dbcsr_tpu.tune`).

Covers the four planes (miner ranking, bounded/faultable trials, the
promotion store's generation contract, transfer + learned fallback)
plus the service loop's admission gate and the acceptance pin: a
promotion bumps the params generation and NO plan cache serves stale
parameters.
"""

import json
import os

import numpy as np
import pytest

import dbcsr_tpu as dt  # noqa: F401 — jax config via conftest
from dbcsr_tpu.acc import params as params_mod
from dbcsr_tpu.obs import metrics
from dbcsr_tpu.tune import miner, predictor, store, trials
from dbcsr_tpu.tune import service as tune_service


@pytest.fixture(autouse=True)
def _healthy_process():
    """The tune cycle defers on the process-wide health verdict, which
    an earlier test file of the same worker can leave DEGRADED or
    CRITICAL (open breakers, a fallback storm in the rolling windows):
    start every test from a clean board."""
    from dbcsr_tpu.obs import health
    from dbcsr_tpu.resilience import breaker

    breaker.reset_board()
    metrics.reset()
    health.reset()


@pytest.fixture
def params_dir(tmp_path, monkeypatch):
    """Hermetic parameter directory: the committed device tables are
    never read or written."""
    monkeypatch.setenv("DBCSR_TPU_PARAMS_DIR", str(tmp_path))
    params_mod.invalidate()
    yield tmp_path
    tune_service.stop_service()
    params_mod.invalidate()


def _counter_total(name: str, **labels) -> float:
    total = 0.0
    for lb, v in metrics.counter_items(name):
        if all(lb.get(k) == val for k, val in labels.items()):
            total += v
    return total


def _fake_query(series):
    """A `timeseries.query`-shaped callable over canned series:
    [(metric, labels, points)] with points [[t, v], ...]."""

    def query(metric=None, labels=None, since=None, until=None,
              agg=None, tier="auto", path=None):
        out = []
        for m, lb, pts in series:
            if metric is not None and m != metric:
                continue
            if labels and any(lb.get(k) != v for k, v in labels.items()):
                continue
            ent = {"metric": m, "labels": dict(lb), "kind": "gauge",
                   "tier": "raw", "points": [list(p) for p in pts]}
            if agg == "last":
                ent["value"] = pts[-1][1] if pts else None
            out.append(ent)
        return out

    return query


# ----------------------------------------------------------- miner


def test_miner_ranks_by_wasted_flop_seconds(params_dir):
    # two underperforming cells on the same slow driver: the one that
    # burned 100x the flops must rank first, whatever its shape
    series = [
        ("dbcsr_tpu_cell_flops_total",
         {"mnk": "8x8x8", "driver": "xla", "dtype": "float64"},
         [[0.0, 1e12]]),
        ("dbcsr_tpu_cell_flops_total",
         {"mnk": "23x23x23", "driver": "xla", "dtype": "float64"},
         [[0.0, 1e10]]),
        ("dbcsr_tpu_achieved_gflops", {"driver": "xla"}, [[0.0, 0.5]]),
        ("dbcsr_tpu_roofline_fraction", {"driver": "xla"}, [[0.0, 0.01]]),
    ]
    cells = miner.mine(query=_fake_query(series), capture_paths=[])
    assert [c["m"] for c in cells] == [8, 23]
    assert cells[0]["wasted_flop_seconds"] > \
        cells[1]["wasted_flop_seconds"] * 50
    assert "floor" in cells[0]["reason"]
    # the queue gauge tracks the mined depth
    g = metrics._gauges.get("dbcsr_tpu_tune_queue_depth")
    assert g is not None and g.value() == 2.0


def test_miner_healthy_cells_not_mined(params_dir):
    series = [
        ("dbcsr_tpu_cell_flops_total",
         {"mnk": "8x8x8", "driver": "xla", "dtype": "float64"},
         [[0.0, 1e12]]),
        ("dbcsr_tpu_achieved_gflops", {"driver": "xla"}, [[0.0, 5.0]]),
        ("dbcsr_tpu_roofline_fraction", {"driver": "xla"}, [[0.0, 0.9]]),
    ]
    assert miner.mine(query=_fake_query(series), capture_paths=[]) == []


def test_miner_donor_prediction_criterion(params_dir):
    # tuned evidence on a neighboring shape says 8 GFLOP/s; the live
    # cell achieves 0.5 at a healthy fraction -> mined via the donor
    # criterion with the donor rate as the target
    params_mod.save_entry({"m": 10, "n": 10, "k": 10, "dtype": "float64",
                           "stack_size": 30000, "driver": "host",
                           "grouping": None, "gflops": 8.0, "env": "cpu"})
    series = [
        ("dbcsr_tpu_cell_flops_total",
         {"mnk": "8x8x8", "driver": "xla", "dtype": "float64"},
         [[0.0, 1e12]]),
        ("dbcsr_tpu_achieved_gflops", {"driver": "xla"}, [[0.0, 0.5]]),
        ("dbcsr_tpu_roofline_fraction", {"driver": "xla"}, [[0.0, 0.9]]),
    ]
    cells = miner.mine(query=_fake_query(series), capture_paths=[])
    assert len(cells) == 1
    assert cells[0]["target_gflops"] == pytest.approx(8.0)
    assert "donor prediction" in cells[0]["reason"]


def test_miner_reads_capture_artifacts(params_dir, tmp_path):
    cap = tmp_path / "captures.jsonl"
    cap.write_text(json.dumps({
        "kernel": "23x23x23", "dtype": "float64", "stack_size": 100000,
        "gflops": 0.2, "modeled": {"roofline_fraction": 0.01},
    }) + "\n" + "torn{line\n")
    cells = miner.mine(query=_fake_query([]), capture_paths=[str(cap)])
    assert len(cells) == 1
    assert (cells[0]["m"], cells[0]["stack_size"]) == (23, 100000)
    assert cells[0]["source"] == "captures.jsonl"


# ---------------------------------------------------------- trials


def test_clamp_stack_size_budget():
    # 23^3 f64: ~1070 B/entry -> a 1 MiB budget clamps hard, a huge
    # budget returns the wanted size
    assert trials.clamp_stack_size(23, 23, 23, "float64", 30000,
                                   budget=1 << 20) < 2000
    assert trials.clamp_stack_size(23, 23, 23, "float64", 30000,
                                   budget=1 << 30) == 30000
    # the floor: a trial can never shrink below timeable size
    assert trials.clamp_stack_size(64, 64, 64, "float64", 30000,
                                   budget=1024) == 256


def test_trial_fault_aborts_with_no_candidates(params_dir):
    from dbcsr_tpu.resilience import faults

    n0 = _counter_total("dbcsr_tpu_tune_trials_total", outcome="faulted")
    cell = dict(m=4, n=4, k=4, dtype="float64", stack_size=256)
    with faults.inject_faults("tune_trial:raise,times=1") as specs:
        res = trials.run_trial(cell, reps=1)
    assert specs[0].fired == 1
    assert res.outcome == "faulted" and not res.ok
    assert res.candidates == [] and res.entry is None
    assert _counter_total("dbcsr_tpu_tune_trials_total",
                          outcome="faulted") == n0 + 1


def test_select_winner_skips_open_breaker(params_dir):
    from dbcsr_tpu.resilience import breaker

    breaker.reset_board()
    board = breaker.get_board()
    key = (4, 4, 4, "float64")
    for _ in range(board.fail_threshold):
        board.record_failure("host", key)
    assert board.state("host", key) == breaker.OPEN
    cands = [{"driver": "host", "grouping": None, "gflops": 99.0},
             {"driver": "xla", "grouping": None, "gflops": 1.0}]
    try:
        got = trials.select_winner(cands, 4, 4, 4, np.float64)
        assert got["driver"] == "xla"
        # a different shape's breaker does not quarantine this cell
        got = trials.select_winner(cands, 5, 5, 5, np.float64)
        assert got["driver"] == "host"
    finally:
        breaker.reset_board()


# ----------------------------------------------------------- store


def test_promotion_provenance_and_ledger(params_dir):
    params_mod.save_entry({"m": 4, "n": 4, "k": 4, "dtype": "float64",
                           "stack_size": 512, "driver": "xla_group",
                           "r0": 4, "grouping": None, "gflops": 0.1,
                           "env": "cpu"})
    rec = store.promote(
        {"m": 4, "n": 4, "k": 4, "dtype": "float64", "stack_size": 256,
         "driver": "host", "grouping": None, "gflops": 3.0, "env": "cpu"},
        trial={"elapsed_s": 1.0}, stack_size=512)
    assert rec["action"] == "promote" and rec["generation"] == 1
    assert rec["prev_row"]["driver"] == "xla_group"
    row = params_mod.lookup(4, 4, 4, np.float64, stack_size=512)
    assert row["driver"] == "host"
    assert row["tuned_by"] == "dbcsr_tpu.tune"
    assert row["trial_stack_size"] == 256  # re-keyed at the mined size
    assert store.live_promotions()[0]["key"] == [4, 4, 4, "float64", 512]
    assert _counter_total("dbcsr_tpu_tune_promotions_total",
                          driver="host") >= 1


def test_demotion_restores_displaced_row(params_dir, monkeypatch):
    monkeypatch.setattr(store, "_live_roofline", lambda driver: 0.5)
    params_mod.save_entry({"m": 4, "n": 4, "k": 4, "dtype": "float64",
                           "stack_size": 512, "driver": "xla",
                           "grouping": None, "gflops": 0.5, "env": "cpu"})
    store.promote({"m": 4, "n": 4, "k": 4, "dtype": "float64",
                   "stack_size": 512, "driver": "host", "grouping": None,
                   "gflops": 3.0, "env": "cpu"})
    gen = params_mod.generation()
    import time as _time

    now = _time.time()
    # pre-promotion collapse alone must NOT condemn the fresh row...
    stale = _fake_query([("dbcsr_tpu_roofline_fraction",
                          {"driver": "host"},
                          [[now - 100.0 + t, 0.05] for t in range(6)])])
    assert store.check_regressions(query=stale) == []
    # ...but a POST-promotion collapse to 0.1x the at-promotion 0.5 does
    collapsed = _fake_query([("dbcsr_tpu_roofline_fraction",
                              {"driver": "host"},
                              [[now + 1.0 + t, 0.05] for t in range(6)])])
    demoted = store.check_regressions(query=collapsed)
    assert demoted == [[4, 4, 4, "float64", 512]]
    assert params_mod.generation() > gen
    row = params_mod.lookup(4, 4, 4, np.float64, stack_size=512)
    assert row["driver"] == "xla"  # displaced row restored
    assert store.live_promotions() == []
    led = store.load_ledger()
    assert led[-1]["action"] == "demote"
    assert "regression" in led[-1]["reason"]
    assert _counter_total("dbcsr_tpu_tune_demotions_total") >= 1


def test_regression_judge_needs_samples(params_dir, monkeypatch):
    monkeypatch.setattr(store, "_live_roofline", lambda driver: 0.5)
    store.promote({"m": 4, "n": 4, "k": 4, "dtype": "float64",
                   "stack_size": 512, "driver": "host", "grouping": None,
                   "gflops": 3.0, "env": "cpu"})
    import time as _time

    now = _time.time()
    # 2 collapsed post-promotion points < min_samples=4: no verdict yet
    short = _fake_query([("dbcsr_tpu_roofline_fraction",
                          {"driver": "host"},
                          [[now + 1.0, 0.01], [now + 2.0, 0.01]])])
    assert store.check_regressions(query=short) == []
    assert store.live_promotions() != []


# ------------------------------------------------- generation contract


def test_promotion_bumps_generation_and_retires_stale_plans(params_dir):
    """The acceptance pin: a promotion bumps the params generation and
    no plan cache serves stale parameters — the multiply AFTER a
    promotion must re-plan (plan-cache miss) and dispatch the promoted
    driver."""
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.ops.test_methods import make_random_matrix

    bs = [4] * 6
    a = make_random_matrix("A", bs, bs, occupation=0.6,
                           rng=np.random.default_rng(0))
    b = make_random_matrix("B", bs, bs, occupation=0.6,
                           rng=np.random.default_rng(1))
    c = dt.create("C", bs, bs)
    params_mod.save_entry({"m": 4, "n": 4, "k": 4, "dtype": "float64",
                           "stack_size": 512, "driver": "xla",
                           "grouping": None, "gflops": 0.5, "env": "cpu"})
    dt.multiply("N", "N", 1.0, a, b, 0.0, c)
    dt.multiply("N", "N", 1.0, a, b, 0.0, c)  # plan cache warm
    hits0 = _counter_total("dbcsr_tpu_plan_cache_total", result="hit")
    miss0 = _counter_total("dbcsr_tpu_plan_cache_total", result="miss")
    dt.multiply("N", "N", 1.0, a, b, 0.0, c)
    assert _counter_total("dbcsr_tpu_plan_cache_total",
                          result="hit") == hits0 + 1
    gen0 = params_mod.generation()
    host0 = stats._driver_agg.get("host")
    host0 = host0.flops if host0 else 0
    store.promote({"m": 4, "n": 4, "k": 4, "dtype": "float64",
                   "stack_size": 512, "driver": "host", "grouping": None,
                   "gflops": 9.0, "env": "cpu"})
    assert params_mod.generation() > gen0
    dt.multiply("N", "N", 1.0, a, b, 0.0, c)
    # the promotion retired the cached plan: this multiply re-planned
    assert _counter_total("dbcsr_tpu_plan_cache_total",
                          result="miss") == miss0 + 1
    # ... and the fresh plan dispatches the PROMOTED driver
    from dbcsr_tpu.acc.smm import _host_smm_available

    if _host_smm_available(np.float64):
        host1 = stats._driver_agg.get("host")
        assert host1 is not None and host1.flops > host0


def test_invalidate_seam_sees_external_table_writes(params_dir):
    """The satellite pin: a process serving the in-memory table must
    pick up an EXTERNAL write (another process's tuner) after
    `invalidate()` — and the generation bump retires memoized
    predictions."""
    params_mod.save_entry({"m": 4, "n": 4, "k": 4, "dtype": "float64",
                           "stack_size": 512, "driver": "xla",
                           "grouping": None, "gflops": 0.5, "env": "cpu"})
    assert params_mod.lookup(4, 4, 4, np.float64)["driver"] == "xla"
    assert params_mod.predict(4, 4, 4, np.float64)["driver"] == "xla"
    # external writer: rewrite the file behind the module's back
    path = params_mod.params_path()
    rows = json.load(open(path))
    rows[0]["driver"] = "host"
    rows[0]["gflops"] = 9.0
    with open(path, "w") as fh:
        json.dump(rows, fh)
    # without the seam the stale in-memory table keeps serving
    assert params_mod.lookup(4, 4, 4, np.float64)["driver"] == "xla"
    gen0 = params_mod.generation()
    assert params_mod.invalidate() == gen0 + 1
    assert params_mod.lookup(4, 4, 4, np.float64)["driver"] == "host"
    assert params_mod.predict(4, 4, 4, np.float64)["driver"] == "host"


def test_delete_entry_removes_and_bumps(params_dir):
    params_mod.save_entry({"m": 4, "n": 4, "k": 4, "dtype": "float64",
                           "stack_size": 512, "driver": "xla",
                           "grouping": None, "gflops": 0.5, "env": "cpu"})
    gen0 = params_mod.generation()
    assert params_mod.delete_entry(4, 4, 4, "float64", 512)
    assert params_mod.generation() == gen0 + 1
    assert params_mod.lookup(4, 4, 4, np.float64) is None
    # removing a missing row is a no-op, generation included
    assert not params_mod.delete_entry(4, 4, 4, "float64", 512)
    assert params_mod.generation() == gen0 + 1


# ------------------------------------------------------- predictor


def _write_kind_table(tmp_path, kind, rows):
    with open(tmp_path / f"parameters_{kind}.json", "w") as fh:
        json.dump(rows, fh)


def test_transfer_scales_by_peak_ratio(params_dir, monkeypatch):
    from dbcsr_tpu.obs import costmodel

    _write_kind_table(params_dir, "TPU_v5_lite", [
        {"m": 23, "n": 23, "k": 23, "dtype": "float64",
         "stack_size": 100000, "driver": "xla_group", "r0": 8,
         "grouping": None, "gflops": 100.0, "env": "onchip"},
    ])
    peaks = {params_mod.device_kind(): 50.0, "TPU_v5_lite": 200.0}
    monkeypatch.setattr(costmodel, "peak_gflops",
                        lambda kind=None, dtype="float64":
                        peaks.get(kind, 0.0))
    got = predictor.transfer_predict(23, 23, 23, np.float64,
                                     stack_size=100000)
    assert got["transfer_from"] == "TPU_v5_lite"
    assert got["gflops"] == pytest.approx(25.0)  # 100 * 50/200
    assert got["gflops_donor"] == 100.0
    # far shapes get no opinion (the 16x flop-ratio cap)
    assert predictor.transfer_predict(256, 256, 256, np.float64) is None


def test_learned_regressor_and_evidence_ladder(params_dir):
    rng = np.random.default_rng(0)
    rows = []
    for _ in range(24):
        m = int(rng.integers(4, 64))
        s = int(rng.integers(1000, 100000))
        # host scales well, xla is 10x slower on this synthetic world
        rows.append({"m": m, "n": m, "k": m, "dtype": "float64",
                     "stack_size": s, "driver": "host",
                     "gflops": 4.0 * (m / 23.0) ** 0.5})
        rows.append({"m": m, "n": m, "k": m, "dtype": "float64",
                     "stack_size": s, "driver": "xla",
                     "gflops": 0.4 * (m / 23.0) ** 0.5})
    reg = predictor.TrialRegressor()
    assert reg.fit(rows) == 48
    est = reg.predict_gflops(23, 23, 23, "float64", 30000)
    assert est["host"] > est["xla"]
    sug = reg.suggest(23, 23, 23, "float64", 30000)
    assert sug["driver"] == "host" and sug["predicted"] == "learned"
    # the ladder: learned is the LAST rung...
    got = predictor.lookup_extended(23, 23, 23, np.float64,
                                    stack_size=30000, regressor=reg)
    assert got["predicted"] == "learned"
    # ...and real evidence outranks it the moment a row exists
    params_mod.save_entry({"m": 23, "n": 23, "k": 23, "dtype": "float64",
                           "stack_size": 30000, "driver": "xla_flat",
                           "grouping": None, "gflops": 1.0, "env": "cpu"})
    got = predictor.lookup_extended(23, 23, 23, np.float64,
                                    stack_size=30000, regressor=reg)
    assert got["driver"] == "xla_flat" and "predicted" not in got


# --------------------------------------------------------- service


def test_cycle_defers_on_degraded_admission(params_dir, monkeypatch):
    from dbcsr_tpu.obs import health

    monkeypatch.setattr(health, "admission_status", lambda: "DEGRADED")
    svc = tune_service.TuneService(interval_s=3600)
    t0 = _counter_total("dbcsr_tpu_tune_trials_total")
    out = svc.cycle(cells=[dict(m=4, n=4, k=4, dtype="float64",
                                stack_size=256)])
    assert out["outcome"] == "deferred:DEGRADED"
    assert _counter_total("dbcsr_tpu_tune_trials_total") == t0
    assert svc.snapshot()["deferred"] == 1


def test_cycle_promotes_end_to_end(params_dir, monkeypatch):
    """One real closed cycle on a tiny cell: trial sweep runs, the
    winner lands with provenance, the outcome is observable."""
    from dbcsr_tpu.resilience import breaker

    # earlier suite tests legitimately leave open breakers at this
    # tiny shape; winner selection would (correctly) quarantine them
    breaker.reset_board()
    monkeypatch.setenv("DBCSR_TPU_TUNE_NREP", "1")
    monkeypatch.setenv("DBCSR_TPU_TUNE_BUDGET_BYTES", str(1 << 20))
    # the mistuned incumbent is a config the f64 sweep never times
    # (pallas): at this tiny trial size every candidate sits in the
    # noise floor, so a winner that HAPPENS to match the incumbent's
    # config would otherwise be (correctly) held as plan-churn-free
    params_mod.save_entry({"m": 4, "n": 4, "k": 4, "dtype": "float64",
                           "stack_size": 512, "driver": "pallas",
                           "grouping": 4, "gflops": 0.01,
                           "env": "cpu"})
    svc = tune_service.TuneService(interval_s=3600)
    cell = dict(m=4, n=4, k=4, dtype="float64", stack_size=512,
                observed_gflops=0.01, target_gflops=10.0,
                wasted_flop_seconds=100.0)
    out = svc.cycle(cells=[cell])
    assert out["outcome"] == "promoted", out
    row = params_mod.lookup(4, 4, 4, np.float64, stack_size=512)
    assert row["tuned_by"] == "dbcsr_tpu.tune"
    assert row["gflops"] > 0.01
    snap = svc.snapshot()
    assert snap["promotions"] == 1 and snap["trials"] == 1
    assert snap["trial_failure_streak"] == 0


def test_faulted_cycle_promotes_nothing(params_dir):
    from dbcsr_tpu.resilience import faults

    svc = tune_service.TuneService(interval_s=3600)
    cell = dict(m=4, n=4, k=4, dtype="float64", stack_size=256,
                observed_gflops=0.01)
    with faults.inject_faults("tune_trial:raise,times=1"):
        out = svc.cycle(cells=[cell])
    assert out["outcome"] == "trial_faulted"
    assert out["promoted"] is None
    assert store.live_promotions() == []
    assert svc.snapshot()["trial_failure_streak"] == 1


def test_obs_surfaces(params_dir):
    """Health component + timeseries collector see the live service."""
    from dbcsr_tpu.obs import health
    from dbcsr_tpu.obs import timeseries as ts

    svc = tune_service.get_service()
    try:
        comp = health.verdict()["components"]["tune"]
        assert comp["status"] == "OK"
        assert comp["running"] is False
        pts = ts._collect_tune()
        names = {p[0] for p in pts}
        assert "dbcsr_tpu_params_generation" in names
        assert "dbcsr_tpu_tune_queue_depth" in names
        # the admission verdict ignores the advisory tune component
        svc.stats["trial_failure_streak"] = 3
        assert health.verdict()["components"]["tune"]["status"] \
            == "DEGRADED"
        assert health.admission_status() == "OK"
    finally:
        svc.stats["trial_failure_streak"] = 0
        tune_service.stop_service()
