"""Fused superstack launches: correctness vs the per-span path,
dispatch accounting, plan-cache byte budgeting, decomposition-on-
failure (chaos), synchronized timing, and the dispatch microbench.
All tier-1, CPU-only."""

import json

import numpy as np
import pytest

import dbcsr_tpu.mm.multiply as mm
from dbcsr_tpu import create, make_random_matrix, multiply, native, to_dense
from dbcsr_tpu.acc import smm
from dbcsr_tpu.core.config import get_config, set_config
from dbcsr_tpu.obs import costmodel, metrics
from dbcsr_tpu.ops.test_methods import checksum
from dbcsr_tpu.resilience import breaker, faults

requires_native = pytest.mark.skipif(
    native.get_lib() is None, reason="native library unavailable"
)


@pytest.fixture(autouse=True)
def _clean_slate():
    from dbcsr_tpu.mm import incremental as _inc

    cfg0 = {f: getattr(get_config(), f)
            for f in ("mm_driver", "superstack", "mm_format", "use_pallas",
                      "flat_gather", "incremental")}
    faults.clear()
    breaker.reset_board()
    metrics.reset()
    mm._plan_cache.clear()
    _inc.reset()
    yield
    faults.clear()
    breaker.reset_board()
    metrics.reset()
    mm._plan_cache.clear()
    _inc.reset()
    set_config(**cfg0)


# mixed blockings: two row/col/k block sizes -> every C bin receives
# MULTIPLE spans (one per k size), the configuration fusion exists for
RBS = [5, 3, 5, 3, 5]
KBS = [4, 2, 4, 2]
CBS = [3, 5, 3]


def _mats(occ=0.7, occ_c=0.4, seed=7, dtype=np.float64):
    rng = np.random.default_rng(seed)
    a = make_random_matrix("a", RBS, KBS, dtype=dtype, occupation=occ,
                           rng=rng)
    b = make_random_matrix("b", KBS, CBS, dtype=dtype, occupation=occ,
                           rng=rng)
    c = make_random_matrix("c", RBS, CBS, dtype=dtype, occupation=occ_c,
                           rng=rng)
    return a, b, c


def _run(mode, alpha=1.0, beta=0.5, seed=7, fresh_c=False, mm_driver=None):
    set_config(superstack=mode,
               **({"mm_driver": mm_driver} if mm_driver else {}))
    mm._plan_cache.clear()
    metrics.reset()
    a, b, c = _mats(seed=seed)
    if fresh_c:
        c = create("c", RBS, CBS, dtype=np.float64)
        beta = 0.0
    multiply("N", "N", alpha, a, b, beta, c)
    return to_dense(c), metrics.snapshot(), c


def _dispatches(snap):
    vals = snap["counters"].get("dbcsr_tpu_dispatches_total", {})
    out = {"fused": 0, "per_span": 0}
    for key, v in vals.items():
        out[json.loads(key)["mode"]] = v
    return out


# ------------------------------------------------------- correctness


@pytest.mark.parametrize("alpha,beta", [(1.0, 0.0), (1.0, 0.5), (-2.0, 1.0)])
def test_fused_matches_per_span_bitwise(alpha, beta):
    """Multi-span-per-C-bin products are BIT-identical across modes:
    fusion chains the same kernels in the same order inside one
    program, so not even the rounding may move."""
    ref, _, _ = _run("per_span", alpha=alpha, beta=beta)
    got, snap, c = _run("fused", alpha=alpha, beta=beta)
    assert np.array_equal(ref, got)
    # and both match the dense oracle
    a, b, c = _mats()
    want = alpha * (to_dense(a) @ to_dense(b)) + beta * to_dense(c)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)
    assert _dispatches(snap)["fused"] >= 1


@pytest.mark.parametrize("driver", ["xla", "xla_flat"])
def test_fused_xla_family_matches_per_span(driver):
    """Force the pure-XLA drivers (this CPU's tuned table would pick
    host): the fused program chains their scan bodies inside one
    donated-C jit, bit-identically to the per-span dispatch loop."""
    if driver == "xla_flat":
        set_config(flat_gather=True)
    ref, _, _ = _run("per_span", mm_driver="xla")
    if driver == "xla_flat":
        set_config(flat_gather=True)
    got, snap, _ = _run("fused", mm_driver="xla")
    assert np.array_equal(ref, got)
    assert _dispatches(snap)["fused"] >= 1
    assert "acc.smm._fused_superstack" in snap["jit"]


def test_group_spans_are_counted_by_their_gather_layout():
    """`dbcsr_tpu_stack_gather_total{layout}` moves once per launched
    `xla_group` span, inside a fused launch as span by span, and reads
    the layout the body gathers from."""
    def counted(mode):
        _, snap, _ = _run(mode, mm_driver="xla_group")
        spans = snap["counters"].get("dbcsr_tpu_stack_gather_total", {})
        return {json.loads(key)["layout"]: v for key, v in spans.items()}

    by_span = counted("per_span")
    assert set(by_span) == {smm.GROUP_GATHER_LAYOUT} == {"row"}
    assert by_span["row"] >= 2  # several spans into one C bin
    assert counted("fused") == by_span


def test_fused_beta0_zero_bins_first_touch():
    """beta == 0: every bin starts as untouched zeros; a fused launch
    is the whole bin's first touch and must account it exactly once
    (the per-span path discards the zero-bin flag span by span)."""
    ref, _, _ = _run("per_span", fresh_c=True)
    got, _, _ = _run("fused", fresh_c=True)
    assert np.array_equal(ref, got)


def test_fused_dispatches_at_most_one_per_c_bin():
    """The tier-1 smoke of the fused contract: fused-mode launches per
    multiply <= #C bins (multi-span bins fuse to ONE dispatch; single-
    span bins stay per-span)."""
    got, snap, c = _run("fused")
    n_cbins = len(c.bins)  # the POST-multiply (grown) pattern's bins
    d = _dispatches(snap)
    assert d["fused"] >= 1
    assert d["fused"] + d["per_span"] <= n_cbins
    # the fused-span histogram observed every fused launch, each >= 2
    hist = snap["histograms"]["dbcsr_tpu_fused_spans"]
    (row,) = hist.values()
    assert row["count"] == d["fused"]
    assert row["sum"] >= 2 * d["fused"]


def test_auto_mode_is_fused():
    set_config(superstack="auto")
    assert mm._superstack_mode() == "fused"
    with pytest.raises(ValueError):
        set_config(superstack="bogus")


def test_env_typo_mode_raises_not_fuses(monkeypatch):
    """Env-applied config validates like set_config does: a typo'd
    control run (DBCSR_TPU_SUPERSTACK=per-span) must fail loudly at
    startup, not silently execute fused and poison the A/B."""
    from dbcsr_tpu.core import config as config_mod

    monkeypatch.setenv("DBCSR_TPU_SUPERSTACK", "per-span")
    with pytest.raises(ValueError, match="superstack"):
        config_mod._apply_env(config_mod.Config())


def test_quarantined_span_driver_routes_bin_per_span():
    """A fused program cannot route around a quarantined member
    kernel: any span whose own (driver, shape) breaker is not closed
    sends the bin per-span BEFORE launching (where execute_stack's
    gate applies), without consuming the half-open trial admission."""
    set_config(superstack="fused")
    mm._plan_cache.clear()
    a, b, _ = _mats()
    c = create("c", RBS, CBS, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c)  # learn the span drivers
    (entry,) = mm._plan_cache.values()
    _cbin, splan = next((cb, sp) for cb, (_drv, sp)
                        in entry.super_plans.items() if sp is not None)
    drv = splan.plans[0].driver
    board = breaker.get_board()
    # quarantine one member driver for EVERY shape key it could carry
    for sm_ in entry.spans:
        m, n, k = sm_[3], sm_[4], sm_[5]
        for _ in range(board.fail_threshold):
            board.record_failure(drv, (m, n, k, "float64"), kind="runtime")
    metrics.reset()
    c = create("c", RBS, CBS, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c)
    d = _dispatches(metrics.snapshot())
    assert d["fused"] == 0  # every bin decomposed pre-emptively


def test_fused_plan_reused_across_repeats():
    """Same-pattern repeats reuse both the per-span plans AND the
    cached superstack plans (no re-preparation)."""
    set_config(superstack="fused")
    mm._plan_cache.clear()
    a, b, _ = _mats()
    c1 = create("c", RBS, CBS, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c1)
    (entry,) = mm._plan_cache.values()
    splans = {cb: sp for cb, (_drv, sp) in entry.super_plans.items()
              if sp is not None}
    assert splans, "no bin fused"
    c2 = create("c", RBS, CBS, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c2)
    (entry2,) = mm._plan_cache.values()
    assert entry2 is entry
    for cb, sp in splans.items():
        assert entry2.super_plans[cb][1] is sp  # reused, not rebuilt
    assert checksum(c1) == checksum(c2)


def test_stale_superstack_rebuilt_after_heal():
    """A failover heals per-span plans IN PLACE (driver changes); the
    cached fused program must notice and rebuild instead of chaining
    the wrong kernel family."""
    set_config(superstack="fused")
    mm._plan_cache.clear()
    a, b, _ = _mats()
    c = create("c", RBS, CBS, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c)
    (entry,) = mm._plan_cache.values()
    cbin, splan = next((cb, sp) for cb, (_drv, sp)
                       in entry.super_plans.items() if sp is not None)
    plans = splan.plans
    # simulate a healed driver: flip span 0 into a DIFFERENT family
    # than its siblings so the rebuilt bin cannot fuse
    old_driver = plans[0].driver
    plans[0].driver = "xla" if old_driver != "xla" else "host"
    rebuilt = entry.superstack_for(cbin, plans, smm.prepare_superstack)
    assert rebuilt is not splan  # mixed family now: rebuilt (to None)
    assert rebuilt is None
    # ...and a cached None is NOT final: healing back to a fusable
    # driver tuple re-evaluates and the bin fuses again
    plans[0].driver = old_driver
    refused = entry.superstack_for(cbin, plans, smm.prepare_superstack)
    assert refused is not None and refused is not splan


@requires_native
def test_fused_host_family_single_fetch():
    """All-host-driver bins fuse too: ONE C fetch + writeback for the
    whole bin instead of one per span, same result."""
    set_config(mm_driver="host")
    ref, _, _ = _run("per_span")
    set_config(mm_driver="host")
    got, snap, c = _run("fused")
    assert np.array_equal(ref, got)
    assert _dispatches(snap)["fused"] >= 1


# ----------------------------------------------------- plan cache


def test_plan_cache_byte_counter_tracks_entries():
    set_config(superstack="fused")
    mm._plan_cache.clear()
    a, b, _ = _mats()
    c = create("c", RBS, CBS, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c)
    assert len(mm._plan_cache) == 1
    assert mm._plan_cache_bytes == sum(
        e.nbytes for e in mm._plan_cache.values())
    assert mm._plan_cache_bytes > 0


def test_plan_cache_byte_bound_eviction():
    """The byte budget evicts oldest-first in O(evicted) — the running
    counter stays consistent through insert/evict cycles (with fused
    plans attached to the entries)."""
    set_config(superstack="fused")
    mm._plan_cache.clear()
    a, b, _ = _mats()
    c = create("c", RBS, CBS, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c)
    (entry,) = mm._plan_cache.values()
    assert any(sp is not None for _drv, sp in entry.super_plans.values())
    old_max = mm._PLAN_CACHE_MAX_BYTES
    mm._PLAN_CACHE_MAX_BYTES = entry.nbytes + 1  # fits exactly one entry
    try:
        for seed in (20, 21, 22):
            a2, b2, c2 = _mats(seed=seed, occ=0.6)
            multiply("N", "N", 1.0, a2, b2, 0.5, c2)
            assert mm._plan_cache_bytes == sum(
                e.nbytes for e in mm._plan_cache.values())
            assert (len(mm._plan_cache) == 1
                    or mm._plan_cache_bytes <= mm._PLAN_CACHE_MAX_BYTES)
    finally:
        mm._PLAN_CACHE_MAX_BYTES = old_max


def test_plan_cache_clear_resets_byte_counter():
    """Tests (and users) clear() the OrderedDict directly; the next
    insert must not inherit a stale byte count."""
    set_config(superstack="fused")
    mm._plan_cache.clear()
    a, b, _ = _mats()
    c = create("c", RBS, CBS, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c)
    assert mm._plan_cache_bytes > 0
    mm._plan_cache.clear()
    c = create("c", RBS, CBS, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c)
    assert mm._plan_cache_bytes == sum(
        e.nbytes for e in mm._plan_cache.values())


# ---------------------------------------------------------- chaos


def test_fault_in_fused_launch_decomposes_identically():
    """A fault inside a fused launch decomposes to per-span failover
    with an IDENTICAL result, and the decomposition is observable."""
    ref, _, _ = _run("per_span", fresh_c=True)
    set_config(superstack="fused")
    mm._plan_cache.clear()
    metrics.reset()
    a, b, _ = _mats()
    c = create("c", RBS, CBS, dtype=np.float64)
    with faults.inject_faults("execute_superstack:raise,times=1"):
        multiply("N", "N", 1.0, a, b, 0.0, c)
    assert np.array_equal(to_dense(c), ref)
    snap = metrics.snapshot()
    fb = snap["counters"]["dbcsr_tpu_driver_fallback_total"]
    assert any("fused" in k and "per_span" in k for k in fb)
    inj = snap["counters"]["dbcsr_tpu_faults_injected_total"]
    assert any("execute_superstack" in k for k in inj)


def test_fault_corruption_in_fused_launch_decomposes():
    """NaN corruption of a fused launch's output is caught (checks are
    force-enabled under injection) and the bin re-runs per-span from
    the pristine buffer — checksum equals the clean run."""
    ref, _, _ = _run("per_span", fresh_c=True)
    set_config(superstack="fused")
    mm._plan_cache.clear()
    a, b, _ = _mats()
    c = create("c", RBS, CBS, dtype=np.float64)
    with faults.inject_faults("execute_superstack:nan,times=1"):
        multiply("N", "N", 1.0, a, b, 0.0, c)
    assert np.array_equal(to_dense(c), ref)
    assert np.isfinite(to_dense(c)).all()


def test_repeated_fused_failures_open_breaker():
    """Persistent fused failures trip the bin's 'fused' breaker: later
    multiplies route per-span WITHOUT attempting the fused launch.
    Incremental reuse is pinned off: a zero-delta repeat would
    legitimately serve the cached result without launching, and this
    test needs every multiply to actually execute."""
    set_config(superstack="fused", incremental="off")
    a, b, _ = _mats()
    with faults.inject_faults("execute_superstack:raise"):
        for _ in range(4):
            c = create("c", RBS, CBS, dtype=np.float64)
            multiply("N", "N", 1.0, a, b, 0.0, c)
    snap = breaker.get_board().snapshot()
    fused_rows = {k: v for k, v in snap.items() if k.startswith("fused|")}
    assert fused_rows
    assert any(row["state"] == "open" for row in fused_rows.values())
    # breaker open: the fused path is skipped pre-emptively (no new
    # failures even though the fault schedule is still armed)
    trips_before = {k: v["failures"] for k, v in fused_rows.items()}
    with faults.inject_faults("execute_superstack:raise"):
        c = create("c", RBS, CBS, dtype=np.float64)
        multiply("N", "N", 1.0, a, b, 0.0, c)
    snap2 = breaker.get_board().snapshot()
    for k, n in trips_before.items():
        assert snap2[k]["failures"] == n


# ------------------------------------------------- timing/costmodel


def test_sync_timing_tags_roofline_rows(monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_SYNC_TIMING", "1")
    got, snap, c = _run("fused")
    assert snap["roofline"], "no driver rollup rows"
    assert all(row["sync"] is True for row in snap["roofline"].values())
    monkeypatch.delenv("DBCSR_TPU_SYNC_TIMING")
    got2, snap2, _ = _run("fused")
    assert all(row["sync"] is False for row in snap2["roofline"].values())
    assert np.array_equal(got, got2)


def test_fused_breaker_not_wedged_half_open_by_span_breaker():
    """The span-breaker probe runs BEFORE allow(fused): when both the
    fused breaker (cooldown elapsed) and a span breaker are open, the
    decompose must not consume the fused half-open trial admission —
    that trial would never be resolved and the fused path would stay
    quarantined forever."""
    set_config(superstack="fused")
    mm._plan_cache.clear()
    a, b, _ = _mats()
    c = create("c", RBS, CBS, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c)
    (entry,) = mm._plan_cache.values()
    cbin, splan = next((cb, sp) for cb, (_drv, sp)
                       in entry.super_plans.items() if sp is not None)
    drv = splan.plans[0].driver
    nspans = len(splan.plans)
    bin_data = c.bins[cbin].data
    bin_key = smm._superstack_key(bin_data, nspans)
    t = [0.0]
    board = breaker.BreakerBoard(clock=lambda: t[0])
    breaker._board = board
    for _ in range(board.fail_threshold):
        board.record_failure("fused", bin_key, kind="runtime")
    for sm_ in entry.spans:
        for _ in range(board.fail_threshold):
            board.record_failure(drv, (sm_[3], sm_[4], sm_[5], "float64"),
                                 kind="runtime")
    assert board.state("fused", bin_key) == breaker.OPEN
    t[0] += board.cooldown_s * 20  # every cooldown elapsed
    c2 = create("c", RBS, CBS, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c2)
    # bin decomposed on the span breaker; the fused trial was NOT
    # consumed — the breaker still shows plain open, not half-open
    assert board.state("fused", bin_key) == breaker.OPEN
    assert checksum(c2) == checksum(c)


def test_fused_xla_cost_capture():
    """DBCSR_TPU_XLA_COST must keep producing drift data under the
    fused default: a fresh fused specialization captures XLA's own
    cost analysis next to the summed analytic model."""
    costmodel.enable_xla_capture(True)
    try:
        _run("fused", mm_driver="xla", fresh_c=True)
        xc = costmodel.xla_costs()
        assert "acc.smm._fused_superstack" in xc
        (rec,) = list(xc["acc.smm._fused_superstack"].values())[:1]
        assert rec["model"]["flops"] > 0 and rec["model"]["bytes"] > 0
    finally:
        costmodel.enable_xla_capture(False)


def test_superstack_bytes_matches_per_span_convention():
    """The fused cost model charges the bin's C round-trip once: the
    helper equals per-span stack_bytes with nseg on the first span
    only — and is strictly below the per-span total."""
    spans = [(5, 3, 4, 100), (5, 3, 2, 40)]
    nseg = 64
    fused_bytes = costmodel.superstack_bytes(spans, nseg=nseg, itemsize=8)
    first = costmodel.stack_bytes(5, 3, 4, 100, nseg=nseg, itemsize=8)
    rest = costmodel.stack_bytes(5, 3, 2, 40, nseg=0, itemsize=8)
    assert fused_bytes == first + rest
    per_span_total = (
        costmodel.stack_bytes(5, 3, 4, 100, nseg=nseg, itemsize=8)
        + costmodel.stack_bytes(5, 3, 2, 40, nseg=nseg, itemsize=8))
    assert fused_bytes < per_span_total


def test_fused_rollup_bytes_below_per_span():
    """End to end: the recorded per-driver bytes of a fused multiply
    undercut the per-span run by exactly the eliminated C round-trips."""
    _, snap_ps, _ = _run("per_span", fresh_c=True)
    _, snap_f, _ = _run("fused", fresh_c=True)

    def total_bytes(snap):
        return sum(r["bytes_moved"] for r in snap["roofline"].values())

    assert total_bytes(snap_f) < total_bytes(snap_ps)


# ------------------------------------------------------- microbench


def test_dispatch_bench_smoke():
    """tools/dispatch_bench.py at a tiny size: identical checksums,
    fused launches <= #C bins, sane report shape."""
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(
        __file__).resolve().parents[1] / "tools"))
    import dispatch_bench

    res = dispatch_bench.run(m=600, n=600, k=600, occ=0.4, nrep=1)
    assert res["checksums_identical"] is True
    assert res["fused_dispatches_per_multiply"] <= res["c_bins"]
    assert (res["dispatches_per_multiply"]["fused"]
            < res["dispatches_per_multiply"]["per_span"])
    assert res["value"] > 0 and res["unit"] == "multiply/s"
