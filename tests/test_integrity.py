"""End-to-end data integrity: ABFT probe checksums (stack, superstack,
dense, tick-pipeline, serve boundaries), the ``flip`` finite-SDC fault
kind, chain checkpoint/rollback, serve drain → restart replay, the
``integrity`` health component, and the watchdog log rotation.

The acceptance contract pinned here: injected ``flip`` faults at the
stack, mesh-shift, and serve-execute sites are DETECTED by the
ABFT/invariant layer and fully recovered — final results bitwise-equal
to the fault-free run.  All tier-1, CPU-only.
"""

import json
import os

import numpy as np
import pytest

from dbcsr_tpu.core import mempool
from dbcsr_tpu.core.config import get_config, set_config
from dbcsr_tpu.core.matrix import BlockSparseMatrix
from dbcsr_tpu.mm.multiply import multiply
from dbcsr_tpu.obs import costmodel, health, metrics
from dbcsr_tpu.ops.test_methods import checksum, make_random_matrix, to_dense
from dbcsr_tpu.resilience import breaker, faults, watchdog


@pytest.fixture(autouse=True)
def _clean_slate():
    from dbcsr_tpu.mm import multiply as mm_mod

    cfg0 = {f: getattr(get_config(), f)
            for f in ("abft", "mm_driver", "mm_format", "use_pallas",
                      "serve_coalesce")}
    faults.clear()
    breaker.reset_board()
    metrics.reset()
    health.reset()
    mm_mod._plan_cache.clear()
    yield
    faults.clear()
    breaker.reset_board()
    metrics.reset()
    health.reset()
    mm_mod._plan_cache.clear()
    set_config(**cfg0)


def _mats(bs=(5,) * 6, dtype=np.float64, occ=0.6, occ_c=0.3, seed=0):
    rng = np.random.default_rng(seed)
    bs = list(bs)
    a = make_random_matrix("A", bs, bs, dtype=dtype, occupation=occ, rng=rng)
    b = make_random_matrix("B", bs, bs, dtype=dtype, occupation=occ, rng=rng)
    c = make_random_matrix("C", bs, bs, dtype=dtype, occupation=occ_c,
                           rng=rng)
    return a, b, c


def _ctr(name):
    c = metrics._counters.get(name)
    return float(sum(c.values.values())) if c is not None else 0.0


def _ctr_by_driver(name):
    c = metrics._counters.get(name)
    out = {}
    if c is not None:
        for key, v in c.values.items():
            d = dict(key).get("driver", "?")
            out[d] = out.get(d, 0) + int(v)
    return out


# ------------------------------------------------------------ tolerance

def test_abft_tolerance_scales_with_dtype_and_depth():
    t64 = costmodel.abft_tolerance("float64", 100, 8)
    t32 = costmodel.abft_tolerance("float32", 100, 8)
    assert 0 < t64 < t32 < 1e-2
    assert costmodel.abft_tolerance("float64", 1000, 8) > t64
    assert costmodel.abft_tolerance("float64", 100, 64) > t64
    # bf16 accumulates in f32 (the engine's _accum_dtype contract)
    assert costmodel.abft_tolerance("bfloat16", 10, 2) == \
        costmodel.abft_tolerance("float32", 10, 2)


def test_config_abft_validation():
    with pytest.raises(ValueError):
        set_config(abft="sometimes")
    set_config(abft="verify")
    assert get_config().abft == "verify"


# ------------------------------------------------------- the flip kind

def test_flip_fault_is_finite_and_deterministic():
    import jax.numpy as jnp

    x = jnp.zeros((4, 3, 3), jnp.float64)
    with faults.inject_faults("site_x:flip,seed=11,times=2"):
        y1 = faults.corrupt("site_x", x)
        y2 = faults.corrupt("site_x", x)
        y3 = faults.corrupt("site_x", x)  # times exhausted
    a1, a2, a3 = (np.asarray(v) for v in (y1, y2, y3))
    assert np.isfinite(a1).all() and (a1 != 0).sum() == 1
    assert (a1 == a2).all()          # seed-deterministic
    assert (a3 == 0).all()           # spec exhausted: untouched
    assert float(np.abs(a1).max()) >= 1024.0  # far above any tolerance


# -------------------------------------------- stack / superstack / dense

def test_stack_flip_detected_and_recovered_bitwise():
    a, b, c = _mats(seed=1)
    ref_a, ref_b, ref_c = _mats(seed=1)
    multiply("N", "N", 1.5, ref_a, ref_b, 0.5, ref_c)
    ref = np.asarray(to_dense(ref_c))

    set_config(abft="verify")
    with faults.inject_faults("execute_stack:flip,seed=5,times=1") as sp:
        multiply("N", "N", 1.5, a, b, 0.5, c)
    assert sp[0].fired == 1
    assert (np.asarray(to_dense(c)) == ref).all()
    assert _ctr("dbcsr_tpu_abft_mismatches_total") >= 1
    assert _ctr("dbcsr_tpu_abft_recoveries_total") >= 1
    # the mismatch classified `sdc` and fed the breaker plane
    fails = metrics._counters.get("dbcsr_tpu_driver_failures_total")
    kinds = {dict(k).get("kind") for k in fails.values}
    assert "sdc" in kinds


def test_deferred_multi_mismatch_recovery_counters_balance():
    """A beta==0 product defers its probes to the product boundary;
    one re-execution heals EVERY mismatched launch, and the recovery
    counter must advance once per counted mismatch — otherwise health
    reports fully-recovered SDC as escaped corruption (false
    CRITICAL)."""
    a, b, c = _mats(seed=6)
    ref_a, ref_b, ref_c = _mats(seed=6)
    multiply("N", "N", 1.0, ref_a, ref_b, 0.0, ref_c)
    ref = np.asarray(to_dense(ref_c))
    set_config(abft="verify")
    with faults.inject_faults(
            "execute_stack:flip,seed=5,times=2,prob=1.0") as sp:
        multiply("N", "N", 1.0, a, b, 0.0, c)
    assert sp[0].fired >= 1
    assert (np.asarray(to_dense(c)) == ref).all()
    mm = _ctr("dbcsr_tpu_abft_mismatches_total")
    rc = _ctr("dbcsr_tpu_abft_recoveries_total")
    assert mm >= 1 and rc == mm


def test_host_flip_corrupted_retry_is_reprobed_and_recovered():
    """A flip corrupting the pristine SAME-DRIVER retry must be caught
    by the candidate probe under ``abft=verify`` too: `_run_candidate`
    used to gate that probe on ``recover`` alone, so a second flip
    landing on the host driver's retry was accepted unprobed — and
    even counted as a recovery.  Pinned: both flips detected, the
    mismatch/recovery counters stay balanced, the chain walks off
    host, and the final result is correct."""
    a, b, c = _mats(seed=9)
    ref_a, ref_b, ref_c = _mats(seed=9)
    set_config(mm_driver="host")
    multiply("N", "N", 1.5, ref_a, ref_b, 0.5, ref_c)
    ref = np.asarray(to_dense(ref_c))

    set_config(abft="verify")
    with faults.inject_faults("host:flip,seed=5,times=2,prob=1.0") as sp:
        multiply("N", "N", 1.5, a, b, 0.5, c)
    assert sp[0].fired == 2  # primary AND its same-driver retry
    mm = _ctr("dbcsr_tpu_abft_mismatches_total")
    rc = _ctr("dbcsr_tpu_abft_recoveries_total")
    assert mm >= 2 and rc == mm
    # the corrupted retry was rejected and the chain walked off host
    fb = metrics._counters.get("dbcsr_tpu_driver_fallback_total")
    pairs = {(dict(k).get("from"), dict(k).get("to"))
             for k in (fb.values if fb is not None else {})}
    assert any(f == "host" and t != "host" for f, t in pairs), pairs
    # healed on a different driver: allclose, not bitwise (the chain
    # candidate's accumulation order is its own)
    assert np.allclose(np.asarray(to_dense(c)), ref, rtol=1e-9, atol=0)


def test_abft_off_is_zero_overhead_and_blind():
    """With the knob off nothing probes: a flip sails through (the
    pre-ABFT world this PR exists to close) — pinned so the knob's
    'off means off' contract stays true."""
    a, b, c = _mats(seed=2)
    ref_a, ref_b, ref_c = _mats(seed=2)
    multiply("N", "N", 1.0, ref_a, ref_b, 0.0, ref_c)
    with faults.inject_faults("execute_stack:flip,seed=5,times=1") as sp:
        multiply("N", "N", 1.0, a, b, 0.0, c)
    assert sp[0].fired == 1
    assert _ctr("dbcsr_tpu_abft_checks_total") == 0
    assert not (np.asarray(to_dense(c))
                == np.asarray(to_dense(ref_c))).all()


def test_superstack_flip_decomposes_and_recovers():
    set_config(superstack="fused")
    a, b, c = _mats(bs=(4,) * 8, occ=0.7, seed=3)
    ref_a, ref_b, ref_c = _mats(bs=(4,) * 8, occ=0.7, seed=3)
    multiply("N", "N", 1.0, ref_a, ref_b, 0.0, ref_c)
    ref = np.asarray(to_dense(ref_c))
    set_config(abft="verify")
    with faults.inject_faults("execute_superstack:flip,seed=9,times=1") \
            as sp:
        multiply("N", "N", 1.0, a, b, 0.0, c)
    if sp[0].fired:  # fused path taken: mismatch -> per-span decompose
        assert _ctr_by_driver(
            "dbcsr_tpu_abft_mismatches_total").get("fused", 0) >= 1
    assert (np.asarray(to_dense(c)) == ref).all()


def test_dense_flip_degrades_to_stack_engine():
    a, b, c = _mats(occ=0.95, occ_c=0.95, seed=4)
    set_config(abft="verify")
    with faults.inject_faults("dense:flip,seed=7,times=1") as sp:
        multiply("N", "N", 2.0, a, b, 0.5, c)
    assert sp[0].fired == 1
    assert c._mm_algorithm == "stack"  # dense condemned, stack healed
    assert _ctr_by_driver(
        "dbcsr_tpu_abft_mismatches_total").get("dense", 0) == 1
    assert _ctr_by_driver(
        "dbcsr_tpu_abft_recoveries_total").get("dense", 0) == 1
    # value-correct vs a clean stack-engine run (dense vs stack differ
    # only in accumulation order, so compare relative)
    ref_a, ref_b, ref_c = _mats(occ=0.95, occ_c=0.95, seed=4)
    set_config(abft="off", mm_format="stack")
    multiply("N", "N", 2.0, ref_a, ref_b, 0.5, ref_c)
    rel = abs(checksum(c) - checksum(ref_c)) / abs(checksum(ref_c))
    assert rel < 1e-11


# --------------------------------------------------- mesh-shift probes

def test_mesh_shift_flip_degrades_to_serial_bitwise():
    from dbcsr_tpu.obs import flight
    from dbcsr_tpu.parallel import make_grid, sparse_multiply_distributed
    from dbcsr_tpu.parallel.sparse_dist import clear_mesh_plans

    mesh = make_grid(4)
    rng = np.random.default_rng(3)
    bs = [3, 5, 4, 2, 6, 3]
    a = make_random_matrix("A", bs, bs, occupation=0.6, rng=rng)
    b = make_random_matrix("B", bs, bs, occupation=0.6, rng=rng)
    set_config(cannon_overlap="double_buffer")
    clear_mesh_plans()
    clean = np.asarray(to_dense(
        sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh)))

    set_config(abft="verify")
    breaker.reset_board()
    clear_mesh_plans()
    with faults.inject_faults("mesh_shift:flip,seed=97,times=1") as sp:
        out = np.asarray(to_dense(
            sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh)))
    assert sp[0].fired == 1
    assert (out == clean).all()
    assert flight.records()[-1]["cannon_mode"] == "serial"
    assert _ctr("dbcsr_tpu_abft_mismatches_total") >= 1
    assert _ctr("dbcsr_tpu_abft_recoveries_total") >= 1


# ------------------------------------------------ chain snapshot/restore

def _density(seed=7, nblk=6, bsize=4):
    from dbcsr_tpu.models.purify import make_test_density

    return make_test_density(nblk, bsize, occ=0.4, seed=seed)


def test_snapshot_restore_roundtrip_and_reuse():
    m = _density()
    before = np.asarray(to_dense(m))
    with mempool.chain() as ch:
        snap = ch.snapshot(m)
        m.map_bin_data(lambda d: d * 3.0)
        assert not (np.asarray(to_dense(m)) == before).all()
        ch.restore(snap)
        assert (np.asarray(to_dense(m)) == before).all()
        # a snapshot installs FRESH copies: restore twice is legal
        m.map_bin_data(lambda d: d + 1.0)
        ch.restore(snap)
        assert (np.asarray(to_dense(m)) == before).all()


def test_restore_after_retire_is_structured_error():
    with mempool.chain() as ch:
        m = _density()
        ch.adopt(m)
        snap = ch.snapshot(m)
        ch.retire(m)
        with pytest.raises(mempool.SnapshotError):
            ch.restore(snap)


def test_nested_chain_restore_honors_owner_retire():
    """A snapshot taken in the OUTER chain refuses to restore from a
    nested chain once the owner retired the matrix."""
    with mempool.chain() as outer:
        m = _density()
        outer.adopt(m)
        snap = outer.snapshot(m)
        with mempool.chain() as inner:
            # restore through the nested chain works while m lives...
            m.map_bin_data(lambda d: d * 2.0)
            inner.restore(snap)
            # ...but not after the OWNER gave the matrix up
            outer.retire(m)
            with pytest.raises(mempool.SnapshotError):
                inner.restore(snap)


def test_shared_bins_never_restored_via_donation():
    if not mempool.enabled():
        pytest.skip("memory pool disabled")
    m = _density()
    twin = m.copy()           # bins now shared with `twin`
    twin_before = np.asarray(to_dense(twin))
    snap = mempool.snapshot_matrix(m)
    returns0 = mempool.pool_stats()["returns"]
    mempool.restore_matrix(snap)
    # the replaced buffers were SHARED: restore must not donate them
    assert mempool.pool_stats()["returns"] == returns0
    assert (np.asarray(to_dense(twin)) == twin_before).all()
    # a pool-owned (chain-adopted), exclusively-held matrix's buffers
    # DO recycle on restore
    with mempool.chain() as ch:
        solo = _density(seed=8)
        ch.adopt(solo)
        snap2 = ch.snapshot(solo)
        returns1 = mempool.pool_stats()["returns"]
        ch.restore(snap2)
        assert mempool.pool_stats()["returns"] > returns1
        ch.detach(solo)


# ------------------------------------------------- chain rollback plane

def test_purify_chain_rollback_bitwise():
    from dbcsr_tpu.models.purify import mcweeny_purify

    ref, _ = mcweeny_purify(_density(), steps=3)
    ref_d = np.asarray(to_dense(ref))
    # ABFT off + active faults: the stack probes are blind, the chain
    # invariant is the detector; flip corrupts step >= 1 mid-chain
    with faults.inject_faults("execute_stack:flip,seed=13,times=1") as sp:
        out, _ = mcweeny_purify(_density(), steps=3)
    assert sp[0].fired == 1
    assert _ctr("dbcsr_tpu_chain_rollback_total") >= 1
    assert (np.asarray(to_dense(out)) == ref_d).all()


@pytest.mark.parametrize("model", ["sign", "invsqrt"])
def test_model_chain_rollback_bitwise(model):
    if model == "sign":
        from dbcsr_tpu.models.sign import sign_iteration as run_model

        def run():
            out, _hist = run_model(_density(seed=9), steps=4)
            return out
    else:
        from dbcsr_tpu.models.invsqrt import invsqrt_iteration

        def run():
            out, _sf, _it = invsqrt_iteration(_density(seed=9), max_iter=4)
            return out
    ref = np.asarray(to_dense(run()))
    with faults.inject_faults("execute_stack:flip,seed=21,times=1") as sp:
        out = np.asarray(to_dense(run()))
    assert sp[0].fired == 1
    assert (out == ref).all()
    assert _ctr("dbcsr_tpu_abft_recoveries_total") >= 1


# -------------------------------------------------- serve-level probes

def test_serve_flip_recovered_bitwise_with_counters():
    from dbcsr_tpu import serve

    bs = [4] * 6

    def build(seed=7):
        a = make_random_matrix("A", bs, bs, occupation=0.5,
                               rng=np.random.default_rng(seed))
        b = make_random_matrix("B", bs, bs, occupation=0.5,
                               rng=np.random.default_rng(seed + 1))
        c = make_random_matrix("C", bs, bs, occupation=0.3,
                               rng=np.random.default_rng(seed + 2))
        return a, b, c

    ref_a, ref_b, ref_c = build()
    multiply("N", "N", 1.0, ref_a, ref_b, 0.0, ref_c)
    ref = np.asarray(to_dense(ref_c))

    set_config(abft="verify")
    eng = serve.ServeEngine(start=True)
    try:
        sess = eng.open_session("abft-t")
        a, b, c = build()
        sess.put("a", a), sess.put("b", b), sess.put("c", c)
        with faults.inject_faults("serve_execute:flip,seed=3,times=1") \
                as sp:
            t = eng.submit(sess, a="a", b="b", c="c", alpha=1.0, beta=0.0)
            assert t.wait(60) and t.state == "done", t.info()
        assert sp[0].fired == 1
        assert t.result.get("verified") == 1
        assert (np.asarray(to_dense(c)) == ref).all()
        assert _ctr_by_driver(
            "dbcsr_tpu_abft_mismatches_total").get("serve", 0) == 1
        assert _ctr_by_driver(
            "dbcsr_tpu_abft_recoveries_total").get("serve", 0) == 1
        sess.close()
    finally:
        eng.shutdown()


# ------------------------------------------------- drain/restart replay

def test_drain_journals_and_restart_replays_exactly_once(tmp_path,
                                                         monkeypatch):
    from dbcsr_tpu import serve

    journal = str(tmp_path / "serve_journal.jsonl")
    monkeypatch.setenv("DBCSR_TPU_SERVE_JOURNAL", journal)
    bs = [4] * 6
    rng = np.random.default_rng(11)
    a = make_random_matrix("A", bs, bs, occupation=0.5, rng=rng)
    b = make_random_matrix("B", bs, bs, occupation=0.5, rng=rng)
    c = make_random_matrix("C", bs, bs, occupation=0.3, rng=rng)

    eng = serve.ServeEngine(start=True)
    sess = eng.open_session("drain-t")
    for nm, m in (("a", a), ("b", b), ("c", c)):
        sess.put(nm, m)
    # stop the worker so the request stays QUEUED for the drain
    eng._stop.set()
    eng._thread.join(10)
    t = eng.submit(sess, a="a", b="b", c="c", alpha=2.0, beta=0.0)
    res = eng.drain(timeout=5)
    assert res["journaled"] == 1 and res["completed_inflight"]
    assert t.state == "journaled"
    # post-drain submissions shed with the structured reason
    t2 = eng.submit(sess, a="a", b="b", c="c")
    assert t2.state == "shed" and "draining" in (t2.error or "")
    # duplicate + torn tail lines: replay must stay exactly-once
    line = open(journal).read().strip()
    with open(journal, "a") as fh:
        fh.write(line + "\n")
        fh.write(line[: len(line) // 2])  # torn tail (killed mid-append)

    # "restart": a new engine in the same process replays on start()
    eng2 = serve.ServeEngine(start=True)
    try:
        replayed = None
        for _ in range(400):
            replayed = eng2.get_request(t.request_id)
            if replayed is not None and replayed.done:
                break
            import time

            time.sleep(0.025)
        assert replayed is not None and replayed.state == "done", (
            replayed.info() if replayed else "never replayed")
        # exactly once: one replayed-request counter tick, original id
        assert _ctr("dbcsr_tpu_serve_journal_replayed_total") == 1
        assert not os.path.exists(journal)  # fully replayed -> removed
        # rebuild the reference from the same seeds: rng was shared
        rng2 = np.random.default_rng(11)
        ra = make_random_matrix("A", bs, bs, occupation=0.5, rng=rng2)
        rb = make_random_matrix("B", bs, bs, occupation=0.5, rng=rng2)
        rc = make_random_matrix("C", bs, bs, occupation=0.3, rng=rng2)
        multiply("N", "N", 2.0, ra, rb, 0.0, rc)
        assert (np.asarray(to_dense(c)) == np.asarray(to_dense(rc))).all()
        sess.close()
    finally:
        eng2.shutdown()


def test_unjournalable_object_params_fail_wedged(tmp_path, monkeypatch):
    from dbcsr_tpu import serve

    monkeypatch.setenv("DBCSR_TPU_SERVE_JOURNAL",
                       str(tmp_path / "j.jsonl"))
    bs = [4] * 4
    rng = np.random.default_rng(5)
    a = make_random_matrix("A", bs, bs, occupation=0.5, rng=rng)
    b = make_random_matrix("B", bs, bs, occupation=0.5, rng=rng)
    c = make_random_matrix("C", bs, bs, occupation=0.3, rng=rng)
    eng = serve.ServeEngine(start=True)
    sess = eng.open_session("obj-t")
    eng._stop.set()
    eng._thread.join(10)
    t = eng.submit(sess, a=a, b=b, c=c)  # raw objects: not journalable
    res = eng.drain(timeout=5)
    assert res["journaled"] == 0
    assert t.state == "failed" and "not journalable" in t.error
    sess.close()


# -------------------------------------------------- health + doctor row

def test_health_integrity_component_verdicts():
    v = health.verdict()
    assert v["components"]["integrity"]["status"] == "OK"
    mm = metrics.counter("dbcsr_tpu_abft_mismatches_total", "t")
    rc = metrics.counter("dbcsr_tpu_abft_recoveries_total", "t")
    # recovered SDC, however repeated, stays DEGRADED
    for _ in range(4):
        mm.inc(driver="pallas")
        rc.inc(driver="pallas")
    v = health.verdict()
    comp = v["components"]["integrity"]
    assert comp["status"] == "DEGRADED"
    assert comp["abft_mismatches"] == {"pallas": 4}
    # corruption that ESCAPED recovery, repeated from one driver ->
    # CRITICAL
    for _ in range(3):
        mm.inc(driver="pallas")
    v = health.verdict()
    assert v["components"]["integrity"]["status"] == "CRITICAL"
    assert v["status"] == "CRITICAL"


def test_health_chain_rollback_degrades():
    metrics.counter("dbcsr_tpu_chain_rollback_total", "t").inc(
        model="purify")
    comp = health.verdict()["components"]["integrity"]
    assert comp["status"] == "DEGRADED"
    assert comp["chain_rollbacks"] == 1


def test_doctor_integrity_row_from_events(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "doctor", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "doctor.py"))
    doctor = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(doctor)
    events = [
        {"event": "abft_mismatch", "driver": "pallas", "site": "stack"},
        {"event": "abft_mismatch", "driver": "pallas", "site": "stack"},
        {"event": "abft_mismatch", "driver": "pallas", "site": "stack"},
        {"event": "chain_rollback", "model": "sign", "step": 1},
        {"event": "serve_drain", "journal": "j.jsonl", "journaled": 2},
        {"event": "serve_replayed", "request_id": "r1", "tenant": "t"},
    ]
    report = doctor.analyze(None, {}, events, [], [], [])
    assert report["integrity"]["mismatches"] == {"pallas": 3}
    assert report["integrity"]["rollbacks"] == 1
    assert report["integrity"]["drains"] == 1
    assert {h["kind"] for h in report["hints"]} >= {
        "abft_mismatch", "sdc_critical", "chain_rollback", "serve_drain"}
    assert report["health"]["status"] == "CRITICAL"


# ----------------------------------------------- watchdog log rotation

def test_watchdog_jsonl_rotation_preserves_streak(tmp_path):
    path = str(tmp_path / "probe.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps({"name": "tpu_probe", "outcome": "WEDGED",
                             "streak": 4, "wedge_streak": 2}) + "\n")
        for i in range(5000):
            fh.write(json.dumps({"name": "capture_attempt",
                                 "status": {"i": i}}) + "\n")
    assert os.path.getsize(path) > 64 * 1024
    assert watchdog.rotate_jsonl(path, 64 * 1024)
    assert os.path.getsize(path) <= 64 * 1024
    # the live wedge streak survives: resume still finds the last
    # record for the channel even though it was the FIRST line
    wd = watchdog.Watchdog("tpu_probe", 10, state_path=path)
    assert wd.streak == 4 and wd.wedge_streak == 2
    # under the cap: no-op
    assert not watchdog.rotate_jsonl(path, 1 << 20)


def test_watchdog_persist_rotates_at_cap(tmp_path, monkeypatch):
    path = str(tmp_path / "wd.jsonl")
    monkeypatch.setenv("DBCSR_TPU_WATCHDOG_LOG_MAX_BYTES", "4096")
    wd = watchdog.Watchdog("chan", deadline_s=10, state_path=path,
                           resume=False)
    for _ in range(200):
        wd.guard(lambda deadline: None)
    assert os.path.getsize(path) <= 4096
