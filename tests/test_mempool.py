"""Device residency: memory pool, chains, donation, and index mirrors.

Covers the `core.mempool` contracts:

* pooled/donated chains are BITWISE identical to the unpooled path
  (purify, invsqrt, sign);
* pool checkout/release/budget-eviction semantics;
* device index mirrors (global content-keyed + per-matrix) invalidate
  when structure changes (finalize);
* chaos: injected faults mid-chain must not corrupt donated buffers
  (the PR-4 decompose caveat extended to recycled storage);
* pool observability (metrics snapshot, health thrash note).
"""

import os
import sys

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax.numpy as jnp  # noqa: E402

import dbcsr_tpu as dt  # noqa: E402
from dbcsr_tpu.core import mempool  # noqa: E402
from dbcsr_tpu.core.matrix import BlockSparseMatrix  # noqa: E402
from dbcsr_tpu.mm.multiply import multiply  # noqa: E402
from dbcsr_tpu.models.invsqrt import invsqrt_iteration  # noqa: E402
from dbcsr_tpu.models.purify import make_test_density, mcweeny_purify  # noqa: E402
from dbcsr_tpu.models.sign import sign_iteration  # noqa: E402
from dbcsr_tpu.ops.operations import add, filter_matrix  # noqa: E402
from dbcsr_tpu.ops.test_methods import make_random_matrix, to_dense  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_pool():
    """Each test starts with an empty, enabled pool and ends restored."""
    was = mempool.enabled()
    mempool.set_enabled(True)
    mempool.clear()
    mempool.reset_stats()
    yield
    mempool.set_enabled(was)
    mempool.clear()


def _chain_result(fn, pooled: bool):
    import dbcsr_tpu.mm.multiply as mm

    mempool.set_enabled(pooled)
    mempool.clear()
    mempool.reset_stats()
    mm._plan_cache.clear()
    return fn()


# ------------------------------------------------------------- identity

def _purify_dense():
    p = make_test_density(8, 5, occ=0.3, seed=3)
    out, _ = mcweeny_purify(p, steps=4, filter_eps=1e-10)
    return np.asarray(to_dense(out))


def _sign_dense():
    rng = np.random.default_rng(5)
    a = make_random_matrix("A", [4] * 6, [4] * 6, occupation=0.5, rng=rng)
    x, _ = sign_iteration(a, steps=4, filter_eps=1e-10)
    return np.asarray(to_dense(x))


def _invsqrt_dense():
    rng = np.random.default_rng(9)
    s = make_random_matrix("S", [4] * 5, [4] * 5, occupation=0.4,
                           matrix_type="S", rng=rng)
    from dbcsr_tpu.ops.operations import add_on_diag, scale

    s = dt.desymmetrize(s)
    scale(s, 0.05)
    add_on_diag(s, 1.0)  # SPD-ish: diagonally dominant
    z, sf, _ = invsqrt_iteration(s, max_iter=6, filter_eps=1e-12)
    return np.asarray(to_dense(z))


@pytest.mark.parametrize("workload", [_purify_dense, _sign_dense,
                                      _invsqrt_dense],
                         ids=["purify", "sign", "invsqrt"])
def test_pooled_chain_bitwise_identical(workload):
    """The device-residency path (pool + donation + mirrors) must be
    BITWISE identical to the unpooled control for every model chain."""
    ref = _chain_result(workload, pooled=False)
    got = _chain_result(workload, pooled=True)
    assert got.dtype == ref.dtype
    assert np.array_equal(got, ref)


def test_pooled_chain_recycles_buffers():
    """A purification loop must actually hit the pool (retired
    iterates feed later checkouts) and leave no stale invalid state."""
    p = make_test_density(8, 5, occ=0.4, seed=1)
    out, _ = mcweeny_purify(p, steps=4, filter_eps=1e-10)
    st = mempool.pool_stats()
    assert st["returns"] > 0
    assert st["hits"] > 0
    from dbcsr_tpu.ops.operations import verify_matrix

    verify_matrix(out)
    # the input survives untouched and fully readable
    verify_matrix(p)


# ------------------------------------------------------ pool semantics

def test_checkout_miss_then_hit_and_zeroed():
    a = mempool.zeros((4, 3, 3), np.float64)
    st0 = mempool.pool_stats()
    assert st0["misses"] == 1 and st0["hits"] == 0
    filled = a + 7.0  # make a non-zero buffer to recycle
    assert mempool.release(filled)
    st1 = mempool.pool_stats()
    assert st1["returns"] == 1
    assert st1["bytes_held"] == 4 * 3 * 3 * 8
    b = mempool.zeros((4, 3, 3), np.float64)
    st2 = mempool.pool_stats()
    assert st2["hits"] == 1
    assert st2["bytes_held"] == 0
    # recycled buffers come back ZEROED, never with stale data
    # (whether the released reference reads as deleted afterwards is
    # backend-dependent — CPU XLA may decline the aliasing — so the
    # zero-content guarantee is the contract, not deletion)
    assert np.array_equal(np.asarray(b), np.zeros((4, 3, 3)))


def test_release_shape_and_dtype_keying():
    x = jnp.ones((2, 5, 5), np.float64)
    assert mempool.release(x)
    # different dtype same shape: miss
    y = mempool.zeros((2, 5, 5), np.float32)
    assert mempool.pool_stats()["hits"] == 0
    del y
    # exact (shape, dtype): hit
    z = mempool.zeros((2, 5, 5), np.float64)
    assert mempool.pool_stats()["hits"] == 1
    del z


def test_budget_eviction(monkeypatch):
    monkeypatch.setenv("DBCSR_TPU_POOL_BYTES", "1000")
    assert mempool.release(jnp.ones((4, 4), np.float64))  # 128 B banked
    big = jnp.ones((64, 64), np.float64)  # 32 KB: over budget
    assert not mempool.release(big)
    st = mempool.pool_stats()
    assert st["evictions"] == 1
    assert st["returns"] == 1
    assert st["bytes_held"] == 128
    assert not big.is_deleted()  # evicted buffers are left alone


def test_budget_evicts_stale_shapes_on_phase_change(monkeypatch):
    """An over-budget release reclaims the OLDEST held buffers instead
    of dropping the incoming one: a workload phase change (new block
    shapes) must not wedge the pool full of dead shapes."""
    monkeypatch.setenv("DBCSR_TPU_POOL_BYTES", "3072")
    for _ in range(4):
        assert mempool.release(jnp.ones((8, 8), np.float64))  # 4 x 512 B
    assert mempool.release(jnp.ones((16, 16), np.float64))    # 2048 B
    st = mempool.pool_stats()
    assert st["bytes_held"] == 3072
    assert st["evictions"] == 2  # two stale 512 B buffers reclaimed
    mempool.zeros((16, 16), np.float64)
    assert mempool.pool_stats()["hits"] == 1  # the new shape is served


def test_release_rejects_non_device_and_double_release():
    assert not mempool.release(np.ones((3, 3)))
    x = jnp.ones((3, 3), np.float64)
    assert mempool.release(x)
    # double release of the SAME (now pool-owned) array: the second
    # entry is skipped at checkout once the first donation deletes it
    assert mempool.release(x)
    a = mempool.zeros((3, 3), np.float64)
    b = mempool.zeros((3, 3), np.float64)  # dead entry skipped -> miss
    assert np.array_equal(np.asarray(a), np.zeros((3, 3)))
    assert np.array_equal(np.asarray(b), np.zeros((3, 3)))


def test_disabled_pool_is_inert():
    mempool.set_enabled(False)
    assert not mempool.release(jnp.ones((2, 2), np.float64))
    z = mempool.zeros((2, 2), np.float64)
    assert np.array_equal(np.asarray(z), np.zeros((2, 2)))
    assert mempool.pool_stats()["returns"] == 0


# ------------------------------------------------------------- chains

def test_chain_adopts_and_frees_temporaries():
    with mempool.chain():
        m = BlockSparseMatrix("t", [3, 3], [3, 3])
        m.put_block(0, 0, np.ones((3, 3)))
        m.finalize()
        assert m._pool_owned
        held = m.bins[0].data
    # chain exit freed the adopted matrix into the pool
    assert not m.valid
    assert mempool.pool_stats()["returns"] >= 1
    assert mempool.pool_stats()["bytes_held"] > 0
    del held


def test_chain_detach_escapes_and_nested_transfer():
    with mempool.chain() as outer:
        with mempool.chain() as inner:
            m = BlockSparseMatrix("t", [3], [3])
            m.put_block(0, 0, np.ones((3, 3)))
            m.finalize()
            inner.detach(m)  # transfers to OUTER, not freed here
        assert m.valid
        outer.detach(m)  # escapes entirely
    assert m.valid
    assert m._pool_owned  # still donates on later mutations


def test_copy_marks_shared_and_blocks_donation():
    with mempool.chain() as ch:
        m = BlockSparseMatrix("t", [3], [3])
        m.put_block(0, 0, np.ones((3, 3)))
        m.finalize()
        c = m.copy()
        data = m.bins[0].data
        ch.retire(m)
        ch.detach(c)  # the copy escapes; m was freed above
    # shared bins are never donated: the copy still reads them
    assert not data.is_deleted()
    assert np.array_equal(c.get_block(0, 0), np.ones((3, 3)))
    assert mempool.pool_stats()["returns"] == 0  # nothing was banked


def test_retire_ignores_unadopted_inputs():
    p = make_test_density(4, 3, occ=0.5, seed=2)  # created OUTSIDE
    with mempool.chain() as ch:
        ch.retire(p)  # must be a no-op
    assert p.valid


# ------------------------------------------------------------- mirrors

def test_upload_index_content_keyed():
    arr = np.arange(16, dtype=np.int32)
    d1 = mempool.upload_index("t", arr)
    d2 = mempool.upload_index("t", np.arange(16, dtype=np.int32))
    assert d1 is d2  # same content -> same device array
    d3 = mempool.upload_index("t", np.arange(17, dtype=np.int32))
    assert d3 is not d1
    h2d = mempool.transfer_totals()["h2d"]
    assert h2d == 16 * 4 + 17 * 4  # two uploads, one mirror hit


def test_device_index_mirror_invalidated_on_finalize():
    m = BlockSparseMatrix("t", [3, 3], [3, 3])
    m.put_block(0, 0, np.ones((3, 3)))
    m.finalize()
    built = []
    hit1 = m.device_index("tag", lambda: built.append(1) or jnp.arange(3))
    hit2 = m.device_index("tag", lambda: built.append(1) or jnp.arange(3))
    assert hit1 is hit2 and len(built) == 1
    # a finalize that CHANGES structure invalidates the mirror
    m.put_block(1, 1, np.ones((3, 3)))
    m.finalize()
    m.device_index("tag", lambda: built.append(1) or jnp.arange(3))
    assert len(built) == 2
    # a value-only finalize keeps the pattern -> mirror survives
    m.put_block(0, 0, np.full((3, 3), 2.0))
    m.finalize()
    m.device_index("tag", lambda: built.append(1) or jnp.arange(3))
    assert len(built) == 2


def test_chain_multiply_steady_state_uploads_collapse():
    """A structure-stable filtered multiply chain must stop uploading
    index arrays after the first iteration (the zero-restage
    contract); the unpooled control re-uploads every iteration."""
    import dbcsr_tpu.mm.multiply as mm
    from dbcsr_tpu.core.config import get_config, set_config

    old_driver = get_config().mm_driver
    set_config(mm_driver="xla", mm_format="stack")
    try:
        per_iter = {}
        for pooled in (False, True):
            mempool.set_enabled(pooled)
            mempool.clear()
            mempool.reset_stats()
            mm._plan_cache.clear()
            p = make_test_density(6, 5, occ=0.9, seed=4)
            deltas = []
            with mempool.chain() as ch:
                cur = p
                for _ in range(4):
                    t0 = mempool.transfer_totals()["h2d"]
                    new = BlockSparseMatrix("C", cur.row_blk_sizes,
                                            cur.col_blk_sizes, cur.dtype)
                    multiply("N", "N", 1.0, cur, cur, 0.0, new,
                             filter_eps=1e-12)
                    deltas.append(mempool.transfer_totals()["h2d"] - t0)
                    if cur is not p:
                        ch.retire(cur)
                    cur = new
            per_iter[pooled] = deltas
        # pattern converges to full by iteration 2: pooled steady-state
        # uploads collapse to zero, the control keeps paying
        assert per_iter[True][-1] == 0
        assert per_iter[False][-1] > 0
    finally:
        set_config(mm_driver=old_driver, mm_format="auto")


def test_added_out_of_place_matches_add_and_keeps_ownership():
    """`added` (the copy-free diff op) must equal add(copy(A), B, ...)
    bitwise and leave both operands unshared (still pool-donatable)."""
    from dbcsr_tpu.ops.operations import added, copy as op_copy, add

    def build():
        rng = np.random.default_rng(21)
        a = make_random_matrix("A", [3, 4], [3, 4], occupation=0.8, rng=rng)
        b = make_random_matrix("B", [3, 4], [3, 4], occupation=0.6, rng=rng)
        return a, b

    a, b = build()
    ref = add(op_copy(a), b, 1.0, -1.0)
    a2, b2 = build()
    out = added(a2, b2, 1.0, -1.0)
    assert np.array_equal(to_dense(out), to_dense(ref))
    assert not a2._bins_shared and not b2._bins_shared


def test_sign_chain_recycles_buffers():
    """The copy-free sign loop must feed the pool (the review finding:
    per-iteration copies used to mark every iterate shared and starve
    the pool)."""
    rng = np.random.default_rng(5)
    a = make_random_matrix("A", [4] * 6, [4] * 6, occupation=0.5, rng=rng)
    sign_iteration(a, steps=4, filter_eps=1e-10)
    st = mempool.pool_stats()
    assert st["returns"] > 0 and st["hits"] > 0


# ------------------------------------------------------------ batched D2H

def test_get_blocks_matches_get_block():
    rng = np.random.default_rng(11)
    m = make_random_matrix("M", [3, 4, 5], [3, 4, 5], occupation=0.6,
                           rng=rng)
    rows, cols = np.meshgrid(np.arange(3), np.arange(3), indexing="ij")
    rows, cols = rows.ravel(), cols.ravel()
    batched = m.get_blocks(rows, cols)
    for r, c, blk in zip(rows, cols, batched):
        single = m.get_block(int(r), int(c))
        if single is None:
            assert blk is None
        else:
            assert np.array_equal(blk, single)


def test_get_blocks_symmetric_unfold_and_work_buffer():
    rng = np.random.default_rng(13)
    m = make_random_matrix("S", [3, 3], [3, 3], occupation=1.0,
                           matrix_type="S", rng=rng)
    m.put_block(0, 1, np.full((3, 3), 4.0))  # staged, not finalized
    got = m.get_blocks([0, 1, 0], [0, 0, 1])
    assert np.array_equal(got[0], m.get_block(0, 0))
    assert np.array_equal(got[1], m.get_block(1, 0))  # folded transpose
    assert np.array_equal(got[2], np.full((3, 3), 4.0))  # work buffer


def test_diag_ops_device_side():
    from dbcsr_tpu.ops.operations import add_on_diag, get_diag, set_diag

    rng = np.random.default_rng(17)
    m = make_random_matrix("M", [3, 4], [3, 4], occupation=1.0, rng=rng)
    before = to_dense(m)
    add_on_diag(m, 2.5)
    after = to_dense(m)
    assert np.allclose(after, before + 2.5 * np.eye(7))
    vals = np.arange(7, dtype=np.float64)
    set_diag(m, vals)
    assert np.array_equal(get_diag(m), vals)
    # steady state: add_on_diag on an existing pattern is one device
    # op — no staging, no finalize (matrix stays valid throughout)
    assert m.valid


# --------------------------------------------------------------- chaos

def test_faults_mid_chain_do_not_corrupt_donated_buffers():
    """Injected stack faults inside a pooled chain must recover (the
    failover chain) with results numerically identical to the clean
    pooled run — recycled buffers never leak a fault's partial state.
    (Failover may legally re-execute a stack on a DIFFERENT driver
    whose accumulation order differs in the last ulp, so the bound is
    the chaos suite's f64 tolerance, not array_equal.)"""
    from dbcsr_tpu.resilience import breaker, faults

    def run(schedule):
        import dbcsr_tpu.mm.multiply as mm

        mempool.clear()
        mempool.reset_stats()
        mm._plan_cache.clear()
        breaker.reset_board()
        p = make_test_density(6, 4, occ=0.5, seed=8)
        if schedule:
            with faults.inject_faults(schedule):
                out, _ = mcweeny_purify(p, steps=3, filter_eps=1e-10)
        else:
            out, _ = mcweeny_purify(p, steps=3, filter_eps=1e-10)
        return np.asarray(to_dense(out))

    clean = run(None)
    for schedule in (
        "execute_stack:raise,seed=5,times=2",
        "execute_stack:nan,seed=6,times=2",
        "prepare_stack:raise,seed=7",
    ):
        faulted = run(schedule)
        np.testing.assert_allclose(faulted, clean, rtol=1e-11,
                                   atol=1e-13, err_msg=schedule)


def test_chain_exit_on_error_frees_without_masking():
    """An exception escaping a chain still frees adopted temporaries
    and propagates unchanged."""
    with pytest.raises(RuntimeError, match="boom"):
        with mempool.chain():
            m = BlockSparseMatrix("t", [3], [3])
            m.put_block(0, 0, np.ones((3, 3)))
            m.finalize()
            raise RuntimeError("boom")
    assert not m.valid  # freed on exit


# ------------------------------------------------------- observability

def test_pool_metrics_in_snapshot_and_prometheus():
    from dbcsr_tpu.obs import metrics

    mempool.release(jnp.ones((2, 2), np.float64))
    mempool.zeros((2, 2), np.float64)
    snap = metrics.snapshot()
    assert snap["pool"]["returns"] == 1
    assert snap["pool"]["hits"] == 1
    assert "transfer" in snap
    text = metrics.prometheus_text()
    assert "dbcsr_tpu_pool_returns_total" in text
    assert "dbcsr_tpu_pool_bytes_held" in text


def test_h2d_d2h_counters_flow():
    from dbcsr_tpu.obs import metrics

    m = make_random_matrix("M", [4] * 3, [4] * 3, occupation=1.0,
                           rng=np.random.default_rng(3))
    c = metrics.counter("dbcsr_tpu_d2h_bytes_total")
    before_counter = c.value()
    before_total = mempool.transfer_totals()["d2h"]
    m.get_block(0, 0)
    d_total = mempool.transfer_totals()["d2h"] - before_total
    assert d_total >= 4 * 4 * 8  # one block fetched
    # registry counter and module total move in lockstep
    assert c.value() - before_counter == d_total


def test_health_pool_thrash_note(monkeypatch):
    from dbcsr_tpu.obs import health

    health.reset()
    monkeypatch.setenv("DBCSR_TPU_POOL_BYTES", "100")
    # many misses + budget evictions => thrash
    for _ in range(20):
        mempool.zeros((8, 8), np.float64)
        mempool.release(jnp.ones((8, 8), np.float64))
    perf = health._eval_perf()
    assert perf["status"] == health.DEGRADED
    assert any("pool thrash" in r for r in perf["reasons"])
    assert perf["pool"]["evictions"] >= 8
