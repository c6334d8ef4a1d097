"""ACC layer tests: the `acc_bench_smm` / `acc_bench_trans` analog.

Validates the batched SMM stack kernel, batched transpose and norms
against a NumPy oracle, the same CPU-checksum pattern as the reference's
standalone acc benchmarks (`src/acc/acc_bench_smm.c`,
`libsmm_acc_benchmark.cpp:60-85`).
"""

import numpy as np
import pytest

from dbcsr_tpu.acc import block_norms, process_stack, transpose_blocks


def _random_stack(rng, na, nb, nc, s, m, n, k, dtype):
    a = rng.standard_normal((na, m, k))
    b = rng.standard_normal((nb, k, n))
    c = rng.standard_normal((nc, m, n))
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        a = a + 1j * rng.standard_normal(a.shape)
        b = b + 1j * rng.standard_normal(b.shape)
        c = c + 1j * rng.standard_normal(c.shape)
    a, b, c = (x.astype(dtype) for x in (a, b, c))
    ai = rng.integers(0, na, s).astype(np.int32)
    bi = rng.integers(0, nb, s).astype(np.int32)
    ci = np.sort(rng.integers(0, nc, s)).astype(np.int32)
    return a, b, c, ai, bi, ci


def _oracle(c, a, b, ai, bi, ci, alpha):
    out = c.copy()
    for s in range(len(ai)):
        out[ci[s]] += alpha * (a[ai[s]] @ b[bi[s]])
    return out


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.complex128])
@pytest.mark.parametrize("mnk", [(4, 4, 4), (23, 23, 23), (5, 13, 23), (1, 3, 4)])
def test_process_stack_vs_oracle(dtype, mnk):
    m, n, k = mnk
    rng = np.random.default_rng(42)
    a, b, c, ai, bi, ci = _random_stack(rng, 17, 19, 11, 200, m, n, k, dtype)
    got = np.asarray(process_stack(c, a, b, ai, bi, ci, alpha=2.0))
    want = _oracle(c, a, b, ai, bi, ci, 2.0)
    # f32 drivers accumulate in f32 (the reference's CPU/GPU sgemm
    # paths likewise); across a 23-deep k and multi-entry runs the
    # order-dependent rounding reaches a few 1e-4 relative — the
    # tolerance covers every dispatchable driver (XLA, pallas, host)
    rtol = 5e-4 if np.dtype(dtype).itemsize <= 8 and dtype == np.float32 else 1e-12
    np.testing.assert_allclose(got, want, rtol=rtol, atol=rtol)


def test_process_stack_chunks_match_single_shot():
    """Chunked processing must accumulate identically (order fixed)."""
    from dbcsr_tpu.core.config import set_config

    rng = np.random.default_rng(0)
    a, b, c, ai, bi, ci = _random_stack(rng, 8, 8, 6, 500, 7, 7, 7, np.float64)
    one = np.asarray(process_stack(c, a, b, ai, bi, ci))
    set_config(mm_stack_size=64)
    try:
        many = np.asarray(process_stack(c, a, b, ai, bi, ci))
    finally:
        set_config(mm_stack_size=30000)
    np.testing.assert_array_equal(one, many)


def test_process_stack_deterministic():
    rng = np.random.default_rng(3)
    a, b, c, ai, bi, ci = _random_stack(rng, 9, 9, 5, 300, 5, 5, 5, np.float32)
    r1 = np.asarray(process_stack(c, a, b, ai, bi, ci))
    r2 = np.asarray(process_stack(c, a, b, ai, bi, ci))
    np.testing.assert_array_equal(r1, r2)


def test_empty_stack_is_noop():
    rng = np.random.default_rng(1)
    c = rng.standard_normal((4, 3, 3))
    out = process_stack(c, np.zeros((1, 3, 3)), np.zeros((1, 3, 3)),
                        np.empty(0, np.int32), np.empty(0, np.int32),
                        np.empty(0, np.int32))
    np.testing.assert_array_equal(np.asarray(out), c)


def test_transpose_blocks():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((10, 5, 13))
    np.testing.assert_array_equal(
        np.asarray(transpose_blocks(x)), np.swapaxes(x, 1, 2)
    )


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
def test_block_norms(dtype):
    rng = np.random.default_rng(9)
    x = rng.standard_normal((6, 4, 8))
    if dtype == np.complex128:
        x = x + 1j * rng.standard_normal(x.shape)
    x = x.astype(dtype)
    want = np.linalg.norm(x.reshape(6, -1), axis=1)
    np.testing.assert_allclose(block_norms(x), want, rtol=1e-12)


def test_flat_gather_matches_default():
    """config.flat_gather relayout must not change results (same
    accumulation order: scan over chunks + sorted scatter-add)."""
    from dbcsr_tpu.core.config import set_config

    rng = np.random.default_rng(11)
    a, b, c, ai, bi, ci = _random_stack(rng, 9, 9, 6, 250, 6, 6, 6, np.float64)
    base = np.asarray(process_stack(c, a, b, ai, bi, ci, alpha=1.5))
    set_config(flat_gather=True)
    try:
        flat = np.asarray(process_stack(c, a, b, ai, bi, ci, alpha=1.5))
    finally:
        set_config(flat_gather=False)
    np.testing.assert_allclose(flat, base, rtol=1e-13, atol=1e-13)


def test_tuned_xla_flat_entry_drives_dispatch(tmp_path, monkeypatch):
    """A tuned driver='xla_flat' entry in the params table must route
    the stack through the flat-gather path (and produce identical
    results) without any config toggles — the per-shape analog of the
    parameter-table dispatch in libsmm_acc.cpp:227-249."""
    from dbcsr_tpu.acc import params as params_mod

    rng = np.random.default_rng(12)
    a, b, c, ai, bi, ci = _random_stack(rng, 9, 9, 6, 250, 7, 6, 5, np.float64)
    base = np.asarray(process_stack(c, a, b, ai, bi, ci, alpha=1.5))

    monkeypatch.setenv("DBCSR_TPU_PARAMS_DIR", str(tmp_path))
    params_mod._cache.clear()
    params_mod.save_entry({"m": 7, "n": 5, "k": 6, "dtype": "float64",
                           "driver": "xla_flat", "grouping": None, "gflops": 1.0})
    try:
        flat = np.asarray(process_stack(c, a, b, ai, bi, ci, alpha=1.5))
    finally:
        params_mod._cache.clear()
    np.testing.assert_allclose(flat, base, rtol=1e-13, atol=1e-13)


def test_validate_kernels_catches_corrupted_kernel(monkeypatch):
    """Ref: libsmm_acc validates each JIT'd kernel against a CPU
    checksum and hard-exits on mismatch (`libsmm_acc.cpp:81-85,216`).
    Here a corrupted Pallas result must be CAUGHT by first-use
    validation — and, since the resilience layer, the validation
    failure opens the (pallas, shape) breaker and the stack re-executes
    on a safe chain driver: the caller gets a CORRECT product, never
    the corrupted one (the reference exits; we degrade)."""
    from dbcsr_tpu.acc import pallas_smm, smm
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.resilience import breaker

    rng = np.random.default_rng(13)
    a, b, c, ai, bi, ci = _random_stack(rng, 8, 8, 6, 100, 8, 8, 8, np.float32)

    real = pallas_smm.process_stack_pallas

    def corrupted(c_data, a_data, b_data, *args, **kw):
        return real(c_data, a_data, b_data, *args, **kw) + 1.0

    monkeypatch.setattr(pallas_smm, "process_stack_pallas", corrupted)
    # validation keys are (m, n, k, dtype, kmerge, r_grp): one per
    # compiled kernel variant (ADVICE r3)
    smm._validated_kernels.difference_update(
        {k for k in smm._validated_kernels if k[:4] == (8, 8, 8, "float32")}
    )
    breaker.reset_board()
    # force the base pallas kernel: auto dispatch never selects
    # interpret-mode pallas off-TPU (and on "TPU" it would try
    # crosspack first, whose separate validation key would pollute
    # the assertion below)
    set_config(mm_driver="pallas", validate_kernels=True)
    try:
        got = np.asarray(process_stack(c.astype(np.float32), a, b, ai, bi, ci))
    finally:
        set_config(mm_driver="auto")
        breaker.reset_board()
    # the corrupted kernel never validated, the shape is quarantined,
    # and the failover product matches the oracle
    assert not any(k[:4] == (8, 8, 8, "float32") for k in smm._validated_kernels)
    from dbcsr_tpu.obs import metrics as obs_metrics

    fails = obs_metrics.snapshot()["counters"].get(
        "dbcsr_tpu_driver_failures_total", {})
    assert any('"kind": "validation"' in key for key in fails)
    want = _oracle(c.astype(np.float32), a, b, ai, bi, ci, 1.0)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


def test_validate_kernels_passes_and_caches():
    from dbcsr_tpu.acc import smm
    from dbcsr_tpu.core.config import set_config

    rng = np.random.default_rng(17)
    a, b, c, ai, bi, ci = _random_stack(rng, 8, 8, 6, 100, 9, 9, 9, np.float32)
    smm._validated_kernels.difference_update(
        {k for k in smm._validated_kernels if k[:4] == (9, 9, 9, "float32")}
    )
    # force the base pallas kernel (auto never selects interpret-mode
    # pallas off-TPU, and a mocked-TPU auto would go crosspack instead)
    set_config(mm_driver="pallas")
    try:
        got = np.asarray(process_stack(c, a, b, ai, bi, ci))
    finally:
        set_config(mm_driver="auto")
    np.testing.assert_allclose(got, _oracle(c, a, b, ai, bi, ci, 1.0), rtol=1e-4, atol=1e-4)
    assert any(k[:4] == (9, 9, 9, "float32") for k in smm._validated_kernels)


def test_forced_pallas_unsupported_dtype_warns():
    from dbcsr_tpu.core.config import set_config

    rng = np.random.default_rng(19)
    a, b, c, ai, bi, ci = _random_stack(rng, 5, 5, 4, 50, 4, 4, 4, np.float64)
    set_config(mm_driver="pallas")
    try:
        with pytest.warns(RuntimeWarning, match="falling back to XLA"):
            got = np.asarray(process_stack(c, a, b, ai, bi, ci))
    finally:
        set_config(mm_driver="auto")
    np.testing.assert_allclose(got, _oracle(c, a, b, ai, bi, ci, 1.0), rtol=1e-12)


def test_pallas_kmerge_variant_matches_looped():
    """The k-merged kernel variant (one (R*k,m)^T x (R*k,n) dot per grid
    step) is numerically identical to the looped variant and the host
    oracle (interpret mode on CPU; the tuner sweeps both on hardware)."""
    import jax.numpy as jnp

    from dbcsr_tpu.acc.pallas_smm import process_stack_pallas

    rng = np.random.default_rng(5)
    m, n, k = 8, 8, 8
    na, nb, nc = 12, 12, 6
    a = jnp.asarray(rng.standard_normal((na, m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((nb, k, n)), jnp.float32)
    nent = 40
    ci = np.sort(rng.integers(0, nc, nent)).astype(np.int32)
    ai = rng.integers(0, na, nent).astype(np.int32)
    bi = rng.integers(0, nb, nent).astype(np.int32)
    c0 = jnp.asarray(rng.standard_normal((nc, m, n)), jnp.float32)
    got_loop = np.asarray(process_stack_pallas(
        jnp.array(c0), a, b, ai, bi, ci, 1.5, grouping=4))
    got_merge = np.asarray(process_stack_pallas(
        jnp.array(c0), a, b, ai, bi, ci, 1.5, grouping=4, variant="kmerge"))
    ref = np.asarray(c0, np.float64).copy()
    for e in range(nent):
        ref[ci[e]] += 1.5 * (np.asarray(a, np.float64)[ai[e]]
                             @ np.asarray(b, np.float64)[bi[e]])
    # f32 data against an f64 oracle; the merged dot sums in a
    # different (single-contraction) order than the looped variant
    np.testing.assert_allclose(got_merge, ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got_merge, got_loop, rtol=1e-4, atol=1e-4)


def test_pallas_kmerge_bf16():
    """bf16 data through the k-merged variant accumulates in f32."""
    import jax.numpy as jnp

    from dbcsr_tpu.acc.pallas_smm import process_stack_pallas

    rng = np.random.default_rng(6)
    m = n = k = 16
    a = jnp.asarray(rng.standard_normal((8, m, k)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((8, k, n)), jnp.bfloat16)
    ci = np.sort(rng.integers(0, 4, 24)).astype(np.int32)
    ai = rng.integers(0, 8, 24).astype(np.int32)
    bi = rng.integers(0, 8, 24).astype(np.int32)
    c0 = jnp.zeros((4, m, n), jnp.bfloat16)
    got = np.asarray(process_stack_pallas(
        c0, a, b, ai, bi, ci, 1.0, grouping=8, variant="kmerge"),
        np.float64)
    ref = np.zeros((4, m, n))
    ah = np.asarray(a, np.float64)
    bh = np.asarray(b, np.float64)
    for e in range(len(ci)):
        ref[ci[e]] += ah[ai[e]] @ bh[bi[e]]
    np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# Cross-packed kernel (P x R MXU tiling; pallas_smm crosspack)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,mnk,pack", [
    (np.float32, (23, 23, 23), None),       # north-star block shape
    (np.float32, (8, 8, 8), None),
    (np.float32, (16, 24, 12), (3, 5)),     # rectangular + forced pack
    ("bfloat16", (23, 23, 23), None),
    (np.float32, (64, 64, 64), None),       # P=R=2 regime
])
def test_crosspack_vs_oracle(dtype, mnk, pack):
    import jax.numpy as jnp

    from dbcsr_tpu.acc import pallas_smm

    m, n, k = mnk
    dt = jnp.bfloat16 if dtype == "bfloat16" else dtype
    rng = np.random.default_rng(31)
    a_h = rng.standard_normal((30, m, k))
    b_h = rng.standard_normal((30, k, n))
    c_h = rng.standard_normal((22, m, n))
    s = 400
    ai = rng.integers(0, 30, s).astype(np.int32)
    bi = rng.integers(0, 30, s).astype(np.int32)
    ci = np.sort(rng.integers(0, 22, s)).astype(np.int32)
    got = pallas_smm.process_stack_crosspack(
        jnp.asarray(c_h, dt), jnp.asarray(a_h, dt), jnp.asarray(b_h, dt),
        ai, bi, ci, 1.3, pack=pack,
    )
    assert got is not None
    want = c_h.copy()
    np.add.at(want, ci, 1.3 * np.einsum("sij,sjk->sik", a_h[ai], b_h[bi]))
    scale = np.abs(want).max()
    err = np.abs(np.asarray(got, np.float64) - want).max() / scale
    # dtype-aware oracle tolerance — the same source of truth the
    # runtime validation gate and ABFT ceilings use (obs.costmodel)
    from dbcsr_tpu.obs import costmodel

    tol = costmodel.kernel_validation_tolerance(
        str(jnp.dtype(dt)), k, int(np.bincount(ci).max()))
    assert err < tol, (err, tol)


def test_crosspack_engine_dispatch_and_validation():
    """mm_driver='pallas_cross' routes through the planner, passes the
    per-variant first-use validation gate, and matches the oracle."""
    import jax.numpy as jnp

    from dbcsr_tpu.acc import smm
    from dbcsr_tpu.core.config import set_config

    rng = np.random.default_rng(33)
    a, b, c, ai, bi, ci = _random_stack(rng, 40, 40, 25, 500, 23, 23, 23,
                                        np.float32)
    smm._validated_kernels.difference_update(
        {kk for kk in smm._validated_kernels if kk[:4] == (23, 23, 23, "float32")}
    )
    set_config(mm_driver="pallas_cross", validate_kernels=True)
    try:
        got = np.asarray(process_stack(jnp.asarray(c), jnp.asarray(a),
                                       jnp.asarray(b), ai, bi, ci, 1.5))
    finally:
        set_config(mm_driver="auto")
    np.testing.assert_allclose(got, _oracle(c, a, b, ai, bi, ci, 1.5),
                               rtol=2e-4, atol=2e-4)
    assert any(
        len(kk) > 4 and kk[4] == "crosspack" for kk in smm._validated_kernels
    )


def test_crosspack_big_blocks_fall_back():
    """Blocks too large for spatial packing (P==1) must fall back to the
    base kernel path and still be exact."""
    import jax.numpy as jnp

    from dbcsr_tpu.core.config import set_config

    rng = np.random.default_rng(35)
    a, b, c, ai, bi, ci = _random_stack(rng, 10, 10, 8, 60, 72, 72, 16,
                                        np.float32)
    set_config(mm_driver="pallas_cross")
    try:
        got = np.asarray(process_stack(jnp.asarray(c), jnp.asarray(a),
                                       jnp.asarray(b), ai, bi, ci, 1.0))
    finally:
        set_config(mm_driver="auto")
    np.testing.assert_allclose(got, _oracle(c, a, b, ai, bi, ci, 1.0),
                               rtol=2e-4, atol=2e-4)


def test_crosspack_long_run_single_c_block():
    """All entries hitting ONE C block exercises the single-run/lane-
    imbalance path (one lane gets everything, others idle on pads)."""
    import jax.numpy as jnp

    from dbcsr_tpu.acc import pallas_smm

    rng = np.random.default_rng(37)
    m = n = k = 16
    a_h = rng.standard_normal((12, m, k))
    b_h = rng.standard_normal((12, k, n))
    c_h = rng.standard_normal((3, m, n))
    s = 200
    ai = rng.integers(0, 12, s).astype(np.int32)
    bi = rng.integers(0, 12, s).astype(np.int32)
    ci = np.full(s, 1, np.int32)
    got = pallas_smm.process_stack_crosspack(
        jnp.asarray(c_h, jnp.float32), jnp.asarray(a_h, jnp.float32),
        jnp.asarray(b_h, jnp.float32), ai, bi, ci, 1.0,
    )
    assert got is not None
    want = c_h.copy()
    np.add.at(want, ci, np.einsum("sij,sjk->sik", a_h[ai], b_h[bi]))
    err = np.abs(np.asarray(got, np.float64) - want).max() / np.abs(want).max()
    assert err < 1e-5, err


def test_crosspack_tuned_table_dispatch(tmp_path, monkeypatch):
    """A tuned-table crosspack entry steers auto dispatch (the analog of
    libsmm_acc.cpp:227-249 parameter lookup)."""
    import json

    import jax.numpy as jnp

    from dbcsr_tpu.acc import params as params_mod
    from dbcsr_tpu.acc import smm
    from dbcsr_tpu.core.config import set_config

    monkeypatch.setenv("DBCSR_TPU_PARAMS_DIR", str(tmp_path))
    entry = {"m": 12, "n": 12, "k": 12, "dtype": "float32",
             "driver": "pallas", "variant": "crosspack", "grouping": 4,
             "pack_p": 4, "gflops": 1.0}
    with open(params_mod.params_path(), "w") as f:
        json.dump([entry], f)
    rng = np.random.default_rng(39)
    a, b, c, ai, bi, ci = _random_stack(rng, 20, 20, 12, 300, 12, 12, 12,
                                        np.float32)
    monkeypatch.setattr(smm, "_on_tpu", lambda: True)
    set_config(mm_driver="auto", validate_kernels=True)
    plan = smm.prepare_stack(jnp.asarray(c), jnp.asarray(a), jnp.asarray(b),
                             ai, bi, ci)
    assert plan.driver == "pallas_cross"
    assert plan.pack == (4, 4)
    got = np.asarray(smm.execute_stack(jnp.asarray(c), jnp.asarray(a),
                                       jnp.asarray(b), plan, 1.0))
    np.testing.assert_allclose(got, _oracle(c, a, b, ai, bi, ci, 1.0),
                               rtol=2e-4, atol=2e-4)


def test_crosspack_predicted_donor_rederives_pack(tmp_path, monkeypatch):
    """A nearest-neighbor-predicted crosspack entry carries a pack tuned
    for a DIFFERENT block shape; dispatch must re-derive (P, R) from the
    target geometry instead of applying the donor's values verbatim."""
    import json

    import jax.numpy as jnp

    from dbcsr_tpu.acc import params as params_mod
    from dbcsr_tpu.acc import pallas_smm, smm
    from dbcsr_tpu.core.config import set_config

    monkeypatch.setenv("DBCSR_TPU_PARAMS_DIR", str(tmp_path))
    # donor tuned at 12^3 with the uncapped (8, 8) pack — legal there,
    # degenerate for 23^3 (8*23 = 184 > 128)
    entry = {"m": 12, "n": 12, "k": 12, "dtype": "float32",
             "driver": "pallas", "variant": "crosspack", "grouping": 8,
             "pack_p": 8, "gflops": 1.0}
    with open(params_mod.params_path(), "w") as f:
        json.dump([entry], f)
    rng = np.random.default_rng(41)
    a, b, c, ai, bi, ci = _random_stack(rng, 20, 20, 12, 300, 23, 23, 23,
                                        np.float32)
    monkeypatch.setattr(smm, "_on_tpu", lambda: True)
    set_config(mm_driver="auto")
    plan = smm.prepare_stack(jnp.asarray(c), jnp.asarray(a), jnp.asarray(b),
                             ai, bi, ci)
    assert plan.driver == "pallas_cross"
    assert plan.pack == pallas_smm.choose_pack(23, 23, 23)
    got = np.asarray(smm.execute_stack(jnp.asarray(c), jnp.asarray(a),
                                       jnp.asarray(b), plan, 1.0))
    np.testing.assert_allclose(got, _oracle(c, a, b, ai, bi, ci, 1.0),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype,mnk", [
    (np.float32, (23, 23, 23)),
    ("bfloat16", (16, 16, 16)),
])
def test_crosspack_vmem_resident_vs_oracle(dtype, mnk):
    """Whole-array-in-VMEM gather variant: identical contract to the
    DMA-stream crosspack (in-kernel dynamic leading-dim gathers)."""
    import jax.numpy as jnp

    from dbcsr_tpu.acc import pallas_smm

    m, n, k = mnk
    dt = jnp.bfloat16 if dtype == "bfloat16" else dtype
    rng = np.random.default_rng(51)
    a_h = rng.standard_normal((24, m, k))
    b_h = rng.standard_normal((24, k, n))
    c_h = rng.standard_normal((18, m, n))
    s = 350
    ai = rng.integers(0, 24, s).astype(np.int32)
    bi = rng.integers(0, 24, s).astype(np.int32)
    ci = np.sort(rng.integers(0, 18, s)).astype(np.int32)
    got = pallas_smm.process_stack_crosspack(
        jnp.asarray(c_h, dt), jnp.asarray(a_h, dt), jnp.asarray(b_h, dt),
        ai, bi, ci, 1.1, vmem_resident=True,
    )
    assert got is not None
    want = c_h.copy()
    np.add.at(want, ci, 1.1 * np.einsum("sij,sjk->sik", a_h[ai], b_h[bi]))
    err = np.abs(np.asarray(got, np.float64) - want).max() / np.abs(want).max()
    from dbcsr_tpu.obs import costmodel

    tol = costmodel.kernel_validation_tolerance(
        str(jnp.dtype(dt)), k, int(np.bincount(ci).max()))
    assert err < tol, (err, tol)


def test_crosspack_vmem_tuned_dispatch(tmp_path, monkeypatch):
    """A tuned crosspack_vmem row selects the VMEM-resident variant
    (gated on the operands actually fitting)."""
    import json

    import jax.numpy as jnp

    from dbcsr_tpu.acc import params as params_mod
    from dbcsr_tpu.acc import smm
    from dbcsr_tpu.core.config import set_config

    monkeypatch.setenv("DBCSR_TPU_PARAMS_DIR", str(tmp_path))
    entry = {"m": 12, "n": 12, "k": 12, "dtype": "float32",
             "driver": "pallas", "variant": "crosspack_vmem", "grouping": 4,
             "pack_p": 4, "gflops": 1.0}
    with open(params_mod.params_path(), "w") as f:
        json.dump([entry], f)
    rng = np.random.default_rng(53)
    a, b, c, ai, bi, ci = _random_stack(rng, 20, 20, 12, 300, 12, 12, 12,
                                        np.float32)
    monkeypatch.setattr(smm, "_on_tpu", lambda: True)
    set_config(mm_driver="auto", validate_kernels=True)
    plan = smm.prepare_stack(jnp.asarray(c), jnp.asarray(a), jnp.asarray(b),
                             ai, bi, ci)
    assert plan.driver == "pallas_cross" and plan.cross_vmem
    got = np.asarray(smm.execute_stack(jnp.asarray(c), jnp.asarray(a),
                                       jnp.asarray(b), plan, 1.0))
    np.testing.assert_allclose(got, _oracle(c, a, b, ai, bi, ci, 1.0),
                               rtol=2e-4, atol=2e-4)
    assert any(
        len(kk) > 4 and kk[4] == "crosspack_vmem"
        for kk in smm._validated_kernels
    )
    # the gate counts TILED bytes: the tuner's S=100000 operands (26 MB
    # of 23x23 f32 data) tile to 147 MiB, which Mosaic refused on a
    # v5e; its S=30000 operands (44 MiB tiled) compiled and validated
    import jax

    from dbcsr_tpu.acc import pallas_smm

    def blocks(n):
        return jax.ShapeDtypeStruct((n, 23, 23), jnp.float32)

    assert not pallas_smm.supports_vmem_resident(blocks(6250), blocks(6250))
    assert pallas_smm.supports_vmem_resident(blocks(1875), blocks(1875))


def test_crosspack_compile_failure_demotes_to_base(monkeypatch):
    """A crosspack COMPILE/lowering failure (not a numeric mismatch)
    must demote the shape for the session and fall back to the base
    kernel with correct results — the unsupported-kernel fallback
    (libsmm_acc.cpp:227-249).  Numeric corruption must still hard-fail
    (covered by test_validate_kernels_catches_corrupted_kernel)."""
    import jax.numpy as jnp

    from dbcsr_tpu.acc import pallas_smm, smm
    from dbcsr_tpu.core.config import set_config

    def boom(*a, **k):
        raise RuntimeError("simulated Mosaic lowering failure")

    monkeypatch.setattr(pallas_smm, "_pallas_crosspack", boom)
    monkeypatch.setattr(pallas_smm, "_pallas_crosspack_vmem", boom)
    smm._cross_disabled.discard((14, 14, 14, "float32"))
    rng = np.random.default_rng(55)
    a, b, c, ai, bi, ci = _random_stack(rng, 16, 16, 10, 300, 14, 14, 14,
                                        np.float32)
    set_config(mm_driver="pallas_cross", validate_kernels=True)
    try:
        plan = smm.prepare_stack(jnp.asarray(c), jnp.asarray(a),
                                 jnp.asarray(b), ai, bi, ci)
        assert plan.driver == "pallas_cross"
        with pytest.warns(RuntimeWarning, match="falling back to the base kernel"):
            got = np.asarray(smm.execute_stack(
                jnp.asarray(c), jnp.asarray(a), jnp.asarray(b), plan, 1.5))
    finally:
        set_config(mm_driver="auto")
    np.testing.assert_allclose(got, _oracle(c, a, b, ai, bi, ci, 1.5),
                               rtol=2e-4, atol=2e-4)
    assert (14, 14, 14, "float32") in smm._cross_disabled
    # the cached plan healed in place: next execute uses the base path
    assert plan.driver != "pallas_cross"
    got2 = np.asarray(smm.execute_stack(
        jnp.asarray(c), jnp.asarray(a), jnp.asarray(b), plan, 1.5))
    np.testing.assert_allclose(got2, got, rtol=1e-6, atol=1e-6)
    smm._cross_disabled.discard((14, 14, 14, "float32"))


def test_auto_crosspack_default_on_tpu(monkeypatch):
    """On a real TPU, untuned f32/bf16 shapes default to the crosspack
    kernel under auto dispatch (tuned rows and the disabled set still
    take precedence)."""
    import jax.numpy as jnp

    from dbcsr_tpu.acc import smm
    from dbcsr_tpu.core.config import set_config

    # the platform_override seam (not a raw _on_tpu monkeypatch) also
    # redirects the params table to the pretend kind, so real cpu-kind
    # tuned rows cannot steer the pretend-TPU dispatch under test
    set_config(platform_override="tpu")
    try:
        rng = np.random.default_rng(57)
        a, b, c, ai, bi, ci = _random_stack(rng, 16, 16, 10, 300, 15, 15, 15,
                                            np.float32)
        set_config(mm_driver="auto")
        plan = smm.prepare_stack(jnp.asarray(c), jnp.asarray(a),
                                 jnp.asarray(b), ai, bi, ci)
        assert plan.driver == "pallas_cross"
        # disabled shapes go back to the base kernel
        smm._cross_disabled.add((15, 15, 15, "float32"))
        try:
            plan2 = smm.prepare_stack(jnp.asarray(c), jnp.asarray(a),
                                      jnp.asarray(b), ai, bi, ci)
            assert plan2.driver != "pallas_cross"
        finally:
            smm._cross_disabled.discard((15, 15, 15, "float32"))
    finally:
        set_config(platform_override="")


def test_crosspack_numpy_input_not_blacklisted(recwarn):
    """process_stack with NUMPY arrays through the crosspack path must
    succeed (c coerced up front), not crash in scatter_lane_outputs and
    silently blacklist the shape via the demotion handler."""
    import warnings

    from dbcsr_tpu.acc import smm
    from dbcsr_tpu.core.config import set_config

    rng = np.random.default_rng(67)
    a, b, c, ai, bi, ci = _random_stack(rng, 16, 16, 10, 200, 8, 8, 8,
                                        np.float32)
    key = smm._stack_shape_key(c, a, b)
    smm._cross_disabled.discard(key)
    set_config(mm_driver="pallas_cross")
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            got = np.asarray(smm.process_stack(c, a, b, ai, bi, ci))
    finally:
        set_config(mm_driver="auto")
    assert key not in smm._cross_disabled
    np.testing.assert_allclose(got, _oracle(c, a, b, ai, bi, ci, 1.0),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Cross-pack launches are chopped by what the kernel prefetches into SMEM
# ---------------------------------------------------------------------------

def _run_stack(run_lens):
    """c_idx of a stack whose C block r has run_lens[r] entries."""
    run_lens = np.asarray(run_lens)
    return np.repeat(np.arange(len(run_lens)), run_lens).astype(np.int32)


@pytest.mark.parametrize("name,pack,budget", [
    # the shape that ran out of smem on the v5e at 32 768 raw entries a
    # launch: (5,5,23), pack (4, 4), 40 000 entries in runs of one and two
    ("short_runs", (4, 4), None),
    ("long_runs", (4, 4), 64 << 10),
    ("one_run_a_lane", (3, 5), 16 << 10),
])
def test_crosspack_launches_fit_the_prefetch_budget(name, pack, budget):
    """Every launch prices under the budget by the function the gate
    uses, the launches cover every entry once, and chunks start at run
    starts."""
    from dbcsr_tpu.acc import pallas_smm

    rng = np.random.default_rng(71)
    P, R = pack
    if name == "short_runs":
        run_lens = rng.integers(1, 3, 27000)
        run_lens = run_lens[:np.searchsorted(np.cumsum(run_lens), 40000)]
    elif name == "long_runs":
        run_lens = rng.integers(200, 400, 120)
    else:
        run_lens = np.full(9, 64 * R)  # each run is max_steps deep
    c_idx = _run_stack(run_lens)
    s = len(c_idx)
    a_idx = np.arange(s, dtype=np.int32)  # an entry's own position
    b_idx = a_idx[::-1].copy()
    # None: the default budget, as the planner calls it
    launches = pallas_smm.prepare_crosspack_launches(
        c_idx, a_idx, b_idx, s, s, P, R,
        **({} if budget is None else {"budget": budget}))
    budget = budget or pallas_smm._CROSS_PREFETCH_BUDGET
    assert launches is not None and len(launches) > 1
    run_starts = set(np.concatenate(
        [[0], np.flatnonzero(np.diff(c_idx)) + 1]).tolist())
    seen_a, next_lo = [], 0
    for lc in launches:
        nsteps = lc["cg"].size // P
        assert lc["ai"].size == nsteps * P * R == lc["bi"].size
        assert lc["cl"].size == nsteps * P
        assert pallas_smm.crosspack_prefetch_bytes(nsteps, P, R) <= budget
        mine = np.sort(lc["ai"][lc["ai"] != s])
        # a contiguous range of the stack that starts where the last
        # launch ended, at a run start
        assert mine[0] == next_lo and mine[0] in run_starts
        np.testing.assert_array_equal(mine, np.arange(mine[0], mine[-1] + 1))
        np.testing.assert_array_equal(
            np.sort(lc["bi"][lc["bi"] != s]), np.sort(b_idx[mine]))
        np.testing.assert_array_equal(
            np.sort(np.concatenate(lc["lane_c"])), np.unique(c_idx[mine]))
        assert lc["nc_out"] > max(len(c) for c in lc["lane_c"])
        next_lo = mine[-1] + 1
        seen_a.append(mine)
    np.testing.assert_array_equal(np.concatenate(seen_a), a_idx)
    if name == "one_run_a_lane":
        assert len(launches) == len(run_lens) // P


def test_crosspack_run_longer_than_a_launch_is_left_to_the_base_kernel():
    """A single run that no launch can hold returns None: the planner
    takes the base kernel, by plan and without a warning."""
    from dbcsr_tpu.acc import pallas_smm

    P, R = 4, 4
    max_steps = pallas_smm._crosspack_max_steps(
        P, R, pallas_smm._CROSS_PREFETCH_BUDGET)
    assert pallas_smm.crosspack_prefetch_bytes(max_steps, P, R) \
        <= pallas_smm._CROSS_PREFETCH_BUDGET < pallas_smm._SMEM_BYTES
    c_idx = _run_stack([3, max_steps * R + 1, 2])
    idx = np.zeros(len(c_idx), np.int32)
    assert pallas_smm.prepare_crosspack_launches(
        c_idx, idx, idx, 0, 0, P, R) is None
    c_idx = _run_stack([3, max_steps * R, 2])
    idx = np.zeros(len(c_idx), np.int32)
    (launch,) = pallas_smm.prepare_crosspack_launches(
        c_idx, idx, idx, 0, 0, P, R)  # a lane of its own, the launch full
    assert launch["cg"].size // P == max_steps


@pytest.mark.parametrize("vmem_resident", [False, True])
def test_crosspack_several_launches_match_numpy_and_themselves(vmem_resident):
    """With a small budget handed in as an argument the (5,5,23) stack
    takes several launches (and as many named write-backs): C agrees
    with the NumPy f64 block product and is bitwise equal on a second
    call."""
    import jax.numpy as jnp

    from dbcsr_tpu.acc import pallas_smm
    from dbcsr_tpu.obs import costmodel

    m, n, k = 5, 5, 23
    rng = np.random.default_rng(73)
    run_lens = rng.integers(1, 3, 1400)
    c_idx = _run_stack(run_lens)
    s, nc = len(c_idx), len(run_lens) + 7  # some C blocks get nothing
    a_h = rng.standard_normal((50, m, k)).astype(np.float32)
    b_h = rng.standard_normal((60, k, n)).astype(np.float32)
    c_h = rng.standard_normal((nc, m, n)).astype(np.float32)
    ai = rng.integers(0, 50, s).astype(np.int32)
    bi = rng.integers(0, 60, s).astype(np.int32)
    budget = 16 << 10
    assert len(pallas_smm.prepare_crosspack_launches(
        c_idx, ai, bi, 50, 60, 4, 4, budget=budget)) >= 5

    def run():
        return np.asarray(pallas_smm.process_stack_crosspack(
            jnp.asarray(c_h), jnp.asarray(a_h), jnp.asarray(b_h),
            ai, bi, c_idx, 1.0, pack=(4, 4), vmem_resident=vmem_resident,
            prefetch_budget=budget))

    got = run()
    want = c_h.astype(np.float64)
    np.add.at(want, c_idx, np.einsum(
        "sij,sjk->sik", a_h[ai].astype(np.float64), b_h[bi].astype(np.float64)))
    err = np.abs(got - want).max() / np.abs(want).max()
    tol = costmodel.kernel_validation_tolerance("float32", k, 2)
    assert err < tol, (err, tol)
    np.testing.assert_array_equal(got[len(run_lens):], c_h[len(run_lens):])
    np.testing.assert_array_equal(run(), got)


@pytest.mark.parametrize("error,reason", [
    (None, None),
    ("simulated Mosaic lowering failure", "lowering"),
    ("RESOURCE_EXHAUSTED: simulated: ran out of memory in memory space smem",
     "transient"),
])
def test_crosspack_fallback_is_counted_by_reason(monkeypatch, error, reason):
    """`dbcsr_tpu_crosspack_fallback_total{reason}` moves by one beside
    the demotion's RuntimeWarning, and by none on a clean launch."""
    import warnings

    import jax.numpy as jnp

    from dbcsr_tpu.acc import pallas_smm, smm
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.obs import metrics

    def total():
        return {lab["reason"]: v for lab, v in metrics.counter_items(
            "dbcsr_tpu_crosspack_fallback_total")}

    if error is not None:
        def boom(*a, **k):
            raise RuntimeError(error)

        monkeypatch.setattr(pallas_smm, "_pallas_crosspack", boom)
    key = (11, 11, 11, "float32")
    smm._cross_disabled.discard(key)
    rng = np.random.default_rng(75)
    a, b, c, ai, bi, ci = _random_stack(rng, 16, 16, 10, 300, 11, 11, 11,
                                        np.float32)
    before = total()
    set_config(mm_driver="pallas_cross", validate_kernels=False)
    try:
        plan = smm.prepare_stack(jnp.asarray(c), jnp.asarray(a),
                                 jnp.asarray(b), ai, bi, ci)
        assert plan.driver == "pallas_cross"
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            got = np.asarray(smm.execute_stack(
                jnp.asarray(c), jnp.asarray(a), jnp.asarray(b), plan, 1.0))
    finally:
        set_config(mm_driver="auto", validate_kernels=True)
        blacklisted = key in smm._cross_disabled
        smm._cross_disabled.discard(key)
    np.testing.assert_allclose(got, _oracle(c, a, b, ai, bi, ci, 1.0),
                               rtol=2e-4, atol=2e-4)
    after = total()
    moved = {r: after.get(r, 0) - before.get(r, 0)
             for r in ("lowering", "transient")}
    fell_back = [w for w in caught if issubclass(w.category, RuntimeWarning)
                 and "falling back to the base kernel" in str(w.message)]
    if reason is None:
        assert moved == {"lowering": 0, "transient": 0} and not fell_back
        assert plan.driver == "pallas_cross"
    else:
        assert moved == {"lowering": 0, "transient": 0, reason: 1}
        assert len(fell_back) == 1 and plan.driver == "pallas"
    # only a lowering gap blacklists the shape for the session
    assert blacklisted == (reason == "lowering")
