"""Multiply engine tests: the dense-oracle pattern of
`tests/dbcsr_test_multiply.F` (densify, BLAS product, compare within eps),
sweeping alpha/beta, transposes, limits, symmetry, dtypes — modeled on the
named cases of `dbcsr_unittest1.F:79-293`."""

import numpy as np
import pytest

from dbcsr_tpu import create, make_random_matrix, multiply, new_transposed, to_dense
from dbcsr_tpu.core.matrix import SYMMETRIC
from dbcsr_tpu.ops.test_methods import checksum, impose_sparsity

RBS = [2, 3, 5, 4]
CBS = [3, 4, 2]
KBS = [4, 2, 3, 5]


def _rand(name, rbs, cbs, occ, dtype=np.float64, seed=0, mtype="N"):
    return make_random_matrix(
        name, rbs, cbs, dtype=dtype, occupation=occ,
        matrix_type=mtype, rng=np.random.default_rng(seed),
    )


def _dense_op(m, trans):
    d = to_dense(m)
    if trans == "N":
        return d
    if trans == "T":
        return d.T
    return d.conj().T


@pytest.mark.parametrize("transa", ["N", "T"])
@pytest.mark.parametrize("transb", ["N", "T"])
@pytest.mark.parametrize("occ", [0.3, 1.0])
def test_multiply_transposes(transa, transb, occ):
    a = _rand("a", RBS if transa == "N" else KBS, KBS if transa == "N" else RBS, occ, seed=1)
    b = _rand("b", KBS if transb == "N" else CBS, CBS if transb == "N" else KBS, occ, seed=2)
    c = create("c", RBS, CBS)
    multiply(transa, transb, 1.0, a, b, 0.0, c)
    want = _dense_op(a, transa) @ _dense_op(b, transb)
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("alpha,beta", [(1.0, 0.0), (2.5, 1.0), (-1.0, 0.5), (0.0, 2.0)])
def test_multiply_alpha_beta(alpha, beta):
    a = _rand("a", RBS, KBS, 0.5, seed=3)
    b = _rand("b", KBS, CBS, 0.5, seed=4)
    c = _rand("c", RBS, CBS, 0.5, seed=5)
    c0 = to_dense(c)
    multiply("N", "N", alpha, a, b, beta, c)
    want = alpha * (to_dense(a) @ to_dense(b)) + beta * c0
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("dtype,trans", [
    (np.float32, "N"),
    (np.complex128, "N"),
    (np.complex128, "C"),
    (np.complex64, "T"),
])
def test_multiply_dtypes(dtype, trans):
    a = _rand("a", RBS if trans == "N" else KBS, KBS if trans == "N" else RBS,
              0.6, dtype=dtype, seed=6)
    b = _rand("b", KBS, CBS, 0.6, dtype=dtype, seed=7)
    c = create("c", RBS, CBS, dtype=dtype)
    multiply(trans, "N", 1.0, a, b, 0.0, c)
    want = _dense_op(a, trans) @ to_dense(b)
    rtol = 2e-5 if np.dtype(dtype).itemsize <= 8 else 1e-12  # f32 + c64 loose
    np.testing.assert_allclose(to_dense(c), want, rtol=rtol, atol=rtol)


def test_multiply_accumulates_pattern_union():
    """C keeps its old blocks (beta) and gains product blocks."""
    a = _rand("a", RBS, KBS, 0.2, seed=8)
    b = _rand("b", KBS, CBS, 0.2, seed=9)
    c = _rand("c", RBS, CBS, 0.2, seed=10)
    c0 = to_dense(c)
    multiply("N", "N", 1.0, a, b, 1.0, c)
    np.testing.assert_allclose(to_dense(c), to_dense(a) @ to_dense(b) + c0,
                               rtol=1e-12, atol=1e-12)


def test_retain_sparsity():
    """ref retain_sparsity: C's pattern is frozen (dbcsr_test_multiply.F:633)."""
    a = _rand("a", RBS, KBS, 0.8, seed=11)
    b = _rand("b", KBS, CBS, 0.8, seed=12)
    c = _rand("c", RBS, CBS, 0.3, seed=13)
    pattern_before = set(map(tuple, zip(*c.entry_coords())))
    c0 = to_dense(c)
    multiply("N", "N", 1.0, a, b, 1.0, c, retain_sparsity=True)
    pattern_after = set(map(tuple, zip(*c.entry_coords())))
    assert pattern_after == pattern_before
    want = impose_sparsity(to_dense(a) @ to_dense(b) + c0, c)
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("limits", [
    dict(first_row=1, last_row=2),
    dict(first_col=0, last_col=1),
    dict(first_k=1, last_k=2),
    dict(first_row=1, last_row=3, first_col=1, last_col=2, first_k=0, last_k=1),
])
def test_multiply_limits(limits):
    """ref multiply_LIMITS cases (dbcsr_unittest1.F): block-index submatrix."""
    a = _rand("a", RBS, KBS, 1.0, seed=14)
    b = _rand("b", KBS, CBS, 1.0, seed=15)
    c = create("c", RBS, CBS)
    multiply("N", "N", 1.0, a, b, 0.0, c, **limits)
    da, db = to_dense(a), to_dense(b)
    roff = np.concatenate([[0], np.cumsum(RBS)])
    coff = np.concatenate([[0], np.cumsum(CBS)])
    koff = np.concatenate([[0], np.cumsum(KBS)])
    r0 = roff[limits.get("first_row", 0)]
    r1 = roff[limits.get("last_row", len(RBS) - 1) + 1]
    c0_ = coff[limits.get("first_col", 0)]
    c1 = coff[limits.get("last_col", len(CBS) - 1) + 1]
    k0 = koff[limits.get("first_k", 0)]
    k1 = koff[limits.get("last_k", len(KBS) - 1) + 1]
    want = np.zeros((sum(RBS), sum(CBS)))
    want[r0:r1, c0_:c1] = da[r0:r1, k0:k1] @ db[k0:k1, c0_:c1]
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)


def test_multiply_symmetric_inputs():
    """Symmetric A stored triangular must multiply as its full self."""
    n = [2, 3, 4]
    a = _rand("a", n, n, 1.0, seed=16, mtype=SYMMETRIC)
    b = _rand("b", n, CBS, 0.7, seed=17)
    c = create("c", n, CBS)
    multiply("N", "N", 1.0, a, b, 0.0, c)
    np.testing.assert_allclose(to_dense(c), to_dense(a) @ to_dense(b),
                               rtol=1e-12, atol=1e-12)


def test_multiply_symmetric_product():
    """C declared symmetric stores only the canonical triangle."""
    n = [2, 3]
    a = _rand("a", n, n, 1.0, seed=18)
    at = to_dense(a)
    # build B = A^T so product A@A^T is symmetric
    c = create("c", n, n, matrix_type=SYMMETRIC)
    multiply("N", "T", 1.0, a, a, 0.0, c)
    rows, cols = c.entry_coords()
    assert (rows <= cols).all()
    np.testing.assert_allclose(to_dense(c), at @ at.T, rtol=1e-12, atol=1e-12)


def test_filter_eps_final_pass():
    a = _rand("a", RBS, KBS, 0.6, seed=19)
    b = _rand("b", KBS, CBS, 0.6, seed=20)
    c = create("c", RBS, CBS)
    eps = 1e30  # absurdly large: every block filtered
    multiply("N", "N", 1.0, a, b, 0.0, c, filter_eps=eps)
    assert c.nblks == 0
    # tiny eps: nothing filtered
    c2 = create("c2", RBS, CBS)
    multiply("N", "N", 1.0, a, b, 0.0, c2, filter_eps=1e-30)
    np.testing.assert_allclose(to_dense(c2), to_dense(a) @ to_dense(b),
                               rtol=1e-12, atol=1e-12)


def test_multiply_deterministic_checksum():
    """Bit-identical checksums across repeats (north-star requirement)."""
    a = _rand("a", [5, 13, 23], [5, 13, 23], 0.5, seed=21)
    b = _rand("b", [5, 13, 23], [5, 13, 23], 0.5, seed=22)
    sums = []
    for _ in range(3):
        c = create("c", [5, 13, 23], [5, 13, 23])
        multiply("N", "N", 1.0, a, b, 0.0, c)
        sums.append(checksum(c))
    assert sums[0] == sums[1] == sums[2]


def test_multiply_flop_count():
    a = _rand("a", [2, 2], [2, 2], 1.0, seed=23)
    b = _rand("b", [2, 2], [2, 2], 1.0, seed=24)
    c = create("c", [2, 2], [2, 2])
    flops = multiply("N", "N", 1.0, a, b, 0.0, c)
    assert flops == 2 * 4 * 4 * 4  # dense 4x4x4 in 2x2 blocks


def test_multiply_empty_matrices():
    a = create("a", RBS, KBS).finalize()
    b = _rand("b", KBS, CBS, 0.5, seed=25)
    c = create("c", RBS, CBS)
    flops = multiply("N", "N", 1.0, a, b, 0.0, c)
    assert flops == 0
    assert c.nblks == 0


@pytest.mark.slow
def test_multiply_mixed_block_sizes_stress():
    """ref dbcsr_unittest3 flavor: block-size triplets incl. odd sizes."""
    rbs = [1, 3, 4, 23]
    kbs = [7, 1, 45, 2]
    cbs = [13, 23, 1]
    a = _rand("a", rbs, kbs, 0.9, seed=26)
    b = _rand("b", kbs, cbs, 0.9, seed=27)
    c = create("c", rbs, cbs)
    multiply("N", "N", 1.0, a, b, 0.0, c)
    np.testing.assert_allclose(to_dense(c), to_dense(a) @ to_dense(b),
                               rtol=1e-12, atol=1e-12)


def test_multiply_aliased_c_is_a():
    """In-place squaring: C aliasing A must not corrupt the engine."""
    n = [2, 3]
    a = _rand("a", n, n, 1.0, seed=30)
    d = to_dense(a)
    multiply("N", "N", 1.0, a, a, 0.0, a)
    np.testing.assert_allclose(to_dense(a), d @ d, rtol=1e-12, atol=1e-12)


def test_multiply_aliased_c_is_b_with_beta():
    n = [2, 3]
    a = _rand("a", n, n, 1.0, seed=31)
    b = _rand("b", n, n, 1.0, seed=32)
    da, db = to_dense(a), to_dense(b)
    multiply("N", "N", 1.0, a, b, 0.5, b)
    np.testing.assert_allclose(to_dense(b), da @ db + 0.5 * db, rtol=1e-12, atol=1e-12)


def test_repeated_multiply_reuses_stack_plan():
    """Same-pattern repeats hit the plan cache (no re-sort/re-upload)
    and produce bit-identical results; a pattern change misses."""
    import dbcsr_tpu.mm.multiply as mm
    from dbcsr_tpu.ops.test_methods import checksum

    mm._plan_cache.clear()
    rbs = [3, 4, 3]
    a = _rand("a", rbs, rbs, 0.6, seed=70)
    b = _rand("b", rbs, rbs, 0.6, seed=71)
    c0 = _rand("c", rbs, rbs, 0.3, seed=72)

    c1 = c0.copy()
    multiply("N", "N", 1.0, a, b, 0.5, c1)
    n_after_first = len(mm._plan_cache)
    assert n_after_first == 1
    cs1 = checksum(c1)

    # same patterns, new A values: cache hit, same plan, new result
    for blk in a.bins:
        if blk.count:
            blk.data = blk.data * 1.0  # same values, fresh buffers
    c2 = c0.copy()
    multiply("N", "N", 1.0, a, b, 0.5, c2)
    assert len(mm._plan_cache) == 1  # reused, not re-prepared
    assert checksum(c2) == cs1  # bit-identical across repeats

    # different A pattern: a fresh plan is prepared
    a2 = _rand("a2", rbs, rbs, 0.5, seed=73)
    c3 = c0.copy()
    multiply("N", "N", 1.0, a2, b, 0.5, c3)
    assert len(mm._plan_cache) == 2


def test_filtered_multiply_plan_cache_contract():
    """filter_eps products depend on values (norms): under device
    residency (core.mempool) they cache keyed by a DIGEST of the
    surviving candidate list — a value change that alters the
    survivors must miss; with residency off they are never cached
    (the historical contract)."""
    import dbcsr_tpu.mm.multiply as mm
    from dbcsr_tpu.core import mempool

    rbs = [3, 4]
    a = _rand("a", rbs, rbs, 1.0, seed=74)
    b = _rand("b", rbs, rbs, 1.0, seed=75)
    was = mempool.enabled()
    try:
        mempool.set_enabled(False)
        mm._plan_cache.clear()
        c = create("c", rbs, rbs)
        multiply("N", "N", 1.0, a, b, 0.0, c, filter_eps=1e-8)
        assert len(mm._plan_cache) == 0

        mempool.set_enabled(True)
        mm._plan_cache.clear()
        c = create("c", rbs, rbs)
        multiply("N", "N", 1.0, a, b, 0.0, c, filter_eps=1e-8)
        assert len(mm._plan_cache) == 1
        # same values -> same survivors -> cache HIT (no new entry)
        c2 = create("c", rbs, rbs)
        multiply("N", "N", 1.0, a, b, 0.0, c2, filter_eps=1e-8)
        assert len(mm._plan_cache) == 1
        # sink one block's norm below the filter so the survivor set
        # changes: same patterns, different value digest -> new key
        blk = a.get_block(0, 0)
        a.put_block(0, 0, np.full_like(blk, 1e-30))
        a.finalize()
        c3 = create("c", rbs, rbs)
        multiply("N", "N", 1.0, a, b, 0.0, c3, filter_eps=1e-8)
        assert len(mm._plan_cache) == 2
    finally:
        mempool.set_enabled(was)
        mm._plan_cache.clear()


def test_dense_mode_matches_sparse_path():
    """Uniform-blocked occ=1 goes dense; force sparse and compare."""
    from dbcsr_tpu.core.config import set_config

    rbs = [4] * 6
    a = _rand("a", rbs, rbs, 1.0, seed=50)
    b = _rand("b", rbs, rbs, 1.0, seed=51)
    c_dense = _rand("c", rbs, rbs, 0.5, seed=52)
    c_sparse = c_dense.copy()
    multiply("N", "N", 1.5, a, b, 0.5, c_dense)  # auto -> dense mode
    set_config(mm_format="stack")
    try:
        multiply("N", "N", 1.5, a, b, 0.5, c_sparse)
    finally:
        set_config(mm_format="auto")
    np.testing.assert_allclose(to_dense(c_dense), to_dense(c_sparse),
                               rtol=1e-12, atol=1e-12)


@pytest.mark.slow
def test_dense_mode_nonuniform_blocking_matches_sparse_path():
    """Non-uniform blockings now take the general make_dense path
    (densify -> one matmul -> carve back into the original blocking,
    ref dbcsr_make_dense/undense, dbcsr_mm.F:593-617)."""
    from dbcsr_tpu.core.config import set_config

    rbs, cbs, kbs = [3, 5, 2, 4], [4, 2, 5], [2, 6, 3]
    a = _rand("a", rbs, kbs, 1.0, seed=60)
    b = _rand("b", kbs, cbs, 1.0, seed=61)
    c_dense = _rand("c", rbs, cbs, 0.5, seed=62)
    c_sparse = c_dense.copy()
    set_config(mm_format="dense")
    try:
        multiply("N", "N", 1.5, a, b, 0.5, c_dense)
    finally:
        set_config(mm_format="auto")
    set_config(mm_format="stack")
    try:
        multiply("N", "N", 1.5, a, b, 0.5, c_sparse)
    finally:
        set_config(mm_format="auto")
    # dense mode leaves a full pattern; values must agree everywhere
    np.testing.assert_allclose(to_dense(c_dense), to_dense(c_sparse),
                               rtol=1e-12, atol=1e-12)
    assert c_dense.nblks == len(rbs) * len(cbs)


@pytest.mark.slow
def test_dense_mode_nonuniform_auto_at_full_occupancy():
    """occ=1 non-uniform matrices take dense mode automatically."""
    rbs, kbs = [3, 5, 4], [2, 6]
    a = _rand("a", rbs, kbs, 1.0, seed=63)
    b = _rand("b", kbs, rbs, 1.0, seed=64)
    c = create("c", rbs, rbs)
    multiply("N", "N", 1.0, a, b, 0.0, c)
    want = np.asarray(to_dense(a)) @ np.asarray(to_dense(b))
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)


def test_dense_mode_not_used_with_filter():
    """filter_eps forces the sparse path even at occ=1."""
    rbs = [4] * 4
    a = _rand("a", rbs, rbs, 1.0, seed=53)
    b = _rand("b", rbs, rbs, 1.0, seed=54)
    c = create("c", rbs, rbs)
    multiply("N", "N", 1.0, a, b, 0.0, c, filter_eps=1e30)
    assert c.nblks == 0  # all filtered -> sparse machinery ran


@pytest.mark.slow
def test_multiply_large_blocks_stress():
    """ref dbcsr_unittest2.F:80-102: large and rectangular block sizes
    (up to 100s) must flow through the engine like small ones — these
    exceed the fused-kernel regime and exercise the big-block path
    (ref cuBLAS fallback for blocks > max_kernel_dim=80)."""
    rbs = [76, 113]
    kbs = [52, 97]
    cbs = [120, 33]
    a = _rand("a", rbs, kbs, 0.9, seed=70)
    b = _rand("b", kbs, cbs, 0.9, seed=71)
    c = create("c", rbs, cbs)
    multiply("N", "N", 1.0, a, b, 0.0, c)
    np.testing.assert_allclose(to_dense(c), to_dense(a) @ to_dense(b),
                               rtol=1e-11, atol=1e-11)


@pytest.mark.slow
def test_multiply_mixed_tiny_and_large_blocks():
    """1-element blocks alongside 100+ blocks in one multiply."""
    rbs = [1, 88, 3]
    kbs = [105, 1, 7]
    cbs = [2, 94]
    a = _rand("a", rbs, kbs, 1.0, seed=72)
    b = _rand("b", kbs, cbs, 1.0, seed=73)
    c = create("c", rbs, cbs)
    multiply("N", "T", 1.0, a, new_transposed(b), 0.0, c)
    np.testing.assert_allclose(to_dense(c), to_dense(a) @ to_dense(b),
                               rtol=1e-11, atol=1e-11)


def test_dense_canvas_cache_hits_and_invalidates():
    """Repeated dense-mode multiplies reuse the densified operands;
    mutating an operand invalidates its canvas (keyed by bin data-array
    identity)."""
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.mm.multiply import _dense_canvas_cached
    from dbcsr_tpu.ops.operations import scale

    rbs = [4] * 5
    a = _rand("a", rbs, rbs, 1.0, seed=80)
    b = _rand("b", rbs, rbs, 1.0, seed=81)
    set_config(mm_format="dense")
    try:
        c1 = create("c", rbs, rbs)
        multiply("N", "N", 1.0, a, b, 0.0, c1)
        canvas1 = a._dense_canvas_cache[1]
        c2 = create("c", rbs, rbs)
        multiply("N", "N", 1.0, a, b, 0.0, c2)
        assert a._dense_canvas_cache[1] is canvas1  # hit
        assert checksum(c1) == checksum(c2)
        scale(a, 2.0)
        c3 = create("c", rbs, rbs)
        multiply("N", "N", 1.0, a, b, 0.0, c3)
        assert a._dense_canvas_cache[1] is not canvas1  # invalidated
        np.testing.assert_allclose(to_dense(c3), 2.0 * to_dense(c1),
                                   rtol=1e-12, atol=1e-12)
    finally:
        set_config(mm_format="auto")


def test_alpha_beta_scalar_typing():
    """Zero-imag complex scalars coerce for real products; nonzero-imag
    raise a clear TypeError (the reference's typed-alpha contract)."""
    a = _rand("a", [2, 2], [2, 2], 1.0, seed=90)
    b = _rand("b", [2, 2], [2, 2], 1.0, seed=91)
    c = create("c", [2, 2], [2, 2])
    multiply("N", "N", complex(2.0, 0.0), a, b, complex(0.0, 0.0), c)
    np.testing.assert_allclose(to_dense(c), 2.0 * (to_dense(a) @ to_dense(b)),
                               rtol=1e-12, atol=1e-12)
    with pytest.raises(TypeError, match="complex alpha"):
        multiply("N", "N", 1.0 + 2.0j, a, b, 0.0, create("c", [2, 2], [2, 2]))


# ---------------------------------------------------------------------------
# Chunked dense mode (beyond the canvas cap; ref dbcsr_mm.F:593-617 —
# the reference's dense mode has no size cap)
# ---------------------------------------------------------------------------

def test_dense_chunked_matches_stack_path(monkeypatch):
    """With the canvas cap shrunk, the dense route must tile over
    k/m-strips and stay exact (incl. beta accumulation)."""
    import dbcsr_tpu as dt
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.mm import multiply as mm

    monkeypatch.setattr(mm, "_DENSE_MAX_CANVAS", 5000)
    rbs = [7] * 13
    kbs = [7] * 17
    cbs = [7] * 11
    a = dt.make_random_matrix("A", rbs, kbs, occupation=0.6,
                              rng=np.random.default_rng(1))
    b = dt.make_random_matrix("B", kbs, cbs, occupation=0.6,
                              rng=np.random.default_rng(2))
    c0 = dt.make_random_matrix("C", rbs, cbs, occupation=0.3,
                               rng=np.random.default_rng(3))
    want = 1.5 * (dt.to_dense(a) @ dt.to_dense(b)) + 0.5 * dt.to_dense(c0)
    assert mm._dense_chunking(13, 11, 17, 7, 7, 7) == (9, 9, 11)
    set_config(mm_format="dense")
    try:
        dt.multiply("N", "N", 1.5, a, b, 0.5, c0)
    finally:
        set_config(mm_format="auto")
    assert c0._mm_algorithm == "dense"
    np.testing.assert_allclose(dt.to_dense(c0), want, rtol=1e-12, atol=1e-12)


def test_dense_chunked_gate_and_feasibility(monkeypatch):
    """The cost-model route beyond the cap requires uniform blockings
    (chunked path) — mixed blockings or an unchunkable geometry must
    leave the gate closed.  (The occupancy-threshold route is
    deliberately not size-capped, matching prior behavior.)"""
    import dbcsr_tpu as dt
    from dbcsr_tpu.mm import multiply as mm

    monkeypatch.setattr(mm, "_DENSE_MAX_CANVAS", 2000)
    # a single block row wider than the cap: the n axis chunks instead
    # of declining (the format planner's wide-N extension)
    assert mm._dense_chunking(4, 50, 4, 10, 10, 10) == (1, 1, 20)
    # a single BLOCK over the cap: genuinely unchunkable, gate closed
    assert mm._dense_chunking(2, 2, 2, 50, 50, 50) is None
    # feasible uniform geometry chunks
    assert mm._dense_chunking(13, 11, 17, 7, 7, 7) is not None

    # LOW-occupancy mixed-blocking over-cap product: every dense route
    # is closed (occupancy below threshold, cost model needs uniform)
    rbs = [7] * 9
    kbs = [7, 5] * 5
    a = dt.make_random_matrix("A", rbs, kbs, occupation=0.5,
                              rng=np.random.default_rng(4))
    b = dt.make_random_matrix("B", kbs, rbs, occupation=0.5,
                              rng=np.random.default_rng(5))
    c = dt.create("C", rbs, rbs, dtype=np.float64)
    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.mm import format_planner as fp

    # on the (seamed) TPU the emulated-dtype rule would take this
    # product — fill and flop ratio pass — were its canvases feasible
    set_config(platform_override="tpu")
    try:
        assert not mm.dense_canvas_feasible(a, b, c, chunked=True)
        assert fp._dense_rule(a, b, c, get_config(), True) is None
        monkeypatch.setattr(mm, "_DENSE_MAX_CANVAS", 2 * 10 ** 8)
        assert fp._dense_rule(a, b, c, get_config(), True) \
            == "cost-model:emulated-dtype"
        monkeypatch.setattr(mm, "_DENSE_MAX_CANVAS", 2000)
    finally:
        set_config(platform_override="")
    dt.multiply("N", "N", 1.0, a, b, 0.0, c)
    assert c._mm_algorithm == "stack"
    np.testing.assert_allclose(
        dt.to_dense(c), dt.to_dense(a) @ dt.to_dense(b),
        rtol=1e-12, atol=1e-12,
    )


def test_uniform_carve_is_block_slicing():
    """The uniform layout carve (`_carve_full_pattern`: the chunked
    executor's strips) is element-exact vs a manual block slicing of
    the canvas (full row-major pattern)."""
    import jax.numpy as jnp

    from dbcsr_tpu.mm import multiply as mm

    rng = np.random.default_rng(7)
    nbr, nbc, bm, bn = 3, 4, 5, 7
    cd_np = rng.standard_normal((nbr * bm, nbc * bn))
    r = np.asarray(mm._carve_full_pattern(jnp.asarray(cd_np), nbr, nbc, bm, bn))
    for bi in range(nbr):
        for bj in range(nbc):
            np.testing.assert_array_equal(
                r[bi * nbc + bj],
                cd_np[bi * bm : (bi + 1) * bm, bj * bn : (bj + 1) * bn],
            )


def _carve_counts():
    from dbcsr_tpu.obs import metrics

    got = {"layout": 0.0, "gather": 0.0}
    for labels, value in metrics.counter_items("dbcsr_tpu_dense_carve_total"):
        got[labels["lowering"]] = value
    return got


def _gather_carve(c, cd):
    """What `carve_full_pattern` did before the layout carve: per-bin
    element-offset gathers in `_bin_entries` slot order, count rows."""
    import jax.numpy as jnp

    from dbcsr_tpu.core.matrix import _bin_entries
    from dbcsr_tpu.mm import multiply as mm

    keys = np.arange(c.nblkrows * c.nblkcols)
    rows, cols = keys // c.nblkcols, keys % c.nblkcols
    nb, nsl, shapes = _bin_entries(c.row_blk_sizes, c.col_blk_sizes, rows, cols)
    out = []
    for b_id, (bm, bn) in enumerate(shapes):
        sel = np.nonzero(nb == b_id)[0]
        ro = np.empty(len(sel), np.int64)
        co = np.empty(len(sel), np.int64)
        ro[nsl[sel]] = c.row_blk_offsets[rows[sel]]
        co[nsl[sel]] = c.col_blk_offsets[cols[sel]]
        out.append(((bm, bn), np.asarray(mm._gather_bin_from_canvas(
            cd, jnp.asarray(ro), jnp.asarray(co), bm=bm, bn=bn))))
    return out


_CARVE_BLOCKINGS = {
    "uniform": ([5] * 4, [7] * 3),
    "ragged_last_row": ([5] * 3 + [2], [7] * 3),
    "ragged_last_col": ([5] * 4, [7] * 2 + [4]),
    "both_ragged": ([23] * 3 + [18], [13] * 4 + [6]),
    "single_block_row": ([6], [4] * 5 + [1]),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32", "float64"])
@pytest.mark.parametrize("blocking", sorted(_CARVE_BLOCKINGS))
def test_layout_carve_matches_gather(blocking, dtype):
    """Near-uniform blockings are carved by one layout program; every
    bin is element-exact to `_gather_bin_from_canvas` in slot order and
    comes out at its bucket capacity with a zero tail."""
    import jax.numpy as jnp

    from dbcsr_tpu.mm import multiply as mm
    from dbcsr_tpu.utils.rounding import bucket_size

    rbs, cbs = _CARVE_BLOCKINGS[blocking]
    rng = np.random.default_rng(11)
    cd = jnp.asarray(rng.standard_normal((sum(rbs), sum(cbs))), dtype=dtype)
    c = create("c", rbs, cbs, dtype=dtype)
    before = _carve_counts()
    mm.carve_full_pattern(c, cd)
    after = _carve_counts()
    assert after["layout"] == before["layout"] + 1
    assert after["gather"] == before["gather"]
    want = _gather_carve(c, cd)
    assert [b.shape for b in c.bins] == [shape for shape, _ in want]
    for b, (_, ref) in zip(c.bins, want):
        got = np.asarray(b.data)
        assert b.count == len(ref) and got.dtype == ref.dtype
        assert got.shape[0] == bucket_size(b.count)
        np.testing.assert_array_equal(got[: b.count], ref)
        assert not np.any(got[b.count:])
    assert sum(b.count for b in c.bins) == len(rbs) * len(cbs)
    np.testing.assert_array_equal(to_dense(c), np.asarray(cd))


def test_irregular_blocking_keeps_the_gather_carve():
    """A genuinely irregular blocking (odd size in the middle) is no
    rectangle per bin: it takes the element gather, and
    `dbcsr_tpu_dense_carve_total` says which lowering each carve took."""
    import jax.numpy as jnp

    from dbcsr_tpu.mm import multiply as mm
    from dbcsr_tpu.mm.multiply import _near_uniform

    rbs = [23, 11, 23, 23]
    assert not _near_uniform(np.asarray(rbs))
    assert not _near_uniform(np.asarray([18, 23, 23]))
    assert _near_uniform(np.asarray([23] * 3 + [18]))
    assert _near_uniform(np.asarray([23, 23, 23]))
    rng = np.random.default_rng(12)
    cd = jnp.asarray(rng.standard_normal((sum(rbs), sum(rbs))))
    before = _carve_counts()
    c = create("c", rbs, rbs)
    mm.carve_full_pattern(c, cd)
    mid = _carve_counts()
    assert (mid["gather"], mid["layout"]) == (before["gather"] + 1,
                                              before["layout"])
    np.testing.assert_array_equal(to_dense(c), np.asarray(cd))
    for b, (_, ref) in zip(c.bins, _gather_carve(c, cd)):
        np.testing.assert_array_equal(np.asarray(b.data)[: b.count], ref)
    # near-uniform rows do not make irregular columns a rectangle
    c2 = create("c2", [23] * 3 + [11], rbs)
    mm.carve_full_pattern(c2, cd)
    c3 = create("c3", [23] * 3 + [11], [23] * 3 + [11])
    mm.carve_full_pattern(c3, cd)
    end = _carve_counts()
    assert (end["gather"], end["layout"]) == (mid["gather"] + 1,
                                              mid["layout"] + 1)
    np.testing.assert_array_equal(to_dense(c2), np.asarray(cd))
    np.testing.assert_array_equal(to_dense(c3), np.asarray(cd))


@pytest.mark.parametrize("blocking", ["uniform", "near_uniform", "irregular"])
def test_dense_general_carve_variants_match_oracle(blocking):
    """The PRODUCTION north-star shape is near-uniform (ceil-division
    blocking: uniform 23s + one trailing 18), which `_dense_multiply`
    carves by the layout program — as it does a uniform blocking, the
    case with no ragged edge; an irregular blocking takes the gather.
    All must be oracle-exact, beta merge into a non-empty C included."""
    from dbcsr_tpu.core.config import set_config

    if blocking == "uniform":
        rbs, cbs, kbs = [23] * 7, [13] * 6, [23] * 5
    elif blocking == "near_uniform":
        rbs = [23] * 6 + [18]   # near-uniform rows
        cbs = [13] * 5 + [7]    # near-uniform cols, different size
    else:
        rbs = [23, 11, 23, 23, 5, 23, 18]
        cbs = [13, 7, 13, 13, 13, 2]
    if blocking != "uniform":
        kbs = [23] * 4 + [11]
    a = _rand("a", rbs, kbs, 0.6, seed=31)
    b = _rand("b", kbs, cbs, 0.6, seed=32)
    c = _rand("c", rbs, cbs, 0.4, seed=33)
    c0 = to_dense(c)
    before = _carve_counts()
    set_config(mm_format="dense")
    try:
        multiply("N", "N", 1.5, a, b, 0.5, c)
    finally:
        set_config(mm_format="auto")
    want = 1.5 * (to_dense(a) @ to_dense(b)) + 0.5 * c0
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)
    after = _carve_counts()
    took = "gather" if blocking == "irregular" else "layout"
    assert {k: after[k] - before[k] for k in after} == {
        "layout": float(took == "layout"), "gather": float(took == "gather")}


def test_dense_mesh_carve_matches_one_chip():
    """`_dense_multiply_mesh` hands `carve_full_pattern` a canvas that
    is sharded over the grid: the layout carve must give the one-chip
    product, bin for bin, at bucket capacity."""
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.parallel import make_grid, sparse_multiply_distributed

    rbs = [5] * 6 + [3]
    cbs = [4] * 7 + [2]
    kbs = [5] * 5 + [1]
    a = _rand("a", rbs, kbs, 0.9, seed=41)
    b = _rand("b", kbs, cbs, 0.9, seed=42)
    c_one = create("c", rbs, cbs)
    before = _carve_counts()
    set_config(mm_format="dense")
    try:
        multiply("N", "N", 1.5, a, b, 0.0, c_one)
        c_mesh = sparse_multiply_distributed(1.5, a, b, 0.0, None,
                                             make_grid(4))
    finally:
        set_config(mm_format="auto")
    assert c_mesh._mm_algorithm == "dense" == c_one._mm_algorithm
    assert _carve_counts()["layout"] == before["layout"] + 2
    assert [(b_.shape, b_.count, b_.data.shape) for b_ in c_mesh.bins] == [
        (b_.shape, b_.count, b_.data.shape) for b_ in c_one.bins]
    for bm_, bo in zip(c_mesh.bins, c_one.bins):
        np.testing.assert_allclose(np.asarray(bm_.data), np.asarray(bo.data),
                                   rtol=1e-13, atol=1e-13)
    np.testing.assert_allclose(
        to_dense(c_mesh), 1.5 * (to_dense(a) @ to_dense(b)),
        rtol=1e-12, atol=1e-12)
