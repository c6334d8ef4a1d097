"""Block-sparse distributed Cannon tests (virtual 8-device CPU mesh)."""

import numpy as np
import pytest

from dbcsr_tpu import checksum, make_random_matrix, to_dense
from dbcsr_tpu.parallel import make_grid, sparse_multiply_distributed


@pytest.fixture(scope="module")
def mesh8():
    return make_grid(8)


@pytest.fixture(scope="module")
def mesh4():
    return make_grid(4)


def _rand(name, rbs, cbs, occ, seed, **kw):
    rng = np.random.default_rng(seed)
    return make_random_matrix(name, rbs, cbs, occupation=occ, rng=rng, **kw)


def test_sparse_cannon_uniform_blocks(mesh8):
    rbs = [4] * 12
    a = _rand("A", rbs, rbs, 0.3, 1)
    b = _rand("B", rbs, rbs, 0.3, 2)
    c = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh8)
    np.testing.assert_allclose(
        to_dense(c), to_dense(a) @ to_dense(b), rtol=1e-12, atol=1e-12
    )


@pytest.mark.slow
def test_sparse_cannon_mixed_blocks(mesh8):
    rng = np.random.default_rng(3)
    rbs = rng.choice([2, 3, 5], 11)
    kbs = rng.choice([4, 2], 9)
    cbs = rng.choice([3, 6], 13)
    a = _rand("A", rbs, kbs, 0.4, 4)
    b = _rand("B", kbs, cbs, 0.4, 5)
    c = sparse_multiply_distributed(-0.5, a, b, 0.0, None, mesh8)
    np.testing.assert_allclose(
        to_dense(c), -0.5 * (to_dense(a) @ to_dense(b)), rtol=1e-12, atol=1e-12
    )


def test_sparse_cannon_beta_accumulate(mesh4):
    rbs = [3] * 8
    a = _rand("A", rbs, rbs, 0.5, 6)
    b = _rand("B", rbs, rbs, 0.5, 7)
    c0 = _rand("C", rbs, rbs, 0.3, 8)
    c = sparse_multiply_distributed(2.0, a, b, 0.5, c0, mesh4)
    want = 2.0 * to_dense(a) @ to_dense(b) + 0.5 * to_dense(c0)
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)


def test_sparse_cannon_deterministic(mesh8):
    rbs = [4] * 10
    a = _rand("A", rbs, rbs, 0.4, 9)
    b = _rand("B", rbs, rbs, 0.4, 10)
    c1 = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh8)
    c2 = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh8)
    assert checksum(c1) == checksum(c2)


def test_sparse_cannon_matches_single_chip_engine(mesh8):
    from dbcsr_tpu import multiply

    rbs = [4] * 10
    a = _rand("A", rbs, rbs, 0.4, 11)
    b = _rand("B", rbs, rbs, 0.4, 12)
    c_host = _rand("C", rbs, rbs, 0.2, 13)
    c_dist = sparse_multiply_distributed(1.0, a, b, 1.0, c_host, mesh8)
    multiply("N", "N", 1.0, a, b, 1.0, c_host)
    np.testing.assert_allclose(
        to_dense(c_dist), to_dense(c_host), rtol=1e-12, atol=1e-12
    )


def test_sparse_cannon_symmetric_input(mesh4):
    rbs = [3] * 8
    a = _rand("A", rbs, rbs, 0.5, 14, matrix_type="S")
    b = _rand("B", rbs, rbs, 0.5, 15)
    c = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh4)
    np.testing.assert_allclose(
        to_dense(c), to_dense(a) @ to_dense(b), rtol=1e-12, atol=1e-12
    )


def test_sparse_cannon_symmetric_c_input(mesh4):
    """Regression: a symmetric C operand must contribute its full dense
    content (both triangles) to beta*C."""
    rbs = [3] * 8
    a = _rand("A", rbs, rbs, 0.5, 16)
    b = _rand("B", rbs, rbs, 0.5, 17)
    c0 = _rand("C", rbs, rbs, 0.4, 18, matrix_type="S")
    c = sparse_multiply_distributed(1.0, a, b, 1.0, c0, mesh4)
    want = to_dense(a) @ to_dense(b) + to_dense(c0)
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)


def test_sparse_cannon_rejects_bad_blocking(mesh4):
    a = _rand("A", [3] * 8, [3] * 8, 0.5, 19)
    b = _rand("B", [3] * 8, [3] * 8, 0.5, 20)
    c_bad = _rand("C", [3] * 8, [4] * 6, 0.5, 21)
    with pytest.raises(ValueError):
        sparse_multiply_distributed(1.0, a, b, 1.0, c_bad, mesh4)


def test_image_distribution_invariants():
    from dbcsr_tpu.parallel import ImageDistribution, make_image_dist

    d = ImageDistribution(3, 2)
    assert d.nimages == 6
    blks = np.arange(25)
    layer, phys = d.split(blks)
    assert phys.max() < 3 and layer.max() < 2
    # every block maps to exactly one image; images partition the blocks
    seen = np.concatenate([d.blocks_of_image(v, 25) for v in range(6)])
    assert sorted(seen.tolist()) == list(range(25))
    np.testing.assert_array_equal(d.image_of(blks), layer * 3 + phys)
    # lcm pairing: a 2-wide axis meets a 3-wide partner on 6 images
    pair = make_image_dist(2, 3)
    assert pair.nimages == 6 and pair.multiplicity == 3


def test_comm_statistics_recorded(mesh8):
    from dbcsr_tpu.core import stats

    stats.reset()
    rbs = [4] * 8
    a = _rand("A", rbs, rbs, 0.5, 30)
    b = _rand("B", rbs, rbs, 0.5, 31)
    sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh8)
    lines = []
    stats.print_statistics(out=lines.append)
    joined = "\n".join(lines)
    assert "ppermute" in joined and "host2dev" in joined
    stats.reset()


@pytest.mark.slow
def test_sparse_cannon_honors_distribution(mesh8):
    """Checksum invariance across 3 different Distributions of the same
    matrices (ref `dbcsr_distribution_new` arbitrary maps,
    `dbcsr_dist_methods.F:49`)."""
    from dbcsr_tpu.core.dist import Distribution, ProcessGrid, dist_bin, random_dist
    from dbcsr_tpu.ops.transformations import redistribute

    s = mesh8.shape["pr"]
    rbs = list(np.random.default_rng(0).choice([3, 5], 12))
    a = _rand("A", rbs, rbs, 0.4, 20)
    b = _rand("B", rbs, rbs, 0.4, 21)
    want = to_dense(a) @ to_dense(b)

    grid = ProcessGrid(s, s, mesh8)
    n = len(rbs)
    dists = [
        None,  # default cyclic
        Distribution(random_dist(n, s, seed=1), random_dist(n, s, seed=2), grid),
        Distribution(
            dist_bin(n, s, element_sizes=np.asarray(rbs)),
            dist_bin(n, s, element_sizes=np.asarray(rbs)[::-1].copy()),
            grid,
        ),
    ]
    sums = []
    for d in dists:
        ad = redistribute(a, d) if d is not None else a
        bd = redistribute(b, d) if d is not None else b
        c = sparse_multiply_distributed(1.0, ad, bd, 0.0, None, mesh8)
        np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)
        sums.append(checksum(c))
    assert sums[0] == sums[1] == sums[2]


@pytest.mark.slow
def test_sparse_cannon_filter_eps_matches_single_chip(mesh8):
    from dbcsr_tpu import multiply

    rbs = [4] * 12
    a = _rand("A", rbs, rbs, 0.5, 22)
    b = _rand("B", rbs, rbs, 0.5, 23)
    eps = 2.0  # aggressive: actually drops blocks
    c_mesh = sparse_multiply_distributed(
        1.0, a, b, 0.0, None, mesh8, filter_eps=eps
    )
    c_host = _rand("C", rbs, rbs, 0.0, 24)
    multiply("N", "N", 1.0, a, b, 0.0, c_host, filter_eps=eps)
    assert len(c_mesh.keys) < 12 * 12  # filtering did something
    np.testing.assert_array_equal(c_mesh.keys, c_host.keys)
    np.testing.assert_allclose(
        to_dense(c_mesh), to_dense(c_host), rtol=1e-12, atol=1e-12
    )


def test_sparse_cannon_retain_sparsity_matches_single_chip(mesh8):
    from dbcsr_tpu import multiply

    rbs = [4] * 10
    a = _rand("A", rbs, rbs, 0.5, 25)
    b = _rand("B", rbs, rbs, 0.5, 26)
    c0 = _rand("C", rbs, rbs, 0.25, 27)
    c_mesh = sparse_multiply_distributed(
        1.0, a, b, 0.5, c0, mesh8, retain_sparsity=True
    )
    c_host = c0.copy()
    multiply("N", "N", 1.0, a, b, 0.5, c_host, retain_sparsity=True)
    np.testing.assert_array_equal(c_mesh.keys, c_host.keys)
    np.testing.assert_allclose(
        to_dense(c_mesh), to_dense(c_host), rtol=1e-12, atol=1e-12
    )


@pytest.mark.slow
def test_tas_grouped_multiply_tall_matrix(mesh8):
    """Group-parallel TAS on the mesh: per-group Cannons over 'kl' with
    the short matrix replicated (ref dbcsr_tas_mm.F:79-806).  Traffic
    must shrink vs the ungrouped engine (no psum of the long C) and the
    result must match exactly."""
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.parallel import tas_grouped_multiply

    rbs = [4] * 48  # tall: 48 block rows
    kbs = [4] * 6   # short k
    cbs = [4] * 6
    a = _rand("A", rbs, kbs, 0.3, 31)
    b = _rand("B", kbs, cbs, 0.6, 32)
    want = to_dense(a) @ to_dense(b)

    stats.reset()
    c_grp = tas_grouped_multiply(1.0, a, b, 0.0, None, mesh8)
    grp_bytes = sum(
        st.nbytes for k, st in stats._comm.items() if k in ("ppermute", "psum")
    )
    stats.reset()
    c_ungrp = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh8)
    ungrp_bytes = sum(
        st.nbytes for k, st in stats._comm.items() if k in ("ppermute", "psum")
    )
    np.testing.assert_allclose(to_dense(c_grp), want, rtol=1e-12, atol=1e-12)
    # two different (both deterministic) algorithms: equal to rounding
    assert np.isclose(checksum(c_grp), checksum(c_ungrp), rtol=1e-12)
    assert grp_bytes < ungrp_bytes, (grp_bytes, ungrp_bytes)


@pytest.mark.slow
def test_tas_grouped_nsplit_decoupled_from_kl(mesh8):
    """nsplit=8 on a kl=2 mesh runs 8 distinct groups (kl position x
    in-slot chunk) and matches the oracle exactly — the computed nsplit
    is honored independent of the physical grid
    (ref `dbcsr_tas_split.F:207-304`)."""
    from dbcsr_tpu.parallel import tas_grouped_multiply

    assert mesh8.shape["kl"] == 2
    rbs = [4] * 64
    kbs = [4] * 5
    a = _rand("A", rbs, kbs, 0.35, 70)
    b = _rand("B", kbs, kbs, 0.7, 71)
    want = to_dense(a) @ to_dense(b)
    for nsplit in (1, 2, 3, 8):
        c = tas_grouped_multiply(1.0, a, b, 0.0, None, mesh8, nsplit=nsplit)
        assert c._tas_ngroups == nsplit, (nsplit, c._tas_ngroups)
        np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)
    # beta-accumulate through the chunked layout too
    c0 = _rand("C", rbs, kbs, 0.2, 72)
    c = tas_grouped_multiply(2.0, a, b, 0.5, c0, mesh8, nsplit=8)
    np.testing.assert_allclose(
        to_dense(c), 2.0 * want + 0.5 * to_dense(c0), rtol=1e-12, atol=1e-12
    )


@pytest.mark.slow
def test_tas_grouped_nsplit_r_tiled(mesh8):
    """Chunked groups compose with the R-tiled stack layout (slot
    offsets + the guaranteed-zero pad row at the chunked buffer end)."""
    from dbcsr_tpu import set_config
    from dbcsr_tpu.parallel import tas_grouped_multiply

    rbs = [3, 5] * 16
    kbs = [4] * 4
    a = _rand("A", rbs, kbs, 0.4, 73)
    b = _rand("B", kbs, kbs, 0.8, 74)
    set_config(mm_driver="xla_group")
    try:
        c = tas_grouped_multiply(1.0, a, b, 0.0, None, mesh8, nsplit=6)
    finally:
        set_config(mm_driver="auto")
    assert c._tas_ngroups == 6
    np.testing.assert_allclose(
        to_dense(c), to_dense(a) @ to_dense(b), rtol=1e-12, atol=1e-12
    )


def test_tas_grouped_beta_accumulate(mesh8):
    from dbcsr_tpu.parallel import tas_grouped_multiply

    rbs = [3] * 30
    kbs = [3] * 4
    a = _rand("A", rbs, kbs, 0.4, 33)
    b = _rand("B", kbs, kbs, 0.7, 34)
    c0 = _rand("C", rbs, kbs, 0.3, 35)
    c = tas_grouped_multiply(2.0, a, b, 0.5, c0, mesh8)
    want = 2.0 * to_dense(a) @ to_dense(b) + 0.5 * to_dense(c0)
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)


def test_tas_multiply_mesh_routes_to_grouped(mesh8):
    """tas_multiply on a mesh with a tall A must produce the same result
    as the single-chip TAS path (identical checksums)."""
    from dbcsr_tpu.tas import tas_multiply

    rbs = [4] * 40
    kbs = [4] * 5
    a = _rand("A", rbs, kbs, 0.3, 37)
    b = _rand("B", kbs, kbs, 0.6, 38)
    c_mesh = _rand("Cm", rbs, kbs, 0.0, 39)
    c_host = _rand("Ch", rbs, kbs, 0.0, 39)
    f1 = tas_multiply("N", "N", 1.0, a, b, 0.0, c_mesh, mesh=mesh8)
    f2 = tas_multiply("N", "N", 1.0, a, b, 0.0, c_host)
    assert f1 == f2  # both report the true flop count of the product
    np.testing.assert_allclose(
        to_dense(c_mesh), to_dense(c_host), rtol=1e-12, atol=1e-12
    )


@pytest.mark.slow
def test_tas_grouped_column_long(mesh8):
    """n-long C goes through the transposed grouped path."""
    from dbcsr_tpu.tas import tas_multiply

    kbs = [4] * 5
    cbs = [4] * 40
    a = _rand("A", kbs, kbs, 0.6, 40)
    b = _rand("B", kbs, cbs, 0.3, 41)
    c_mesh = _rand("Cm", kbs, cbs, 0.0, 42)
    c_host = _rand("Ch", kbs, cbs, 0.0, 42)
    tas_multiply("N", "N", 1.0, a, b, 0.0, c_mesh, mesh=mesh8)
    tas_multiply("N", "N", 1.0, a, b, 0.0, c_host)
    np.testing.assert_allclose(
        to_dense(c_mesh), to_dense(c_host), rtol=1e-12, atol=1e-12
    )


@pytest.mark.slow
def test_sparse_cannon_r_tiled_stacks(mesh8):
    """mm_driver='xla_group' forces the R-tiled mesh stack layout (the
    TPU-emulation path) on any platform; results and determinism must
    match the per-entry layout."""
    from dbcsr_tpu import set_config

    rbs = [3, 5, 4] * 4
    a = _rand("A", rbs, rbs, 0.4, 41)
    b = _rand("B", rbs, rbs, 0.4, 42)
    c0 = _rand("C", rbs, rbs, 0.3, 43)
    set_config(mm_driver="xla_group")
    try:
        c_tiled = sparse_multiply_distributed(1.5, a, b, 0.5, c0.copy(), mesh8)
        cs = checksum(c_tiled)
        c_rep = sparse_multiply_distributed(1.5, a, b, 0.5, c0.copy(), mesh8)
        assert checksum(c_rep) == cs  # bit-identical repeats
    finally:
        set_config(mm_driver="auto")
    c_plain = sparse_multiply_distributed(1.5, a, b, 0.5, c0.copy(), mesh8)
    want = 1.5 * (to_dense(a) @ to_dense(b)) + 0.5 * to_dense(c0)
    np.testing.assert_allclose(to_dense(c_tiled), want, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(to_dense(c_plain), want, rtol=1e-12, atol=1e-12)


@pytest.mark.slow
def test_mesh_element_limits_unaligned_match_single_chip(mesh4):
    """Element-granular limits that do NOT align with block boundaries
    are exact on the mesh path (crop + elementwise windowed beta, ref
    `dbcsr_crop_matrix` inside make_m2s, `dbcsr_mm_cannon.F:194-220`),
    matching the single-chip engine bit-for-bit in pattern and to
    rounding in values."""
    from dbcsr_tpu import multiply

    rbs = [3, 5, 4, 6] * 2  # 36 elements, uneven boundaries
    a = _rand("A", rbs, rbs, 0.6, 90)
    b = _rand("B", rbs, rbs, 0.6, 91)
    c0 = _rand("C", rbs, rbs, 0.4, 92)
    el = (2, 31, 4, 33, 1, 30)  # 0-based inclusive, straddles blocks
    c_mesh = sparse_multiply_distributed(
        1.5, a, b, 0.5, c0, mesh4, element_limits=el
    )
    c_host = c0.copy()
    multiply("N", "N", 1.5, a, b, 0.5, c_host, element_limits=el)
    np.testing.assert_allclose(
        to_dense(c_mesh), to_dense(c_host), rtol=1e-12, atol=1e-12
    )
    # repeats are bit-identical (plan + elementwise window cached)
    c_rep = sparse_multiply_distributed(
        1.5, a, b, 0.5, c0, mesh4, element_limits=el
    )
    assert checksum(c_rep) == checksum(c_mesh)


@pytest.mark.slow
def test_mesh_element_limits_k_window(mesh4):
    """A k-only element window (crops both operands, no beta window)."""
    from dbcsr_tpu import multiply

    rbs = [4, 3, 5] * 3
    a = _rand("A", rbs, rbs, 0.5, 93)
    b = _rand("B", rbs, rbs, 0.5, 94)
    el = (None, None, None, None, 2, 26)
    c_mesh = sparse_multiply_distributed(
        1.0, a, b, 0.0, None, mesh4, element_limits=el
    )
    c_host = _rand("Ch", rbs, rbs, 0.0, 95)
    multiply("N", "N", 1.0, a, b, 0.0, c_host, element_limits=el)
    np.testing.assert_allclose(
        to_dense(c_mesh), to_dense(c_host), rtol=1e-12, atol=1e-12
    )


def test_mesh_residency_no_restaging(mesh8):
    """A second same-pattern mesh multiply must upload NOTHING: the plan
    (stacks + index maps) is pattern-cached and the panels are cached by
    bin data identity (the rank-resident data-area analog,
    `dbcsr_types.F:363-461` / mempools `dbcsr_mem_methods.F`)."""
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.parallel.sparse_dist import clear_mesh_plans

    clear_mesh_plans()
    rbs = [4] * 10
    a = _rand("A", rbs, rbs, 0.4, 50)
    b = _rand("B", rbs, rbs, 0.4, 51)
    stats.reset()
    c1 = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh8)
    assert stats._comm["host2dev"].nbytes > 0  # plan build uploads indices
    stats.reset()
    c2 = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh8)
    assert stats._comm["host2dev"].nbytes == 0  # fully resident repeat
    assert checksum(c1) == checksum(c2)
    stats.reset()
    clear_mesh_plans()


def test_mesh_residency_data_change_same_pattern(mesh8):
    """Changing operand VALUES (same pattern) must reassemble panels on
    device — the plan cache may hit but the data-identity panel cache
    must miss — and still upload nothing from host."""
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.parallel.sparse_dist import clear_mesh_plans

    clear_mesh_plans()
    rbs = [3] * 9
    a = _rand("A", rbs, rbs, 0.5, 52)
    b = _rand("B", rbs, rbs, 0.5, 53)
    c1 = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh8)
    a.map_bin_data(lambda d: 2.0 * d)  # values change, pattern unchanged
    stats.reset()
    c2 = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh8)
    assert stats._comm["host2dev"].nbytes == 0
    np.testing.assert_allclose(
        to_dense(c2), 2.0 * to_dense(c1), rtol=1e-12, atol=1e-12
    )
    stats.reset()
    clear_mesh_plans()


def test_mesh_residency_c_feedback_loop(mesh8):
    """SCF-style loop: C feeds back as the accumulate operand.  After
    the pattern converges (rep 2), further reps are fully resident."""
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.parallel.sparse_dist import clear_mesh_plans

    clear_mesh_plans()
    rbs = [4] * 8
    a = _rand("A", rbs, rbs, 0.5, 54)
    b = _rand("B", rbs, rbs, 0.5, 55)
    c = None
    dense_c = np.zeros((sum(rbs), sum(rbs)))
    for rep in range(4):
        c = sparse_multiply_distributed(1.0, a, b, 0.5, c, mesh8)
        dense_c = to_dense(a) @ to_dense(b) + 0.5 * dense_c
        if rep == 3:
            stats.reset()
            c = sparse_multiply_distributed(1.0, a, b, 0.5, c, mesh8)
            dense_c = to_dense(a) @ to_dense(b) + 0.5 * dense_c
            assert stats._comm["host2dev"].nbytes == 0
    np.testing.assert_allclose(to_dense(c), dense_c, rtol=1e-12, atol=1e-12)
    stats.reset()
    clear_mesh_plans()


@pytest.mark.slow
def test_sparse_cannon_complex128(mesh8):
    """c128 with complex alpha/beta through the mesh Cannon (CPU
    backend; the chip rejects C128) vs the dense oracle, incl. a
    Hermitian operand (ref `dbcsr_unittest1.F` complex type coverage)."""
    rbs = [3, 4] * 5
    rng = np.random.default_rng(80)
    a = make_random_matrix("A", rbs, rbs, dtype=np.complex128,
                           occupation=0.4, rng=rng)
    b = make_random_matrix("B", rbs, rbs, dtype=np.complex128,
                           occupation=0.4, rng=rng, matrix_type="H")
    c0 = make_random_matrix("C", rbs, rbs, dtype=np.complex128,
                            occupation=0.3, rng=rng)
    alpha, beta = 1.5 - 0.5j, 0.25 + 1.0j
    c = sparse_multiply_distributed(alpha, a, b, beta, c0, mesh8)
    want = alpha * (to_dense(a) @ to_dense(b)) + beta * to_dense(c0)
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)
    # determinism with complex data
    c2 = sparse_multiply_distributed(alpha, a, b, beta, c0, mesh8)
    assert checksum(c) == checksum(c2)


@pytest.mark.slow
def test_sparse_cannon_complex128_r_tiled(mesh8):
    """c128 through the R-tiled (r0) mesh layout — mm_driver='xla_group'
    forces on CPU the layout auto mode would pick for c128 on TPU
    (`_stack_r0`); previously untested on any backend with complex
    data."""
    from dbcsr_tpu import set_config

    rbs = [3, 5, 4] * 3
    rng = np.random.default_rng(81)
    a = make_random_matrix("A", rbs, rbs, dtype=np.complex128,
                           occupation=0.45, rng=rng)
    b = make_random_matrix("B", rbs, rbs, dtype=np.complex128,
                           occupation=0.45, rng=rng)
    c0 = make_random_matrix("C", rbs, rbs, dtype=np.complex128,
                            occupation=0.3, rng=rng)
    alpha, beta = -0.5 + 2.0j, 0.5 - 0.25j
    set_config(mm_driver="xla_group")
    try:
        c_tiled = sparse_multiply_distributed(alpha, a, b, beta, c0, mesh8)
        cs = checksum(c_tiled)
        c_rep = sparse_multiply_distributed(alpha, a, b, beta, c0, mesh8)
        assert checksum(c_rep) == cs  # bit-identical repeats
    finally:
        set_config(mm_driver="auto")
    want = alpha * (to_dense(a) @ to_dense(b)) + beta * to_dense(c0)
    np.testing.assert_allclose(to_dense(c_tiled), want, rtol=1e-12, atol=1e-12)
    # grouped TAS with complex + r0 as well
    from dbcsr_tpu.parallel import tas_grouped_multiply

    set_config(mm_driver="xla_group")
    try:
        c_grp = tas_grouped_multiply(alpha, a, b, 0.0, None, mesh8, nsplit=4)
    finally:
        set_config(mm_driver="auto")
    np.testing.assert_allclose(
        to_dense(c_grp), alpha * (to_dense(a) @ to_dense(b)),
        rtol=1e-12, atol=1e-12,
    )


def test_mesh_dense_mode_high_fill_routes_dense(mesh8):
    """High-fill products on the mesh route through the dense 2.5D
    Cannon (the parallel-driver make_dense gate, `dbcsr_mm.F:593-617`)
    and match the stack path exactly in pattern-union terms."""
    from dbcsr_tpu import set_config

    rbs = [4] * 8
    a = _rand("A", rbs, rbs, 0.95, 60)
    b = _rand("B", rbs, rbs, 0.95, 61)
    c0 = _rand("C", rbs, rbs, 0.3, 62)
    # occupation >= DENSE_OCC_THRESHOLD (0.8) routes dense on any platform
    c_dense = sparse_multiply_distributed(1.5, a, b, 0.5, c0, mesh8)
    assert c_dense._mm_algorithm == "dense"
    set_config(mm_format="stack")
    try:
        c_stack = sparse_multiply_distributed(1.5, a, b, 0.5, c0, mesh8)
    finally:
        set_config(mm_format="auto")
    assert c_stack._mm_algorithm == "stack"
    want = 1.5 * (to_dense(a) @ to_dense(b)) + 0.5 * to_dense(c0)
    np.testing.assert_allclose(to_dense(c_dense), want, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(to_dense(c_stack), want, rtol=1e-12, atol=1e-12)
    # true-flop reporting is algorithm-independent (marketing vs true,
    # dbcsr_mm.F:664-667)
    assert c_dense._last_flops == c_stack._last_flops


@pytest.mark.slow
def test_mesh_dense_mode_mixed_blockings(mesh4):
    """Non-uniform blockings run the general canvas path under the mesh
    dense Cannon (padded to grid divisibility)."""
    from dbcsr_tpu import set_config

    rng = np.random.default_rng(63)
    rbs = list(rng.choice([3, 5], 7))
    kbs = list(rng.choice([2, 4], 6))
    cbs = list(rng.choice([3, 6], 5))
    a = _rand("A", rbs, kbs, 0.9, 64)
    b = _rand("B", kbs, cbs, 0.9, 65)
    set_config(mm_format="dense")
    try:
        c = sparse_multiply_distributed(-2.0, a, b, 0.0, None, mesh4)
    finally:
        set_config(mm_format="auto")
    assert c._mm_algorithm == "dense"
    np.testing.assert_allclose(
        to_dense(c), -2.0 * (to_dense(a) @ to_dense(b)), rtol=1e-12, atol=1e-12
    )


def test_mesh_dense_mode_never_on_filtered_products(mesh4):
    """filter_eps / retain_sparsity / limits keep the stack path (dense
    mode must not silently densify a filtered C)."""
    rbs = [4] * 8
    a = _rand("A", rbs, rbs, 0.95, 66)
    b = _rand("B", rbs, rbs, 0.95, 67)
    c = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh4, filter_eps=1e-8)
    assert c._mm_algorithm == "stack"
    c0 = _rand("C", rbs, rbs, 0.3, 68)
    c2 = sparse_multiply_distributed(
        1.0, a, b, 1.0, c0, mesh4, retain_sparsity=True
    )
    assert c2._mm_algorithm == "stack"


@pytest.mark.parametrize("case", [
    "format_stack", "format_dense", "format_auto",
    "gate_filter", "gate_retain_sparsity", "gate_limits", "gate_symmetric_c",
])
def test_mesh_and_one_chip_ask_one_decider(mesh4, case):
    """`multiply` and `sparse_multiply_distributed` take the format from
    the same `mm.format_planner.choose`: every spelling of `mm_format`
    and every structural gate gives the same `_mm_algorithm` on both.
    The operands are dense-eligible (occupancy over
    `DENSE_OCC_THRESHOLD`), so it is the gate or the force that
    decides."""
    from dbcsr_tpu import multiply, set_config
    from dbcsr_tpu.mm import format_planner as fp

    rbs = [4] * 8
    a = _rand("A", rbs, rbs, 0.95, 70)
    b = _rand("B", rbs, rbs, 0.95, 71)
    kw = {}
    c_type = "N"
    want = "stack"
    if case.startswith("format_"):
        fmt = case[len("format_"):]
        want = "stack" if fmt == "stack" else "dense"
    else:
        fmt = "auto"
        if case == "gate_filter":
            kw["filter_eps"] = 1e-8
        elif case == "gate_retain_sparsity":
            kw["retain_sparsity"] = True
        elif case == "gate_limits":
            kw.update(first_row=1, last_row=5)
        else:
            # A*A^T: a product that a symmetric C can hold
            from dbcsr_tpu.ops.transformations import new_transposed

            c_type, b = "S", new_transposed(a)

    def c_in():
        return _rand("C", rbs, rbs, 0.4, 72, matrix_type=c_type)

    set_config(mm_format=fmt)
    fp.reset()
    try:
        c_one = c_in()
        multiply("N", "N", 1.0, a, b, 1.0, c_one, **kw)
        c_mesh = sparse_multiply_distributed(1.0, a, b, 1.0, c_in(), mesh4,
                                             **kw)
    finally:
        set_config(mm_format="auto")
        fp.reset()
    assert c_mesh._mm_algorithm == c_one._mm_algorithm == want
    np.testing.assert_allclose(to_dense(c_mesh), to_dense(c_one),
                               rtol=1e-12, atol=1e-12)


def test_sparse_cannon_r_tiled_filtering(mesh8):
    """R-tiled layout + on-the-fly filtering/retain_sparsity agree with
    the single-chip engine."""
    from dbcsr_tpu import create, multiply, set_config

    rbs = [4] * 10
    a = _rand("A", rbs, rbs, 0.5, 44)
    b = _rand("B", rbs, rbs, 0.5, 45)
    set_config(mm_driver="xla_group")
    try:
        c_mesh = sparse_multiply_distributed(
            1.0, a, b, 0.0, None, mesh8, filter_eps=0.5
        )
    finally:
        set_config(mm_driver="auto")
    c_ref = create("c", rbs, rbs)
    multiply("N", "N", 1.0, a, b, 0.0, c_ref, filter_eps=0.5)
    assert np.array_equal(c_mesh.keys, c_ref.keys)
    np.testing.assert_allclose(to_dense(c_mesh), to_dense(c_ref),
                               rtol=1e-12, atol=1e-12)


def test_tas_grouped_residency_no_restaging(mesh8):
    """The grouped TAS path is rank-resident too: a repeated
    same-pattern grouped multiply uploads nothing."""
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.parallel import tas_grouped_multiply
    from dbcsr_tpu.parallel.sparse_dist import clear_mesh_plans

    clear_mesh_plans()
    rbs = [4] * 32
    kbs = [4] * 4
    a = _rand("A", rbs, kbs, 0.4, 96)
    b = _rand("B", kbs, kbs, 0.7, 97)
    c1 = tas_grouped_multiply(1.0, a, b, 0.0, None, mesh8, nsplit=4)
    stats.reset()
    c2 = tas_grouped_multiply(1.0, a, b, 0.0, None, mesh8, nsplit=4)
    assert stats._comm["host2dev"].nbytes == 0
    assert checksum(c1) == checksum(c2)
    stats.reset()
    clear_mesh_plans()


# ---------------------------------------------------------------------------
# Rectangular grids (all-gather engine; ref arbitrary nprows x npcols
# grids via image distributions, dbcsr_types.F:188-223,
# dbcsr_mm_dist_operations.F:58)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh6():
    return make_grid(6)  # (kl=1, pr=2, pc=3)


def test_rect_grid_shapes():
    assert dict(make_grid(6).shape) == {"kl": 1, "pr": 2, "pc": 3}
    assert dict(make_grid(8, layers=1).shape) == {"kl": 1, "pr": 2, "pc": 4}


@pytest.mark.slow
def test_rect_sparse_multiply_mixed_blocks(mesh6):
    rng = np.random.default_rng(61)
    rbs = rng.choice([2, 3, 5], 11)
    kbs = rng.choice([4, 2], 9)
    cbs = rng.choice([3, 6], 13)
    a = _rand("A", rbs, kbs, 0.4, 62)
    b = _rand("B", kbs, cbs, 0.4, 63)
    c = sparse_multiply_distributed(-0.5, a, b, 0.0, None, mesh6)
    np.testing.assert_allclose(
        to_dense(c), -0.5 * (to_dense(a) @ to_dense(b)), rtol=1e-12, atol=1e-12
    )


@pytest.mark.slow
def test_rect_8dev_one_layer_beta():
    mesh = make_grid(8, layers=1)  # (1, 2, 4)
    rbs = [3] * 9
    a = _rand("A", rbs, rbs, 0.5, 64)
    b = _rand("B", rbs, rbs, 0.5, 65)
    c0 = _rand("C", rbs, rbs, 0.3, 66)
    c = sparse_multiply_distributed(2.0, a, b, 0.5, c0, mesh)
    want = 2.0 * to_dense(a) @ to_dense(b) + 0.5 * to_dense(c0)
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-12, atol=1e-12)


@pytest.mark.slow
def test_rect_with_k_layers():
    mesh = make_grid(6, layers=2)  # (2, 1, 3): layers + rectangular
    rbs = [4] * 8
    a = _rand("A", rbs, rbs, 0.5, 67)
    b = _rand("B", rbs, rbs, 0.5, 68)
    c = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh)
    np.testing.assert_allclose(
        to_dense(c), to_dense(a) @ to_dense(b), rtol=1e-12, atol=1e-12
    )


@pytest.mark.slow
def test_rect_r_tiled_stacks(mesh6):
    """Forced xla_group exercises the R-tiled stack layout against the
    GATHERED panel indexing (in-tile pads must hit the zero rows)."""
    from dbcsr_tpu.core.config import set_config

    rbs = [3] * 10
    a = _rand("A", rbs, rbs, 0.5, 69)
    b = _rand("B", rbs, rbs, 0.5, 70)
    set_config(mm_driver="xla_group")
    try:
        c = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh6)
    finally:
        set_config(mm_driver="auto")
    np.testing.assert_allclose(
        to_dense(c), to_dense(a) @ to_dense(b), rtol=1e-12, atol=1e-12
    )


def test_rect_filter_eps_matches_single_chip(mesh6):
    from dbcsr_tpu import create, multiply

    rbs = [4] * 9
    a = _rand("A", rbs, rbs, 0.5, 71)
    b = _rand("B", rbs, rbs, 0.5, 72)
    eps = 0.4
    c_mesh = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh6,
                                         filter_eps=eps)
    c_ref = create("Cref", rbs, rbs, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c_ref, filter_eps=eps)
    np.testing.assert_allclose(to_dense(c_mesh), to_dense(c_ref),
                               rtol=1e-12, atol=1e-12)
    assert set(map(tuple, np.argwhere(to_dense(c_mesh) != 0).tolist())) == set(
        map(tuple, np.argwhere(to_dense(c_ref) != 0).tolist())
    )


def test_rect_deterministic(mesh6):
    rbs = [4] * 10
    a = _rand("A", rbs, rbs, 0.4, 73)
    b = _rand("B", rbs, rbs, 0.4, 74)
    cks = {checksum(sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh6))
           for _ in range(3)}
    assert len(cks) == 1


def test_rect_block_limits(mesh6):
    from dbcsr_tpu import create, multiply

    rbs = [4] * 9
    a = _rand("A", rbs, rbs, 0.6, 75)
    b = _rand("B", rbs, rbs, 0.6, 76)
    c_mesh = sparse_multiply_distributed(
        1.0, a, b, 0.0, None, mesh6, first_row=2, last_row=6,
        first_col=1, last_col=7,
    )
    c_ref = create("Cref", rbs, rbs, dtype=np.float64)
    multiply("N", "N", 1.0, a, b, 0.0, c_ref, first_row=2, last_row=6,
             first_col=1, last_col=7)
    np.testing.assert_allclose(to_dense(c_mesh), to_dense(c_ref),
                               rtol=1e-12, atol=1e-12)


@pytest.mark.slow
def test_rect_complex128(mesh6):
    rbs = [3] * 8
    a = _rand("A", rbs, rbs, 0.5, 77, dtype=np.complex128)
    b = _rand("B", rbs, rbs, 0.5, 78, dtype=np.complex128)
    c = sparse_multiply_distributed(1.0 + 0.5j, a, b, 0.0, None, mesh6)
    np.testing.assert_allclose(
        to_dense(c), (1.0 + 0.5j) * (to_dense(a) @ to_dense(b)),
        rtol=1e-12, atol=1e-12,
    )


def test_rect_comm_statistics(mesh6):
    from dbcsr_tpu.core import stats

    rbs = [4] * 8
    a = _rand("A", rbs, rbs, 0.5, 79)
    b = _rand("B", rbs, rbs, 0.5, 80)
    stats.reset()
    sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh6)
    assert "all_gather" in stats._comm and stats._comm["all_gather"].nbytes > 0


@pytest.mark.parametrize("r0", [0, 8])
def test_tick_chunks_bound_temp_memory(r0):
    """Per-tick sub-chunking (the 1x1-grid memory-thrash fix).  Flat
    ticks (r0 = 0): chunk counts divide the bucket capacity exactly
    and bound rows at the entry target.  Grouped ticks (r0 = 8): the
    chunk is the one-chip engine's (`group_chunk_groups`), so a chunk
    of every plan launches at most its slots plus a step of rounding a
    width class, however many entries a tick carries."""
    from dbcsr_tpu.acc.smm import build_stacks_group_tiles, group_chunk_groups
    from dbcsr_tpu.parallel.sparse_dist import (
        _TICK_CHUNK_ENTRIES,
        _stack_chunk_groups,
        _tick_chunks,
    )
    from dbcsr_tpu.utils.rounding import bucket_size

    if r0 == 0:
        assert _stack_chunk_groups(0, 23, 23, 23, np.float64) == 0
        for n in (1, 16, 30000, 823000, 5_000_000):
            cap = bucket_size(n)
            nchunk, rows = _tick_chunks(cap)
            assert nchunk * rows == cap
            if cap > _TICK_CHUNK_ENTRIES:
                # bounded: a further halving would be possible only if
                # it broke divisibility
                assert rows <= _TICK_CHUNK_ENTRIES \
                    or cap % (nchunk * 2) != 0
            else:
                assert nchunk == 1
        assert _tick_chunks(bucket_size(823000))[1] <= 32768
        return
    # 2 048 slots a chunk at 23^3 in f64, 1 024 in c128, what one chip runs
    assert _stack_chunk_groups(8, 23, 23, 23, np.float64) == 256 \
        == group_chunk_groups(8, 23, 23, 23, 8, 30000)
    assert _stack_chunk_groups(8, 23, 23, 23, np.complex128) == 128
    rng = np.random.default_rng(5)
    for nruns in (3, 700, 40000):
        # two stacks, the second a third as full, runs of 1..12
        stack = np.repeat([0, 1], [nruns, nruns // 3])
        runs = rng.integers(1, 13, len(stack))
        seg = np.concatenate([np.arange(nruns), np.arange(nruns // 3)])
        zeros = np.zeros(int(runs.sum()), np.int32)
        tiles = build_stacks_group_tiles(
            np.repeat(stack, runs), 2, np.repeat(seg, runs), zeros, zeros,
            8, 9, 9, nruns, 256)
        chunk_slots = sum(ga.shape[2] * ga.shape[3] for ga, _, _ in tiles.tiles)
        assert chunk_slots <= 2048 + 16 * sum(tiles.widths)
        assert tiles.live[0] >= tiles.live[1] >= 1
        assert tiles.tiles[0][0].shape[1] >= tiles.live[0]


# ---------------------------------------------------------------------------
# The filtered f64 product on the 2x2 grid (the `northstar_2x2.scf_f64`
# deployment in miniature) against a dense NumPy f64 product with the
# filter applied by its definition: a C block is kept iff its Frobenius
# norm is at least eps.
# ---------------------------------------------------------------------------

_FILTER_EPS = 1e-7
# 23-blocks and a ragged 18, as 434 x 23 + 18: the north star's values,
# every block norm near its side length, nothing for the filter to drop
_NORTHSTAR_LIKE = dict(sizes=[23] * 5 + [18], occ=0.5, decay=0.0, seed=71)
# blocks of 5 and a ragged 3 that decay by 10**-3.4 a block off the
# diagonal: the on-the-fly skip leaves candidates out and whole blocks
# of C with them
_DECAYING = dict(sizes=[5] * 11 + [3], occ=0.6, decay=3.4, seed=73)
# north-star values with one block of C that cancels to 1e-9: both its
# candidates pass the skip, so only the final pass can drop it
_CANCELLING = dict(sizes=[5] * 7 + [3], occ=0.5, decay=0.0, seed=75,
                   cancel=dict(i=1, j=2, k=(3, 5), delta=1e-10))


def _run_lengths_there(n):
    """On device (0, 0) of the 2x2 grid (blocks go to devices
    cyclically; tick = k's parity there) tick 0 holds runs of r0 + 1,
    r0 and 1 candidates: C(0,0) meets all nine even k, C(0,2) eight of
    them, C(0,4) one."""
    a = np.zeros((n, n), bool)
    b = np.zeros((n, n), bool)
    a[0, :] = True
    a[3, [1, 3]] = True
    b[:, 0] = True
    b[np.arange(0, 16, 2), 2] = True   # eight even k
    b[1, 2] = b[0, 4] = True
    b[[1, 3], 1] = True
    return a, b


def _idle_devices_there(n):
    """A in even rows and even k only: the devices of grid row 1 get
    no candidate at all, and on those of row 0 every candidate falls
    into one of the two ticks."""
    rng = np.random.default_rng(77)
    a = rng.random((n, n)) < 0.7
    b = rng.random((n, n)) < 0.7
    a[1::2, :] = a[:, 1::2] = b[1::2, :] = False
    return a, b


def _unequal_live_there(n):
    """Three of A's twelve odd rows hold blocks, all of its even ones:
    at 128 slots a chunk the devices of grid row 0 run four times the
    chunks a tick of those of row 1, on runs of the same lengths."""
    rng = np.random.default_rng(79)
    a = rng.random((n, n)) < 0.8
    a[7::2, :] = False
    b = rng.random((n, n)) < 0.5
    return a, b


# the grouped tiling's corners, on north-star values (PR 33): run
# lengths around r0; devices and ticks with no candidate (`live` 0);
# chunk counts that differ by device (`mm_stack_size` 128: 16 groups of
# r0 a chunk)
_RUN_LENGTHS = dict(sizes=[3] * 17 + [2], occ=0.0, decay=0.0, seed=81,
                    there=_run_lengths_there)
_IDLE_DEVICES = dict(sizes=[4] * 9 + [3], occ=0.0, decay=0.0, seed=83,
                     there=_idle_devices_there)
_UNEQUAL_LIVE = dict(sizes=[4] * 23 + [3], occ=0.0, decay=0.0, seed=85,
                     there=_unequal_live_there, stack_size=128)
_FILTERED_CASES = {"northstar_like": _NORTHSTAR_LIKE, "decaying": _DECAYING,
                   "cancelling": _CANCELLING, "run_lengths": _RUN_LENGTHS,
                   "idle_devices": _IDLE_DEVICES,
                   "unequal_live": _UNEQUAL_LIVE}


def _draw(sizes, occ, decay, rng, there=None):
    """Dense standard-normal blocks scaled by 10**(-decay * |i - j|),
    and which blocks are there (``there`` if given; else the diagonal
    always, the others at ``occ``)."""
    n = len(sizes)
    off = np.concatenate([[0], np.cumsum(sizes)])
    dense = np.zeros((off[-1], off[-1]))
    present = np.zeros((n, n), bool)
    for i in range(n):
        for j in range(n):
            if there[i, j] if there is not None else (
                    i == j or rng.random() < occ):
                present[i, j] = True
                dense[off[i]:off[i + 1], off[j]:off[j + 1]] = (
                    rng.standard_normal((sizes[i], sizes[j]))
                    * 10.0 ** (-decay * abs(i - j)))
    return dense, present, off


def _stage(name, dense, present, sizes, off):
    from dbcsr_tpu import create

    m = create(name, sizes, sizes, "float64")
    for i, j in zip(*np.nonzero(present)):
        m.put_block(i, j, dense[off[i]:off[i + 1], off[j]:off[j + 1]])
    return m.finalize()


def _block_norms(dense, off):
    n = len(off) - 1
    return np.array([[np.linalg.norm(dense[off[i]:off[i + 1],
                                           off[j]:off[j + 1]])
                      for j in range(n)] for i in range(n)])


@pytest.fixture(scope="module", params=sorted(_FILTERED_CASES))
def filtered_case(request):
    case = _FILTERED_CASES[request.param]
    sizes = case["sizes"]
    rng = np.random.default_rng(case["seed"])
    there = case["there"](len(sizes)) if "there" in case else (None, None)
    a_dense, a_there, off = _draw(sizes, case["occ"], case["decay"], rng,
                                  there[0])
    b_dense, b_there, _ = _draw(sizes, case["occ"], case["decay"], rng,
                                there[1])
    if "cancel" in case:
        # C[i, j] = Y X - Y X (1 - delta): column j of B holds X and
        # -X (1 - delta) and nothing else, row i of A holds Y twice
        i, j, (k1, k2), delta = (case["cancel"][key]
                                 for key in ("i", "j", "k", "delta"))

        def blk(r, c):
            return slice(off[r], off[r + 1]), slice(off[c], off[c + 1])

        b_there[:, j] = False
        b_dense[:, off[j]:off[j + 1]] = 0.0
        b_there[[k1, k2], j] = a_there[i, [k1, k2]] = True
        b_dense[blk(k1, j)] = rng.standard_normal((sizes[k1], sizes[j]))
        b_dense[blk(k2, j)] = -(1.0 - delta) * b_dense[blk(k1, j)]
        a_dense[blk(i, k1)] = rng.standard_normal((sizes[i], sizes[k1]))
        a_dense[blk(i, k2)] = a_dense[blk(i, k1)]
    a = _stage("A", a_dense, a_there, sizes, off)
    b = _stage("B", b_dense, b_there, sizes, off)
    return request.param, a, b, a_dense, b_dense, off


def _filtered_on_mesh(a, b, mesh, overlap="auto", case=None):
    """The product as the deployment runs it on a TPU: r0 = 8 grouped
    stacks (forced here, where f64 is native), `filter_eps` 1e-7; a
    case may shrink the chunk (`mm_stack_size`)."""
    from dbcsr_tpu import get_config, set_config

    cfg = get_config()
    prev = dict(mm_driver=cfg.mm_driver, cannon_overlap=cfg.cannon_overlap,
                mm_stack_size=cfg.mm_stack_size)
    set_config(mm_driver="xla_group", cannon_overlap=overlap,
               mm_stack_size=_FILTERED_CASES.get(case, {}).get(
                   "stack_size", cfg.mm_stack_size))
    try:
        return sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh,
                                           filter_eps=_FILTER_EPS)
    finally:
        set_config(**prev)


def _plan_lookups():
    """`dbcsr_tpu_mesh_plan_total` by its ``cache`` label."""
    from dbcsr_tpu.obs import metrics

    got = {"hit": 0, "miss": 0, "uncacheable": 0}
    got.update((labels["cache"], v) for labels, v in
               metrics.counter_items("dbcsr_tpu_mesh_plan_total"))
    return got


def test_filtered_product_on_2x2_matches_numpy_with_the_filter_by_definition(
        mesh4, filtered_case):
    name, a, b, a_dense, b_dense, off = filtered_case
    n = len(off) - 1
    want = a_dense @ b_dense
    norms = _block_norms(want, off)
    kept = norms >= _FILTER_EPS
    # what the on-the-fly skip may leave out of block (i, j): a
    # candidate (i, k, j) is skipped iff |A_ik| |B_kj| < eps / n_i, n_i
    # the blocks of A's row i (`mm.multiply._candidates`), so the
    # skipped products of one C block sum to less than eps in norm, and
    # to exactly `skipped` here
    na, nb = _block_norms(a_dense, off), _block_norms(b_dense, off)
    pair = na[:, :, None] * nb[None, :, :]
    row_eps = _FILTER_EPS / np.maximum(1, (na > 0).sum(axis=1))
    skipped = np.where((pair > 0) & (pair < row_eps[:, None, None]),
                       pair, 0.0).sum(axis=1)
    assert skipped.max() < _FILTER_EPS
    # the data leaves a gap around eps, so that neither rounding nor
    # the skipped products can move a block across it
    gap = (norms < _FILTER_EPS / 10) | (norms > _FILTER_EPS * 10)
    assert gap.all() and skipped.max() < _FILTER_EPS / 10
    reached = (norms > 0).sum()
    if name == "decaying":
        assert 0 < kept.sum() < reached and skipped.max() > 0.0
    elif name == "cancelling":
        # the one block that cancels, and nothing was skipped for it
        assert kept.sum() == reached - 1 and skipped.max() == 0.0
        assert 0 < norms[1, 2] < _FILTER_EPS / 10
    else:
        # north-star values: every block the product reaches is kept,
        # no candidate skipped
        assert kept.sum() == reached > 0 and skipped.max() == 0.0
        assert name != "northstar_like" or reached > n

    c = _filtered_on_mesh(a, b, mesh4, case=name)
    rows, cols = c.entry_coords()
    got_kept = np.zeros((n, n), bool)
    got_kept[rows, cols] = True
    np.testing.assert_array_equal(got_kept, kept)
    got = to_dense(c)
    assert got.dtype == np.float64
    # f64 rounding of a k = 133 (or 58) dot, relative to max|C|: a
    # product computed in f32 misses it by six orders
    round_off = 1e-13 * np.abs(want).max()
    for i, j in zip(*np.nonzero(kept)):
        blk = (slice(off[i], off[i + 1]), slice(off[j], off[j + 1]))
        err = np.linalg.norm(got[blk] - want[blk])
        assert err <= skipped[i, j] + round_off * got[blk].size ** 0.5, \
            (i, j, err, skipped[i, j])
    for i, j in zip(*np.nonzero(~kept)):
        assert not got[off[i]:off[i + 1], off[j]:off[j + 1]].any()


def test_filtered_serial_and_double_buffered_ticks_are_bit_identical(
        mesh4, filtered_case):
    name, a, b, *_ = filtered_case
    by_mode = {mode: _filtered_on_mesh(a, b, mesh4, overlap=mode, case=name)
               for mode in ("serial", "double_buffer")}
    serial, db = by_mode["serial"], by_mode["double_buffer"]
    np.testing.assert_array_equal(serial.keys, db.keys)
    assert checksum(serial) == checksum(db)
    assert np.array_equal(to_dense(serial), to_dense(db))


def _spy_plan_builds(monkeypatch) -> list:
    """Every `_build_mesh_plan` run from now on, one entry each."""
    from dbcsr_tpu.parallel import sparse_dist as sd

    runs, build = [], sd._build_mesh_plan

    def spy(*args, **kw):
        runs.append(1)
        return build(*args, **kw)

    monkeypatch.setattr(sd, "_build_mesh_plan", spy)
    return runs


def test_second_filtered_product_hits_the_plan_and_compiles_nothing(
        mesh4, filtered_case, monkeypatch):
    import jax
    import jax.monitoring as mon

    from dbcsr_tpu.core import timings
    from dbcsr_tpu.obs import flight

    name, a, b, *_ = filtered_case
    compiles = []

    def listener(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    def spans():
        st = timings._stats.get("mesh_plan_build")
        return st.calls if st else 0

    first = _filtered_on_mesh(a, b, mesh4, case=name)
    runs = _spy_plan_builds(monkeypatch)
    mon.register_event_duration_secs_listener(listener)
    try:
        before, opened = _plan_lookups(), spans()
        second = _filtered_on_mesh(a, b, mesh4, case=name)
        jax.block_until_ready([bn.data for bn in second.bins])
    finally:
        mon.unregister_event_duration_listener(listener)
    after = _plan_lookups()
    # the same operands reach the same survivors: the plan keyed by the
    # patterns and their digest is found, and only the key is paid
    assert {k: after[k] - before[k] for k in after} == \
        {"hit": 1, "miss": 0, "uncacheable": 0}
    assert spans() == opened + 1 and runs == []
    assert flight.records()[-1]["plan_cache"] == "hit"
    assert compiles == []
    np.testing.assert_array_equal(first.keys, second.keys)
    assert checksum(first) == checksum(second)
    assert np.array_equal(to_dense(first), to_dense(second))


def test_filtered_product_of_new_values_misses_and_keeps_the_filter(
        mesh4, monkeypatch):
    """The same patterns, values that decay faster: the norm skip keeps
    other survivors, so the digest differs, the plan is built anew, and
    the product is NumPy's under the filter's definition (blocks under
    eps dropped, skipped candidates summing under eps a block)."""
    sizes = [4] * 10
    rng = np.random.default_rng(91)
    a_dense, there_a, off = _draw(sizes, 0.5, 1.0, rng)
    b_dense, there_b, _ = _draw(sizes, 0.5, 1.0, rng)
    a = _stage("A", a_dense, there_a, sizes, off)
    b = _stage("B", b_dense, there_b, sizes, off)
    a2_dense = a_dense * 1e-3
    a2 = _stage("A", a2_dense, there_a, sizes, off)
    assert a2.pattern_fingerprint() == a.pattern_fingerprint()
    _filtered_on_mesh(a, b, mesh4)
    runs = _spy_plan_builds(monkeypatch)
    before = _plan_lookups()
    c = _filtered_on_mesh(a2, b, mesh4)
    after = _plan_lookups()
    assert {k: after[k] - before[k] for k in after} == \
        {"hit": 0, "miss": 1, "uncacheable": 0}
    assert runs == [1]
    want = a2_dense @ b_dense
    na, nb = _block_norms(a2_dense, off), _block_norms(b_dense, off)
    pair = na[:, :, None] * nb[None, :, :]
    row_eps = _FILTER_EPS / np.maximum(1, (na > 0).sum(axis=1))
    skipped = np.where((pair > 0) & (pair < row_eps[:, None, None]),
                       pair, 0.0).sum(axis=1)
    # other survivors than the first product's
    first_pair = _block_norms(a_dense, off)[:, :, None] * nb[None, :, :]
    first_eps = _FILTER_EPS / np.maximum(1, (na > 0).sum(axis=1))
    assert ((first_pair < first_eps[:, None, None])
            != (pair < row_eps[:, None, None])).any()
    got = to_dense(c)
    rows, cols = c.entry_coords()
    norms = _block_norms(want, off)
    # f64 rounding of a k = 40 dot, a block of 16 elements
    slack = 1e-13 * np.abs(want).max() * 4
    for i, j in zip(rows, cols):
        blk = (slice(off[i], off[i + 1]), slice(off[j], off[j + 1]))
        assert np.linalg.norm(got[blk] - want[blk]) <= skipped[i, j] + slack
        assert norms[i, j] >= _FILTER_EPS - skipped[i, j] - slack
    kept = np.zeros((10, 10), bool)
    kept[rows, cols] = True
    assert 0 < kept.sum() < (norms > 0).sum()
    for i, j in zip(*np.nonzero(~kept)):
        assert norms[i, j] < _FILTER_EPS + skipped[i, j] + slack
        assert not got[off[i]:off[i + 1], off[j]:off[j + 1]].any()


def test_a_hit_shares_no_mutable_state_with_its_result(mesh4, monkeypatch):
    """The filter drops blocks from a product whose plan was cached;
    the plan's C keys, binning and maps are what they were, and a third
    product from the same plan is the second bit for bit."""
    from dbcsr_tpu.parallel import sparse_dist as sd

    sizes = [4] * 12
    rng = np.random.default_rng(93)
    a_dense, there_a, off = _draw(sizes, 0.5, 1.5, rng)
    b_dense, there_b, _ = _draw(sizes, 0.5, 1.5, rng)
    a = _stage("A", a_dense, there_a, sizes, off)
    b = _stage("B", b_dense, there_b, sizes, off)
    sd.clear_mesh_plans()
    first = _filtered_on_mesh(a, b, mesh4)
    (plan,) = sd._mesh_plan_cache.values()
    # the filter dropped blocks of C: the plan holds more than the result
    assert first.nblks < len(plan.c_keys)
    held = {
        "c_keys": plan.c_keys.copy(),
        "c_binning": tuple(np.array(x, copy=True) for x in plan.c_binning[:2]),
        "collect": [np.asarray(x).copy() for x in plan.collect_own
                    + plan.collect_perm],
        "a_asm": [np.asarray(x).copy() for x in plan.a_asm.flat_pos
                  + plan.a_asm.src_slots],
    }
    runs = _spy_plan_builds(monkeypatch)
    second = _filtered_on_mesh(a, b, mesh4)
    third = _filtered_on_mesh(a, b, mesh4)
    assert runs == []
    assert [id(p) for p in sd._mesh_plan_cache.values()] == [id(plan)]
    assert second.nblks == first.nblks < len(plan.c_keys)
    np.testing.assert_array_equal(plan.c_keys, held["c_keys"])
    for got, want in zip(plan.c_binning[:2], held["c_binning"]):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(plan.collect_own + plan.collect_perm,
                         held["collect"]):
        np.testing.assert_array_equal(np.asarray(got), want)
    for got, want in zip(plan.a_asm.flat_pos + plan.a_asm.src_slots,
                         held["a_asm"]):
        np.testing.assert_array_equal(np.asarray(got), want)
    for c in (second, third):
        np.testing.assert_array_equal(c.keys, first.keys)
        assert checksum(c) == checksum(first)
        assert np.array_equal(to_dense(c), to_dense(first))


def test_filtered_plan_tiles_every_device_and_tick_by_its_own_runs(
        mesh4, filtered_case, monkeypatch):
    """What the mesh plan hands the tick programs (`_fill_stacks` at
    r0 = 8, so `acc/smm.py:build_stacks_group_tiles`): one set of class
    shapes for the grid, per (device, tick) its own candidates and its
    own `live`, 0 where it has none; and the program's slot counters
    say what was planned."""
    from dbcsr_tpu.acc.smm import GroupTiles
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.obs import metrics
    from dbcsr_tpu.parallel import sparse_dist as sd

    name, a, b, a_dense, b_dense, off = filtered_case
    n = len(off) - 1
    seen = []
    fill = sd._fill_stacks

    def spy(*args, **kw):
        seen.append((fill(*args, **kw), args[5], kw))
        return seen[-1][0]

    def slots():
        got = {lab["kind"]: v for lab, v in metrics.counter_items(
            "dbcsr_tpu_stack_slots_total")}
        return got.get("live", 0.0), got.get("launched", 0.0)

    monkeypatch.setattr(sd, "_fill_stacks", spy)
    sd.clear_mesh_plans()  # the case's plan may be cached: build it here
    live0, launched0 = slots()
    rolled0 = stats.driver_rollup().get("mesh", {})
    _filtered_on_mesh(a, b, mesh4, case=name)
    (tiles, cap_c, kw), = seen
    assert isinstance(tiles, GroupTiles) and kw["r0"] == 8
    # 2 048 slots a chunk at 23^3, `mm_stack_size` slots at small blocks
    assert kw["chunk_groups"] == {"northstar_like": 256,
                                  "unequal_live": 16}.get(name, 3750)
    # the candidates by (device, tick), from the patterns: blocks go to
    # devices cyclically, tick = the alignment step at which k meets
    na, nb = _block_norms(a_dense, off) > 0, _block_norms(b_dense, off) > 0
    i, k, j = np.nonzero(na[:, :, None] & nb[None, :, :])
    stack = ((i % 2) * 2 + j % 2) * 2 + (k % 2 - i % 2 - j % 2) % 2
    want = np.bincount(stack, minlength=8)
    # per stack: the ids that are no pad, over its live chunks
    held = sum((ga != kw["pad_a"]).reshape(8, -1).sum(axis=1)
               for ga, _, _ in tiles.tiles)
    if name == "decaying":  # the norm skip left candidates out
        assert (held <= want).all() and 0 < held.sum() < want.sum()
    else:
        np.testing.assert_array_equal(held, want)
    assert tiles.entries == held.sum()
    assert tiles.live.shape == (8,)
    np.testing.assert_array_equal(tiles.live == 0, held == 0)
    nchunks = tiles.tiles[0][0].shape[1]
    assert 1 <= tiles.live.max() <= nchunks
    for ga, gb, gc in tiles.tiles:
        assert ga.shape[:2] == gb.shape[:2] == gc.shape[:2] == (8, nchunks)
        assert 0 <= ga.min() and ga.max() <= kw["pad_a"]
        assert 0 <= gb.min() and gb.max() <= kw["pad_b"]
        for s in range(8):
            # C rows ascend through a stack's chunks, dead groups last;
            # nothing lives past its live count
            assert (np.diff(gc[s].reshape(-1)) >= 0).all()
            dead = gc[s, tiles.live[s]:]
            assert (dead == cap_c).all()
            assert (ga[s, tiles.live[s]:] == kw["pad_a"]).all()
    if name == "run_lengths":
        # device (0, 0), tick 0: the run of 9 is a group of 8 and one
        # of 1 in the widest class, the run of 8 fills one, the run of
        # 1 goes to the narrowest class there is
        assert tiles.widths[0] == 8 and tiles.live[0] == tiles.live.max() == 1
        ga, _, gc = tiles.tiles[0]
        full = (ga[0, 0] != kw["pad_a"]).sum(axis=1)
        assert full[:3].tolist() == [8, 1, 8] and not full[3:].any()
        assert gc[0, 0, 0] == gc[0, 0, 1] != gc[0, 0, 2]
        ga, _, _ = tiles.tiles[-1]
        assert (ga[0, 0] != kw["pad_a"]).sum(axis=1).max() == 1
    elif name == "idle_devices":
        assert tiles.live.tolist() == [1, 0, 0, 1, 0, 0, 0, 0]
    elif name == "unequal_live":
        by_device = tiles.live.reshape(4, 2)
        assert by_device[:2].min() > by_device[2:].max() >= 1
    live1, launched1 = slots()
    assert live1 - live0 == tiles.entries
    assert launched1 - launched0 == tiles.slots_launched
    rolled = stats.driver_rollup()["mesh"]
    assert rolled["slots_live"] - rolled0.get("slots_live", 0) \
        == tiles.entries
    assert rolled["slots_launched"] - rolled0.get("slots_launched", 0) \
        == tiles.slots_launched == int(tiles.live.sum()) * sum(
            ga.shape[2] * ga.shape[3] for ga, _, _ in tiles.tiles)


def test_unfiltered_mesh_plan_lookups_are_counted_miss_then_hit(mesh4):
    from dbcsr_tpu.parallel.sparse_dist import clear_mesh_plans

    rbs = [4] * 9
    a = _rand("A", rbs, rbs, 0.3, 73)
    b = _rand("B", rbs, rbs, 0.3, 74)
    clear_mesh_plans()
    start = _plan_lookups()
    for _ in range(2):
        c = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh4)
        assert c._mm_algorithm == "stack"
    end = _plan_lookups()
    assert {k: end[k] - start[k] for k in end} == \
        {"miss": 1, "hit": 1, "uncacheable": 0}
