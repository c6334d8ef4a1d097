"""Tensor layer tests (modeled on `dbcsr_tensor_unittest.F:101-300`):
format permutations must carry identical blocks; 3- and 4-rank
contractions vs einsum oracle."""

import itertools

import numpy as np
import pytest

from dbcsr_tpu.tensor import BlockSparseTensor, contract, create_tensor, remap, tensor_copy


def _rand_tensor(name, blk_sizes, occ, row_dims=None, col_dims=None, seed=0):
    rng = np.random.default_rng(seed)
    t = create_tensor(name, blk_sizes, row_dims, col_dims)
    nblks = t.nblks_per_dim
    for idx in itertools.product(*(range(n) for n in nblks)):
        if rng.random() < occ:
            t.put_block(idx, rng.standard_normal(t.block_shape(idx)))
    return t.finalize()


def test_put_get_roundtrip_rank3():
    sizes = [[2, 3], [4, 2], [3]]
    t = create_tensor("t", sizes, (0,), (1, 2))
    blk = np.random.default_rng(0).standard_normal((3, 2, 3))
    t.put_block((1, 1, 0), blk)
    t.finalize()
    np.testing.assert_array_equal(t.get_block((1, 1, 0)), blk)
    assert t.get_block((0, 0, 0)) is None


@pytest.mark.parametrize("mapping", [((0,), (1, 2)), ((1,), (0, 2)),
                                     ((0, 1), (2,)), ((2, 0), (1,))])
def test_formats_carry_identical_blocks(mapping):
    """ref dbcsr_t_test_formats: same tensor in different nd->2d mappings
    must hold identical blocks."""
    sizes = [[2, 3], [4, 2], [3, 2]]
    t0 = _rand_tensor("t0", sizes, occ=0.7, seed=1)
    t1 = remap(t0, *mapping)
    assert sorted(t0.block_indices()) == sorted(t1.block_indices())
    for idx, blk in t0.iterate_blocks():
        np.testing.assert_array_equal(t1.get_block(idx), blk)
    np.testing.assert_array_equal(t0.to_dense(), t1.to_dense())


def test_tensor_copy_between_mappings():
    sizes = [[2, 2], [3], [2, 4]]
    src = _rand_tensor("s", sizes, occ=0.8, row_dims=(0, 1), col_dims=(2,), seed=2)
    dst = create_tensor("d", sizes, (2,), (1, 0))
    tensor_copy(dst, src)
    np.testing.assert_array_equal(dst.to_dense(), src.to_dense())


def test_tensor_copy_summation_and_preserved_blocks():
    """summation adds into overlapping dest blocks; blocks only in dest
    survive an overwrite copy (device-side merge semantics match the
    old per-block path)."""
    sizes = [[2, 2], [3], [2, 4]]
    src = _rand_tensor("s", sizes, occ=0.6, row_dims=(0, 1), col_dims=(2,), seed=7)
    base = _rand_tensor("d", sizes, occ=0.6, row_dims=(2,), col_dims=(1, 0), seed=8)

    d_sum = create_tensor("ds", sizes, (2,), (1, 0))
    tensor_copy(d_sum, base)
    tensor_copy(d_sum, src, summation=True)
    np.testing.assert_allclose(d_sum.to_dense(), base.to_dense() + src.to_dense(),
                               rtol=1e-13, atol=1e-13)

    d_ow = create_tensor("do", sizes, (2,), (1, 0))
    tensor_copy(d_ow, base)
    tensor_copy(d_ow, src)
    want = base.to_dense().copy()
    # src blocks overwrite; dest-only blocks survive
    src_keys = set(map(tuple, np.asarray(src.block_indices())))
    offs = [np.concatenate([[0], np.cumsum(s)]) for s in src.blk_sizes]
    for idx, blk in src.iterate_blocks():
        sl = tuple(slice(offs[d][idx[d]], offs[d][idx[d]] + blk.shape[d])
                   for d in range(src.ndim))
        want[sl] = blk
    np.testing.assert_allclose(d_ow.to_dense(), want, rtol=1e-13, atol=1e-13)


def test_tensor_copy_rejects_mismatched_blockings():
    """Per-dim blockings that flatten to the same matrix block shape
    must still be rejected (data would be silently reinterpreted)."""
    src = create_tensor("s", [[2], [3]], (0, 1), ())
    src.put_block((0, 0), np.arange(6.0).reshape(2, 3))
    src.finalize()
    dst = create_tensor("d", [[3], [2]], (0, 1), ())
    with pytest.raises(ValueError, match="blockings differ"):
        tensor_copy(dst, src)


def test_rank4_remap_roundtrip():
    """rank-4 remap across disjoint mappings is an exact bijection."""
    sizes = [[2, 3], [2], [3, 2], [2, 2]]
    t0 = _rand_tensor("t4", sizes, occ=0.5, row_dims=(0, 1), col_dims=(2, 3), seed=9)
    t1 = remap(t0, (3, 1), (0, 2))
    t2 = remap(t1, (0, 1), (2, 3))
    np.testing.assert_array_equal(t0.to_dense(), t1.to_dense())
    np.testing.assert_array_equal(t0.to_dense(), t2.to_dense())


def test_contract_rank3_with_matrix():
    """T(i,j,k) * M(k,l) -> C(i,j,l)  (3-center integral pattern)."""
    si, sj, sk, sl = [2, 3], [3, 2], [4, 2], [2, 2]
    a = _rand_tensor("a", [si, sj, sk], occ=0.8, seed=3)
    b = _rand_tensor("b", [sk, sl], occ=0.9, seed=4)
    c = create_tensor("c", [si, sj, sl])
    c.finalize()
    contract(1.0, a, b, 0.0, c,
             contract_a=(2,), notcontract_a=(0, 1),
             contract_b=(0,), notcontract_b=(1,),
             map_1=(0, 1), map_2=(2,))
    want = np.einsum("ijk,kl->ijl", a.to_dense(), b.to_dense())
    np.testing.assert_allclose(c.to_dense(), want, rtol=1e-12, atol=1e-12)


def test_contract_rank3_rank3_over_two_dims():
    """A(i,a,b) * B(j,a,b) -> C(i,j) (RPA-like double contraction)."""
    si, sj, sa, sb = [2, 2], [3], [2, 3], [2, 2]
    a = _rand_tensor("a", [si, sa, sb], occ=0.9, seed=5)
    b = _rand_tensor("b", [sj, sa, sb], occ=0.9, seed=6)
    c = create_tensor("c", [si, sj])
    c.finalize()
    contract(1.0, a, b, 0.0, c,
             contract_a=(1, 2), notcontract_a=(0,),
             contract_b=(1, 2), notcontract_b=(0,),
             map_1=(0,), map_2=(1,))
    want = np.einsum("iab,jab->ij", a.to_dense(), b.to_dense())
    np.testing.assert_allclose(c.to_dense(), want, rtol=1e-12, atol=1e-12)


@pytest.mark.slow
def test_contract_rank3_mesh_matches_oracle():
    """rank-3 contraction routed over the 8-device mesh
    (`contract(mesh=...)` -> the distributed TAS/Cannon path) against
    the einsum oracle and the single-chip result (ref
    `dbcsr_tensor_unittest.F:101-300` contractions)."""
    from dbcsr_tpu.parallel import make_grid

    mesh = make_grid(8)
    si, sj, sk, sl = [2, 3] * 4, [3, 2] * 3, [4, 2] * 2, [2, 2]
    a = _rand_tensor("a", [si, sj, sk], occ=0.5, seed=30)
    b = _rand_tensor("b", [sk, sl], occ=0.8, seed=31)
    c_mesh = create_tensor("cm", [si, sj, sl])
    c_mesh.finalize()
    c_host = create_tensor("ch", [si, sj, sl])
    c_host.finalize()
    kw = dict(contract_a=(2,), notcontract_a=(0, 1),
              contract_b=(0,), notcontract_b=(1,),
              map_1=(0, 1), map_2=(2,))
    contract(1.0, a, b, 0.0, c_mesh, mesh=mesh, **kw)
    contract(1.0, a, b, 0.0, c_host, **kw)
    want = np.einsum("ijk,kl->ijl", a.to_dense(), b.to_dense())
    np.testing.assert_allclose(c_mesh.to_dense(), want, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(c_mesh.to_dense(), c_host.to_dense(),
                               rtol=1e-12, atol=1e-12)


@pytest.mark.slow
def test_contract_rank3_rank3_mesh_double_contraction():
    """A(i,a,b) * B(j,a,b) -> C(i,j) over the mesh, with alpha/beta."""
    from dbcsr_tpu.parallel import make_grid

    mesh = make_grid(8)
    si, sj, sa, sb = [2, 2] * 3, [3] * 4, [2, 3] * 2, [2, 2]
    a = _rand_tensor("a", [si, sa, sb], occ=0.6, seed=32)
    b = _rand_tensor("b", [sj, sa, sb], occ=0.6, seed=33)
    c = _rand_tensor("c", [si, sj], occ=0.4, seed=34)
    before = c.to_dense().copy()
    contract(2.0, a, b, 0.5, c, mesh=mesh,
             contract_a=(1, 2), notcontract_a=(0,),
             contract_b=(1, 2), notcontract_b=(0,),
             map_1=(0,), map_2=(1,))
    want = 2.0 * np.einsum("iab,jab->ij", a.to_dense(), b.to_dense()) + 0.5 * before
    np.testing.assert_allclose(c.to_dense(), want, rtol=1e-12, atol=1e-12)


def test_contract_beta_and_alpha():
    si, sk = [2, 3], [3, 2]
    a = _rand_tensor("a", [si, sk], occ=1.0, seed=7)
    b = _rand_tensor("b", [sk, si], occ=1.0, seed=8)
    c = _rand_tensor("c", [si, si], occ=0.5, seed=9)
    c0 = c.to_dense()
    contract(2.0, a, b, 0.5, c,
             contract_a=(1,), notcontract_a=(0,),
             contract_b=(0,), notcontract_b=(1,))
    want = 2.0 * np.einsum("ik,kj->ij", a.to_dense(), b.to_dense()) + 0.5 * c0
    np.testing.assert_allclose(c.to_dense(), want, rtol=1e-12, atol=1e-12)


def test_contract_into_nonstandard_c_mapping():
    """C stored with a different mapping than the contraction layout."""
    si, sj, sk = [2, 2], [3, 2], [2, 3]
    a = _rand_tensor("a", [si, sk], occ=1.0, seed=10)
    b = _rand_tensor("b", [sk, sj], occ=1.0, seed=11)
    c = create_tensor("c", [si, sj], row_dims=(1,), col_dims=(0,))
    c.finalize()
    contract(1.0, a, b, 0.0, c,
             contract_a=(1,), notcontract_a=(0,),
             contract_b=(0,), notcontract_b=(1,))
    want = a.to_dense() @ b.to_dense()
    np.testing.assert_allclose(c.to_dense(), want, rtol=1e-12, atol=1e-12)


def test_contract_rank4():
    """A(i,j,a,b) * B(a,b,k,l) -> C(i,j,k,l)."""
    s = [2, 2]
    a = _rand_tensor("a", [s, s, s, s], occ=0.6, seed=12)
    b = _rand_tensor("b", [s, s, s, s], occ=0.6, seed=13)
    c = create_tensor("c", [s, s, s, s])
    c.finalize()
    contract(1.0, a, b, 0.0, c,
             contract_a=(2, 3), notcontract_a=(0, 1),
             contract_b=(0, 1), notcontract_b=(2, 3),
             map_1=(0, 1), map_2=(2, 3))
    want = np.einsum("ijab,abkl->ijkl", a.to_dense(), b.to_dense())
    np.testing.assert_allclose(c.to_dense(), want, rtol=1e-12, atol=1e-12)


def test_contract_validates_blockings():
    a = _rand_tensor("a", [[2], [3]], occ=1.0, seed=14)
    b = _rand_tensor("b", [[4], [2]], occ=1.0, seed=15)
    c = create_tensor("c", [[2], [2]])
    c.finalize()
    with pytest.raises(ValueError):
        contract(1.0, a, b, 0.0, c, (1,), (0,), (0,), (1,))


def test_contract_with_bounds():
    """bounds restrict the contraction to block-index ranges; the result
    must equal the einsum of the cropped operands."""
    si, sj, sk = [2, 3, 2], [3, 2, 4], [4, 2, 3]
    koff = np.concatenate([[0], np.cumsum(sk)])
    a2 = _rand_tensor("a2", [si, sk], occ=0.9, seed=13)
    b2 = _rand_tensor("b2", [sk, sj], occ=0.9, seed=14)
    c2 = create_tensor("c2", [si, sj])
    from dbcsr_tpu.tensor import contract as t_contract

    t_contract(
        1.0, a2, b2, 0.0, c2,
        contract_a=(1,), notcontract_a=(0,),
        contract_b=(0,), notcontract_b=(1,),
        bounds_1=[(1, 2)],
    )
    a2d = a2.to_dense().copy()
    b2d = b2.to_dense().copy()
    a2d[:, : koff[1]] = 0
    b2d[: koff[1], :] = 0
    want2 = a2d @ b2d
    np.testing.assert_allclose(c2.to_dense(), want2, rtol=1e-10, atol=1e-12)


def test_batched_contract_accumulates_chunks():
    """Chunking the contracted dim over bounds inside a batched context
    must reproduce the full contraction, with filtering deferred."""
    from dbcsr_tpu.tensor import batched_contraction, contract as t_contract

    si, sk, sj = [2, 3], [3, 2, 4, 2], [2, 3]
    a = _rand_tensor("a", [si, sk], occ=1.0, seed=21)
    b = _rand_tensor("b", [sk, sj], occ=1.0, seed=22)
    c = create_tensor("c", [si, sj])
    c.finalize()
    nk = len(sk)
    with batched_contraction(c):
        for k0 in range(nk):
            t_contract(
                1.0, a, b, 1.0, c,
                contract_a=(1,), notcontract_a=(0,),
                contract_b=(0,), notcontract_b=(1,),
                bounds_1=[(k0, k0)],
                filter_eps=1e-12,
            )
    want = a.to_dense() @ b.to_dense()
    np.testing.assert_allclose(c.to_dense(), want, rtol=1e-10, atol=1e-12)


def test_restrict_tensor_drops_out_of_range_blocks():
    from dbcsr_tpu.tensor import restrict_tensor

    sizes = [[2, 3, 2], [3, 2], [2, 2, 3]]
    t = _rand_tensor("t", sizes, occ=1.0, seed=31)
    r = restrict_tensor(t, {0: (1, 2), 2: (0, 1)})
    nd = r.entry_multi_coords()
    assert len(nd) and (nd[:, 0] >= 1).all() and (nd[:, 2] <= 1).all()
    for idx, blk in r.iterate_blocks():
        np.testing.assert_array_equal(t.get_block(idx), blk)


def test_tas_batched_mm_state_machine():
    from dbcsr_tpu.ops.test_methods import make_random_matrix, to_dense
    from dbcsr_tpu.tas import batched_mm, tas_multiply

    rng = np.random.default_rng(41)
    rbs = [3] * 20
    cbs = [4, 4]
    a = make_random_matrix("A", rbs, cbs, occupation=0.7, rng=rng)  # tall
    b = make_random_matrix("B", cbs, cbs, occupation=1.0, rng=rng)
    c = make_random_matrix("C", rbs, cbs, occupation=0.0, rng=rng)
    want = np.zeros((sum(rbs), sum(cbs)))
    with batched_mm(c):
        for rep in range(3):
            tas_multiply("N", "N", 1.0, a, b, 1.0, c, filter_eps=1e-12)
            want += to_dense(a) @ to_dense(b)
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-10, atol=1e-12)


@pytest.mark.slow
def test_tas_batched_split_reoptimizes_on_sparsity_change():
    """The cached batch split is re-chosen when it leaves the
    acceptance window of the current-sparsity optimum (the analog of
    the batched pgrid re-optimization, `dbcsr_tensor.F:1964-2186`;
    window = default_nsplit_accept_ratio, `dbcsr_tas_split.F:57`)."""
    from dbcsr_tpu.ops.test_methods import make_random_matrix, to_dense
    from dbcsr_tpu.parallel import make_grid
    from dbcsr_tpu.tas import batched_mm, tas_multiply

    # on a grid: one chip splits only where the caller asks
    mesh = make_grid(4)
    rng = np.random.default_rng(43)
    rbs = [3] * 64  # long m: optimum nsplit >> 1
    cbs = [4, 4]
    a = make_random_matrix("A", rbs, cbs, occupation=0.9, rng=rng)
    b = make_random_matrix("B", cbs, cbs, occupation=1.0, rng=rng)
    c = make_random_matrix("C", rbs, cbs, occupation=0.0, rng=rng)
    want = np.zeros((sum(rbs), sum(cbs)))
    with batched_mm(c):  # AUTO split: only auto splits float
        state = c._tas_batched_state
        # simulate a split cached under long-gone sparsity (the
        # between-batch drift case): stale auto value, counts unchecked.
        # (An nsplit given at batched_mm init is user-pinned and never
        # re-optimized — see test_batched_pgrid_reoptimization.)
        state["nsplit"] = 1
        state["nblks_checked"] = None
        tas_multiply("N", "N", 1.0, a, b, 1.0, c, mesh=mesh)
        want += to_dense(a) @ to_dense(b)
        assert state["nsplit"] > 1, "stale nsplit=1 should have been re-chosen"
        assert state.get("resplit_count", 0) == 1
        tas_multiply("N", "N", 1.0, a, b, 1.0, c, mesh=mesh)
        want += to_dense(a) @ to_dense(b)
        # second call: cached split now optimal, no further re-split
        assert state.get("resplit_count", 0) == 1
    np.testing.assert_allclose(to_dense(c), want, rtol=1e-10, atol=1e-12)


# ------------------------------------------------ dbcsr_t_* API parity
def test_tensor_api_parity_surface():
    import io as _io

    from dbcsr_tpu.tensor.types import create_tensor

    rng = np.random.default_rng(17)
    t = create_tensor("t", [[2, 3], [3, 2], [2, 2]])
    t.reserve_blocks([[0, 0, 0], [1, 1, 1]])
    assert t.nblks == 2
    t.put_block([0, 1, 0], rng.standard_normal((2, 2, 2)))
    t.finalize()
    t.set_value(2.0)
    assert np.allclose(t.get_block([0, 1, 0]), 2.0)
    t.scale(0.5)
    assert np.allclose(t.get_block([0, 1, 0]), 1.0)
    info = t.get_info()
    assert info["ndim"] == 3 and info["nblks"] == 3
    assert t.get_nze() == 12 + 12 + 8  # (2,3,2) + (3,2,2) + (2,2,2)
    mi = t.get_mapping_info()
    assert mi["dims_2d"] == (t.matrix.nblkrows, t.matrix.nblkcols)
    assert isinstance(t.checksum(), float)
    assert t.get_stored_coordinates([0, 0, 0]) == (0, 0)
    assert t.blk_sizes_of([1, 0, 1]) == (3, 3, 2)
    buf = _io.StringIO()
    t.write_blocks(buf)
    assert "block (0, 0, 0)" in buf.getvalue()
    buf2 = _io.StringIO()
    t.write_split_info(buf2)
    assert "2d grid" in buf2.getvalue()
    t.filter(1e30)
    assert t.nblks == 0
    t.clear()
    assert t.nblks == 0 and t.matrix.valid


def test_tensor_split_blocks():
    from dbcsr_tpu.tensor.types import create_tensor, split_blocks

    rng = np.random.default_rng(18)
    t = create_tensor("t", [[4, 2], [3, 3]])
    t.put_block([0, 0], rng.standard_normal((4, 3)))
    t.put_block([1, 1], rng.standard_normal((2, 3)))
    t.finalize()
    s = split_blocks(t, [[2, 2, 2], [3, 1, 2]])
    np.testing.assert_allclose(s.to_dense(), t.to_dense())
    assert s.nblks > t.nblks
    with pytest.raises(ValueError):
        split_blocks(t, [[3, 3], [3, 3]])  # breaks an old boundary


def test_tensor_matrix_copies():
    from dbcsr_tpu import create, make_random_matrix, to_dense
    from dbcsr_tpu.tensor.types import (
        copy_matrix_to_tensor,
        copy_tensor_to_matrix,
        create_tensor,
    )

    rng = np.random.default_rng(19)
    m = make_random_matrix("m", [2, 3], [3, 2], occupation=0.8, rng=rng)
    t = create_tensor("t", [[2, 3], [3, 2]], row_dims=(0,), col_dims=(1,))
    copy_matrix_to_tensor(m, t)
    np.testing.assert_allclose(t.to_dense(), to_dense(m))
    m2 = create("m2", [2, 3], [3, 2])
    copy_tensor_to_matrix(t, m2)
    np.testing.assert_allclose(to_dense(m2), to_dense(m))


def test_contract_test_harness():
    """dbcsr_t_contract_test analog: contraction vs dense einsum oracle."""
    from dbcsr_tpu.tensor.contract import contract_test
    from dbcsr_tpu.tensor.types import create_tensor

    rng = np.random.default_rng(21)
    a = create_tensor("a", [[2, 3], [3], [2, 2]])
    b = create_tensor("b", [[3], [2, 2], [4]])
    c = create_tensor("c", [[2, 3], [2, 2], [2, 2], [4]])
    for t in (a, b):
        for idx in np.ndindex(*t.nblks_per_dim):
            if rng.random() < 0.7:
                t.put_block(list(idx), rng.standard_normal(t.block_shape(idx)))
        t.finalize()
    c.finalize()
    msgs = []
    assert contract_test(2.0, a, b, 0.0, c, [1], [0, 2], [0], [1, 2],
                         io=msgs.append)
    assert msgs and "OK" in msgs[0]


def test_contract_test_with_bounds_and_filter_reject():
    from dbcsr_tpu.tensor.contract import contract_test
    from dbcsr_tpu.tensor.types import create_tensor

    si, sk, sj = [2, 3, 2], [4, 2, 3], [3, 2]
    a = _rand_tensor("a", [si, sk], occ=0.9, seed=23)
    b = _rand_tensor("b", [sk, sj], occ=0.9, seed=24)
    c = create_tensor("c", [si, sj])
    c.finalize()
    assert contract_test(1.0, a, b, 0.0, c, [1], [0], [0], [1],
                         bounds_1=[(1, 2)], io=lambda *_: None)
    with pytest.raises(ValueError, match="filter_eps"):
        contract_test(1.0, a, b, 0.0, c, [1], [0], [0], [1],
                      filter_eps=1e-10, io=lambda *_: None)


@pytest.mark.slow
def test_contract_rank3_rect_mesh_matches_oracle():
    """Tensor contraction over a RECTANGULAR 6-device mesh: the
    nd->2d-mapped product runs through the all-gather engine with
    oracle-equal results (ref arbitrary nprows x npcols grids,
    dbcsr_types.F:188-223)."""
    from dbcsr_tpu.parallel import make_grid

    mesh = make_grid(6)  # (kl=1, pr=2, pc=3)
    assert mesh.shape["pr"] != mesh.shape["pc"]
    si, sj, sk, sl = [2, 3] * 4, [3, 2] * 3, [4, 2] * 2, [2, 2]
    a = _rand_tensor("a", [si, sj, sk], occ=0.5, seed=60)
    b = _rand_tensor("b", [sk, sl], occ=0.8, seed=61)
    c = create_tensor("c", [si, sj, sl])
    c.finalize()
    contract(1.0, a, b, 0.0, c, mesh=mesh,
             contract_a=(2,), notcontract_a=(0, 1),
             contract_b=(0,), notcontract_b=(1,),
             map_1=(0, 1), map_2=(2,))
    want = np.einsum("ijk,kl->ijl", a.to_dense(), b.to_dense())
    np.testing.assert_allclose(c.to_dense(), want, rtol=1e-12, atol=1e-12)
