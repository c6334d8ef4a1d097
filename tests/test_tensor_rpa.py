"""The chi(i tau) batch of cubic-scaling RPA through the tensor layer on
the CPU: `tensor.contract` inside `batched_contraction`, over
`tas_multiply`, over `mm.multiply`, held to the plain NumPy batch of
the benchmark's generator `benchmark/generators/rpa_chi.py` (the
deployment of `rpa_h2o32`) at two and three molecules; the counters the
tensor layer keeps; the remap programs keyed by bucket; the span of the
deferred filter; and the width cap that keeps a 169-deep span in the
sliced form."""

import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import dbcsr_tpu as dt
import dbcsr_tpu.tensor as dtt
from dbcsr_tpu.acc import smm
from dbcsr_tpu.core import timings
from dbcsr_tpu.core.config import get_config, set_config
from dbcsr_tpu.obs import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rpa():
    spec = importlib.util.spec_from_file_location(
        "_test_rpa_chi",
        os.path.join(REPO, "benchmark", "generators", "rpa_chi.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


rpa = _rpa()
with open(os.path.join(REPO, "benchmark", "configs", "rpa_h2o32.json")) as _fh:
    CONFIG = json.load(_fh)


def _deployment(molecules: int, cutoff: float, seed: int = 2147483659):
    dt.init_lib()
    recipe = dict(CONFIG["recipe"], batches=2, cutoff=cutoff)
    return rpa.Deployment(recipe, molecules, CONFIG["pattern_seed"], seed)


def _flops(dep, product):
    return sum(info["flops"] for info in dep.reference(product).infos)


@pytest.fixture
def no_incremental():
    was = get_config().incremental
    set_config(incremental="off")
    yield
    set_config(incremental=was)


@pytest.fixture
def fake_tpu():
    was = get_config().platform_override
    set_config(platform_override="tpu")
    yield
    set_config(platform_override=was)


# ------------------------------------------------- against the reference
@pytest.mark.parametrize("molecules,cutoff", [(2, 0.40), (3, 0.25)],
                         ids=["2h2o_r040", "3h2o_r025"])
def test_chi_batches_agree_with_the_numpy_batch(molecules, cutoff,
                                                no_incremental):
    """Every batch of a tau point: the program's flops are the NumPy
    batch's to the flop, chi's pattern is its pattern, and every block
    of chi lies within the written tolerance.  At 3 molecules and 0.25
    nm the cutoffs store part of the triples; at 2 and 0.40 all."""
    dep = _deployment(molecules, cutoff)
    n = dep.box.natoms
    share = dep.t3.nblks / float(n) ** 3
    assert (share < 1.0) == (cutoff == 0.25)
    for product in range(dep.batches):
        chi, flops = dep.run_batch(product)
        ref = dep.reference(product)
        assert flops == _flops(dep, product) > 0
        rows, cols = chi.matrix.entry_coords()
        assert np.array_equal(rows * n + cols, ref.chi.keys)
        tol = dep.tolerance(product)
        scale = np.abs(ref.chi.flat).max()
        for e in range(ref.chi.nblks):
            got = chi.matrix.get_block(int(ref.chi.rows[e]),
                                       int(ref.chi.cols[e]))
            assert np.abs(got - ref.chi.block(e)).max() <= tol * scale
        assert dep.check(product, chi)["ok"]
    # the shapes the deployment forces: C blocks of (P nu) = 56 x 13
    # rows in steps 1-2, k = 13 x 13 pairs in step 3
    stacks = [st for info in dep.reference(0).infos for st in info["stacks"]]
    assert max(m for m, _, _, _, _ in stacks) == 728
    assert max(k for _, _, k, _, _ in stacks) == 169


def test_a_block_of_chi_off_by_a_part_in_1e10_is_not_correct():
    dep = _deployment(2, 0.40)
    ref = dep.reference(0).chi
    blocks = {(int(r), int(c)): ref.block(e).copy() for e, (r, c) in
              enumerate(zip(ref.rows, ref.cols))}
    tol = dep.tolerance(0)
    ok = rpa.compare_chi(ref, ref.keys, blocks, tol)
    assert ok["ok"] and ok["compared_blocks"] == ref.nblks
    assert ok["bins"] == [(14, 14), (14, 56), (56, 14), (56, 56)]
    # the largest element of the largest block, off by a part in 1e10
    key = max(blocks, key=lambda k: np.abs(blocks[k]).max())
    blk = blocks[key]
    idx = np.unravel_index(np.argmax(np.abs(blk)), blk.shape)
    blk[idx] *= 1.0 + 1e-10
    got = rpa.compare_chi(ref, ref.keys, blocks, tol)
    assert not got["ok"] and got["rel_err"] > tol
    # a block the reference keeps and the program lacks
    del blocks[key]
    assert not rpa.compare_chi(ref, ref.keys, blocks, tol)["ok"]
    # or one the filter dropped that the program kept
    assert not rpa.compare_chi(ref, np.append(ref.keys, -1), blocks,
                               tol)["ok"]


def test_the_batch_computed_in_float32_fails_the_tolerance_by_orders():
    dep = _deployment(2, 0.40)
    want = dep.reference(0)
    f32 = dep.reference(0, compute=np.float32)
    blocks = {(int(r), int(c)): f32.chi.block(e) for e, (r, c) in
              enumerate(zip(f32.chi.rows, f32.chi.cols))}
    got = rpa.compare_chi(want.chi, f32.chi.keys, blocks, dep.tolerance(0))
    assert not got["ok"] and got["rel_err"] > 100 * dep.tolerance(0)


# ---------------------------------------------------------- the counters
def _counter(name):
    return {tuple(sorted(lab.items())): v
            for lab, v in metrics.counter_items(name)}


def _delta(before, after):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def test_one_contraction_counts_its_remaps_groups_and_batch(no_incremental):
    """Step 3 of a batch lays M^occ and M^virt out anew (roles a and b,
    every stored block, read and written once) and splits the long k;
    the batch's finalize counts one."""
    dep = _deployment(2, 0.40)
    ao, ri = dep.box.ao, dep.box.ri
    names = ("dbcsr_tpu_tensor_remap_blocks_total",
             "dbcsr_tpu_tensor_remap_bytes_total",
             "dbcsr_tpu_tas_groups_total", "dbcsr_tpu_tensor_batches_total")
    m = {}
    for name, d in (("M_occ", dep.tensor_docc), ("M_virt", dep.tensor_dvirt)):
        m[name] = dtt.create_tensor(name, [ri, ao, ao], row_dims=(0, 1),
                                    col_dims=(2,))
        with dtt.batched_contraction(m[name]):
            dtt.contract(1.0, dep.tensor_3c, d, 0.0, m[name], (1,), (0, 2),
                         (0,), (1,), map_1=(0, 1), map_2=(2,),
                         filter_eps=dep.filter_eps, bounds_3=[(0, 2)])
    before = {name: _counter(name) for name in names}
    chi = dtt.create_tensor("chi", [ri, ri], row_dims=(0,), col_dims=(1,))
    with dtt.batched_contraction(chi, nsplit=3):
        dtt.contract(1.0, m["M_occ"], m["M_virt"], 0.0, chi, (1, 2), (0,),
                     (1, 2), (0,), map_1=(0,), map_2=(1,),
                     filter_eps=dep.filter_eps)
    moved = {name: _delta(before[name], _counter(name)) for name in names}
    for role, t in (("a", m["M_occ"]), ("b", m["M_virt"])):
        nel = sum(int(np.prod(t.blk_sizes_of(idx))) for idx in
                  t.block_indices())
        assert moved["dbcsr_tpu_tensor_remap_blocks_total"][
            (("role", role),)] == t.nblks
        assert moved["dbcsr_tpu_tensor_remap_bytes_total"][
            (("role", role),)] == 2 * 8 * nel
    assert moved["dbcsr_tpu_tas_groups_total"] == {(("long_dim", "k"),): 3}
    assert moved["dbcsr_tpu_tensor_batches_total"] == {(): 1}


def test_the_deferred_filter_runs_under_its_own_span(no_incremental):
    """Each of a batch's three finalizes filters its result once, under
    `dbcsr_tpu:tensor_batch_filter`, outside `tensor_contract` (whose
    multiplies run unfiltered inside the batch)."""
    dep = _deployment(2, 0.40)
    timings.reset()
    dep.run_batch(0)
    stats = timings._stats
    assert stats["tensor_batch_filter"].calls == 3
    assert stats["tensor_contract"].calls == 3
    assert "tensor_batch_filter" not in stats["tensor_contract"].callees
    assert "multiply_filter" not in stats


def test_a_result_mapped_back_counts_role_c(no_incremental):
    rng = np.random.default_rng(5)
    sizes = [np.array([2, 3]), np.array([3, 2]), np.array([2, 2])]
    a = dtt.create_tensor("a", sizes, row_dims=(0,), col_dims=(1, 2))
    for idx in [(0, 0, 0), (1, 1, 1), (0, 1, 0)]:
        a.put_block(idx, rng.standard_normal(a.block_shape(idx)))
    a.finalize()
    b = dtt.create_tensor("b", [sizes[2], np.array([4])], row_dims=(0,),
                          col_dims=(1,))
    for idx in [(0, 0), (1, 0)]:
        b.put_block(idx, rng.standard_normal(b.block_shape(idx)))
    b.finalize()
    # C stored (0, 1, 3) as rows 0 / cols (1, 3): the product lands as
    # rows (0, 1) and is mapped back
    c = dtt.create_tensor("c", [sizes[0], sizes[1], np.array([4])],
                          row_dims=(0,), col_dims=(1, 2))
    before = _counter("dbcsr_tpu_tensor_remap_blocks_total")
    dtt.contract(1.0, a, b, 0.0, c, (2,), (0, 1), (0,), (1,),
                 map_1=(0, 1), map_2=(2,))
    moved = _delta(before, _counter("dbcsr_tpu_tensor_remap_blocks_total"))
    assert moved[(("role", "c"),)] == c.nblks == 3
    want = np.einsum("ijk,kl->ijl", a.to_dense(), b.to_dense())
    np.testing.assert_allclose(c.to_dense(), want, rtol=1e-13, atol=1e-13)


def test_remap_programs_are_keyed_by_the_bucket_of_the_slot_count():
    """Two operands whose blocks of one shape number 17 and 19 (one
    bucket, 20) reuse one remap program."""
    tc = importlib.import_module("dbcsr_tpu.tensor.contract")
    rng = np.random.default_rng(3)
    sizes = [np.full(6, 2), np.full(6, 3), np.full(6, 2)]
    triples = [tuple(int(x) for x in t) for t in rng.permutation(
        np.array(np.meshgrid(range(6), range(6), range(6))).reshape(3, -1).T)]
    compiled = []
    for count in (17, 19):
        t = dtt.create_tensor("t", sizes, row_dims=(0, 1), col_dims=(2,))
        for idx in triples[:count]:
            t.put_block(idx, rng.standard_normal(t.block_shape(idx)))
        t.finalize()
        before = tc._remap_rows._cache_size()
        out = dtt.remap(t, (0,), (1, 2))
        compiled.append(tc._remap_rows._cache_size() - before)
        np.testing.assert_array_equal(out.to_dense(), t.to_dense())
    assert compiled[1] == 0


# ------------------------------------------------------ the sliced width
def _stack(m, n, k, runs, seed):
    rng = np.random.default_rng(seed)
    entries = int(np.sum(runs))
    a = rng.standard_normal((entries, m, k))
    b = rng.standard_normal((entries, k, n))
    ci = np.repeat(np.arange(len(runs)), runs).astype(np.int32)
    ai = np.arange(entries, dtype=np.int32)
    return a, b, ai, ai.copy(), ci


@pytest.mark.parametrize("k,width", [(5, 8), (23, 8), (128, 8), (129, 4),
                                     (169, 4), (256, 4), (257, 2),
                                     (1024, 1)])
def test_the_widest_group_keeps_r0_k_within_the_sliced_depth(fake_tpu, k,
                                                             width):
    assert smm.sliced_width(8, k, np.float64) == width
    assert smm.group_dot_form(np.float64, width * k) == "sliced"


def test_the_width_is_left_where_the_form_is_not_sliced(fake_tpu):
    assert smm.sliced_width(8, 169, np.float32) == 8
    assert smm.sliced_width(8, 1025, np.float64) == 8  # no width serves
    set_config(platform_override="cpu")
    assert smm.sliced_width(8, 169, np.float64) == 8


# (width, groups) of each class the plan of `runs` below holds: what r0 =
# 8 gave before the cap existed
PINNED_CLASSES = {(23, 23, 23): ((8, 280), (4, 140), (2, 140)),
                  (5, 13, 23): ((8, 280), (4, 140), (2, 140))}


@pytest.mark.parametrize("mnk", [(23, 23, 23), (5, 13, 23)],
                         ids=["23_23_23", "5_13_23"])
def test_existing_spans_keep_their_width_classes(fake_tpu, mnk):
    """The cap touches no block of k * 8 <= 1 024: a 23-deep span's
    plan is the one r0 = 8 gives, class for class."""
    m, n, k = mnk
    runs = np.tile([1, 2, 3, 4, 5, 8, 11], 70)
    a, b, ai, bi, ci = _stack(m, n, k, runs, seed=11)
    plan = smm.prepare_stack(jnp.zeros((len(runs), m, n)), jnp.asarray(a),
                             jnp.asarray(b), ai, bi, ci)
    assert plan.driver == "xla_group" and plan.dot_form == "sliced"
    want = smm.build_group_tiles(
        ci, ai, bi, 8, len(a), len(b), len(runs),
        smm.group_chunk_groups(8, m, n, k, 8, get_config().mm_stack_size))
    assert plan.r_grp == 8
    assert plan.group_classes == tuple(zip(want.widths, want.groups))
    assert plan.group_classes == PINNED_CLASSES[mnk]


def test_a_169_deep_span_runs_sliced_at_width_4(fake_tpu):
    m = n = 14
    k = 169
    runs = np.tile([1, 3, 4, 6, 9], 160)  # 3 680 entries: past 2 048
    a, b, ai, bi, ci = _stack(m, n, k, runs, seed=7)
    c0 = jnp.zeros((len(runs), m, n))
    plan = smm.prepare_stack(c0, jnp.asarray(a), jnp.asarray(b), ai, bi, ci)
    assert plan.driver == "xla_group" and plan.dot_form == "sliced"
    assert plan.r_grp == 4 and max(w for w, _ in plan.group_classes) == 4
    got = np.asarray(smm.execute_stack(c0, jnp.asarray(a), jnp.asarray(b),
                                       plan, 1.0))
    want, bound = np.zeros(got.shape), np.zeros(got.shape)
    np.add.at(want, ci, a[ai] @ b[bi])
    np.add.at(bound, ci, np.abs(a[ai]) @ np.abs(b[bi]))
    assert (np.abs(got - want) <= 2.0 ** -46 * bound).all()


# ------------------------------------------- shapes that batches share
def test_power_buckets():
    from dbcsr_tpu.utils.rounding import bucket_pow2, bucket_pow4

    assert [bucket_pow2(n) for n in (0, 1, 16, 17, 33, 1000)] == \
        [0, 16, 16, 32, 64, 1024]
    assert [bucket_pow4(n) for n in (0, 1, 2, 4, 5, 17, 64, 65)] == \
        [0, 1, 4, 4, 16, 64, 64, 256]


def test_a_tensors_bins_come_in_powers_of_two_and_copies_keep_it():
    from dbcsr_tpu.ops.operations import copy as matrix_copy

    t = dtt.create_tensor("t", [np.full(6, 2), np.full(6, 3)],
                          row_dims=(0,), col_dims=(1,))
    assert t.matrix.moving_pattern
    for i in range(6):
        for j in range(3):
            t.put_block((i, j), np.ones((2, 3)))
    t.finalize()
    assert [b.data.shape[0] for b in t.matrix.bins] == [32]  # 18 blocks
    assert matrix_copy(t.matrix).moving_pattern
    plain = dt.create("p", np.full(6, 2), np.full(6, 3), "float64")
    assert not plain.moving_pattern and plain.bin_capacity(18) == 20


def test_a_restricted_copy_keeps_its_sources_capacities():
    t = dtt.create_tensor("t", [np.full(8, 2), np.full(8, 3)],
                          row_dims=(0,), col_dims=(1,))
    for i in range(8):
        for j in range(8):
            t.put_block((i, j), np.full((2, 3), float(i * 8 + j)))
    t.finalize()
    cap = t.matrix.bins[0].data.shape[0]
    for hi in (1, 4):
        r = dtt.restrict_tensor(t, {1: (0, hi)})
        assert r.matrix.bins[0].data.shape[0] == cap == 64
        assert r.nblks == 8 * (hi + 1)
        # the rows past the kept blocks are zeros (a pad row the stacks
        # may read)
        data = np.asarray(r.matrix.bins[0].data)
        assert not data[r.nblks:].any()
        np.testing.assert_array_equal(
            r.to_dense(), np.where(np.arange(8 * 3)[None, :] < 3 * (hi + 1),
                                   t.to_dense(), 0.0))


def test_the_subset_gather_is_keyed_by_the_bucket_of_the_count():
    from dbcsr_tpu.ops import operations as ops

    m = dt.create("m", np.full(8, 2), np.full(8, 2), "float64")
    for i in range(8):
        for j in range(8):
            m.put_block(i, j, np.full((2, 2), 1.0 + i + j))
    m.finalize()
    compiled = []
    for keep in (17, 19):  # one bucket, 20
        mask = np.zeros(m.nblks, bool)
        mask[:keep] = True
        before = ops._gather_pad._cache_size()
        sub = ops.compress(m.copy(), mask)
        compiled.append(ops._gather_pad._cache_size() - before)
        assert sub.nblks == keep
        assert not np.asarray(sub.bins[0].data)[keep:].any()
    assert compiled[1] == 0


def test_a_moving_stack_takes_one_width_and_chunks_in_powers_of_four():
    runs = np.tile([1, 2, 3, 9, 11], 300)
    ci = np.repeat(np.arange(len(runs)), runs).astype(np.int32)
    ai = np.arange(len(ci), dtype=np.int32)
    args = (ci, ai, ai.copy(), 8, len(ci), len(ci), len(runs), 64)
    plain = smm.build_group_tiles(*args)
    moving = smm.build_group_tiles(*args, moving=True)
    assert len(plain.widths) > 1
    assert moving.widths == (8,)
    nchunks = moving.tiles[0][0].shape[0]
    assert nchunks >= moving.live and nchunks in (1, 4, 16, 64, 256)
    # the same entries, every one placed once
    ga, _, _ = moving.tiles[0]
    placed = ga[ga < len(ci)]
    assert sorted(placed.tolist()) == ai.tolist()
