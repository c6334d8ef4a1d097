"""The mesh collect (`parallel/sparse_dist.py:_collect_bins`, PR 38):
every device takes its own blocks out of its own C panel as rows, the
pieces are all-gathered, and one row gather puts them in slot order.
It moves blocks and computes nothing, so its bins are held bit for bit
to the semantics of the program it replaced, written out in NumPy: a
`take` by global position out of the flat panel buffer and a
`zeros().at[slot].set()` of what was taken."""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from dbcsr_tpu import make_random_matrix, multiply, to_dense
from dbcsr_tpu.core import stats
from dbcsr_tpu.core.matrix import _bin_entries
from dbcsr_tpu.obs import metrics
from dbcsr_tpu.parallel import (make_grid, sparse_dist as sd,
                                sparse_multiply_distributed)
from dbcsr_tpu.parallel.overlap import _HashableMesh
from dbcsr_tpu.utils.rounding import bucket_size

SLOTS = "dbcsr_tpu_mesh_collect_slots_total"


def _reference_bins(c_flat, flat_pos, nb, nsl, shapes) -> list:
    """What the parent's program returned: per bin the blocks taken at
    their global positions, cut to the bin's shape, set into zeros at
    their in-bin slots; index rows padded to the bucket with position 0
    and a slot past the bin, which is dropped."""
    outs = []
    for b, (bmb, bnb) in enumerate(shapes):
        sel = np.nonzero(nb == b)[0]
        cap = bucket_size(len(sel))
        fp = np.zeros(cap, np.int64)
        fp[: len(sel)] = flat_pos[sel]
        sl = np.full(cap, cap, np.int64)
        sl[: len(sel)] = nsl[sel]
        blk = c_flat[fp][:, :bmb, :bnb]
        out = np.zeros((cap, bmb, bnb), c_flat.dtype)
        out[sl[sl < cap]] = blk[sl < cap]
        outs.append(out)
    return outs


def _same_bits(got, want) -> bool:
    got, want = np.asarray(got), np.asarray(want)
    return (got.shape == want.shape and got.dtype == want.dtype
            and np.array_equal(got.view(np.uint64), want.view(np.uint64)))


def _panel_values(rng, shape) -> np.ndarray:
    """Random panels with the values a move must carry unchanged, and
    nothing zero: a pad slot may not read a row the panel never wrote."""
    vals = rng.standard_normal(shape)
    flat = vals.reshape(-1)
    for i, v in enumerate((-0.0, np.inf, -np.inf, np.nan, 5e-324, 1e308)):
        flat[i::97][:3] = v
    return vals


# name -> (devices, the mesh axes C is sharded over, row and column
# block sizes, share of the blocks C holds or a rule, seed)
_CASES = {
    # the deployment's grid: uniform blocks with the ragged last one
    "grid_2x2": (4, ("pr", "pc"), [5] * 11 + [3], [5] * 11 + [3], 0.6, 1),
    "mesh_1x1x1": (1, ("pr", "pc"), [4] * 9 + [2], [4] * 9 + [2], 0.5, 2),
    # two layers: C is whole after the layer reduction, sharded over
    # ('pr','pc') alone and gathered over them alone
    "layers_kl2": (8, ("pr", "pc"), [3] * 10, [3] * 13, 0.4, 3),
    # several shape bins, one of a ragged block
    "mixed_blocks": (4, ("pr", "pc"), [2, 3, 5] * 4 + [4],
                     [3, 6] * 5 + [1], 0.5, 4),
    # the blocks of the ragged last row's bins all live on grid row 1:
    # two devices own no block of them
    "device_without_a_bins_blocks": (4, ("pr", "pc"), [4] * 7 + [3],
                                     [4] * 8, 1.0, 5),
    # 8 x 8 blocks, all there: 16 a device and 64 in the bin, both a
    # bucket to the block: no pad slot, and a device's list still holds
    # the pad row a pad slot would name
    "counts_at_a_buckets_edge": (4, ("pr", "pc"), [3] * 8, [3] * 8, 1.0, 6),
    # one device at its bucket's edge (20 of 6 x 7 + ...), the others
    # under it, pad slots in the bin
    "one_device_at_the_edge": (4, ("pr", "pc"), [2] * 9, [2] * 8, "edge", 7),
    # the grouped TAS layout: panels sharded over all three axes
    "all_three_axes": (8, ("kl", "pr", "pc"), [4] * 9 + [2], [4] * 6, 0.7, 8),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_collect_returns_the_parents_bins_bit_for_bit(case):
    ndev, axes, rbs, cbs, occ, seed = _CASES[case]
    mesh = make_grid(ndev)
    grid = tuple(mesh.shape[ax] for ax in axes)
    rng = np.random.default_rng(seed)
    rbs, cbs = np.asarray(rbs, np.int32), np.asarray(cbs, np.int32)
    there = (np.ones((len(rbs), len(cbs)), bool) if occ == 1.0
             else rng.random((len(rbs), len(cbs))) < (0.5 if occ == "edge"
                                                      else occ))
    # blocks go to devices cyclically, rows over all but the last axis
    nrow = int(np.prod(grid[:-1]))
    if occ == "edge":  # device 0 gets exactly 20 blocks
        dev0 = (np.arange(len(rbs))[:, None] % nrow == 0) \
            & (np.arange(len(cbs))[None, :] % grid[-1] == 0)
        there[dev0] = np.arange(dev0.sum()) < 20
    rows, cols = np.nonzero(there)
    c_dev = (rows % nrow) * grid[-1] + cols % grid[-1]
    c_local = sd._panel_slots(c_dev)
    per_dev = np.bincount(c_dev, minlength=len(mesh.devices.flat))
    cap_local = bucket_size(int(per_dev.max()))
    if case == "counts_at_a_buckets_edge":
        assert per_dev.tolist() == [16] * 4 and cap_local == 16
    if occ == "edge":
        assert per_dev[0] == cap_local == 20 and per_dev[1:].max() < 20
    nb, nsl, shapes = _bin_entries(rbs, cbs, rows, cols)
    bm, bn = int(rbs.max()), int(cbs.max())
    host = _panel_values(rng, grid + (cap_local, bm, bn))
    c_panels = jax.device_put(host, NamedSharding(mesh, P(*axes)))

    own, perm, counts, shipped = sd._collect_maps(
        mesh, axes, nb, nsl, len(shapes), c_dev, c_local, cap_local)
    got = sd._collect_bins(c_panels, own, perm, shapes=tuple(shapes),
                           mesh_ref=_HashableMesh(mesh))

    want = _reference_bins(host.reshape(-1, bm, bn),
                           c_dev * cap_local + c_local, nb, nsl, shapes)
    assert len(got) == len(want) == len(shapes)
    for g, w, n, shape in zip(got, want, counts, shapes):
        assert _same_bits(g, w), (case, shape)
        assert not np.asarray(g)[n:].any()  # pad slots, NaN-free zeros
        # whole on every device of the mesh
        assert g.sharding.is_fully_replicated
        assert len(g.sharding.device_set) == ndev
    if case == "device_without_a_bins_blocks":
        ragged = [b for b, s in enumerate(shapes) if s[0] == 3]
        assert ragged and all(
            np.bincount(c_dev[nb == b], minlength=4)[:2].tolist() == [0, 0]
            for b in ragged)
    # every index array is bucketed, and what is shipped is C and pads
    assert all(x.shape[-1] == bucket_size(x.shape[-1]) for x in own)
    assert all(x.shape == (bucket_size(n),) for x, n in zip(perm, counts))
    assert shipped == sum(int(np.prod(x.shape)) for x in own) >= len(rows)
    assert sum(counts) == len(rows)


def test_grouped_tas_plan_collects_what_its_c_assembly_placed():
    """The grouped TAS plan's maps (`_build_grouped_plan`), held to a
    map the plan makes by other code: where `cinit_asm` scatters the
    blocks of a C that holds the whole pattern is where the collect
    must find them."""
    mesh = make_grid(8)  # (2, 2, 2): two groups of a 2x2 grid
    g, s = mesh.shape["kl"], mesh.shape["pr"]
    rng = np.random.default_rng(11)
    rbs, kbs, cbs = [3, 5] * 12, [4] * 6, [5, 2] * 3
    a = make_random_matrix("A", rbs, kbs, occupation=0.4, rng=rng)
    b = make_random_matrix("B", kbs, cbs, occupation=0.5, rng=rng)
    c = make_random_matrix("C", rbs, cbs, occupation=1.0, rng=rng)
    a, b, c, dtype, bm, bk, bn = sd._prepare_operands(a, b, c)
    plan = sd._build_grouped_plan(a, b, c, mesh, g, s, dtype, bm, bk, bn,
                                  sd._stack_r0(dtype), None, None)
    assert np.array_equal(plan.c_keys, c.keys)
    shape = (g, s, s, plan.q * plan.cap_c, bm, bn)
    host = _panel_values(rng, shape)
    c_panels = jax.device_put(host, NamedSharding(mesh, P("kl", "pr", "pc")))
    bins = sd._collected_bins(plan, _HashableMesh(mesh), c_panels)
    flat = host.reshape(-1, bm, bn)
    asm = plan.cinit_asm
    assert len(bins) == len(c.bins) == len(asm.bin_ids) > 1
    for b_id, fp, ss in zip(asm.bin_ids, asm.flat_pos, asm.src_slots):
        n = c.bins[b_id].count
        bmb, bnb = c.bins[b_id].shape
        want = np.zeros((bucket_size(n), bmb, bnb))
        want[np.asarray(ss)[:n]] = flat[np.asarray(fp)[:n]][:, :bmb, :bnb]
        got = next(x for x in bins if x.shape == (bmb, bnb))
        assert got.count == n and _same_bits(got.data, want)


def _collect_slots() -> tuple:
    got = {lab["kind"]: v for lab, v in metrics.counter_items(SLOTS)}
    return got.get("live", 0.0), got.get("shipped", 0.0)


def _rolled() -> tuple:
    mesh = stats.driver_rollup().get("mesh", {})
    return mesh.get("collect_live", 0), mesh.get("collect_shipped", 0)


def test_a_product_that_bears_no_block_dispatches_no_collect():
    mesh = make_grid(4)
    rng = np.random.default_rng(12)
    rbs = [4] * 6
    a = make_random_matrix("A", rbs, rbs, occupation=0.0, rng=rng)
    b = make_random_matrix("B", rbs, rbs, occupation=0.5, rng=rng)
    before = _collect_slots(), _rolled()
    c = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh,
                                    filter_eps=1e-7)
    assert c.nblks == 0 and not to_dense(c).any()
    assert (_collect_slots(), _rolled()) == before


def test_filtered_mesh_product_matches_one_chip_and_counts_its_slots():
    """End to end on the 2x2 grid against the one-chip engine, with a
    filter that drops blocks; the collect's counter says how many
    blocks of C were carved and how many piece slots crossed the grid
    for them."""
    mesh = make_grid(4)
    rng = np.random.default_rng(13)
    rbs = [5] * 13 + [2]
    a = make_random_matrix("A", rbs, rbs, occupation=0.3, rng=rng)
    b = make_random_matrix("B", rbs, rbs, occupation=0.3, rng=rng)
    eps = 2.0  # of blocks whose norms are a few units: some are dropped
    one = make_random_matrix("C", rbs, rbs, occupation=0.0, rng=rng)
    multiply("N", "N", 1.0, a, b, 0.0, one, filter_eps=eps)
    fates = "dbcsr_tpu_filter_blocks_total"

    def born():
        return sum(v for _, v in metrics.counter_items(fates))

    live0, shipped0 = _collect_slots()
    rolled0, born0 = _rolled(), born()
    got = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh,
                                      filter_eps=eps)
    assert 0 < got.nblks == one.nblks < 14 * 14  # the filter bit
    assert np.array_equal(got.keys, one.keys)
    np.testing.assert_allclose(to_dense(got), to_dense(one),
                               rtol=1e-12, atol=1e-12)
    live, shipped = (x - x0 for x, x0 in zip(_collect_slots(),
                                             (live0, shipped0)))
    # every block the product bore was carved (the filter's two fates
    # count them); a device ships its bucket, so at least a pad row
    assert live == born() - born0 >= got.nblks
    assert shipped >= live + 4
    assert tuple(x - x0 for x, x0 in zip(_rolled(), rolled0)) \
        == (live, shipped)


@pytest.mark.parametrize("npanels", [1, 4, 40000])
def test_panel_slots_count_entries_in_key_order(npanels):
    """`_panel_slots`, which gives a device's piece its order: the n-th
    entry of a panel, counted in the order given, gets slot n."""
    rng = np.random.default_rng(npanels)
    ids = rng.integers(0, npanels, 5000)
    seen, want = {}, []
    for p in ids.tolist():
        want.append(seen.get(p, 0))
        seen[p] = want[-1] + 1
    got = sd._panel_slots(ids)
    assert got.dtype == np.int64 and got.tolist() == want
    assert sd._panel_slots(ids[:0]).shape == (0,)
