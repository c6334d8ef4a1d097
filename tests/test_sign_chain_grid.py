"""The sign chain on a process grid (`models.sign.sign_iteration(...,
mesh=mesh)`) on four of conftest's virtual CPU devices: against the
benchmark's NumPy chain, against the one-chip chain, across a
`bucket_size` boundary of the mesh plan's capacities, with the mesh
filter's drops counted and no program shape new in a second chain; and
the chain without a mesh is the chain it was.  Counts and results only."""

import os

import numpy as np
import pytest

import dbcsr_tpu as dt
from benchmark import arithmetic, harness, reference
from dbcsr_tpu.core import mempool
from dbcsr_tpu.core.matrix import BlockSparseMatrix
from dbcsr_tpu.mm.multiply import multiply
from dbcsr_tpu.models.sign import sign_iteration, sign_step
from dbcsr_tpu.obs import metrics
from dbcsr_tpu.ops.operations import add_on_diag, scale
from dbcsr_tpu.parallel import make_grid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the benchmark's own chain: NumPy float64, nothing of the program
sc = harness._load_code(os.path.join(REPO, "benchmark", "generators",
                                     "sign_chain.py"))
RECIPE = dict(occupied_per_block=4, coupling=0.25, decay_length=0.22,
              virtual_width=2.0)
EPS, TOL, MAX_STEPS = 1e-7, 1e-6, 9
PROGRAMS = "dbcsr_tpu_mesh_programs_total"
FATES = "dbcsr_tpu_filter_blocks_total"


@pytest.fixture(scope="module")
def mesh4():
    return make_grid(4)


@pytest.fixture()
def driver(request):
    """The stack layout of the mesh ticks: `auto` is the flat one on a
    CPU, `xla_group` the grouped tiles a TPU runs in f64."""
    cfg = dt.get_config()
    prev = (cfg.incremental, cfg.mm_driver)
    dt.set_config(incremental="off",
                  mm_driver=getattr(request, "param", "auto"))
    yield
    dt.set_config(incremental=prev[0], mm_driver=prev[1])


def _hamiltonian(molecules: int, seed: int):
    """H of ``molecules`` blocks of 5 with a ragged 3 at the end at
    occupancy 0.4, as the benchmark draws it, and the same H staged
    through the public API."""
    sizes = arithmetic.expand_block_sizes(molecules * 5 - 2, [[1, 5]])
    h = sc.draw_hamiltonian(sizes, 0.4, 12341313, seed, **RECIPE)
    mat = dt.create("H", sizes.astype(np.int32), sizes.astype(np.int32),
                    "float64")
    for rows, cols, data in h.by_shape():
        mat.put_blocks(rows, cols, data)
    return h, mat.finalize()


def _keys(x) -> np.ndarray:
    rows, cols = x.entry_coords()
    return np.sort(np.asarray(rows, np.int64) * x.nblkcols
                   + np.asarray(cols, np.int64))


def _counted(name: str) -> dict:
    """{label value: count} of a counter with one label."""
    return {next(iter(lab.values())): value
            for lab, value in metrics.counter_items(name)}


def _tolerance(h, products: int) -> float:
    return products * arithmetic.reference_tolerance(
        "float64", int(h.sizes.max()), len(h.sizes))


# ------------------------------------------- against the plain reference
@pytest.mark.parametrize("driver", ["auto", "xla_group"], indirect=True)
@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 11])
def test_chain_on_the_grid_is_the_reference_chain(mesh4, driver, seed):
    h, mat = _hamiltonian(40, seed)
    want = sc.reference_chain(h, filter_eps=EPS, tol=TOL,
                              max_steps=MAX_STEPS)
    assert sum(p["pruned"] for p in want.products) > 0
    assert sum(p["c_dropped"] for p in want.products) > 0
    x, history = sign_iteration(mat, steps=MAX_STEPS, filter_eps=EPS,
                                tol=TOL, mesh=mesh4)
    assert len(history) == want.steps == x._last_steps
    assert np.array_equal(_keys(x), want.x.keys)
    assert x._last_flops == sum(p["flops"] for p in want.products)
    # sampled block rows, as the cell's check takes them
    rows = reference.sample_block_rows(h.sizes, seed)
    ents = np.nonzero(np.isin(want.x.rows, rows))[0]
    scale_ = max(1.0, float(np.abs(want.x.data).max()))
    got = x.get_blocks(want.x.rows[ents], want.x.cols[ents])
    err = max(float(np.abs(blk - want.x.data[
        e, :h.sizes[want.x.rows[e]], :h.sizes[want.x.cols[e]]]).max())
        for e, blk in zip(ents, got))
    assert err / scale_ <= _tolerance(h, len(want.products))
    occupied = int(sc.occupied_of(h.sizes, 4).sum())
    assert np.trace(dt.to_dense(x)) == pytest.approx(
        h.sizes.sum() - 2 * occupied, abs=1e-6)


# --------------------------------------------- against the one-chip chain
@pytest.mark.parametrize("driver", ["auto", "xla_group"], indirect=True)
@pytest.mark.parametrize("seed", [3, 4])
def test_chain_on_the_grid_is_the_one_chip_chain(mesh4, driver, seed):
    h, mat = _hamiltonian(40, seed)
    one, history_one = sign_iteration(mat, steps=MAX_STEPS, filter_eps=EPS,
                                      tol=TOL)
    grid, history = sign_iteration(mat, steps=MAX_STEPS, filter_eps=EPS,
                                   tol=TOL, mesh=mesh4)
    assert len(history) == len(history_one)
    assert np.array_equal(_keys(grid), _keys(one))
    assert grid._last_flops == one._last_flops > 0
    a, b = dt.to_dense(grid), dt.to_dense(one)
    assert np.abs(a - b).max() / max(1.0, np.abs(b).max()) <= \
        2 * _tolerance(h, 2 * len(history))
    # where X lives between two products: whole on every device
    assert all(len(bin_.data.devices()) == 4 for bin_ in grid.bins)
    assert all(len(bin_.data.devices()) == 1 for bin_ in one.bins)


# ------------------------------- patterns that move, drops that are counted
def test_chain_crosses_bucket_boundaries_and_counts_its_programs(
        mesh4, driver):
    """60 molecules: C's panels, the operands' panels and the stacks
    cross `bucket_size` boundaries between the products of one chain,
    so its ticks, shifts, finishes, collects, assemblies and panel
    cuts run at several shapes; the result is the reference's all the
    same, the filter's drops are the reference's, and a second chain on
    the same H meets no shape it has not run."""
    h, mat = _hamiltonian(60, 7)
    want = sc.reference_chain(h, filter_eps=EPS, tol=TOL,
                              max_steps=MAX_STEPS)
    programs0, fates0 = _counted(PROGRAMS), _counted(FATES)
    x, history = sign_iteration(mat, steps=MAX_STEPS, filter_eps=EPS,
                                tol=TOL, mesh=mesh4)
    first = {k: v - programs0.get(k, 0)
             for k, v in _counted(PROGRAMS).items()}
    assert set(first) == {"assembly", "cut", "tick", "shift", "finish",
                          "collect"}
    # cap_c took two values, the panels and stacks more
    assert first["finish"] >= 2 and first["collect"] >= 2
    assert first["tick"] >= 3 and first["shift"] >= 2
    assert len(history) == want.steps
    assert np.array_equal(_keys(x), want.x.keys)
    assert x._last_flops == sum(p["flops"] for p in want.products)
    fates = {k: v - fates0.get(k, 0) for k, v in _counted(FATES).items()}
    dropped = sum(p["c_dropped"] for p in want.products)
    born = sum(p["c_born"] for p in want.products)
    assert fates == {"dropped": dropped, "kept": born - dropped}
    assert dropped > 0
    again, _ = sign_iteration(mat, steps=MAX_STEPS, filter_eps=EPS,
                              tol=TOL, mesh=mesh4)
    assert {k: v - programs0.get(k, 0)
            for k, v in _counted(PROGRAMS).items()} == first
    assert dt.checksum(again) == dt.checksum(x)


@pytest.mark.parametrize("driver", ["xla_group"], indirect=True)
def test_second_chain_finds_every_plan_and_drops_panels_before_plans(
        mesh4, driver, monkeypatch):
    """More survivor sets than the cache held until PR 40 (8): a second
    chain on the same H reaches the same survivors product by product,
    so it finds every plan and builds none, and its X is the first
    chain's bit for bit.  Under a byte budget that holds the plans but
    not all their panels, the least recently used plans give up their
    panels and no plan goes."""
    from dbcsr_tpu.parallel import sparse_dist as sd

    def lookups() -> dict:
        return _counted("dbcsr_tpu_mesh_plan_total")

    def panel_bytes(plan) -> int:
        return sum(int(p.nbytes) for _, p, _ in plan.panel_cache.values())

    h, mat = _hamiltonian(40, 6)
    sd.clear_mesh_plans()
    start = lookups()
    x, history = sign_iteration(mat, steps=MAX_STEPS, filter_eps=EPS,
                                tol=TOL, mesh=mesh4)
    first = {k: v - start.get(k, 0) for k, v in lookups().items()}
    products = 2 * len(history)
    plans = list(sd._mesh_plan_cache.values())
    assert first.get("uncacheable", 0) == 0
    assert first["miss"] == len(plans) > 8
    assert first["miss"] + first.get("hit", 0) == products
    own = sum(p.nbytes() - panel_bytes(p) for p in plans)
    budget = own + 3 * max(panel_bytes(p) for p in plans) // 2
    assert sum(p.nbytes() for p in plans) > budget
    monkeypatch.setattr(sd, "_MESH_PLAN_MAX_BYTES", budget)
    middle = lookups()
    again, _ = sign_iteration(mat, steps=MAX_STEPS, filter_eps=EPS,
                              tol=TOL, mesh=mesh4)
    second = {k: v - middle.get(k, 0) for k, v in lookups().items()}
    assert {k: v for k, v in second.items() if v} == {"hit": products}
    cached = list(sd._mesh_plan_cache.values())
    assert sorted(map(id, cached)) == sorted(map(id, plans))
    assert sum(p.nbytes() for p in cached) <= budget
    assert not cached[0].panel_cache and cached[-1].panel_cache
    assert dt.checksum(again) == dt.checksum(x)
    assert np.array_equal(dt.to_dense(again), dt.to_dense(x))


def test_mesh_products_say_what_they_did_on_their_flight_records(
        mesh4, driver):
    """One record a product, with the flops and the surviving blocks:
    what the cell's check reads to name the first product that differs
    from the reference."""
    from dbcsr_tpu.obs import flight

    h, mat = _hamiltonian(40, 5)
    want = sc.reference_chain(h, filter_eps=EPS, tol=TOL,
                              max_steps=MAX_STEPS)
    sign_iteration(mat, steps=MAX_STEPS, filter_eps=EPS, tol=TOL,
                   mesh=mesh4)
    records = flight.records()[-len(want.products):]
    assert [r["op"] for r in records] == ["mesh_multiply"] * len(records)
    assert [(r["flops"], r["kept_blocks"]) for r in records] == [
        (p["flops"], p["c_born"] - p["c_dropped"]) for p in want.products]


# --------------------------------------------- without a mesh: as it was
def _step_as_it_was(x, filter_eps):
    """`sign_step` as it stood before it took a mesh: the two products
    through `mm.multiply` into matrices made here."""
    with mempool.chain() as ch:
        x2 = BlockSparseMatrix("X2", x.row_blk_sizes, x.col_blk_sizes,
                               x.dtype, x.dist)
        flops = multiply("N", "N", 1.0, x, x, 0.0, x2, filter_eps=filter_eps)
        scale(x2, -1.0)
        add_on_diag(x2, 3.0)
        out = BlockSparseMatrix("X'", x.row_blk_sizes, x.col_blk_sizes,
                                x.dtype, x.dist)
        flops += multiply("N", "N", 0.5, x, x2, 0.0, out,
                          filter_eps=filter_eps)
        ch.retire(x2)
        ch.detach(out)
    return out, int(flops)


@pytest.mark.parametrize("filter_eps", [EPS, None])
def test_step_without_a_mesh_gives_the_bits_it_gave(driver, filter_eps):
    h, mat = _hamiltonian(40, 6)
    x = scale(dt.copy(mat, name="X"), 1.0 / sc.gershgorin(h))
    for _ in range(3):
        was, flops = _step_as_it_was(x, filter_eps)
        now = sign_step(x, filter_eps=filter_eps)
        assert now._last_flops == flops
        assert np.array_equal(_keys(now), _keys(was))
        assert [b.shape for b in now.bins] == [b.shape for b in was.bins]
        for got, want in zip(now.bins, was.bins):
            assert np.array_equal(np.asarray(got.data),
                                  np.asarray(want.data))
        x = now
