"""Filtered float64 products on atom blocks {5, 13, 23} with a ragged
last block, as `mixed10k_filtered.scf_f64` sends them, at a few hundred
rows: on a device that emulates f64 (`config.platform_override`, the
seam `emulated_dtype_on_tpu` documents) `prepare_stack` groups the spans
of 2 048 entries and more and multiplies them in the sliced form, and
leaves the short ones to a per-entry driver: more than one stack driver
in one product.  What each launch counts says which span took which:
`dbcsr_tpu_stack_entries_total{driver, mnk, kind}`, the `mnk` label of
`dbcsr_tpu_device_entries_total` and `dbcsr_tpu_stack_dot_total`, and
`stats.driver_rollup()[driver]["entries_by_mnk"]`; they move at the
launch, so a product whose plans all hit the plan cache counts like the
one that made them.
"""

import types

import numpy as np
import pytest

import dbcsr_tpu as dt
from dbcsr_tpu.core import stats
from dbcsr_tpu.core.config import get_config, set_config
from dbcsr_tpu.obs import metrics

from benchmark import arithmetic

# 29 blocks of 5, 7 of 13, 7 of 23 and a ragged 3: at occupancy 0.7 the
# seven triples with two or three dimensions of 5 pass 2 048 entries
# (about 11 900 and 2 900), the other twenty and the ragged ones do not
SIZES = arithmetic.expand_block_sizes(400, [[4, 5], [1, 13], [1, 23]])
OCC = 0.7
EPS = 1e-7
COUNTERS = ("dbcsr_tpu_stack_entries_total", "dbcsr_tpu_device_entries_total",
            "dbcsr_tpu_stack_dot_total", "dbcsr_tpu_stack_gather_total",
            "dbcsr_tpu_stack_slots_total", "dbcsr_tpu_plan_cache_total")


@pytest.fixture
def fake_tpu():
    was = get_config().platform_override
    set_config(platform_override="tpu")
    yield
    set_config(platform_override=was)


def _snapshot() -> dict:
    return {name: {tuple(sorted(lab.items())): v
                   for lab, v in metrics.counter_items(name)}
            for name in COUNTERS}


def _moved(before: dict, after: dict, name: str, **labels) -> dict:
    """{the other labels' values: delta} of the series under ``labels``."""
    out = {}
    for key, v in after[name].items():
        lab = dict(key)
        if all(lab.get(k) == want for k, want in labels.items()):
            rest = tuple(v2 for k, v2 in key if k not in labels)
            delta = v - before[name].get(key, 0)
            if delta:
                out[rest] = out.get(rest, 0) + delta
    return out


def _entries_by_triple(a, b) -> dict:
    """{(m, n, k): stack entries} of A @ B, from the patterns alone."""
    ar, ac = a.entry_coords()
    br, bc = b.entry_coords()
    return {(m, n, k): e for m, n, k, e, _ in arithmetic.product_stacks(
        ar, ac, br, bc, SIZES, SIZES, SIZES)}


def _product(a, b, eps=EPS):
    c = dt.create("C", SIZES, SIZES, np.float64)
    flops = dt.multiply("N", "N", 1.0, a, b, 0.0, c, filter_eps=eps)
    return c, int(flops)


def _label(t) -> str:
    return "%dx%dx%d" % t


@pytest.fixture(scope="module")
def two_products():
    """Two products of the same operands under the override, with the
    counters before, between and after."""
    was = get_config().platform_override
    set_config(platform_override="tpu")
    try:
        rng = np.random.default_rng(39)
        a = dt.make_random_matrix("A", SIZES, SIZES, np.float64, OCC, rng=rng)
        b = dt.make_random_matrix("B", SIZES, SIZES, np.float64, OCC, rng=rng)
        rolled0 = stats.driver_rollup()
        s0 = _snapshot()
        c1, flops1 = _product(a, b)
        s1, rolled1 = _snapshot(), stats.driver_rollup()
        c2, flops2 = _product(a, b)
        s2 = _snapshot()
    finally:
        set_config(platform_override=was)
    want = _entries_by_triple(a, b)
    return types.SimpleNamespace(
        a=a, b=b, c1=c1, c2=c2, flops1=flops1, flops2=flops2, s0=s0, s1=s1,
        s2=s2, rolled0=rolled0, rolled1=rolled1, want=want,
        grouped={t for t, e in want.items() if e >= 2048})


def test_the_blocking_is_the_cells_and_both_sides_of_the_gate(two_products):
    assert set(SIZES[:-1].tolist()) == {5, 13, 23} and SIZES[-1] == 3
    assert SIZES.sum() == 400
    p = two_products
    assert p.grouped == {(5, 5, 5), (5, 5, 13), (5, 13, 5), (13, 5, 5),
                         (5, 5, 23), (5, 23, 5), (23, 5, 5)}
    assert any(3 in t for t in p.want)
    assert len(p.want) > 27 + len(p.grouped)


def test_product_is_numpys_at_the_benchmarks_tolerance(two_products):
    p = two_products
    ref = dt.to_dense(p.a) @ dt.to_dense(p.b)
    tol = arithmetic.reference_tolerance("float64", 23, len(SIZES))
    assert np.abs(dt.to_dense(p.c1) - ref).max() <= tol * np.abs(ref).max()
    assert p.flops1 == sum(2 * m * n * k * e
                           for (m, n, k), e in p.want.items())


def test_every_grouped_span_is_counted_sliced_under_its_mnk(two_products):
    p = two_products
    dots = _moved(p.s0, p.s1, "dbcsr_tpu_stack_dot_total")
    assert dots == {("sliced", _label(t)): 1 for t in p.grouped}
    # summed over the new label: the spans launched
    assert sum(dots.values()) == sum(_moved(
        p.s0, p.s1, "dbcsr_tpu_stack_gather_total").values()) \
        == len(p.grouped)


def test_every_span_is_counted_under_the_driver_that_took_it(two_products):
    p = two_products
    live = _moved(p.s0, p.s1, "dbcsr_tpu_stack_entries_total", kind="live")
    assert {mnk for drv, mnk in live if drv == "xla_group"} == \
        {_label(t) for t in p.grouped}
    assert {drv for drv, _ in live} == {"xla_group", "xla"}
    assert {mnk: e for (_, mnk), e in live.items()} == \
        {_label(t): e for t, e in p.want.items()}
    # live + pad is what the device works through
    pad = _moved(p.s0, p.s1, "dbcsr_tpu_stack_entries_total", kind="pad")
    launched = _moved(p.s0, p.s1, "dbcsr_tpu_device_entries_total")
    assert set(launched) == set(live)
    for key, n in launched.items():
        assert n == live[key] + pad.get(key, 0) and n >= live[key]


def test_sums_over_mnk_are_the_planned_tiles_and_the_rollup_splits_them(
        two_products):
    p = two_products
    live = _moved(p.s0, p.s1, "dbcsr_tpu_stack_entries_total", kind="live")
    launched = _moved(p.s0, p.s1, "dbcsr_tpu_device_entries_total")
    group_live = sum(e for (drv, _), e in live.items() if drv == "xla_group")
    group_launched = sum(e for (drv, _), e in launched.items()
                         if drv == "xla_group")
    # what the plans counted where they were made (PR 31)
    assert _moved(p.s0, p.s1, "dbcsr_tpu_stack_slots_total") == {
        ("live",): group_live, ("launched",): group_launched}
    assert group_live == sum(p.want[t] for t in p.grouped) < group_launched
    was, now = (r.get("xla_group", {}) for r in (p.rolled0, p.rolled1))
    assert now["slots_live"] - was.get("slots_live", 0) == group_live
    assert now["slots_launched"] - was.get("slots_launched", 0) == \
        group_launched
    for t in p.grouped:
        before = was.get("entries_by_mnk", {}).get(
            _label(t), {"live": 0, "launched": 0})
        after = now["entries_by_mnk"][_label(t)]
        assert after["live"] - before["live"] == p.want[t]
        assert after["launched"] - before["launched"] == \
            launched[("xla_group", _label(t))]
    assert set(p.rolled1["xla"]["entries_by_mnk"]) >= \
        {_label(t) for t in p.want if t not in p.grouped}


def test_a_product_of_cached_plans_counts_what_the_first_counted(
        two_products):
    p = two_products
    assert _moved(p.s1, p.s2, "dbcsr_tpu_plan_cache_total") == {("hit",): 1}
    # nothing was planned
    assert _moved(p.s1, p.s2, "dbcsr_tpu_stack_slots_total") == {}
    for name in ("dbcsr_tpu_stack_entries_total",
                 "dbcsr_tpu_device_entries_total",
                 "dbcsr_tpu_stack_dot_total"):
        assert _moved(p.s1, p.s2, name) == _moved(p.s0, p.s1, name) != {}, \
            name


def test_product_is_bit_identical_run_to_run(two_products):
    p = two_products
    assert p.flops2 == p.flops1
    assert dt.checksum(p.c2) == dt.checksum(p.c1)
    np.testing.assert_array_equal(dt.to_dense(p.c2), dt.to_dense(p.c1))


def test_a_filter_that_drops_agrees_with_numpys_on_every_bin(fake_tpu):
    """Half the blocks of A and B are 1e-20 of the others, so what they
    bring to C is under the rounding of any block the others reach and a
    C block that only they reach has a norm of 1e-19: the filter keeps
    the blocks NumPy's filter of the reference keeps, in all sixteen
    (m, n) bins, with the values of the reference."""
    rng = np.random.default_rng(40)
    mats = []
    for name in "AB":
        m = dt.make_random_matrix(name, SIZES, SIZES, np.float64, 0.25,
                                  rng=rng)
        rows, cols = m.entry_coords()
        small = rng.random(len(rows)) < 0.5
        faint = dt.create(name, SIZES, SIZES, np.float64)
        for r, c, s in zip(rows, cols, small):
            faint.put_block(int(r), int(c),
                            m.get_block(int(r), int(c)) * (1e-20 if s else 1))
        mats.append(faint.finalize())
    a, b = mats
    c, _ = _product(a, b)
    ref = dt.to_dense(a) @ dt.to_dense(b)
    off = np.concatenate([[0], np.cumsum(SIZES)])
    nb = len(SIZES)
    norms = np.array([[np.linalg.norm(ref[off[i]:off[i + 1],
                                          off[j]:off[j + 1]])
                       for j in range(nb)] for i in range(nb)])
    keep = norms >= EPS
    reached = norms > 0
    assert 0 < keep.sum() < reached.sum()
    rows, cols = c.entry_coords()
    kept = np.zeros((nb, nb), bool)
    kept[rows, cols] = True
    for m in np.unique(SIZES):
        for n in np.unique(SIZES):
            sel = np.ix_(SIZES == m, SIZES == n)
            assert (kept[sel] == keep[sel]).all(), (m, n)
            if 3 not in (m, n):  # a ragged bin holds one row or column
                assert keep[sel].any() and \
                    (reached[sel] & ~keep[sel]).any(), (m, n)
    tol = arithmetic.reference_tolerance("float64", 23, nb)
    want = np.where(np.repeat(np.repeat(keep, SIZES, 0), SIZES, 1), ref, 0.0)
    assert np.abs(dt.to_dense(c) - want).max() <= tol * np.abs(ref).max()
