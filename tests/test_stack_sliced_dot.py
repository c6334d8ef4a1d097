"""The sliced dot of the grouped stack loop (`acc.smm.group_dot_form`
"sliced": real f64 on a device that emulates it): the operands are cut
into bf16 slices once per stored block (`_bf16_slices`), a group's
strips are its gathered slice blocks set on end (`_slice_blocks`) and
its product is one native dot whose tiles are exact (`_sliced_dot`).

On a CPU f64 is native, so the form runs here as a static argument of
the programs, and through a plan only under `config.platform_override`
(the seam `emulated_dtype_on_tpu` documents).  Every other dtype and
precision keeps the compiler's dot, bit for bit the loop PR 33 left.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dbcsr_tpu.acc import smm
from dbcsr_tpu.core.config import get_config, set_config

S = smm.SLICES


@pytest.fixture
def fake_tpu():
    was = get_config().platform_override
    set_config(platform_override="tpu")
    yield
    set_config(platform_override=was)


# ------------------------------------------------------------------ the cut
def _values(kind, shape, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape)
    if kind == "decaying":  # four decades inside a block
        x *= 10.0 ** rng.uniform(-4, 0, shape)
    elif kind == "half_zero":
        x[rng.random(shape) < 0.5] = 0.0
    elif kind == "huge":  # up to where a product leaves f32's range
        x = np.sign(x) * rng.uniform(1, 2, shape) \
            * 2.0 ** rng.integers(100, 126, shape)
    elif kind == "denormal_adjacent":  # lo ends in the last window kept
        x = np.sign(x) * rng.uniform(1, 2, shape) \
            * 2.0 ** rng.integers(-67, -60, shape)
    elif kind == "one_grid":  # every leading one in window 0
        x = np.sign(x) * rng.uniform(1, 64, shape)
    elif kind == "sixty_binades":
        x *= 2.0 ** rng.integers(-30, 30, shape)
    elif kind == "powers_of_two":  # and their neighbours below
        x = np.sign(x) * 2.0 ** rng.integers(-20, 20, shape) \
            * (1 - (rng.random(shape) < 0.5) * 2.0 ** -53)
    return x


KINDS = ("random", "decaying", "half_zero", "huge", "denormal_adjacent",
         "sixty_binades", "powers_of_two")


def _halves(x):
    """The two f32 halves a TPU keeps an f64 in, summed in f64."""
    hi = x.astype(np.float32)
    lo = (x - hi).astype(np.float32)
    return hi.astype(np.float64) + lo.astype(np.float64)


def _slices(x):
    sl = jax.jit(smm._bf16_slices)(jnp.asarray(x))
    assert sl.dtype == jnp.bfloat16 and sl.shape == (len(x), S) + x.shape[1:]
    return np.asarray(sl.astype(jnp.float64))


@pytest.mark.parametrize("kind", KINDS)
def test_slices_sum_to_the_two_f32_halves_exactly(kind):
    x = _values(kind, (6, 23, 19))
    sl = _slices(x)
    total = np.zeros(x.shape, np.longdouble)
    for i in range(S):  # disjoint windows: any order is exact
        total += sl[:, i]
    np.testing.assert_array_equal(total.astype(np.float64), _halves(x))


@pytest.mark.parametrize("kind", KINDS)
def test_a_slice_is_a_digit_of_its_window_and_survives_bf16(kind):
    """A slice is an integer of -128 to 127 times 2^(8w), w = the
    slice's index mod 8: so a bf16 holds it, and a product of two has
    14 bits."""
    x = _values(kind, (6, 23, 19), seed=1)
    sl = _slices(x)
    again = np.asarray(jnp.asarray(sl).astype(jnp.bfloat16)
                       .astype(jnp.float64))
    np.testing.assert_array_equal(again, sl)
    mant, exp = np.frexp(sl)  # sl = mant * 2^exp, 0.5 <= |mant| < 1
    nz = sl != 0
    for i in range(S):
        # the window: the largest w = i (mod 8) whose unit is not over
        # the slice
        w = (exp[:, i] - 1 - ((exp[:, i] - 1 - 8 * i) % 64)) // 8
        digit = np.abs(sl[:, i]) / 2.0 ** (8 * w)
        assert (w[nz[:, i]] % S == i).all()
        assert (digit[nz[:, i]] == np.round(digit[nz[:, i]])).all()
        assert digit[nz[:, i]].max(initial=0) <= 128


def test_bits_below_two_to_minus_120_are_dropped_and_nothing_else():
    x = _values("random", (4, 23, 23), seed=2) * 2.0 ** -100
    sl = _slices(x)
    lost = np.abs(sl.sum(axis=1) - _halves(x))
    assert 0 < lost.max() < 2.0 ** -120


# ----------------------------------------------------- the slice-pair dots
def _strips(a, b):
    """The sliced strips of ONE group whose slots are a's and b's
    blocks, as `group_chunk_loop` sets them on end."""
    a_sl = smm._slice_blocks(jnp.asarray(a), 2)
    b_sl = smm._slice_blocks(jnp.asarray(b), 1)
    w = len(a)
    return (a_sl.reshape(1, w * a_sl.shape[1], -1),
            b_sl.reshape(1, w * b_sl.shape[1], -1))


@pytest.mark.parametrize("kind", ["one_grid", "random", "decaying",
                                  "sixty_binades"])
def test_every_slice_pair_dot_at_depth_184_is_exact(kind):
    """Depth 8 x 23: each of the 64 (m, n) tiles of the group's one
    dot is the NumPy f64 product of its two slices, which is exact (22
    bits): to the bit where the strip's elements share their leading
    window; where they do not, a slice index holds windows 2^64 apart
    and the lower falls under the upper's last bit."""
    m = n = k = 23
    a = _values(kind, (8, m, k), seed=3)
    b = _values(kind, (8, k, n), seed=4)
    amat, bmat = _strips(a, b)
    assert amat.shape == (1, 8 * 32, S * 24) and amat.dtype == jnp.bfloat16
    assert bmat.shape == (1, 8 * 32, S * 23)
    tiles = jax.lax.dot_general(amat, bmat, (((1,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
    tiles = np.asarray(tiles, np.float64).reshape(S, 24, S, n)
    a_sl, b_sl = _slices(a), _slices(b)
    for i in range(S):
        for j in range(S):
            want = np.einsum("wmk,wkn->mn", a_sl[:, i], b_sl[:, j])
            if kind == "one_grid":
                np.testing.assert_array_equal(tiles[i, :m, j], want)
            else:
                size = np.einsum("wmk,wkn->mn", np.abs(a_sl[:, i]),
                                 np.abs(b_sl[:, j]))
                assert (np.abs(tiles[i, :m, j] - want)
                        <= 2.0 ** -40 * size).all()
    assert not tiles[:, m:].any()  # the zero rows that fill A's tiles


def test_sliced_depth_stops_where_a_tile_could_round():
    assert smm.SLICED_MAX_DEPTH * 128 * 128 == 2 ** 24


# ------------------------------------------------------- the group product
def _stack(m, n, k, runs, seed, kind="decaying"):
    rng = np.random.default_rng(seed)
    na, nb = 30, 31
    a = _values(kind, (na, m, k), seed=seed + 1)
    b = _values(kind, (nb, k, n), seed=seed + 2)
    ci = np.repeat(np.arange(len(runs)), runs).astype(np.int32)
    ai = rng.integers(0, na, len(ci)).astype(np.int32)
    bi = rng.integers(0, nb, len(ci)).astype(np.int32)
    return a, b, ai, bi, ci


def _group_product(a, b, ai, bi, ci, nseg, c0, dot_form, alpha=1.0,
                   prec=None, r0=8):
    tiles = smm.build_group_tiles(ci, ai, bi, r0, len(a), len(b), nseg, 16)
    idx = [jnp.asarray(np.int32(tiles.live))] \
        + [jnp.asarray(x) for x in tiles.flat()]
    got = smm._process_stack_xla_group(
        jnp.array(c0), smm._append_pad_row(jnp.asarray(a)),
        smm._append_pad_row(jnp.asarray(b)), *idx,
        jnp.asarray(alpha, c0.dtype), prec=prec, dot_form=dot_form)
    return np.asarray(got), tiles


# entries of each C block: the widths the plan opens at r0 = 8
RUNS = {
    "w8_4_2_1": np.tile([1, 2, 3, 4, 5, 8, 11], 9),
    "w8": np.full(40, 8),
    "w1": np.ones(60, int),
    "long_runs": np.arange(30) % 9 + 17,
}


@pytest.mark.parametrize("mnk", [(23, 23, 23), (23, 18, 23), (5, 13, 23),
                                 (13, 5, 5), (4, 3, 40)])
@pytest.mark.parametrize("runs", sorted(RUNS))
def test_sliced_group_product_is_the_numpy_product(runs, mnk):
    """Element by element within 2^-46 of sum |a||b| (the two f32
    halves hold 2^-48 of an element; the tiles are exact), for widths
    8 / 4 / 2 / 1, depths that fill no tile (5, 23, 40 to 16, 32, 48)
    and the pad row of short groups."""
    m, n, k = mnk
    a, b, ai, bi, ci = _stack(m, n, k, RUNS[runs], seed=7)
    nseg = len(RUNS[runs]) + 2
    c0 = _values("random", (nseg, m, n), seed=5)
    got, tiles = _group_product(a, b, ai, bi, ci, nseg, c0, "sliced",
                                alpha=0.75)
    if runs == "w8_4_2_1":
        assert tiles.widths[:3] == (8, 4, 2)
    want, bound = c0.copy(), np.abs(c0)
    np.add.at(want, ci, 0.75 * (a[ai] @ b[bi]))
    np.add.at(bound, ci, 0.75 * (np.abs(a[ai]) @ np.abs(b[bi])))
    assert (np.abs(got - want) <= 2.0 ** -46 * bound).all()
    # and nothing of C that the stack does not name is touched
    np.testing.assert_array_equal(got[nseg - 2:], c0[nseg - 2:])


def test_sliced_group_product_is_bit_identical_run_to_run():
    a, b, ai, bi, ci = _stack(23, 23, 23, RUNS["w8_4_2_1"], seed=9)
    nseg = len(RUNS["w8_4_2_1"])
    c0 = np.zeros((nseg, 23, 23))
    one, _ = _group_product(a, b, ai, bi, ci, nseg, c0, "sliced")
    two, _ = _group_product(a, b, ai, bi, ci, nseg, c0, "sliced")
    np.testing.assert_array_equal(one, two)


# ------------------------------------------- who takes the form, who not
def test_form_is_sliced_for_emulated_real_f64_alone(fake_tpu):
    form = smm.group_dot_form
    assert form(np.float64, 184) == "sliced"
    assert form(np.float64, smm.SLICED_MAX_DEPTH) == "sliced"
    assert form(np.float64, smm.SLICED_MAX_DEPTH + 8) == "compiler"
    assert form(np.float32, 184) == "compiler"
    assert form(np.complex128, 184) == "compiler"
    assert form("bfloat16", 184) == "compiler"
    assert form(np.float64, 184, prec=("float32", False)) == "compiler"
    assert form(np.float64, 184, prec=("float32", True)) == "compiler"


def test_form_is_the_compilers_where_f64_is_native():
    assert smm.group_dot_form(np.float64, 184) == "compiler"


def _loop_of_pr33(c, a, b, live, *tiles_alpha, prec=None):
    """`group_chunk_loop` as PR 33 left it: the reference the
    compiler's form is held to bit for bit."""
    *flat, alpha = tiles_alpha
    tiles = [flat[i:i + 3] for i in range(0, len(flat), 3)]
    live = jnp.reshape(live, ())
    _, m, n = c.shape
    k = a.shape[2]
    a_rows, b_rows = smm._block_rows(a), smm._block_rows(b)
    acc = smm._accum_dtype(c.dtype)

    def body(t, c):
        for ga, gb, gc in tiles:
            _, ch, w = ga.shape
            ia = jax.lax.dynamic_index_in_dim(ga, t, keepdims=False)
            ib = jax.lax.dynamic_index_in_dim(gb, t, keepdims=False)
            ic = jax.lax.dynamic_index_in_dim(gc, t, keepdims=False)
            ablk = smm._take_rows(a_rows, ia.reshape(-1)).reshape(ch, w, m, k)
            bblk = smm._take_rows(b_rows, ib.reshape(-1))
            amat = jnp.swapaxes(ablk, 1, 2).reshape(ch, m, w * k)
            bmat = bblk.reshape(ch, w * k, n)
            ragged = -(w * k) % 8
            if ragged:
                amat = jnp.pad(amat, ((0, 0), (0, 0), (0, ragged)))
                bmat = jnp.pad(bmat, ((0, 0), (0, ragged), (0, 0)))
            prod = smm._batch_dot(amat, bmat, acc, prec)
            prod = (alpha.astype(acc) * prod).astype(c.dtype)
            c = smm._accumulate_chunk(c, prod, ic)
        return c

    return jax.lax.fori_loop(0, live, body, c)


@pytest.mark.parametrize("dtype,prec", [
    ("float32", None), ("float64", None), ("complex128", None),
    ("bfloat16", None),
    ("float64", ("float32", False)), ("float64", ("float32", True)),
    ("float32", ("bfloat16", False)), ("float32", ("bfloat16", True)),
], ids=lambda v: "native" if v is None else
   v if isinstance(v, str) else f"{v[0]}{'+comp' if v[1] else ''}")
def test_every_other_dtype_and_precision_keeps_its_bits(dtype, prec,
                                                        request):
    """Whatever is not real f64 executed as it is on a device that
    emulates it (asked under the override; native f64 without it) plans
    the compiler's form, and that form is the loop it was."""
    if (dtype, prec) != ("float64", None):
        request.getfixturevalue("fake_tpu")
    m, n, k = 5, 4, 3
    a, b, ai, bi, ci = _stack(m, n, k, RUNS["w8_4_2_1"], seed=11,
                              kind="random")
    if dtype == "complex128":
        a = a + 1j * a[::-1]
        b = b - 1j * b[::-1]
    nseg = len(RUNS["w8_4_2_1"])
    a, b = (jnp.asarray(x).astype(dtype) for x in (a, b))
    c0 = jnp.zeros((nseg, m, n), dtype)
    form = smm.group_dot_form(dtype, 8 * k, prec)
    assert form == "compiler"
    tiles = smm.build_group_tiles(ci, ai, bi, 8, len(a), len(b), nseg, 16)
    idx = [jnp.asarray(np.int32(tiles.live))] \
        + [jnp.asarray(x) for x in tiles.flat()]
    args = (smm._append_pad_row(a), smm._append_pad_row(b), *idx,
            jnp.asarray(1.5, dtype))
    got = smm._process_stack_xla_group(jnp.array(c0), *args, prec=prec,
                                       dot_form=form)
    was = jax.jit(_loop_of_pr33, static_argnames=("prec",))(
        jnp.array(c0), *args, prec=prec)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(was))


# ------------------------------------------------ the plan and the counter
def _counts():
    """(spans by the form of their dot, spans by gather layout, the
    rollup's field)."""
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.obs import metrics

    forms = {}
    for lab, v in metrics.counter_items("dbcsr_tpu_stack_dot_total"):
        forms[lab["form"]] = forms.get(lab["form"], 0) + v  # over mnk
    spans = sum(v for _, v in metrics.counter_items(
        "dbcsr_tpu_stack_gather_total"))
    rolled = stats.driver_rollup().get("xla_group", {}).get("dot_forms", {})
    return forms, spans, dict(rolled)


def _product(dtype, filter_eps):
    """A product whose stacks pass the 2 048 entries that make an
    emulated dtype group them, through `dt.multiply`."""
    import dbcsr_tpu as dt

    rng = np.random.default_rng(17)
    sizes = np.full(36, 5, np.int32)
    a = dt.make_random_matrix("A", sizes, sizes, dtype, 0.5, rng=rng)
    b = dt.make_random_matrix("B", sizes, sizes, dtype, 0.5, rng=rng)
    c = dt.create("C", sizes, sizes, dtype)
    dt.multiply("N", "N", 1.0, a, b, 0.0, c, filter_eps=filter_eps)
    return dt.to_dense(a), dt.to_dense(b), dt.to_dense(c)


def test_filtered_f64_product_counts_every_span_sliced(fake_tpu):
    forms0, spans0, rolled0 = _counts()
    a, b, c = _product(np.float64, 1e-9)
    forms1, spans1, rolled1 = _counts()
    launched = spans1 - spans0
    assert launched >= 1
    assert forms1.get("sliced", 0) - forms0.get("sliced", 0) == launched
    assert forms1.get("compiler", 0) == forms0.get("compiler", 0)
    assert rolled1.get("sliced", 0) - rolled0.get("sliced", 0) == launched
    assert rolled1.get("compiler", 0) == rolled0.get("compiler", 0)
    np.testing.assert_allclose(c, a @ b, rtol=0, atol=2.0 ** -44
                               * (np.abs(a) @ np.abs(b)).max())


def test_f32_product_counts_nothing(fake_tpu):
    forms0, spans0, _ = _counts()
    a, b, c = _product(np.float32, None)
    forms1, spans1, _ = _counts()
    assert forms1 == forms0 and spans1 == spans0
    np.testing.assert_allclose(c, a @ b, rtol=1e-4, atol=1e-4)


def test_a_forced_native_group_span_counts_the_compilers_form():
    """`mm_driver="xla_group"` groups any dtype on any platform (how the
    CPU suite covers the layout): native f64 keeps the compiler's dot
    and says so."""
    m = n = k = 5
    a, b, ai, bi, ci = _stack(m, n, k, RUNS["w8_4_2_1"], seed=13,
                              kind="random")
    nseg = len(RUNS["w8_4_2_1"])
    forms0, _, _ = _counts()
    set_config(mm_driver="xla_group")
    try:
        plan = smm.prepare_stack(jnp.zeros((nseg, m, n)), jnp.asarray(a),
                                 jnp.asarray(b), ai, bi, ci)
        got = smm.execute_stack(jnp.zeros((nseg, m, n)), jnp.asarray(a),
                                jnp.asarray(b), plan, 1.0)
    finally:
        set_config(mm_driver="auto")
    forms1, _, _ = _counts()
    assert plan.dot_form == "compiler"
    assert forms1.get("compiler", 0) - forms0.get("compiler", 0) == 1
    assert forms1.get("sliced", 0) == forms0.get("sliced", 0)
    want = np.zeros((nseg, m, n))
    np.add.at(want, ci, a[ai] @ b[bi])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-13, atol=1e-13)


def test_a_plan_under_the_override_is_sliced_and_runs(fake_tpu):
    m = n = k = 5
    runs = np.tile([1, 2, 3, 4, 5, 8, 11], 70)  # 2 380 entries: past 2 048
    a, b, ai, bi, ci = _stack(m, n, k, runs, seed=13)
    c0 = jnp.zeros((len(runs), m, n))
    plan = smm.prepare_stack(c0, jnp.asarray(a), jnp.asarray(b), ai, bi, ci)
    assert plan.driver == "xla_group" and plan.dot_form == "sliced"
    got = np.asarray(smm.execute_stack(c0, jnp.asarray(a), jnp.asarray(b),
                                       plan, 1.0))
    want, bound = np.zeros(got.shape), np.zeros(got.shape)
    np.add.at(want, ci, a[ai] @ b[bi])
    np.add.at(bound, ci, np.abs(a[ai]) @ np.abs(b[bi]))
    assert (np.abs(got - want) <= 2.0 ** -46 * bound).all()


# ---------------------------------------------------------------- the mesh
def test_filtered_f64_product_on_a_mesh_is_sliced(fake_tpu):
    """The Cannon ticks run the same loop on a device's own panels: the
    cut once per tick, the counter once per product under its driver."""
    import dbcsr_tpu as dt
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.parallel import make_grid, sparse_multiply_distributed

    def mesh_forms():
        return dict(stats.driver_rollup().get("mesh", {}).get("dot_forms",
                                                              {}))

    rng = np.random.default_rng(19)
    sizes = np.full(12, 5, np.int32)
    a = dt.make_random_matrix("A", sizes, sizes, np.float64, 0.6, rng=rng)
    b = dt.make_random_matrix("B", sizes, sizes, np.float64, 0.6, rng=rng)
    forms0, rolled0 = _counts()[0], mesh_forms()
    c = sparse_multiply_distributed(1.0, a, b, 0.0, None, make_grid(4),
                                    filter_eps=1e-9)
    forms1, rolled1 = _counts()[0], mesh_forms()
    assert forms1.get("sliced", 0) - forms0.get("sliced", 0) == 1
    assert forms1.get("compiler", 0) == forms0.get("compiler", 0)
    assert rolled1.get("sliced", 0) - rolled0.get("sliced", 0) == 1
    a_d, b_d = dt.to_dense(a), dt.to_dense(b)
    np.testing.assert_allclose(
        dt.to_dense(c), a_d @ b_d, rtol=0,
        atol=2.0 ** -44 * (np.abs(a_d) @ np.abs(b_d)).max())
