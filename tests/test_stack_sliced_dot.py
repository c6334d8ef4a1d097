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


# ------------------------------------------------------ the weight classes
FOLDS = (8, 4, 2, 1)


def test_fold_is_the_widest_whose_class_sum_stays_exact():
    big = smm.SLICED_FOLD_MIN_GROUPS
    assert [smm.sliced_fold(d, big) for d in (16, 128, 129, 256, 512, 1024)] \
        == [8, 8, 4, 4, 2, 1]
    for f in FOLDS:
        assert smm.sliced_fold(smm.SLICED_MAX_DEPTH // f, big) == f
    # the mixed10k_filtered strips: k = 5, 13 fill 16 rows, 19, 23 fill 32
    assert [smm.sliced_depth(k) for k in (5, 13, 19, 23)] == [16, 16, 32, 32]
    assert [smm.sliced_fold(w * 32, big) for w in (1, 2, 4, 8)] \
        == [8, 8, 8, 4]


def test_a_chunk_of_few_groups_keeps_its_tiles_unfolded():
    """Under `SLICED_FOLD_MIN_GROUPS` groups a chunk's tiles go to the
    f64 sums as they are (the north star's chunks: 176, 144 and 64
    groups of widths 8, 4, 2), and the sum is the one without a fold,
    bit for bit."""
    small = smm.SLICED_FOLD_MIN_GROUPS - 1
    assert [smm.sliced_fold(d, small) for d in (16, 256, 1024)] == [1, 1, 1]
    assert [smm.sliced_fold(256, g) for g in (64, 144, 176)] == [1, 1, 1]
    a, b = _values("random", (2, 23, 23), 31), _values("random", (2, 23, 23), 32)
    amat, bmat = _strips(a, b)
    amat, bmat = (jnp.broadcast_to(x, (64,) + x.shape[1:]) for x in (amat, bmat))
    got = jax.jit(smm._sliced_dot, static_argnums=(2, 3, 4))(
        amat, bmat, 23, 23, jnp.float64)
    tiles = jax.lax.dot_general(amat, bmat, (((1,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
    rows = smm._halve(tiles.reshape(64, S, 24, S * 23).astype(jnp.float64), 1)
    want = smm._halve(rows.reshape(64, 24, S, 23), 2)[:, :23]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("f", FOLDS)
def test_a_partial_class_holds_the_tiles_of_its_class_and_block(f):
    """Batch t holds tile (i, j) = divmod(t, 8) as the integer t + 1 and
    nothing else: partial class c of i-block b holds it exactly where
    b = i // f and c = (i + j) mod 8, and no add rounds; at f = 1 the
    tiles stay where they are."""
    mp, n = 8, 3
    tiles = np.zeros((S * S, S * mp, S * n), np.float32)
    for t in range(S * S):
        i, j = divmod(t, S)
        tiles[t, i * mp:(i + 1) * mp, j * n:(j + 1) * n] = t + 1
    hi, lo = smm._fold_classes(jnp.asarray(tiles), n, f)
    hi = np.asarray(hi)
    assert hi.shape == ((S * S, S, mp, S * n) if f == 1
                        else (S * S, S // f, mp, S, n))
    hi = hi.reshape(S * S, S // f, mp, S, n)
    for t in range(S * S):
        i, j = divmod(t, S)
        want = np.zeros(hi.shape[1:], np.float32)
        want[i // f, :, (i + j) % S if f > 1 else j] = t + 1
        np.testing.assert_array_equal(hi[t], want)
    assert lo is None if f == 1 else \
        (np.asarray(lo).shape == (S * S, mp, n) and not np.asarray(lo).any())


def _class_strips(depth, split, odd_slice=5):
    """One group's sliced strips (1, depth, 8*8) and (1, depth, 8*2)
    built from digits and windows: slice i of A in window i - 4, slice
    j of B in window 4 - ((4 - j) mod 8), so class 4 lies on the one
    grid 2^0 and the others on two grids 2^64 apart; with ``split`` the
    A elements of every odd depth index lie 8 windows lower, so every
    class spans 2^64.  Every digit is -128 (products of 2^14), but at
    depth 0 A's slice ``odd_slice`` and its class-4 partner in B hold
    127: one odd product in one tile."""
    mp, n = 8, 2
    wa = np.arange(S) - 4 - 8 * (split * (np.arange(depth) % 2))[:, None]
    wb = 4 - (4 - np.arange(S)) % S
    da = np.full((depth, S), -128.0)
    db = np.full((depth, S), -128.0)
    da[0, odd_slice] = db[0, (4 - odd_slice) % S] = 127.0
    a = da * 2.0 ** (8 * wa)  # (depth, slice)
    b = db * 2.0 ** (8 * wb[None, :])
    amat = np.repeat(a[:, :, None], mp, 2).reshape(1, depth, S * mp)
    bmat = np.repeat(b[:, :, None], n, 2).reshape(1, depth, S * n)
    return a, b, jnp.asarray(amat, jnp.bfloat16), jnp.asarray(bmat,
                                                             jnp.bfloat16)


def _folded(amat, bmat, f):
    """The partial class tiles [block, class] of element (0, 0) and the
    folds' error term."""
    tiles = jax.lax.dot_general(amat, bmat, (((1,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
    hi, lo = smm._fold_classes(tiles, 2, f)
    hi = np.asarray(hi, np.float64).reshape(1, S // f, 8, S, 2)[0, :, 0, :, 0]
    if f == 1:  # the tiles as they are: (i, j) to (i, class i + j)
        return np.stack([np.roll(row, i) for i, row in enumerate(hi)]), 0.0
    return hi, float(lo[0, 0, 0])


def _exact_partials(a, b, f):
    """{(block, class): (exact sum of its products rounded once, sum of
    their magnitudes, whether they lie on one grid)}."""
    import math

    out = {}
    for blk in range(S // f):
        for c in range(S):
            terms = [a[k, i] * b[k, (c - i) % S] for i in
                     range(blk * f, (blk + 1) * f) for k in range(len(a))]
            out[blk, c] = (math.fsum(terms), math.fsum(map(abs, terms)),
                           _one_grid(a, b, blk, c, f))
    return out


def _one_grid(a, b, blk, c, f):
    """The products of a partial lie on one grid 2^(8s): every pair of
    windows of its tiles sums to one s (windows read off the values:
    a digit of 127 or 128 sets the window's 2^7 bit)."""
    sa = np.floor(np.log2(np.abs(a)) / 8).astype(int)
    sb = np.floor(np.log2(np.abs(b)) / 8).astype(int)
    return len({int(sa[k, i] + sb[k, (c - i) % S])
                for i in range(blk * f, (blk + 1) * f)
                for k in range(len(a))}) == 1


@pytest.mark.parametrize("split", [False, True], ids=["one_grid", "split"])
@pytest.mark.parametrize("f", FOLDS)
def test_partial_classes_are_exact_at_the_deepest_depth_of_their_fold(
        f, split):
    """At the deepest strip ``f`` is allowed (1 024 / f), with digits of
    128: a partial class tile whose products lie on one grid is their
    sum to the bit (every class-4 partial here, the odd one 2^24 - 255);
    where they lie 2^64 apart it is within 2^-40 of their magnitudes,
    as a single tile is.  Where the tiles are exact (one window a slice
    along the strip) what the f32 sums dropped comes back in the fold's
    error term: hi and lo together are within 2^-56 of the magnitudes
    of the exact sum; split, the tiles' own rounding stays."""
    import math

    depth = smm.SLICED_MAX_DEPTH // f
    assert smm.sliced_fold(depth, smm.SLICED_FOLD_MIN_GROUPS) == f
    a, b, amat, bmat = _class_strips(depth, split)
    hi, lo = _folded(amat, bmat, f)
    parts = _exact_partials(a, b, f)
    for (blk, c), (want, size, one) in parts.items():
        if one:
            assert hi[blk, c] == want, (blk, c)
        else:
            assert abs(hi[blk, c] - want) <= 2.0 ** -40 * size, (blk, c)
    ones = {key for key, (_, _, one) in parts.items() if one}
    if split:
        assert not ones
    else:
        assert {(blk, 4) for blk in range(S // f)} <= ones
        assert parts[5 // f, 4][0] == 2 ** 24 - 255
    total = math.fsum(w for w, _, _ in parts.values())
    size = math.fsum(s for _, s, _ in parts.values())
    got = math.fsum(hi.ravel().tolist() + [lo])
    assert abs(got - total) <= 2.0 ** (-40 if split else -56) * size
    if f > 1 and not split:  # folds of two grids drop what lo holds
        assert lo != 0 and len(ones) < len(parts)


@pytest.mark.parametrize("f", FOLDS)
def test_one_step_past_the_depth_a_class_sum_can_round(f):
    """Twice that depth, the odd partial of class 4 is 2^25 - 255: more
    bits than f32 holds, so the sum the fold keeps is not the exact
    one (for f = 1 the tile itself rounds in the dot)."""
    depth = 2 * smm.SLICED_MAX_DEPTH // f
    a, b, amat, bmat = _class_strips(depth, split=False)
    hi, _ = _folded(amat, bmat, f)
    want = _exact_partials(a, b, f)[5 // f, 4][0]
    assert want == 2 ** 25 - 255 and hi[5 // f, 4] != want


@pytest.mark.parametrize("groups", [64, 1024])
@pytest.mark.parametrize("depth", [256, 32])
def test_sliced_dot_traces_no_larger_than_a_few_dozen_equations(depth,
                                                                groups):
    """Every span of every fused program traces `_sliced_dot` once a
    width class (about 200 times in `mixed10k_filtered.scf_f64`'s 15
    programs), so what it adds to its jaxpr is paid in every start's
    trace and lowering: 19 equations without a fold (64 groups), 28
    and 30 with it (1 024 groups; fold 8 and 4)."""
    amat = jax.ShapeDtypeStruct((groups, depth, S * 24), jnp.bfloat16)
    bmat = jax.ShapeDtypeStruct((groups, depth, S * 23), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda x, y: smm._sliced_dot(x, y, 23, 23, jnp.float64))(amat, bmat)
    assert len(jaxpr.jaxpr.eqns) <= 30


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
                   prec=None, r0=8, chunk_groups=16):
    tiles = smm.build_group_tiles(ci, ai, bi, r0, len(a), len(b), nseg,
                                  chunk_groups)
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


@pytest.mark.parametrize("mnk", [(23, 23, 23), (5, 13, 23), (4, 3, 40)])
@pytest.mark.parametrize("width", [1, 2, 4, 8])
def test_folded_group_product_is_the_numpy_product(width, mnk):
    """The same bound where a chunk holds enough groups to fold its
    tiles: 300 runs of one width, one chunk of 320 groups, so every
    fold the depth allows is taken (8 up to depth 128, 4 at 192 and
    256, 2 at 384: k = 40 fills 48 rows)."""
    m, n, k = mnk
    runs = np.full(300, width)
    a, b, ai, bi, ci = _stack(m, n, k, runs, seed=8)
    nseg = len(runs) + 2
    c0 = _values("random", (nseg, m, n), seed=6)
    got, tiles = _group_product(a, b, ai, bi, ci, nseg, c0, "sliced",
                                alpha=0.75, chunk_groups=512)
    (ga, _, _), = tiles.tiles
    assert ga.shape[1:] == (320, width)
    assert smm.sliced_fold(width * smm.sliced_depth(k), 320) == \
        {(8, 23): 4, (8, 40): 2, (4, 40): 4}.get((width, k), 8)
    want, bound = c0.copy(), np.abs(c0)
    np.add.at(want, ci, 0.75 * (a[ai] @ b[bi]))
    np.add.at(bound, ci, 0.75 * (np.abs(a[ai]) @ np.abs(b[bi])))
    assert (np.abs(got - want) <= 2.0 ** -46 * bound).all()
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


def _folds():
    """Width classes launched sliced, by the tiles a class folds."""
    from dbcsr_tpu.obs import metrics

    return {lab["fold"]: v for lab, v in
            metrics.counter_items("dbcsr_tpu_sliced_fold_total")}


def _moved(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _expected_folds(classes, k):
    """{fold: width classes} for ((groups a chunk, width), ...)."""
    want = {}
    for groups, w in classes:
        fold = str(smm.sliced_fold(w * smm.sliced_depth(k), groups))
        want[fold] = want.get(fold, 0) + 1
    return want


@pytest.mark.parametrize("runs,folds", [
    ((8,), {"4"}),                          # one class of 256 groups a chunk
    ((1,), {"8"}),                          # width 1, 2 048 a chunk
    ((1, 1, 1, 2, 3, 4, 5, 8, 11), {"1"}),  # four classes, 64-320 a chunk
], ids=["w8", "w1", "w8_4_2_1"])
def test_sliced_spans_count_their_width_classes_by_fold(fake_tpu, runs,
                                                        folds):
    """At 23-blocks a slot brings 32 rows of depth: a class of width 1,
    2 or 4 (depth 32 to 128) folds 8 tiles, width 8 (256) folds 4, in
    chunks of at least `SLICED_FOLD_MIN_GROUPS` groups; smaller chunks
    fold none.  Counted once a class at every launch, so a reused plan
    counts again."""
    m = n = k = 23
    runs = np.tile(runs, 2400 // sum(runs) + 1)  # past 2 048 entries
    a, b, ai, bi, ci = _stack(m, n, k, runs, seed=21)
    plan = smm.prepare_stack(jnp.zeros((len(runs), m, n)), jnp.asarray(a),
                             jnp.asarray(b), ai, bi, ci)
    assert plan.dot_form == "sliced"
    classes = [shape[1:] for shape in smm._group_idx_shapes(plan)]
    want = _expected_folds(classes, k)
    assert folds <= set(want)
    before = _folds()
    for _ in range(2):
        got = smm.execute_stack(jnp.zeros((len(runs), m, n)), jnp.asarray(a),
                                jnp.asarray(b), plan, 1.0)
    assert _moved(before, _folds()) == {f: 2 * c for f, c in want.items()}
    want, bound = np.zeros(got.shape), np.zeros(got.shape)
    np.add.at(want, ci, a[ai] @ b[bi])
    np.add.at(bound, ci, np.abs(a[ai]) @ np.abs(b[bi]))
    assert (np.abs(np.asarray(got) - want) <= 2.0 ** -46 * bound).all()


def test_f32_product_counts_no_fold(fake_tpu):
    before = _folds()
    _product(np.float32, None)
    assert _folds() == before


def test_mesh_product_counts_its_width_classes_by_fold(fake_tpu,
                                                      monkeypatch):
    """Once a product, the grouped stacks of the mesh plan: each of its
    width classes under the fold of its strip and chunk."""
    import dbcsr_tpu as dt
    from dbcsr_tpu.parallel import (make_grid, sparse_dist,
                                    sparse_multiply_distributed)

    plans = []
    note = sparse_dist._note_mesh_dot
    monkeypatch.setattr(sparse_dist, "_note_mesh_dot",
                        lambda plan: (plans.append(plan), note(plan)))
    rng = np.random.default_rng(23)
    sizes = np.full(12, 23, np.int32)
    a = dt.make_random_matrix("A", sizes, sizes, np.float64, 0.6, rng=rng)
    b = dt.make_random_matrix("B", sizes, sizes, np.float64, 0.6, rng=rng)
    folds0 = _folds()
    c = sparse_multiply_distributed(1.0, a, b, 0.0, None, make_grid(4),
                                    filter_eps=1e-9)
    (plan,) = plans
    assert plan.dot_form == "sliced"
    classes = [tile[0].shape[-2:] for tile in plan.stacks_dev[1]]
    assert len(classes) >= 2
    assert _moved(folds0, _folds()) == _expected_folds(classes, 23)
    a_d, b_d = dt.to_dense(a), dt.to_dense(b)
    np.testing.assert_allclose(
        dt.to_dense(c), a_d @ b_d, rtol=0,
        atol=2.0 ** -44 * (np.abs(a_d) @ np.abs(b_d)).max())


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
