"""The XLA stack bodies add each chunk into the loop-carried C in place
(`acc.smm._accumulate_chunk`): one sorted scatter-add per chunk.  Every
body and the fused program are held to a NumPy loop over the stack on
the chunkings that exercise the scatter's edges: ids that must drop, a
C block whose entries lie in two chunks, a chunk with nothing live.
The grouped body's plan (`build_group_tiles`: width classes from the
run lengths, a live chunk count) is held to the stack it tiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dbcsr_tpu.acc import smm

M, N, K = 5, 4, 3
NSEG = 6
NA = NB = 7
PER_BLOCK = (3, 0, 7, 2, 5, 3)  # entries of each C block: 20, 12 runs of R0
R0 = 2

# case -> (C random?, entries (groups) a chunk, whole dead chunks after)
CASES = {
    "c_zero": (False, 10, 0),
    "c_random": (True, 10, 0),
    "padded_ids_dropped": (True, 8, 0),
    "block_straddles_chunks": (True, 5, 0),
    "dead_chunk": (True, 10, 1),
}
GROUP_CHUNK = {10: 6, 8: 5, 5: 3}  # the same edges, counted in groups
BODIES = ("xla", "xla_flat", "xla_group", "fused")


def _operands(seed, c_random, k=K):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((NA, M, k))
    b = rng.standard_normal((NB, k, N))
    c = rng.standard_normal((NSEG, M, N)) if c_random \
        else np.zeros((NSEG, M, N))
    ci = np.repeat(np.arange(NSEG), PER_BLOCK).astype(np.int32)
    ai = rng.integers(0, NA, len(ci)).astype(np.int32)
    bi = rng.integers(0, NB, len(ci)).astype(np.int32)
    return c, a, b, ai, bi, ci


def _numpy_loop(c, a, b, ai, bi, ci, alpha):
    out = c.copy()
    for s in range(len(ci)):
        out[ci[s]] += alpha * (a[ai[s]] @ b[bi[s]])
    return out


def _chunked(rows, chunk, dead_row, dead_chunks):
    """(nchunks, chunk, ...) from per-entry rows, filled up with
    ``dead_row`` and followed by ``dead_chunks`` chunks of it."""
    rows = list(rows)
    total = -(-len(rows) // chunk) * chunk + dead_chunks * chunk
    rows += [dead_row] * (total - len(rows))
    return np.asarray(rows, np.int32).reshape((-1, chunk) + np.shape(dead_row))


def _flat_idx(ai, bi, ci, chunk, dead_chunks):
    # a dropped entry names LIVE rows of A and B: only its id drops it
    return tuple(_chunked(x, chunk, dead, dead_chunks)
                 for x, dead in ((ai, 0), (bi, 0), (ci, NSEG)))


def _group_idx(ai, bi, ci, chunk, dead_chunks, a_pad, b_pad):
    """Runs of R0 entries of one C block, as `build_group_tiles` lays
    out a single width class; a short run is filled with the zero pad
    rows.  First the live chunk count: the dead chunks are bucket slack
    the loop never runs."""
    ga, gb, gc = [], [], []
    for blk in range(NSEG):
        (where,) = np.nonzero(ci == blk)
        for s in range(0, len(where), R0):
            run = where[s:s + R0]
            fill = R0 - len(run)
            ga.append(list(ai[run]) + [a_pad] * fill)
            gb.append(list(bi[run]) + [b_pad] * fill)
            gc.append(blk)
    gc = _chunked(gc, chunk, NSEG, dead_chunks)
    return (np.int32(len(gc) - dead_chunks),
            _chunked(ga, chunk, [0] * R0, dead_chunks),
            _chunked(gb, chunk, [0] * R0, dead_chunks), gc)


def _span_idx(driver, ai, bi, ci, chunk, dead_chunks):
    if driver == "xla_group":
        return _group_idx(ai, bi, ci, GROUP_CHUNK[chunk], dead_chunks,
                          NA, NB)
    return _flat_idx(ai, bi, ci, chunk, dead_chunks)


_SPAN_FN = {"xla": smm._process_stack_xla,
            "xla_flat": smm._process_stack_xla_flat,
            "xla_group": smm._process_stack_xla_group}


def _run_span(driver, c, a, b, idx, alpha):
    """One per-span program, as `_execute_plan` launches it."""
    a, b = jnp.asarray(a), jnp.asarray(b)
    if driver == "xla_group":
        a, b = smm._append_pad_row(a), smm._append_pad_row(b)
    return _SPAN_FN[driver](
        jnp.array(c), a, b, *map(jnp.asarray, idx),
        jnp.asarray(alpha, c.dtype))


def _run_fused(drivers, c, spans, alpha):
    """One fused program over ``spans`` = [(a, b, idx)], one driver
    each; a grouped span appends its pad rows inside the program."""
    sig = ("xla", False, tuple(
        (d, 4 if d == "xla_group" else 3, d == "xla_group",
         d == "xla_group", 1, False, None, "compiler")
        for d in drivers))
    flat = [jnp.asarray(x) for a, b, idx in spans for x in (a, b, *idx)]
    return smm._fused_fn(sig)(
        jnp.array(c), jnp.asarray(alpha, c.dtype), *flat)


def _second_span(seed):
    """A span of another k into the same C bin (its own A, B, stack)."""
    _, a, b, ai, bi, ci = _operands(seed, False, k=2)
    return a, b, ai, bi, ci


def _run(body, case, alpha=1.5):
    """(C the program returns, C the NumPy loop gives)."""
    c_random, chunk, dead_chunks = CASES[case]
    c, a, b, ai, bi, ci = _operands(7, c_random)
    want = _numpy_loop(c, a, b, ai, bi, ci, alpha)
    if body != "fused":
        idx = _span_idx(body, ai, bi, ci, chunk, dead_chunks)
        return _run_span(body, c, a, b, idx, alpha), want
    a2, b2, ai2, bi2, ci2 = _second_span(8)
    want = _numpy_loop(want, a2, b2, ai2, bi2, ci2, alpha)
    spans = [(a, b, _span_idx("xla_group", ai, bi, ci, chunk, dead_chunks)),
             (a2, b2, _span_idx("xla", ai2, bi2, ci2, chunk, dead_chunks))]
    return _run_fused(("xla_group", "xla"), c, spans, alpha), want


def test_the_chunkings_have_the_edges_their_names_say():
    _, _, _, ai, bi, ci = _operands(7, True)
    for driver in ("xla", "xla_group"):
        def ids(case):
            _, chunk, dead = CASES[case]
            return _span_idx(driver, ai, bi, ci, chunk, dead)[-1]

        assert (ids("c_zero") < NSEG).all()  # nothing dropped there
        padded = ids("padded_ids_dropped")
        assert (padded[-1] == NSEG).any() and (padded[-1] < NSEG).any()
        straddle = ids("block_straddles_chunks")
        assert straddle[0, -1] == straddle[1, 0] < NSEG
        assert (ids("dead_chunk")[-1] == NSEG).all()


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("body", BODIES)
def test_chunks_accumulate_to_the_numpy_loop(body, case):
    got, want = _run(body, case)
    assert got.dtype == jnp.float64
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-13, atol=1e-13)


@pytest.mark.parametrize("body", BODIES)
def test_two_runs_are_bit_identical(body):
    first, _ = _run(body, "block_straddles_chunks")
    second, _ = _run(body, "block_straddles_chunks")
    np.testing.assert_array_equal(np.asarray(first), np.asarray(second))


@pytest.mark.parametrize("driver", sorted(_SPAN_FN))
def test_fused_equals_span_by_span_bitwise(driver):
    c_random, chunk, dead_chunks = CASES["padded_ids_dropped"]
    c, a, b, ai, bi, ci = _operands(7, c_random)
    a2, b2, ai2, bi2, ci2 = _second_span(8)
    spans = [(a, b, _span_idx(driver, ai, bi, ci, chunk, dead_chunks)),
             (a2, b2, _span_idx(driver, ai2, bi2, ci2, chunk, dead_chunks))]
    fused = _run_fused((driver, driver), c, spans, 0.75)
    by_span = c
    for a_s, b_s, idx in spans:
        by_span = np.asarray(_run_span(driver, by_span, a_s, b_s, idx, 0.75))
    np.testing.assert_array_equal(np.asarray(fused), by_span)


def _lane_gather_group_body(c, a, b, live, *tiles_alpha):
    """The grouped body as it gathered until PR 29: `jnp.take` of whole
    (m, k) blocks out of the 3-D bins, then the relayout to strips
    (filled up with zero columns to whole sublanes, as since PR 31); a
    `lax.scan` over every chunk of the extent, as until PR 31.  Kept as
    the reference the block-row gather and the loop over the live
    chunks are held to bit for bit."""
    *flat, alpha = tiles_alpha
    tiles = [flat[i:i + 3] for i in range(0, len(flat), 3)]

    def step(c, idx):
        for ia, ib, ic in idx:
            ch, w = ia.shape
            ablk = jnp.take(a, ia.reshape(-1), axis=0)
            bblk = jnp.take(b, ib.reshape(-1), axis=0)
            amat = jnp.swapaxes(ablk.reshape((ch, w) + a.shape[1:]), 1, 2)
            amat = amat.reshape(ch, a.shape[1], -1)
            bmat = bblk.reshape(ch, -1, b.shape[2])
            ragged = -amat.shape[2] % 8  # zero columns, as the body's
            amat = jnp.pad(amat, ((0, 0), (0, 0), (0, ragged)))
            bmat = jnp.pad(bmat, ((0, 0), (0, ragged), (0, 0)))
            prod = smm._batch_dot(amat, bmat, c.dtype, None)
            c = smm._accumulate_chunk(c, alpha * prod, ic)
        return c, None

    return jax.lax.scan(step, c, tiles)[0]


def _real_stack(m, n, k, seed=29, nseg=40, na=30, nb=31, entries=500,
                runs=None):
    """Operands and a sorted stack: ``entries`` drawn over the C blocks,
    or ``runs[i]`` entries of C block i."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((na, m, k))
    b = rng.standard_normal((nb, k, n))
    c = rng.standard_normal((nseg, m, n))
    if runs is None:
        ci = np.sort(rng.integers(0, nseg, entries)).astype(np.int32)
    else:
        ci = np.repeat(np.arange(len(runs)), runs).astype(np.int32)
    ai = rng.integers(0, na, len(ci)).astype(np.int32)
    bi = rng.integers(0, nb, len(ci)).astype(np.int32)
    return c, a, b, ai, bi, ci


def _device_tiles(tiles):
    """A plan's arguments as `prepare_stack` hands them to the body."""
    return tuple(map(jnp.asarray, (np.int32(tiles.live), *tiles.flat())))


@pytest.mark.parametrize("mnk", [(23, 23, 23), (23, 23, 18), (5, 13, 23),
                                 (5, 5, 5)])
def test_group_body_is_the_numpy_product_and_the_lane_gather_bitwise(mnk):
    """Gathering A and B as whole block rows gives the NumPy product
    into a non-zero C, and the bits the gather of 3-D blocks gave."""
    c, a, b, ai, bi, ci = _real_stack(*mnk, entries=170)
    na, nb, nseg = len(a), len(b), len(c)
    tiles = smm.build_group_tiles(ci, ai, bi, 8, na, nb, nseg, 16)
    assert len(tiles.widths) > 1  # these runs open narrower classes
    idx = _device_tiles(tiles)
    a_dev = smm._append_pad_row(jnp.asarray(a))
    b_dev = smm._append_pad_row(jnp.asarray(b))
    got = smm._process_stack_xla_group(
        jnp.array(c), a_dev, b_dev, *idx, jnp.asarray(1.5))
    want = _numpy_loop(c, a, b, ai, bi, ci, 1.5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-13, atol=1e-12)
    old = _lane_gather_group_body(
        jnp.array(c), a_dev, b_dev, *idx, jnp.asarray(1.5))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(old))


def _one_width_tiles(c_idx, a_idx, b_idx, r0, a_pad, b_pad, c_pad,
                     chunk_groups):
    """`build_group_tiles` as it was until PR 31: every run tiled into
    groups of r0, the groups cut into chunks in order."""
    ga, gb, gc = [], [], []
    for blk in np.unique(c_idx):
        (where,) = np.nonzero(c_idx == blk)
        for s in range(0, len(where), r0):
            run = where[s:s + r0]
            fill = r0 - len(run)
            ga.append(list(a_idx[run]) + [a_pad] * fill)
            gb.append(list(b_idx[run]) + [b_pad] * fill)
            gc.append(blk)
    dead = smm.bucket_size(-(-len(gc) // chunk_groups), minimum=1) \
        - -(-len(gc) // chunk_groups)
    return (_chunked(ga, chunk_groups, [a_pad] * r0, dead),
            _chunked(gb, chunk_groups, [b_pad] * r0, dead),
            _chunked(gc, chunk_groups, c_pad, dead))


# the north star's run-length histogram (runs of 1..12 entries a C
# block; ISSUE 31), a hundredth of it
_NS_RUNS = np.repeat(np.arange(1, 13), [101, 223, 331, 360, 323, 235, 150,
                                        80, 39, 17, 6, 3])
# name -> (entries of each C block, widths the plan must come to at r0=8)
RUN_SHAPES = {
    "all_runs_1": (np.ones(300, int), None),
    "all_runs_r0": (None, "one"),
    "runs_past_2_r0": (np.arange(300) % 9 + 17, None),
    "north_star": (np.random.default_rng(31).permutation(_NS_RUNS), None),
    "one_block": (np.array([77]), None),
    "mixed_with_empty_blocks": (np.arange(300) % 14, None),
}


def _tiled(shape, r0, chunk_groups):
    runs, _ = RUN_SHAPES[shape]
    if runs is None:
        runs = np.full(300, r0)
    nseg = len(runs) + 3
    _, a, b, ai, bi, ci = _real_stack(5, 4, 3, seed=r0 * chunk_groups,
                                      nseg=nseg, runs=runs)
    tiles = smm.build_group_tiles(ci, ai, bi, r0, len(a), len(b), nseg,
                                  chunk_groups)
    return tiles, (ai, bi, ci), (len(a), len(b), nseg), runs


@pytest.mark.parametrize("shape", sorted(RUN_SHAPES))
@pytest.mark.parametrize("r0,chunk_groups", [(8, 16), (8, 256), (4, 7),
                                             (2, 64), (16, 5)])
def test_group_tiles_hold_every_entry_once_and_the_pad_row_else(
        shape, r0, chunk_groups):
    """What the body rests on, whatever the run lengths: every stack
    entry sits in exactly one slot, every other slot names the pad row
    (so every id lies in [0, pad row], which the gathers promise and do
    not check), a chunk's segment ids are sorted with its dead groups
    last, a class's groups are sorted by C block over the chunks, a C
    block's groups lie in one class in stack order (a run of at most r0
    entries is one group of the narrowest class that holds it), and the
    live chunk count is the least that covers the groups."""
    tiles, (ai, bi, ci), (na, nb, nseg), runs = _tiled(shape, r0,
                                                       chunk_groups)
    assert tiles.entries == len(ci)
    assert sorted(tiles.widths, reverse=True) == list(tiles.widths)
    assert max(tiles.widths) <= r0
    seen = []
    for (ga, gb, gc), w, groups in zip(tiles.tiles, tiles.widths,
                                       tiles.groups):
        nchunks, cap = gc.shape
        assert ga.shape == gb.shape == (nchunks, cap, w)
        assert ga.dtype == gb.dtype == gc.dtype == np.int32
        assert nchunks == smm.bucket_size(tiles.live, minimum=1)
        assert ga.min() >= 0 and ga.max() <= na
        assert gb.min() >= 0 and gb.max() <= nb
        assert gc.min() >= 0 and gc.max() <= nseg
        assert (np.diff(gc, axis=1) >= 0).all()  # sorted within a chunk
        alive = gc < nseg
        assert alive.sum() == groups
        assert (np.diff(gc[alive]) >= 0).all()   # and over the chunks
        assert not alive[tiles.live:].any()
        live = ga != na
        assert (live == (gb != nb)).all()
        # a group fills its first slots, and a dead group none
        assert (live.sum(axis=2) > 0).reshape(-1).tolist() \
            == alive.reshape(-1).tolist()
        assert (np.diff(live.astype(int), axis=2) <= 0).all()
        row_of_slot = np.broadcast_to(
            np.arange(nchunks * cap).reshape(nchunks, cap, 1), live.shape)
        seen += zip(np.repeat(gc.reshape(-1), live.sum(axis=2).reshape(-1)),
                    ga[live], gb[live], row_of_slot[live],
                    np.full(live.sum(), w))
    assert tiles.slots_launched == tiles.live * sum(
        gc.shape[1] * w for (_, _, gc), w in zip(tiles.tiles, tiles.widths))
    # every entry once (a C block's entries as a multiset: two of them
    # may name the same A and B blocks)
    assert sorted((c, a, b) for c, a, b, _, _ in seen) \
        == sorted(zip(ci, ai, bi))
    # a C block's entries: one class, its rows in stack order; a run
    # of at most r0 is one group of the narrowest class that holds it
    by_block = {}
    for c, a, b, row, w in seen:
        by_block.setdefault(c, []).append((row, w, a, b))
    at = 0
    for c, run in zip(np.flatnonzero(runs), runs[runs > 0]):
        slots = by_block[c]
        assert len({w for _, w, _, _ in slots}) == 1
        assert [row for row, _, _, _ in slots] == sorted(
            row for row, _, _, _ in slots)
        assert [(a, b) for _, _, a, b in slots] \
            == list(zip(ai[at:at + run], bi[at:at + run]))
        w = slots[0][1]
        if run <= r0:
            assert len({row for row, _, _, _ in slots}) == 1
            assert w == min(x for x in tiles.widths if x >= run)
        else:
            assert w == r0
            assert len({row for row, _, _, _ in slots}) == -(-run // r0)
        at += run
    # the least count: the last live chunk holds a group of some class,
    # and the class that fills its chunks has no room for one fewer
    assert any((gc[tiles.live - 1] < nseg).any() for _, _, gc in tiles.tiles)
    assert tiles.live == max(
        -(-n // gc.shape[1]) for n, (_, _, gc) in zip(tiles.groups,
                                                      tiles.tiles))


def _tiles_digest(shape):
    """One hash over what `build_group_tiles` plans for ``shape`` at
    five (r0, chunk_groups): counts, shapes and every id."""
    import hashlib

    h = hashlib.sha256()
    for r0, chunk_groups in [(8, 16), (8, 256), (4, 7), (2, 64), (16, 5)]:
        tiles, *_ = _tiled(shape, r0, chunk_groups)
        h.update(repr((int(tiles.live), tiles.groups, tiles.entries,
                       tiles.widths)).encode())
        for x in tiles.flat():
            h.update(repr((x.shape, str(x.dtype))).encode())
            h.update(np.ascontiguousarray(x).tobytes())
    return h.hexdigest()[:16]


# `_tiles_digest` of PR 32's `build_group_tiles`
_TILES_PR32 = {
    "all_runs_1": "6f90203b77df1580",
    "all_runs_r0": "83767fedec541ea0",
    "mixed_with_empty_blocks": "eadbd8554f751f6a",
    "north_star": "837a2cf6fd1177c1",
    "one_block": "17603896802aa1e5",
    "runs_past_2_r0": "d577668589b0ee13"
}


@pytest.mark.parametrize("shape", sorted(RUN_SHAPES))
def test_one_stack_gets_bit_for_bit_the_arrays_it_got(shape):
    """PR 33 made the tiling plan several stacks at once (a mesh's
    devices and ticks): the one stack of one chip must come out as it
    did, id for id, so that its programs and its plan cache hold."""
    assert _tiles_digest(shape) == _TILES_PR32[shape]


@pytest.mark.parametrize("shapes", [
    ("north_star", "all_runs_1", None, "runs_past_2_r0"),
    ("one_block", None, None),
    (None, None),
])
def test_several_stacks_share_class_shapes_and_keep_their_own_entries(shapes):
    """`build_stacks_group_tiles`, what a mesh plan calls for its
    (device, tick) stacks: one set of classes and chunk capacities,
    every stack's entries in its own rows and nowhere else, `live` its
    own chunk count, 0 for a stack with no entry (None here)."""
    r0, chunk_groups, na, nb, nseg = 8, 16, 40, 50, 4000
    rng = np.random.default_rng(len(shapes))
    per_stack = []
    for shape in shapes:
        runs = np.zeros(0, int) if shape is None else RUN_SHAPES[shape][0]
        ci = np.repeat(np.arange(len(runs)), runs).astype(np.int32)
        per_stack.append((ci, rng.integers(0, na, len(ci)).astype(np.int32),
                          rng.integers(0, nb, len(ci)).astype(np.int32)))
    stack_of = np.repeat(np.arange(len(shapes)),
                         [len(ci) for ci, _, _ in per_stack])
    ci, ai, bi = (np.concatenate(x) for x in zip(*per_stack))
    tiles = smm.build_stacks_group_tiles(
        stack_of, len(shapes), ci, ai, bi, r0, na, nb, nseg, chunk_groups)
    assert tiles.entries == len(ci)
    assert tiles.live.shape == (len(shapes),)
    nchunks = tiles.tiles[0][0].shape[1]
    assert nchunks == smm.bucket_size(int(tiles.live.max()), minimum=1) or (
        len(ci) == 0 and nchunks == 1)
    assert tiles.slots_launched == int(tiles.live.sum()) * sum(
        ga.shape[2] * ga.shape[3] for ga, _, _ in tiles.tiles)
    for s, (ci_s, ai_s, bi_s) in enumerate(per_stack):
        got = []
        for ga, gb, gc in tiles.tiles:
            assert ga.shape[:2] == (len(shapes), nchunks)
            there = ga[s] != na
            assert not there[tiles.live[s]:].any()
            assert (gc[s, tiles.live[s]:] == nseg).all()
            rows = np.broadcast_to(gc[s][:, :, None], there.shape)
            got += zip(rows[there], ga[s][there], gb[s][there])
        assert sorted(got) == sorted(zip(ci_s, ai_s, bi_s))
        assert (tiles.live[s] == 0) == (len(ci_s) == 0)
        if len(ci_s):  # the least count that covers its fullest class
            assert tiles.live[s] == max(
                -(-int((gc[s] < nseg).sum()) // gc.shape[2])
                for _, _, gc in tiles.tiles)


@pytest.mark.parametrize("r0,chunk_groups", [(8, 16), (4, 7), (2, 64)])
def test_full_runs_give_one_class_and_the_arrays_of_one_width(
        r0, chunk_groups):
    """A stack whose runs fill r0 keeps the single width and the very
    arrays `build_group_tiles` made before it knew classes."""
    tiles, (ai, bi, ci), (na, nb, nseg), runs = _tiled(
        "all_runs_r0", r0, chunk_groups)
    assert tiles.widths == (r0,)
    want = _one_width_tiles(ci, ai, bi, r0, na, nb, nseg, chunk_groups)
    for got, old in zip(tiles.tiles[0], want):
        np.testing.assert_array_equal(got, old)
    assert tiles.live == -(-tiles.groups[0] // chunk_groups)
    assert tiles.entries == tiles.groups[0] * r0  # every group full


def test_a_stack_under_one_chunk_gets_a_chunk_of_its_own_size():
    """434 C blocks with the north star's runs fill 434 groups: one
    chunk of their bucketed counts, not `chunk_groups` groups of r0."""
    runs = np.random.default_rng(5).permutation(_NS_RUNS)[:434]
    _, a, b, ai, bi, ci = _real_stack(5, 4, 3, nseg=434, runs=runs)
    tiles = smm.build_group_tiles(ci, ai, bi, 8, len(a), len(b), 434, 3750)
    assert tiles.live == 1
    assert all(ga.shape[:2] == (1, smm.bucket_size(n))
               for (ga, _, _), n in zip(tiles.tiles, tiles.groups))
    assert tiles.slots_launched < 3750 * 8 // 6


def test_north_star_runs_open_classes_and_fill_the_slots():
    """Runs of mean 4.4 at r0 = 8: a single width fills 54% of its
    slots (49% with the bucketed chunks launched, as until PR 31); the
    classes fill at least 70% of what the live chunks launch."""
    runs = np.random.default_rng(31).permutation(np.tile(_NS_RUNS, 10))
    _, a, b, ai, bi, ci = _real_stack(5, 4, 3, nseg=len(runs), runs=runs)
    tiles = smm.build_group_tiles(ci, ai, bi, 8, len(a), len(b), len(runs),
                                  256)
    one_width = 8 * int((-(-runs // 8)).sum())
    assert 0.53 < len(ci) / one_width < 0.55
    assert tiles.widths == (8, 4, 2)
    assert tiles.entries / tiles.slots_launched >= 0.70
    assert sum(tiles.groups) == int((-(-runs // 8)).sum())  # no group more


def test_prepare_stack_counts_live_and_launched_slots_and_names_classes():
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.obs import metrics

    def slots():
        got = {lab["kind"]: v for lab, v in metrics.counter_items(
            "dbcsr_tpu_stack_slots_total")}
        return got.get("live", 0.0), got.get("launched", 0.0)

    runs, _ = RUN_SHAPES["north_star"]
    nseg = len(runs)
    c, a, b, ai, bi, ci = _real_stack(5, 4, 3, nseg=nseg, runs=runs)
    was = get_config().mm_driver
    live0, launched0 = slots()
    rolled0 = stats.driver_rollup().get("xla_group", {})
    set_config(mm_driver="xla_group")
    try:
        plan = smm.prepare_stack(jnp.asarray(c), jnp.asarray(a),
                                 jnp.asarray(b), ai, bi, ci)
    finally:
        set_config(mm_driver=was)
    tiles = smm.build_group_tiles(
        ci, ai, bi, plan.r_grp, len(a), len(b), nseg,
        smm.group_chunk_groups(plan.r_grp, 5, 4, 3, 8,
                               get_config().mm_stack_size))
    assert plan.driver == "xla_group" and plan.r_grp == 8
    assert plan.group_classes == tuple(zip(tiles.widths, tiles.groups))
    assert len(plan.group_idx) == 1 + 3 * len(tiles.widths)
    assert int(plan.group_idx[0].reshape(())) == tiles.live
    live1, launched1 = slots()
    assert live1 - live0 == len(ci)
    assert launched1 - launched0 == tiles.slots_launched
    assert (live1 - live0) / (launched1 - launched0) >= 0.65
    rolled = stats.driver_rollup()["xla_group"]
    assert rolled["slots_live"] - rolled0.get("slots_live", 0) == len(ci)
    assert rolled["slots_launched"] - rolled0.get("slots_launched", 0) \
        == tiles.slots_launched
    assert set(rolled["groups_by_width"]) >= set(tiles.widths)
    # the plan runs, and gives the NumPy loop's C
    got = smm.execute_stack(jnp.array(c), jnp.asarray(a), jnp.asarray(b),
                            plan, 1.5)
    np.testing.assert_allclose(np.asarray(got),
                               _numpy_loop(c, a, b, ai, bi, ci, 1.5),
                               rtol=1e-13, atol=1e-12)


@pytest.mark.parametrize("shape", sorted(RUN_SHAPES))
def test_classed_group_body_is_the_numpy_loop_and_one_width_bitwise(shape):
    """The body over the plan's classes gives the NumPy loop's C; two
    runs give the same bits; and where the plan has one class, the bits
    the scan over every chunk of one width gave."""
    tiles, (ai, bi, ci), (na, nb, nseg), _ = _tiled(shape, 8, 16)
    c, a, b, _, _, _ = _real_stack(5, 4, 3, seed=8 * 16, nseg=nseg,
                                   runs=np.ones(1, int))
    a_dev = smm._append_pad_row(jnp.asarray(a))
    b_dev = smm._append_pad_row(jnp.asarray(b))
    idx = _device_tiles(tiles)
    runs_ = [smm._process_stack_xla_group(
        jnp.array(c), a_dev, b_dev, *idx, jnp.asarray(0.75))
        for _ in range(2)]
    np.testing.assert_array_equal(*map(np.asarray, runs_))
    np.testing.assert_allclose(
        np.asarray(runs_[0]), _numpy_loop(c, a, b, ai, bi, ci, 0.75),
        rtol=1e-13, atol=1e-12)
    # the same classes chunk by chunk under a scan that runs the whole
    # extent: the loop's traced bound and its dynamic slices change
    # no bit
    old = _lane_gather_group_body(jnp.array(c), a_dev, b_dev, *idx,
                                  jnp.asarray(0.75))
    np.testing.assert_array_equal(np.asarray(runs_[0]), np.asarray(old))


def test_chunks_past_the_live_count_are_not_read():
    """A bucketed chunk changes nothing when it is run (its groups are
    dead), and is not run at all: with entries that WOULD change C in
    the chunks past a forced live count, C is what the live chunks
    give."""
    tiles, (ai, bi, ci), (na, nb, nseg), _ = _tiled("north_star", 8, 16)
    assert tiles.live >= 4 and len(tiles.widths) > 1
    c, a, b, _, _, _ = _real_stack(5, 4, 3, seed=8 * 16, nseg=nseg,
                                   runs=np.ones(1, int))
    a_dev = smm._append_pad_row(jnp.asarray(a))
    b_dev = smm._append_pad_row(jnp.asarray(b))
    flat = list(map(jnp.asarray, tiles.flat()))

    def run(live, arrays=flat):
        return np.asarray(smm._process_stack_xla_group(
            jnp.array(c), a_dev, b_dev, jnp.asarray(np.int32(live)),
            *arrays, jnp.asarray(1.0)))

    nchunks = flat[0].shape[0]
    if nchunks > tiles.live:  # running the dead chunks adds nothing
        np.testing.assert_array_equal(run(tiles.live), run(nchunks))
    # the first two chunks alone = the stack entries they hold
    forced = 2
    held = np.zeros(len(ci), bool)
    for ga, gb, gc in tiles.tiles:
        live = ga[:forced] != na
        for blk, ia, ib in zip(
                np.repeat(gc[:forced].reshape(-1),
                          live.sum(axis=2).reshape(-1)),
                ga[:forced][live], gb[:forced][live]):
            (hit,) = np.nonzero((ci == blk) & (ai == ia) & (bi == ib)
                                & ~held)
            held[hit[0]] = True
    assert 0 < held.sum() < len(ci)
    want = _numpy_loop(c, a, b, ai[held], bi[held], ci[held], 1.0)
    got = run(forced)
    np.testing.assert_allclose(got, want, rtol=1e-13, atol=1e-12)
    assert np.abs(run(tiles.live) - got).max() > 1e-3  # they do hold work


def test_fused_classed_span_equals_the_span_alone_bitwise():
    """A span of several classes inside the fused program gives the
    bits of its own program."""
    tiles, (ai, bi, ci), (na, nb, nseg), _ = _tiled("north_star", 8, 16)
    c, a, b, _, _, _ = _real_stack(5, 4, 3, seed=8 * 16, nseg=nseg,
                                   runs=np.ones(1, int))
    idx = _device_tiles(tiles)
    sig = ("xla", False,
           (("xla_group", len(idx), True, True, 8, False, None, "compiler"),))
    fused = smm._fused_fn(sig)(jnp.array(c), jnp.asarray(0.75),
                               jnp.asarray(a), jnp.asarray(b), *idx)
    alone = smm._process_stack_xla_group(
        jnp.array(c), smm._append_pad_row(jnp.asarray(a)),
        smm._append_pad_row(jnp.asarray(b)), *idx, jnp.asarray(0.75))
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(alone))
