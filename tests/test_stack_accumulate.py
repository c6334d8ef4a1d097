"""The XLA stack bodies add each chunk into the loop-carried C in place
(`acc.smm._accumulate_chunk`): one sorted scatter-add per chunk.  Every
body and the fused program are held to a NumPy loop over the stack on
the chunkings that exercise the scatter's edges: ids that must drop, a
C block whose entries lie in two chunks, a chunk with nothing live.
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
    them out; a short run is filled with the zero pad rows."""
    ga, gb, gc = [], [], []
    for blk in range(NSEG):
        (where,) = np.nonzero(ci == blk)
        for s in range(0, len(where), R0):
            run = where[s:s + R0]
            fill = R0 - len(run)
            ga.append(list(ai[run]) + [a_pad] * fill)
            gb.append(list(bi[run]) + [b_pad] * fill)
            gc.append(blk)
    return (_chunked(ga, chunk, [0] * R0, dead_chunks),
            _chunked(gb, chunk, [0] * R0, dead_chunks),
            _chunked(gc, chunk, NSEG, dead_chunks))


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
        (d, 3, d == "xla_group", d == "xla_group", 1, False, None)
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
            return _span_idx(driver, ai, bi, ci, chunk, dead)[2]

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


def _lane_gather_group_body(c, a, b, ga, gb, gc, alpha):
    """The grouped body as it gathered until PR 29: `jnp.take` of whole
    (m, k) blocks out of the 3-D bins, then the relayout to strips.
    Kept as the reference the block-row gather is held to bit for
    bit."""
    r0 = ga.shape[2]

    def step(c, idx):
        ia, ib, ic = idx
        ch = ia.shape[0]
        ablk = jnp.take(a, ia.reshape(-1), axis=0)
        bblk = jnp.take(b, ib.reshape(-1), axis=0)
        amat = jnp.swapaxes(ablk.reshape((ch, r0) + a.shape[1:]), 1, 2)
        prod = smm._batch_dot(amat.reshape(ch, a.shape[1], -1),
                              bblk.reshape(ch, -1, b.shape[2]),
                              c.dtype, None)
        return smm._accumulate_chunk(c, alpha * prod, ic), None

    return jax.lax.scan(step, c, (ga, gb, gc))[0]


def _real_stack(m, n, k, seed=29, nseg=40, na=30, nb=31, entries=500):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((na, m, k))
    b = rng.standard_normal((nb, k, n))
    c = rng.standard_normal((nseg, m, n))
    ci = np.sort(rng.integers(0, nseg, entries)).astype(np.int32)
    ai = rng.integers(0, na, entries).astype(np.int32)
    bi = rng.integers(0, nb, entries).astype(np.int32)
    return c, a, b, ai, bi, ci


@pytest.mark.parametrize("mnk", [(23, 23, 23), (23, 23, 18), (5, 13, 23),
                                 (5, 5, 5)])
def test_group_body_is_the_numpy_product_and_the_lane_gather_bitwise(mnk):
    """Gathering A and B as whole block rows gives the NumPy product
    into a non-zero C, and the bits the gather of 3-D blocks gave."""
    c, a, b, ai, bi, ci = _real_stack(*mnk)
    na, nb, nseg = len(a), len(b), len(c)
    idx = tuple(map(jnp.asarray, smm.build_group_tiles(
        ci, ai, bi, 8, na, nb, nseg, 16)))
    a_dev = smm._append_pad_row(jnp.asarray(a))
    b_dev = smm._append_pad_row(jnp.asarray(b))
    got = smm._process_stack_xla_group(
        jnp.array(c), a_dev, b_dev, *idx, jnp.asarray(1.5))
    want = _numpy_loop(c, a, b, ai, bi, ci, 1.5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-13, atol=1e-12)
    old = _lane_gather_group_body(
        jnp.array(c), a_dev, b_dev, *idx, jnp.asarray(1.5))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(old))


@pytest.mark.parametrize("r0,chunk_groups,entries", [
    (8, 16, 500), (4, 7, 333), (2, 64, 1), (16, 5, 90)])
def test_group_tiles_name_only_rows_up_to_the_pad_row(r0, chunk_groups,
                                                      entries):
    """What the body's `promise_in_bounds` gathers rest on: every id
    `build_group_tiles` emits lies in [0, pad row], live slots hold the
    stack's own ids in stack order, and every other slot the pad row."""
    _, a, b, ai, bi, ci = _real_stack(5, 4, 3, seed=entries,
                                      entries=entries)
    na, nb, nseg = len(a), len(b), 40
    ga, gb, gc = smm.build_group_tiles(ci, ai, bi, r0, na, nb, nseg,
                                       chunk_groups)
    assert ga.shape == gb.shape == gc.shape + (r0,)
    assert gc.shape[1] == chunk_groups
    assert ga.dtype == gb.dtype == gc.dtype == np.int32
    assert ga.min() >= 0 and ga.max() <= na
    assert gb.min() >= 0 and gb.max() <= nb
    assert gc.min() >= 0 and gc.max() <= nseg
    assert (np.diff(gc.reshape(-1)) >= 0).all()
    live = ga != na
    assert (live == (gb != nb)).all()
    np.testing.assert_array_equal(ga[live], ai)
    np.testing.assert_array_equal(gb[live], bi)
    np.testing.assert_array_equal(
        np.repeat(gc.reshape(-1), live.sum(axis=2).reshape(-1)), ci)
