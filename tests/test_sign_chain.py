"""The Newton-Schulz sign chain as a deployment (PR 32): what
`sign_iteration` reports, the counters a filtered product bumps when the
norm test prunes and the filter drops, the union add as one named
program a bin, and the chain's host spans.  CPU; counts only."""

import jax.numpy as jnp
import numpy as np
import pytest

import dbcsr_tpu as dt
from dbcsr_tpu.core import timings
from dbcsr_tpu.models import sign as sign_mod
from dbcsr_tpu.models.sign import sign_iteration
from dbcsr_tpu.obs import metrics
from dbcsr_tpu.ops import operations as ops

EPS = 1e-7


def _matrix(name, sizes, entries):
    """A finalized matrix from {(row, col): block}."""
    sizes = np.asarray(sizes, np.int32)
    m = dt.create(name, sizes, sizes, "float64")
    for (r, c), blk in entries.items():
        m.put_blocks(np.array([r]), np.array([c]), blk[None])
    return m.finalize()


def _decaying_h(nb=6, bs=4, seed=0):
    """A gapped chain of ``nb`` molecules on a line: diagonal blocks
    diag(-1, +1, ...), neighbours coupled by 10^-2 per step of distance,
    so that the weakest stored blocks (1e-8) lie under `EPS`."""
    rng = np.random.default_rng(seed)
    blocks = {}
    for i in range(nb):
        blocks[(i, i)] = np.diag(np.where(np.arange(bs) < 1, -1.0, 1.0))
        for j in range(i + 1, nb):
            g = rng.standard_normal((bs, bs))
            g *= 10.0 ** (-2 * (j - i)) / np.linalg.norm(g)
            blocks[(i, j)], blocks[(j, i)] = g, g.T.copy()
    return _matrix("H", [bs] * nb, blocks), blocks


def _counter(name, **labels):
    return sum(v for lab, v in metrics.counter_items(name)
               if all(lab.get(k) == w for k, w in labels.items()))


def test_chain_reports_the_flops_of_its_products_and_its_steps(monkeypatch):
    h, _ = _decaying_h()
    returned = []
    real = sign_mod.multiply

    def recording(*args, **kw):
        returned.append(real(*args, **kw))
        return returned[-1]

    monkeypatch.setattr(sign_mod, "multiply", recording)
    x, history = sign_iteration(h, steps=12, filter_eps=EPS, tol=1e-6)
    assert len(returned) == 2 * len(history) and all(returned)
    assert x._last_flops == sum(returned)
    assert x._last_steps == len(history) < 12
    step = sign_mod.sign_step(x, filter_eps=EPS)
    assert step._last_flops == sum(returned[-2:])


def test_counters_move_by_the_hand_counted_amounts():
    """One filtered product of the 6-block X0 with itself, counted by a
    triple loop: the norm test in float32 as `dbcsr_mm_cannon.F` states
    it, then the filter on the blocks the survivors make."""
    h, blocks = _decaying_h()
    norm = {key: np.float32(np.linalg.norm(b)) ** 2
            for key, b in blocks.items()}
    nb = 6
    row_eps = (np.float32(EPS) / np.float32(nb)) ** 2  # every row is full
    kept, pruned, born = 0, 0, {}
    for i in range(nb):
        for k in range(nb):
            for j in range(nb):
                if norm[(i, k)] * norm[(k, j)] >= row_eps:
                    kept += 1
                    born[(i, j)] = born.get((i, j), 0) + \
                        blocks[(i, k)] @ blocks[(k, j)]
                else:
                    pruned += 1
    dropped = sum(np.linalg.norm(b) < EPS for b in born.values())
    assert kept + pruned == nb ** 3 and pruned > 0 and dropped > 0
    before = {
        (n, f): _counter(n, fate=f)
        for n in ("dbcsr_tpu_candidates_total", "dbcsr_tpu_filter_blocks_total")
        for f in ("kept", "pruned", "dropped")}
    c = dt.create("C", h.row_blk_sizes, h.col_blk_sizes, "float64")
    flops = dt.multiply("N", "N", 1.0, h, h, 0.0, c, filter_eps=EPS)
    moved = {key: _counter(key[0], fate=key[1]) - v
             for key, v in before.items()}
    assert flops == 2 * 4 ** 3 * kept
    assert moved[("dbcsr_tpu_candidates_total", "kept")] == kept
    assert moved[("dbcsr_tpu_candidates_total", "pruned")] == pruned
    assert moved[("dbcsr_tpu_filter_blocks_total", "dropped")] == dropped
    assert moved[("dbcsr_tpu_filter_blocks_total", "kept")] == \
        len(born) - dropped == c.nblks
    # an unfiltered product has no norm test and no filter: neither moves
    c2 = dt.create("C2", h.row_blk_sizes, h.col_blk_sizes, "float64")
    dt.multiply("N", "N", 1.0, h, h, 0.0, c2)
    assert {key: _counter(key[0], fate=key[1]) - v
            for key, v in before.items()} == moved


def test_plan_cache_counts_hit_miss_and_evicted(monkeypatch):
    from dbcsr_tpu.mm import multiply as mm

    h, blocks = _decaying_h()
    other = _matrix("H'", [4] * 6, {k: b for k, b in blocks.items()
                                    if k != (0, 5)})
    mm._plan_cache.clear()
    monkeypatch.setattr(mm, "_PLAN_CACHE_MAX", 1)

    def counts():
        return {r: _counter("dbcsr_tpu_plan_cache_total", result=r)
                for r in ("hit", "miss", "evicted")}

    def product(a):
        c = dt.create("C", h.row_blk_sizes, h.col_blk_sizes, "float64")
        dt.multiply("N", "N", 1.0, a, h, 0.0, c, filter_eps=EPS)

    c0 = counts()
    product(h)       # miss
    product(h)       # hit
    product(other)   # another pattern: miss, and the first is evicted
    c1 = counts()
    assert {r: c1[r] - c0[r] for r in c0} == {"hit": 1, "miss": 2,
                                              "evicted": 1}
    mm._plan_cache.clear()


def _patterns(kind, nb):
    rng = np.random.default_rng(5)
    every = [(r, c) for r in range(nb) for c in range(nb)]
    rng.shuffle(every)
    half = len(every) // 2
    return {"aligned": (every[:half], every[:half]),
            "disjoint": (every[:half], every[half:]),
            "overlapping": (every[:half], every[half // 2:half + half // 2]),
            }[kind]


@pytest.mark.parametrize("kind", ["aligned", "disjoint", "overlapping"])
@pytest.mark.parametrize("sizes,shape", [
    ([23] * 4 + [18], (23, 23)), ([23] * 4 + [18], (23, 18)),
    ([5] * 6, (5, 5))], ids=["23x23", "23x18", "5x5"])
def test_add_union_bin_is_the_eager_gather_and_add(kind, sizes, shape):
    """`_add_union` through the one jitted program a bin, against the
    eager `.at[].add(fac * take(...))` chain it replaced, bit for bit."""
    rng = np.random.default_rng(11)
    keys_a, keys_b = _patterns(kind, len(sizes))

    def draw(name, keys):
        return _matrix(name, sizes, {
            (r, c): rng.standard_normal((sizes[r], sizes[c]))
            for r, c in keys})

    a, b = draw("A", keys_a), draw("B", keys_b)
    alpha = jnp.asarray(1.0, a.dtype)
    beta = jnp.asarray(-0.75, a.dtype)
    out = dt.create("S", a.row_blk_sizes, a.col_blk_sizes, "float64")
    ops._add_union(out, a, b, alpha, beta)
    got = next(bn for bn in out.bins if bn.shape == shape)
    # the eager path, on the structure the union add installed
    want = jnp.zeros(got.data.shape, got.data.dtype)
    rows, cols = out.entry_coords()
    for src, fac in ((a, alpha), (b, beta)):
        srows, scols = src.entry_coords()
        sel = [e for e in range(src.nblks)
               if (sizes[srows[e]], sizes[scols[e]]) == shape]
        if not sel:
            continue
        src_bin = src.bins[src.ent_bin[sel[0]]]
        dst = np.searchsorted(out.keys, src.keys[sel])
        assert all(out.bins[out.ent_bin[d]].shape == shape for d in dst)
        want = want.at[jnp.asarray(out.ent_slot[dst])].add(
            fac * jnp.take(src_bin.data, jnp.asarray(src.ent_slot[sel]),
                           axis=0))
    assert got.count > 0
    assert np.array_equal(np.asarray(got.data), np.asarray(want))
    np.testing.assert_allclose(
        dt.to_dense(out), dt.to_dense(a) - 0.75 * dt.to_dense(b),
        rtol=0, atol=1e-15)


def test_chain_spans_appear_once_a_step():
    h, _ = _decaying_h()
    timings.reset()
    x, history = sign_iteration(h, steps=12, filter_eps=EPS, tol=1e-6)
    steps = len(history)
    calls = {name: st.calls for name, st in timings._stats.items()}
    assert calls["sign_step"] == steps
    assert calls["multiply"] == 2 * steps
    # X_new - X of every step but those whose two patterns are equal
    # (the aligned add runs no union)
    assert 1 <= calls["add_union"] <= steps
    # one wait for ||X_new - X|| a step, and the Gershgorin norm's at
    # the start (one fetch a bin)
    assert calls["norm_fetch"] == steps + len(h.bins)
    # the nested spans leave the step a self time of its own
    step = timings._stats["sign_step"]
    assert 0 < step.self_time < step.total
    assert {"multiply", "add_union", "norm_fetch"} <= set(step.callees)
