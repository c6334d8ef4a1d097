"""The benchmark's own arithmetic: block multisets, true flops, modelled
bytes, the reference tolerance and the statistics of a sample.

Copies of what the program also has (`perf/driver.expand_block_sizes`,
`obs/costmodel.stack_flops/stack_bytes/superstack_bytes/dense_cost/
kernel_validation_tolerance`), kept here so that a PR to the program
cannot move the yardstick.  NumPy and the standard library only.
"""

from __future__ import annotations

import numpy as np


def expand_block_sizes(total: int, pattern) -> np.ndarray:
    """Cycle (multiplicity, size) pairs until ``total`` is covered; the
    last block is ragged (10 000 by 23 is 434 x 23 + one 18)."""
    sizes, covered = [], 0
    while covered < total:
        for mult, size in pattern:
            for _ in range(int(mult)):
                take = min(int(size), total - covered)
                if take <= 0:
                    break
                sizes.append(take)
                covered += take
            if covered >= total:
                break
    return np.asarray(sizes, np.int32)


def stack_flops(m: int, n: int, k: int, entries: int) -> int:
    """True flops of one (m,n,k) stack: 2*m*n*k per entry."""
    return 2 * m * n * k * entries


def stack_bytes(m: int, n: int, k: int, entries: int, *,
                nseg: int | None = None, itemsize: int = 8) -> int:
    """Least HBM traffic of one stack: one A (m,k) and one B (k,n) block
    gathered per entry, each C segment read and written once.  Tile
    padding and revisited gathers only add to it."""
    if nseg is None:
        nseg = entries
    return itemsize * (entries * (m * k + k * n) + 2 * nseg * m * n)


def superstack_bytes(span_shapes, *, nseg: int, itemsize: int = 8) -> int:
    """Least HBM traffic of one fused C-bin launch: every (m,n,k,entries)
    span gathers its own A and B blocks, the bin's ``nseg`` C blocks are
    read and written once for the whole launch."""
    gather, m, n = 0, 0, 0
    for m, n, k, entries in span_shapes:
        gather += entries * (m * k + k * n)
    return itemsize * (gather + 2 * nseg * m * n)


def dense_cost(m: int, n: int, k: int, *, itemsize: int = 8) -> dict:
    """Flops and bytes of one dense (m,k)x(k,n) matmul."""
    flops = 2 * m * n * k
    nbytes = itemsize * (m * k + k * n + 2 * m * n)
    return {"flops": flops, "bytes": nbytes, "intensity": flops / nbytes}


def product_stacks(a_rows, a_cols, b_rows, b_cols,
                   m_sizes, k_sizes, n_sizes) -> list:
    """[(m, n, k, entries, c_blocks_of_that_mn)] of the product of two
    block patterns, from the patterns alone: an entry is one (i,k,j)
    with A(i,k) and B(k,j) stored.  ``c_blocks`` counts the distinct
    (i,j) the (m,n) bin of C receives (the same for every k of a bin)."""
    m_sizes = np.asarray(m_sizes)
    k_sizes = np.asarray(k_sizes)
    n_sizes = np.asarray(n_sizes)
    nbr, nbk, nbc = len(m_sizes), len(k_sizes), len(n_sizes)
    pa = np.zeros((nbr, nbk), bool)
    pa[a_rows, a_cols] = True
    pb = np.zeros((nbk, nbc), bool)
    pb[b_rows, b_cols] = True
    # C's pattern: float32 matmul of 0/1 matrices counts exactly up to 2^24
    reach = (pa.astype(np.float32) @ pb.astype(np.float32)) > 0
    out = []
    for m in np.unique(m_sizes):
        a_m = pa[m_sizes == m].sum(axis=0).astype(np.int64)  # per k
        for n in np.unique(n_sizes):
            b_n = pb[:, n_sizes == n].sum(axis=1).astype(np.int64)
            c_blocks = int(reach[np.ix_(m_sizes == m, n_sizes == n)].sum())
            for k in np.unique(k_sizes):
                entries = int((a_m * b_n)[k_sizes == k].sum())
                if entries:
                    out.append((int(m), int(n), int(k), entries, c_blocks))
    return out


def true_flops(stacks) -> int:
    return sum(stack_flops(m, n, k, e) for m, n, k, e, _ in stacks)


def fused_stack_bytes(stacks, itemsize: int) -> int:
    """Least bytes the stack engine must move for one product: per C
    bin one fused launch (`superstack_bytes`)."""
    bins: dict = {}
    for m, n, k, e, cb in stacks:
        bins.setdefault((m, n), (cb, []))[1].append((m, n, k, e))
    return sum(superstack_bytes(spans, nseg=cb, itemsize=itemsize)
               for cb, spans in bins.values())


_EPS = {"float64": 2.220446049250313e-16, "float32": 1.1920929e-07}


def reference_tolerance(dtype: str, k: int, depth: int) -> float:
    """Largest elementwise error, relative to max|C|, allowed against the
    f64 NumPy reference: 2*eps*sqrt((k+1)*(depth+1)) for a k-deep dot
    summed over ``depth`` blocks, never under 4*eps*sqrt(k+1) (the
    program's `kernel_validation_tolerance` for f32 and f64).  At the
    north star (k=23, depth=435) that is 4.5e-14 in f64 and 2.4e-5 in
    f32, so an f64 cell computed in f32 fails by nine orders."""
    eps = _EPS[str(dtype)]  # an unknown dtype is an error, not a default
    k, depth = max(int(k), 1), max(int(depth), 1)
    return max(2.0 * eps * float((k + 1) * (depth + 1)) ** 0.5,
               4.0 * eps * float(k + 1) ** 0.5)


def quartiles(xs) -> dict:
    """n, median and quartiles of a sample (linear interpolation)."""
    a = np.sort(np.asarray(list(xs), np.float64))
    if not len(a):
        return {"n": 0}
    q1, med, q3 = np.percentile(a, [25, 50, 75])
    return {"n": int(len(a)), "min": float(a[0]), "q1": float(q1),
            "median": float(med), "q3": float(q3), "max": float(a[-1])}


def median(xs) -> float:
    return float(np.median(np.asarray(list(xs), np.float64)))
