"""The plain reference: operands drawn from the seed by the benchmark
itself, and the product in NumPy float64 on sampled block rows.

Nothing here goes through the program: A and B are the generator's own
blocks (`draw_blocks`, a copy of the `make_random_matrix` draw), not a
read-back of what the program stored.  Only C comes from the program.
"""

from __future__ import annotations

import dataclasses

import numpy as np

N_SAMPLE_ROWS = 8


@dataclasses.dataclass
class BlockSet:
    """A block-sparse matrix on the host: blocks in row-major block
    order, each stored row-major in ``flat`` at ``offsets``."""

    row_sizes: np.ndarray
    col_sizes: np.ndarray
    rows: np.ndarray     # block row of each stored block
    cols: np.ndarray     # block column
    offsets: np.ndarray  # start of each block in ``flat``; one extra at the end
    flat: np.ndarray     # values, in the cell's dtype

    @property
    def nblks(self) -> int:
        return len(self.rows)

    def block(self, e: int) -> np.ndarray:
        shape = (self.row_sizes[self.rows[e]], self.col_sizes[self.cols[e]])
        return self.flat[self.offsets[e]:self.offsets[e + 1]].reshape(shape)

    def by_shape(self):
        """(rows, cols, (N, bm, bn) array) per distinct block shape:
        what a bulk `put_blocks` takes."""
        bm = self.row_sizes[self.rows]
        bn = self.col_sizes[self.cols]
        for m, n in sorted(set(zip(bm.tolist(), bn.tolist()))):
            sel = np.nonzero((bm == m) & (bn == n))[0]
            idx = self.offsets[sel][:, None] + np.arange(m * n)[None, :]
            yield (self.rows[sel], self.cols[sel],
                   self.flat[idx].reshape(len(sel), m, n))


def draw_blocks(pattern_rng, value_rng, row_sizes, col_sizes,
                occupancy: float, dtype) -> BlockSet:
    """The `make_random_matrix` draw: one uniform per block position,
    stored where it is under ``occupancy``; then a standard normal per
    element, block by block in row-major block order (one flat draw
    consumes the stream exactly as the per-block draws do).  Given one
    generator twice it is that draw bit for bit; the benchmark draws
    the pattern from the configuration's `pattern_seed` and the values
    from `--seed`."""
    row_sizes = np.asarray(row_sizes, np.int64)
    col_sizes = np.asarray(col_sizes, np.int64)
    present = pattern_rng.random((len(row_sizes), len(col_sizes))) < occupancy
    rows, cols = np.nonzero(present)
    nel = row_sizes[rows] * col_sizes[cols]
    offsets = np.concatenate([[0], np.cumsum(nel)]).astype(np.int64)
    flat = value_rng.standard_normal(int(offsets[-1])).astype(dtype)
    return BlockSet(row_sizes, col_sizes, rows.astype(np.int64),
                    cols.astype(np.int64), offsets, flat)


def sample_block_rows(row_sizes, seed: int) -> list:
    """Block rows the reference is computed on: the first, the (ragged)
    last, one seeded pick of every distinct row-block size (each (m, n)
    bin of C has its own kernels, and a panel spans every block column),
    then seeded picks up to ``N_SAMPLE_ROWS``."""
    row_sizes = np.asarray(row_sizes)
    nblkrows = len(row_sizes)
    rng = np.random.default_rng(seed + 1)
    picks = {0, nblkrows - 1}
    for size in np.unique(row_sizes):
        picks.add(int(rng.choice(np.nonzero(row_sizes == size)[0])))
    while len(picks) < min(N_SAMPLE_ROWS, nblkrows):
        picks.add(int(rng.integers(0, nblkrows)))
    return sorted(picks)


def product_rows(a: BlockSet, b: BlockSet, block_rows, alpha=1.0) -> dict:
    """{block row: alpha * (A @ B)[that block row, :]} in float64, from
    the stored blocks alone."""
    b_start = np.searchsorted(b.rows, np.arange(len(b.row_sizes) + 1))
    b_off = np.concatenate([[0], np.cumsum(b.col_sizes)])
    out = {}
    for r in block_rows:
        acc = np.zeros((int(a.row_sizes[r]), int(b_off[-1])), np.float64)
        for e in range(*np.searchsorted(a.rows, [r, r + 1])):
            k = int(a.cols[e])
            panel = np.zeros((int(b.row_sizes[k]), int(b_off[-1])), np.float64)
            for f in range(b_start[k], b_start[k + 1]):
                c0 = b_off[b.cols[f]]
                panel[:, c0:c0 + b.col_sizes[b.cols[f]]] = b.block(f)
            acc += a.block(e).astype(np.float64) @ panel
        out[int(r)] = alpha * acc
    return out


def compare_rows(ref: dict, got: dict, tol: float) -> dict:
    """Largest elementwise error over the sampled rows, relative to the
    largest reference value (at least 1), against ``tol``."""
    err, scale, finite = 0.0, 1.0, True
    for r, want in ref.items():
        have = np.asarray(got[r], np.float64)
        if have.shape != want.shape or not np.all(np.isfinite(have)):
            finite = False
            continue
        err = max(err, float(np.max(np.abs(have - want))))
        scale = max(scale, float(np.max(np.abs(want))))
    rel = err / scale
    return {"rel_err": rel, "tol": tol, "rows": sorted(ref),
            "ok": bool(finite and rel <= tol)}
