"""Randomized test utilities: the dense oracle pattern.

Analog of `src/ops/dbcsr_test_methods.F` (`dbcsr_make_random_matrix`:70,
`dbcsr_to_dense_local`) — the reference's core verification approach
(SURVEY §4): build random block-sparse matrices, run the sparse op,
densify, compare against dense NumPy within epsilon.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from dbcsr_tpu.core.dist import Distribution
from dbcsr_tpu.core.kinds import dtype_of, is_complex
from dbcsr_tpu.core.matrix import NO_SYMMETRY, BlockSparseMatrix


# module-level generator used when no rng is passed; re-seedable like
# the reference's global random-matrix seed (ref `dbcsr_reset_randmat_seed`)
_RANDMAT_SEED = 0
_randmat_rng = np.random.default_rng(_RANDMAT_SEED)


def reset_randmat_seed(seed: int = _RANDMAT_SEED) -> None:
    """Reset the default random-matrix stream (ref
    `dbcsr_reset_randmat_seed`, `dbcsr_api.F:177`) so runs reproduce."""
    global _randmat_rng
    _randmat_rng = np.random.default_rng(seed)


def make_random_matrix(
    name: str,
    row_blk_sizes,
    col_blk_sizes,
    dtype=np.float64,
    occupation: float = 0.5,
    dist: Optional[Distribution] = None,
    matrix_type: str = NO_SYMMETRY,
    rng=None,
) -> BlockSparseMatrix:
    """Random block-sparse matrix with ~`occupation` block fill
    (ref `dbcsr_make_random_matrix`, `dbcsr_test_methods.F:70`)."""
    rng = rng or _randmat_rng
    m = BlockSparseMatrix(name, row_blk_sizes, col_blk_sizes, dtype, dist, matrix_type)
    dt = dtype_of(dtype)
    nbr, nbc = m.nblkrows, m.nblkcols
    present = rng.random((nbr, nbc)) < occupation
    if matrix_type != NO_SYMMETRY:
        present = np.triu(present)
    rows, cols = np.nonzero(present)
    for r, c in zip(rows, cols):
        shape = m.block_shape(r, c)
        blk = rng.standard_normal(shape)
        if is_complex(dt):
            blk = blk + 1j * rng.standard_normal(shape)
        if matrix_type != NO_SYMMETRY and r == c:
            blk = (blk + _fold(blk, matrix_type)) / 2  # consistent diagonal
        m.put_block(r, c, blk.astype(dt))
    return m.finalize()


def _fold(blk, matrix_type):
    if matrix_type == "S":
        return blk.T
    if matrix_type == "A":
        return -blk.T
    return blk.conj().T


def to_dense(matrix: BlockSparseMatrix) -> np.ndarray:
    """Densify locally (ref `dbcsr_to_dense_local`,
    used at `tests/dbcsr_test_multiply.F:315-317`)."""
    out = np.zeros((matrix.nfullrows, matrix.nfullcols), dtype=np.dtype(matrix.dtype))
    row_off = matrix.row_blk_offsets
    col_off = matrix.col_blk_offsets
    for r, c, blk in matrix.iterate_blocks():
        out[row_off[r] : row_off[r] + blk.shape[0], col_off[c] : col_off[c] + blk.shape[1]] = blk
        if matrix.matrix_type != NO_SYMMETRY and r != c:
            tb = _fold(blk, matrix.matrix_type)
            out[col_off[c] : col_off[c] + blk.shape[1], row_off[r] : row_off[r] + blk.shape[0]] = tb
    return out


def from_dense(
    name: str,
    dense: np.ndarray,
    row_blk_sizes,
    col_blk_sizes,
    dist: Optional[Distribution] = None,
    keep_zero_blocks: bool = False,
) -> BlockSparseMatrix:
    """Blocked matrix from a dense array, dropping all-zero blocks."""
    m = BlockSparseMatrix(name, row_blk_sizes, col_blk_sizes, dense.dtype, dist)
    row_off = m.row_blk_offsets
    col_off = m.col_blk_offsets
    for r in range(m.nblkrows):
        for c in range(m.nblkcols):
            blk = dense[
                row_off[r] : row_off[r + 1], col_off[c] : col_off[c + 1]
            ]
            if keep_zero_blocks or np.any(blk != 0):
                m.put_block(r, c, blk)
    return m.finalize()


def impose_sparsity(dense: np.ndarray, matrix: BlockSparseMatrix) -> np.ndarray:
    """Zero out dense entries outside the matrix's block pattern
    (ref `dbcsr_impose_sparsity`, `dbcsr_test_multiply.F:633`)."""
    mask = np.zeros_like(dense, dtype=bool)
    row_off = matrix.row_blk_offsets
    col_off = matrix.col_blk_offsets
    rows, cols = matrix.entry_coords()
    for r, c in zip(rows, cols):
        mask[row_off[r] : row_off[r + 1], col_off[c] : col_off[c + 1]] = True
        if matrix.matrix_type != NO_SYMMETRY and r != c:
            mask[col_off[c] : col_off[c + 1], row_off[r] : row_off[r + 1]] = True
    out = dense.copy()
    out[~mask] = 0
    return out


_pos_term_jit = None


def _pos_checksum_bin(data, ro, co):
    """Jitted per-bin position-dependent checksum term (one compiled
    callable, retraced per bin shape; returns a device scalar)."""
    global _pos_term_jit
    if _pos_term_jit is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _term(data, ro, co):
            bm, bn = data.shape[1], data.shape[2]
            grow = ro[:, None, None] + 1.0 + jnp.arange(
                bm, dtype=jnp.float64)[None, :, None]
            gcol = co[:, None, None] + 1.0 + jnp.arange(
                bn, dtype=jnp.float64)[None, None, :]
            w = jnp.log(jnp.abs(grow * gcol))
            return (jnp.real(data).astype(jnp.float64) * w).sum()

        _pos_term_jit = _term
    return _pos_term_jit(data, ro, co)


def checksum(matrix: BlockSparseMatrix, pos: bool = False) -> float:
    """Scalar checksum (ref `dbcsr_checksum`, `src/dist/dbcsr_dist_util.F:431`).

    Default: sum of squares of stored elements.  With ``pos``, the
    position-dependent variant of the reference (`pd_blk_cs`,
    `dbcsr_dist_util.F:551`): sum of Re(a[r,c]) * log(grow * gcol) with
    1-based global element coordinates — catches blocks landing at wrong
    positions, which the plain sum of squares cannot.
    """
    if pos:
        # per-bin DEVICE reduction, one 8-byte fetch per bin (not a
        # full-matrix d2h): the perf driver computes this checksum
        # after every run
        import jax.numpy as jnp

        row_off = matrix.row_blk_offsets
        col_off = matrix.col_blk_offsets
        rows, cols = matrix.entry_coords()
        total = 0.0
        for b_id, b in enumerate(matrix.bins):
            if b.count == 0:
                continue
            mask = matrix.ent_bin == b_id
            ro = np.zeros(b.count, np.float64)
            co = np.zeros(b.count, np.float64)
            slots = matrix.ent_slot[mask]
            ro[slots] = row_off[rows[mask]]
            co[slots] = col_off[cols[mask]]
            total += float(
                _pos_checksum_bin(b.data[: b.count], jnp.asarray(ro),
                                  jnp.asarray(co))
            )
        return total
    norms = matrix.block_norms().astype(np.float64)
    if matrix.matrix_type != NO_SYMMETRY:
        rows, cols = matrix.entry_coords()
        w = np.where(rows == cols, 1.0, 2.0)
        return float((w * norms**2).sum())
    return float((norms**2).sum())
