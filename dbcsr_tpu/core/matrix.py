"""The block-sparse matrix type.

Analog of `dbcsr_type` (`src/core/dbcsr_types.F:363-461`): a CSR index
over blocks plus block data.  TPU-first data model (SURVEY §7 design
mapping):

* Host index (NumPy): sorted int64 keys ``row * nblkcols + col`` with a
  derived ``row_ptr`` — the reference's row_p/col_i/blk_p triplet.
* Device data (HBM): one jax array per distinct block shape, of shape
  ``(capacity, bm, bn)`` — "shape bins".  The reference enumerates block
  sizes the same way (`dbcsr_mm_common.F:309` enumerate_blk_sizes);
  binning keeps every kernel launch statically shaped for XLA while
  supporting arbitrary mixed block sizes.  ``capacity >= count`` is
  bucketed (mempool analog) so repeated multiplies reuse compiled code.
* Assembly goes through a host-side work buffer then `finalize()`, like
  the reference's work matrices -> `dbcsr_finalize`
  (`src/work/dbcsr_work_operations.F:749`).

Symmetric/antisymmetric/hermitian matrices store the canonical upper
triangle only (row <= col), as the reference does; `put_block` folds
lower-triangle writes onto the stored transpose.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dbcsr_tpu.core import mempool
from dbcsr_tpu.core.dist import Distribution
from dbcsr_tpu.core.kinds import dtype_of, is_complex
from dbcsr_tpu.core.lib import ensure_init
from dbcsr_tpu.core.timings import booked
from dbcsr_tpu.utils.rounding import bucket_pow2, bucket_size

# matrix_type flags, ref dbcsr_type_no_symmetry/_symmetric/_antisymmetric/
# _hermitian in src/core/dbcsr_types.F
NO_SYMMETRY = "N"
SYMMETRIC = "S"
ANTISYMMETRIC = "A"
HERMITIAN = "H"


@dataclasses.dataclass
class _Bin:
    """One block-shape bin: device array of same-shape blocks."""

    shape: Tuple[int, int]
    data: object  # jnp.ndarray (capacity, bm, bn)
    count: int

    @property
    def capacity(self) -> int:
        return self.data.shape[0]


def _fold_block(block: np.ndarray, matrix_type: str) -> np.ndarray:
    """Transform a lower-triangle block to its stored upper-triangle image."""
    if matrix_type == SYMMETRIC:
        return block.T
    if matrix_type == ANTISYMMETRIC:
        return -block.T
    if matrix_type == HERMITIAN:
        return block.conj().T
    raise AssertionError(matrix_type)


@jax.jit
def _rezero_pad_rows(data, count):
    # count is a traced scalar: one compiled program per bin shape, not
    # one per (shape, count) pair as matrices grow
    mask = (jnp.arange(data.shape[0]) < count).reshape(-1, 1, 1)
    return jnp.where(mask, data, jnp.zeros_like(data))


@jax.jit
def _migrate_blocks(dst, src, src_slots, dst_slots):
    """Device-to-device move of surviving blocks into a rebuilt bin —
    the no-host-round-trip half of `dbcsr_merge_all`
    (`dbcsr_work_operations.F:1393`)."""
    return dst.at[dst_slots].set(jnp.take(src, src_slots, axis=0), mode="drop")


@functools.partial(jax.jit, static_argnames=("add",))
def _scatter_staged(dst, blocks, slots, add: bool):
    if add:
        return dst.at[slots].add(blocks, mode="drop")
    return dst.at[slots].set(blocks, mode="drop")


class BlockSparseMatrix:
    """A distributed block-compressed sparse row matrix."""

    # a matrix whose pattern moves from call to call (a tensor's, cut
    # into batches and refilled batch after batch:
    # `tensor.types.BlockSparseTensor`) takes bins in powers of two, and
    # the stacks that fill it take shapes that do not follow its counts
    # (`acc.smm.prepare_stack`), so that one batch's programs serve the
    # next
    moving_pattern = False

    def bin_capacity(self, n: int) -> int:
        """The capacity of a bin of ``n`` blocks."""
        return bucket_pow2(n) if self.moving_pattern else bucket_size(n)

    def __init__(
        self,
        name: str,
        row_blk_sizes,
        col_blk_sizes,
        dtype=np.float64,
        dist: Optional[Distribution] = None,
        matrix_type: str = NO_SYMMETRY,
    ):
        ensure_init()
        self.name = name
        self.row_blk_sizes = np.ascontiguousarray(row_blk_sizes, np.int32)
        self.col_blk_sizes = np.ascontiguousarray(col_blk_sizes, np.int32)
        self.dtype = dtype_of(dtype)
        self.matrix_type = matrix_type
        if matrix_type != NO_SYMMETRY:
            if len(self.row_blk_sizes) != len(self.col_blk_sizes) or not np.array_equal(
                self.row_blk_sizes, self.col_blk_sizes
            ):
                raise ValueError("symmetric matrix needs identical row/col blocking")
            if matrix_type == HERMITIAN and not is_complex(self.dtype):
                matrix_type = self.matrix_type = SYMMETRIC
        self.dist = dist or Distribution.trivial(
            len(self.row_blk_sizes), len(self.col_blk_sizes)
        )
        assert self.dist.nblkrows == self.nblkrows
        assert self.dist.nblkcols == self.nblkcols
        # finalized index
        self.keys = np.empty(0, np.int64)
        self.row_ptr = np.zeros(self.nblkrows + 1, np.int64)
        self.ent_bin = np.empty(0, np.int32)
        self.ent_slot = np.empty(0, np.int32)
        self.bins: List[_Bin] = []
        self._shape_to_bin: Dict[Tuple[int, int], int] = {}
        self.valid = True
        # pre-finalize work buffer: (row, col) -> host block
        self._work: Dict[Tuple[int, int], np.ndarray] = {}
        # batched staging: (keys int64, blocks (N, bm, bn), summation)
        self._work_batches: List[Tuple[np.ndarray, np.ndarray, bool]] = []
        # device residency (core.mempool): pool-owned matrices donate
        # replaced bin buffers back to the pool from the mutation
        # funnels; copy() marks bins shared, which disables donation
        self._pool_owned = False
        self._bins_shared = False
        # per-matrix device index mirrors, invalidated when the pattern
        # fingerprint changes (any structure-altering finalize)
        self._dev_mirrors: Dict = {}
        self._mirror_fp = None
        # value-delta tracking (mm.incremental / serve.product_cache):
        # a monotone mutation epoch plus a bounded journal of
        # (epoch, dirtied block keys | None) entries — None marks a
        # structure change (everything dirty).  Each matrix owns its
        # delta state exclusively; `copy()` deliberately does NOT
        # carry it over (shared bins never alias delta state).
        self._epoch = 0
        self._delta_log: List = []
        ch = mempool.current_chain()
        if ch is not None:
            ch.adopt(self)

    # ---------------------------------------------------------------- shape
    @property
    def nblkrows(self) -> int:
        return len(self.row_blk_sizes)

    @property
    def nblkcols(self) -> int:
        return len(self.col_blk_sizes)

    @property
    def nfullrows(self) -> int:
        return int(self.row_blk_sizes.sum())

    @property
    def nfullcols(self) -> int:
        return int(self.col_blk_sizes.sum())

    @property
    def row_blk_offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.row_blk_sizes)]).astype(np.int64)

    @property
    def col_blk_offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.col_blk_sizes)]).astype(np.int64)

    @property
    def nblks(self) -> int:
        return len(self.keys)

    @property
    def nnz(self) -> int:
        rows, cols = self.entry_coords()
        return int(
            (self.row_blk_sizes[rows].astype(np.int64) * self.col_blk_sizes[cols]).sum()
        )

    def occupation(self) -> float:
        """Fraction of nonzero elements (ref dbcsr_get_occupation)."""
        full = self.nfullrows * self.nfullcols
        return self.nnz / full if full else 0.0

    def setname(self, name: str) -> None:
        """Ref `dbcsr_setname`."""
        self.name = str(name)

    def get_stored_coordinates(self, row: int, col: int):
        """Owning (prow, pcol) of a block under this matrix's
        distribution (ref `dbcsr_get_stored_coordinates`)."""
        srow, scol = row, col
        if self.matrix_type != NO_SYMMETRY and row > col:
            srow, scol = col, row  # canonical triangle owns the block
        return self.dist.stored_coordinates(srow, scol)

    @property
    def valid_index(self) -> bool:
        """Finalized and consistent (ref `dbcsr_valid_index`)."""
        return self.valid

    @property
    def _donatable(self) -> bool:
        """THE donation-eligibility rule, single-sourced: replaced bin
        buffers may return to the memory pool only when this matrix is
        pool-owned (chain-adopted) and its bins were never shared
        through `copy` (a shared buffer must never be recycled)."""
        return self._pool_owned and not self._bins_shared

    def get_data_size(self) -> int:
        """Stored elements incl. bucket padding — the data-area size
        (ref `dbcsr_get_data_size`)."""
        return int(sum(b.capacity * b.shape[0] * b.shape[1] for b in self.bins))

    def get_info(self) -> dict:
        """Structure summary (ref `dbcsr_get_info`, `dbcsr_api.F`)."""
        return {
            "name": self.name,
            "matrix_type": self.matrix_type,
            "data_type": np.dtype(self.dtype).name,
            "nblkrows_total": self.nblkrows,
            "nblkcols_total": self.nblkcols,
            "nfullrows_total": self.nfullrows,
            "nfullcols_total": self.nfullcols,
            "nblks": self.nblks,
            "nze": self.nnz,
            "data_size": self.get_data_size(),
            "occupation": self.occupation(),
            "row_blk_sizes": self.row_blk_sizes.copy(),
            "col_blk_sizes": self.col_blk_sizes.copy(),
            "row_blk_offsets": self.row_blk_offsets[:-1].copy(),
            "col_blk_offsets": self.col_blk_offsets[:-1].copy(),
            "distribution": self.dist,
        }

    def block_shape(self, row: int, col: int) -> Tuple[int, int]:
        return int(self.row_blk_sizes[row]), int(self.col_blk_sizes[col])

    def entry_coords(self) -> Tuple[np.ndarray, np.ndarray]:
        """(rows, cols) arrays for all finalized entries, key-ordered."""
        return (
            (self.keys // self.nblkcols).astype(np.int64),
            (self.keys % self.nblkcols).astype(np.int64),
        )

    # ------------------------------------------------------------- assembly
    def put_block(self, row: int, col: int, block, summation: bool = False) -> None:
        """Stage a block for the next `finalize` (ref `dbcsr_put_block`,
        `src/block/dbcsr_block_access.F:73-76`)."""
        row, col, block = self._canonicalize(row, col, np.asarray(block))
        bm, bn = self.block_shape(row, col)
        if block.shape != (bm, bn):
            raise ValueError(
                f"block ({row},{col}) has shape {block.shape}, expected {(bm, bn)}"
            )
        block = block.astype(self.dtype, copy=True)
        key = (row, col)
        if summation and key in self._work:
            self._work[key] = self._work[key] + block
        elif summation and self._find_entry(row, col) >= 0:
            existing = self.get_block(row, col)
            self._work[key] = existing + block
        else:
            self._work[key] = block
        self.valid = False

    def put_blocks(self, rows, cols, blocks, summation: bool = False) -> None:
        """Stage many blocks at once — the vectorized assembly path
        (array-of-blocks analog of the reference's work matrices,
        `dbcsr_work_operations.F:674`; merged by `finalize` without a
        host round-trip of existing device data).

        ``blocks`` is an (N, bm, bn) array (uniform shape) or a list of
        2-D arrays; the data is snapshotted (caller may reuse buffers).
        Staged batches become visible at `finalize`; they are applied
        after any single `put_block` stagings, in call order, with
        ``summation=True`` batches adding to whatever value the block
        has at merge time.  Duplicates within one call are pre-reduced:
        summed when ``summation``, last-write-wins otherwise.
        """
        self._work_batches.extend(
            self._make_batches(rows, cols, blocks, summation)
        )
        self.valid = False

    def _validate_coords(self, rows: np.ndarray, cols: np.ndarray) -> None:
        if rows.min() < 0 or rows.max() >= self.nblkrows or cols.min() < 0 or (
            cols.max() >= self.nblkcols
        ):
            raise IndexError("block coordinates out of range")

    def _validate_batch_shape(self, rows, cols, bm: int, bn: int) -> None:
        if not (
            np.all(self.row_blk_sizes[rows] == bm)
            and np.all(self.col_blk_sizes[cols] == bn)
        ):
            raise ValueError(
                f"batch of shape ({bm},{bn}) does not match the blocking "
                f"at all its coordinates"
            )

    def _make_batches(self, rows, cols, blocks, summation: bool):
        """Canonicalize (symmetry fold), validate, group by block shape,
        and pre-reduce duplicates; returns [(keys, (N,bm,bn) array,
        summation)] staging batches."""
        rows = np.ascontiguousarray(rows, np.int64)
        cols = np.ascontiguousarray(cols, np.int64)
        if len(rows) != len(cols):
            raise ValueError("rows/cols length mismatch")
        if len(rows) == 0:
            return []
        self._validate_coords(rows, cols)
        uniform = isinstance(blocks, np.ndarray) and blocks.ndim == 3
        if not uniform and len(blocks) != len(rows):
            raise ValueError("blocks length mismatch")
        # canonicalize BEFORE grouping: folding transposes blocks, which
        # changes their shape group for rectangular off-diagonal blocks
        if self.matrix_type != NO_SYMMETRY:
            fold = rows > cols
            if fold.any():
                blocks = [
                    _fold_block(np.asarray(blocks[i]), self.matrix_type)
                    if fold[i] else np.asarray(blocks[i])
                    for i in range(len(rows))
                ]
                uniform = False
                rows, cols = np.where(fold, cols, rows), np.where(fold, rows, cols)
        if uniform:
            groups = [(np.arange(len(rows)), np.array(blocks, dtype=self.dtype))]
        else:
            shapes = np.array([np.asarray(b).shape for b in blocks], np.int64)
            code = shapes[:, 0] << 32 | shapes[:, 1]
            groups = []
            for u in np.unique(code):
                idx = np.nonzero(code == u)[0]
                groups.append(
                    (idx, np.stack([blocks[i] for i in idx]).astype(self.dtype))
                )
        out = []
        for idx, arr in groups:
            r, c = rows[idx], cols[idx]
            bm, bn = arr.shape[1], arr.shape[2]
            self._validate_batch_shape(r, c, bm, bn)
            keys = r * self.nblkcols + c
            if len(np.unique(keys)) != len(keys):
                if summation:
                    uniq, inv = np.unique(keys, return_inverse=True)
                    red = np.zeros((len(uniq), bm, bn), self.dtype)
                    np.add.at(red, inv, arr)
                    keys, arr = uniq, red
                else:
                    # deterministic last-write-wins (jnp scatter with
                    # duplicate indices is undefined-order)
                    uniq, first_rev = np.unique(keys[::-1], return_index=True)
                    last = len(keys) - 1 - first_rev
                    keys, arr = uniq, arr[last]
            out.append((keys, arr, summation))
        return out

    def stage_device_blocks(self, rows, cols, blocks, summation: bool = False) -> None:
        """Stage an (N, bm, bn) DEVICE array of uniform-shape blocks
        without a host round-trip — the device-side sibling of
        `put_blocks` (used by the tensor reshape path, ref
        `dbcsr_t_reshape`'s buffered block alltoall,
        `dbcsr_tensor_reshape.F:67,288`).  The batch merges at
        `finalize` via the same device gather/scatter as host batches.

        ``blocks`` may hold more rows than there are coordinates (a
        bucketed batch, so that the program that made it and the merge
        that takes it are keyed by the bucket): row i is block i of the
        coordinates, the rows past them are never read.

        Caller contract: (row, col) pairs are unique within the batch
        (jnp scatter with duplicates is undefined-order), and the
        matrix has no symmetry (device blocks are not host-foldable).
        """
        if self.matrix_type != NO_SYMMETRY:
            raise NotImplementedError(
                "stage_device_blocks requires a non-symmetric matrix"
            )
        rows = np.ascontiguousarray(rows, np.int64)
        cols = np.ascontiguousarray(cols, np.int64)
        if len(rows) != len(cols) or len(rows) > blocks.shape[0]:
            raise ValueError("rows/cols/blocks length mismatch")
        if len(rows) == 0:
            return
        self._validate_coords(rows, cols)
        self._validate_batch_shape(rows, cols, int(blocks.shape[1]), int(blocks.shape[2]))
        keys = rows * self.nblkcols + cols
        if blocks.dtype != np.dtype(self.dtype):
            blocks = blocks.astype(self.dtype)
        self._work_batches.append((keys, blocks, summation))
        self.valid = False

    def reserve_block(self, row: int, col: int) -> None:
        """Ref `dbcsr_reserve_block2d`: allocate a zero block."""
        row, col, _ = self._canonicalize(row, col, None)
        if (row, col) not in self._work and self._find_entry(row, col) < 0:
            self._work[(row, col)] = np.zeros(self.block_shape(row, col), self.dtype)
            self.valid = False

    def _canonicalize(self, row, col, block):
        if not (0 <= row < self.nblkrows and 0 <= col < self.nblkcols):
            raise IndexError(f"block ({row},{col}) out of range")
        if self.matrix_type != NO_SYMMETRY and row > col:
            if block is not None:
                block = _fold_block(block, self.matrix_type)
            row, col = col, row
        return row, col, block

    def finalize(self) -> "BlockSparseMatrix":
        """Merge staged blocks into the CSR index (ref `dbcsr_finalize` ->
        `dbcsr_merge_all`, `dbcsr_work_operations.F:749,1393`).

        Existing device data is never round-tripped through host:
        surviving blocks move bin-to-bin with one device gather/scatter
        per shape, and only the staged host blocks are uploaded.
        """
        if not self._work and not self._work_batches:
            self.valid = True
            return self
        with booked("matrix_finalize"):
            return self._merge_staged()

    def _merge_staged(self) -> "BlockSparseMatrix":
        nbc = self.nblkcols
        if self._work:
            # single-put stagings become a leading replace batch (keys
            # are already canonical; dict semantics were last-wins)
            self._work_batches = self._make_batches(
                np.array([r for (r, _) in self._work], np.int64),
                np.array([c for (_, c) in self._work], np.int64),
                [blk for blk in self._work.values()],
                False,
            ) + self._work_batches
            self._work.clear()
        staged_keys = np.unique(
            np.concatenate([k for (k, _, _) in self._work_batches]))
        merged = np.union1d(self.keys, staged_keys)
        # same-pattern finalize (the SCF-loop value update): the delta
        # journal records exactly the staged keys instead of marking
        # the whole matrix dirty
        same_pattern = len(merged) == len(self.keys) and np.array_equal(
            merged, self.keys)
        rows = (merged // nbc).astype(np.int64)
        cols = (merged % nbc).astype(np.int64)
        nb, nsl, shapes = _bin_entries(
            self.row_blk_sizes, self.col_blk_sizes, rows, cols
        )
        shape_to_bin = {(int(bm), int(bn)): i for i, (bm, bn) in enumerate(shapes)}
        counts = np.bincount(nb, minlength=len(shapes))
        data_arrs = [
            mempool.zeros((self.bin_capacity(int(counts[i])), int(bm),
                           int(bn)),
                          self.dtype)
            for i, (bm, bn) in enumerate(shapes)
        ]
        # 1) surviving old blocks: device-to-device migration per shape
        if len(self.keys):
            pos_old = np.searchsorted(merged, self.keys)
            new_bin_of_old = nb[pos_old]
            for b in range(len(shapes)):
                old_sel = np.nonzero(new_bin_of_old == b)[0]
                if not len(old_sel):
                    continue
                src = self.bins[self.ent_bin[old_sel[0]]]
                data_arrs[b] = _migrate_blocks(
                    data_arrs[b],
                    src.data,
                    mempool.upload_index("fin_src", self.ent_slot[old_sel]),
                    mempool.upload_index("fin_dst", nsl[pos_old[old_sel]]),
                )
        # 2) staged batches in call order (a batch is shape-uniform ->
        #    exactly one bin; single puts were prepended as a batch)
        for keys_b, arr, summation in self._work_batches:
            b = shape_to_bin[(arr.shape[1], arr.shape[2])]
            slots = nsl[np.searchsorted(merged, keys_b)]
            if isinstance(arr, np.ndarray):
                mempool.record_h2d(arr.nbytes)  # staged host blocks
            if len(slots) < arr.shape[0]:
                # a device batch of bucketed rows (`stage_device_blocks`):
                # the rows past its keys land past the bin and drop
                slots = np.concatenate([slots, np.full(
                    arr.shape[0] - len(slots), data_arrs[b].shape[0],
                    slots.dtype)])
            data_arrs[b] = _scatter_staged(
                data_arrs[b], jnp.asarray(arr),
                mempool.upload_index("fin_slot", slots), bool(summation)
            )
        bins = [
            _Bin((int(bm), int(bn)), data_arrs[i], int(counts[i]))
            for i, (bm, bn) in enumerate(shapes)
        ]
        self._work.clear()
        self._work_batches.clear()
        self.set_structure_from_device(
            merged, bins, binning=(nb, nsl, shapes),
            value_delta_keys=staged_keys if same_pattern else None)
        return self

    def set_structure_from_device(
        self, keys: np.ndarray, bins: List[_Bin], binning=None,
        value_delta_keys=None,
    ) -> None:
        """Adopt a prebuilt index + device bins (used by the multiply
        engine, which assembles C on device).  ``binning`` optionally
        carries a precomputed ``_bin_entries`` result to avoid
        recomputing it.  ``value_delta_keys`` refines the delta
        journal: a same-pattern caller (value-only finalize) passes
        exactly the touched block keys; the default None records a
        structure change (everything dirty).

        Caller contract (every in-tree caller satisfies it): ``bins``
        hold FRESHLY CONSTRUCTED device arrays not aliased into any
        other matrix — which is why a full restructure clears the
        `copy`-induced shared mark: the new bins are exclusively this
        matrix's again, so pool donation resumes."""
        keys = np.ascontiguousarray(keys, np.int64)
        rows = (keys // self.nblkcols).astype(np.int64)
        cols = (keys % self.nblkcols).astype(np.int64)
        if binning is None:
            binning = _bin_entries(self.row_blk_sizes, self.col_blk_sizes, rows, cols)
        bin_ids, slots, shapes = binning
        # pool-owned matrices donate the buffers this restructure
        # retires (the dbcsr_mem_methods "return to pool" half);
        # anything aliased into the NEW bins — or ever shared via
        # copy() — is kept
        old_data = [b.data for b in self.bins] if self._donatable else None
        self.keys = keys
        self.row_ptr = np.zeros(self.nblkrows + 1, np.int64)
        self.row_ptr[1:] = np.cumsum(np.bincount(rows, minlength=self.nblkrows))
        self.ent_bin = bin_ids
        self.ent_slot = slots
        by_shape = {b.shape: b for b in bins}
        self.bins = [by_shape[(int(bm), int(bn))] for (bm, bn) in shapes]
        self._shape_to_bin = {b.shape: i for i, b in enumerate(self.bins)}
        self._work.clear()
        self._work_batches.clear()
        self.invalidate_dense_cache()  # structure changed
        if old_data is not None:
            live = {id(b.data) for b in self.bins}
            for d in old_data:
                if id(d) not in live:
                    mempool.release(d)
        self._bins_shared = False  # fresh bins: exclusively owned again
        self._note_mutation(value_delta_keys)
        self.valid = True

    # --------------------------------------------------------------- access
    def _find_entry(self, row: int, col: int) -> int:
        key = row * self.nblkcols + col
        i = np.searchsorted(self.keys, key)
        if i < len(self.keys) and self.keys[i] == key:
            return int(i)
        return -1

    def get_block(self, row: int, col: int, unfold: bool = True):
        """Fetch one block to host; None if absent (ref `dbcsr_get_block_p`)."""
        srow, scol = row, col
        folded = False
        if self.matrix_type != NO_SYMMETRY and row > col:
            srow, scol, folded = col, row, True
        if (srow, scol) in self._work:
            blk = self._work[(srow, scol)].copy()
        else:
            e = self._find_entry(srow, scol)
            if e < 0:
                return None
            b = self.bins[self.ent_bin[e]]
            blk = np.asarray(b.data[self.ent_slot[e]])
            mempool.record_d2h(blk.nbytes)
        if folded and unfold:
            blk = _fold_block(blk, self.matrix_type)
        return blk

    def get_blocks(self, rows, cols, unfold: bool = True) -> List:
        """Fetch many blocks with ONE batched device gather per shape
        bin instead of a per-entry D2H round-trip (`get_block` in a
        loop fetches block-by-block; this is its `stage_device_blocks`
        sibling on the read side).  Returns a list aligned with
        ``rows``/``cols``; absent blocks are None.  Blocks still
        sitting in the pre-finalize work buffer are served from host."""
        rows = np.ascontiguousarray(rows, np.int64)
        cols = np.ascontiguousarray(cols, np.int64)
        if len(rows) != len(cols):
            raise ValueError("rows/cols length mismatch")
        n = len(rows)
        out: List = [None] * n
        if n == 0:
            return out
        self._validate_coords(rows, cols)
        srows, scols = rows.copy(), cols.copy()
        folded = np.zeros(n, bool)
        if self.matrix_type != NO_SYMMETRY:
            folded = rows > cols
            srows = np.where(folded, cols, rows)
            scols = np.where(folded, rows, cols)
        keys = srows * self.nblkcols + scols
        pos = np.searchsorted(self.keys, keys)
        pos_c = np.minimum(pos, max(len(self.keys) - 1, 0))
        found = (
            np.zeros(n, bool) if len(self.keys) == 0
            else self.keys[pos_c] == keys
        )
        for b_id, b in enumerate(self.bins):
            sel = np.nonzero(found & (self.ent_bin[pos_c] == b_id))[0]
            if not len(sel):
                continue
            slots = self.ent_slot[pos_c[sel]]
            fetched = np.asarray(
                jnp.take(b.data, mempool.upload_index("getblk", slots),
                         axis=0))
            mempool.record_d2h(fetched.nbytes)
            for i, e in enumerate(sel):
                out[e] = fetched[i]
        for e in range(n):
            key = (int(srows[e]), int(scols[e]))
            if key in self._work:
                out[e] = self._work[key].copy()
            if out[e] is not None and folded[e] and unfold:
                out[e] = _fold_block(out[e], self.matrix_type)
        return out

    def iterate_blocks(self) -> Iterator[Tuple[int, int, np.ndarray]]:
        """Iterate stored blocks in index order (ref `dbcsr_iterator_*`,
        `src/block/dbcsr_iterator_operations.F:91`).  Fetches each bin
        from device once."""
        if not self.valid:
            raise RuntimeError("finalize() before iterating")
        host_bins = [np.asarray(b.data[: b.count]) for b in self.bins]
        mempool.record_d2h(sum(hb.nbytes for hb in host_bins))
        rows, cols = self.entry_coords()
        for e in range(self.nblks):
            yield int(rows[e]), int(cols[e]), host_bins[self.ent_bin[e]][
                self.ent_slot[e]
            ]

    def iterator(self) -> "BlockIterator":
        """Reference-style explicit iterator (ref `dbcsr_iterator_start`
        / `_blocks_left` / `_next_block` / `_stop`,
        `src/block/dbcsr_iterator_operations.F:44-91`); `iterate_blocks`
        is the Pythonic equivalent."""
        return BlockIterator(self)

    def block_norms(self) -> np.ndarray:
        """Frobenius norm per finalized entry, key-ordered (device
        compute).  Memoized against the bin data-array identities
        under device residency (`core.mempool`): a matrix used as both
        operands of a filtered product — or reused across a chain's
        multiplies — computes (and fetches) its norms once, like the
        reference's per-data-area `calc_norms` caching.  The cache
        holds the hashed arrays, so ids cannot recycle (the
        `core.digests.buffers_key` identity-key convention)."""
        from dbcsr_tpu.core import digests

        key = digests.buffers_key(b.data for b in self.bins)
        cached = getattr(self, "_norms_cache", None)
        if mempool.enabled() and cached is not None and cached[0] == key:
            return cached[1]
        from dbcsr_tpu.acc.smm import block_norms as _bn

        out = np.zeros(self.nblks, np.float64)
        for b_id, b in enumerate(self.bins):
            if b.count == 0:
                continue
            norms = _bn(b.data)
            mask = self.ent_bin == b_id
            out[mask] = np.asarray(norms)[self.ent_slot[mask]]
        if mempool.enabled():
            self._norms_cache = (key, out, [b.data for b in self.bins])
        return out

    # ------------------------------------------------------------ structure
    def pattern_fingerprint(self):
        """Cheap content hash of the sparsity pattern (keys + the full
        BLOCKING vectors — same keys under different blockings are
        different patterns), memoized against the keys array object.
        Holding the hashed array alive makes the identity check sound
        (no id reuse).  Used to key plan caches for repeated
        same-pattern multiplies (SCF-style loops)."""
        from dbcsr_tpu.core import digests

        if getattr(self, "_blk_fp", None) is None:
            self._blk_fp = digests.digest(
                self.row_blk_sizes.tobytes(), self.col_blk_sizes.tobytes()
            )[:8]
        if getattr(self, "_fp_keys", None) is not self.keys:
            self._fp_keys = self.keys
            self._fp = (
                self.nblkrows, self.nblkcols, len(self.keys), self._blk_fp,
                digests.digest(self.keys.tobytes())[:8],
            )
        return self._fp

    # ---------------------------------------------------------- value deltas
    # bounded journal: older baselines than the journal reaches degrade
    # to "unknown" (full recompute), never to a wrong delta
    _DELTA_LOG_MAX = 64

    @property
    def mutation_epoch(self) -> int:
        """Monotone per-matrix mutation counter: bumped by every
        mutation funnel (finalize/restructure, `map_bin_data`, diag
        writes, donated adds, pool restore/free).  Consumers snapshot
        it and later ask `dirty_keys_since` for the delta."""
        return self._epoch

    def _note_mutation(self, keys) -> None:
        """Record one mutation: ``keys`` is the int64 block-key array
        the mutation touched (values only, structure unchanged), or
        None for a structure change / unknown extent (everything
        dirty).  The journal holds consecutive epochs; a None entry
        resets it (nothing older can be reconstructed past it)."""
        self._epoch += 1
        if keys is None:
            self._delta_log = [(self._epoch, None)]
            return
        self._delta_log.append(
            (self._epoch, np.asarray(keys, np.int64)))
        if len(self._delta_log) > self._DELTA_LOG_MAX:
            del self._delta_log[0]

    def dirty_keys_since(self, epoch: int):
        """Block keys whose VALUES may have changed since ``epoch`` (a
        prior `mutation_epoch` snapshot): an int64 key array (possibly
        empty = provably unchanged), or None when the delta is unknown
        — the structure changed, the journal no longer reaches back to
        ``epoch``, or ``epoch`` was never this matrix's (a rolled-back
        or foreign epoch).  None always means "treat everything as
        dirty"; it is never wrong, only conservative."""
        if epoch == self._epoch:
            return np.empty(0, np.int64)
        if epoch > self._epoch or not self._delta_log:
            return None
        first = self._delta_log[0][0]
        if epoch < first - 1:
            return None  # journal truncated past the baseline
        parts = []
        for e, k in self._delta_log:
            if e <= epoch:
                continue
            if k is None:
                return None
            parts.append(k)
        if not parts:
            return None  # epoch inside a reset journal: unknown
        return np.unique(np.concatenate(parts))

    def copy(self, name: Optional[str] = None) -> "BlockSparseMatrix":
        m = BlockSparseMatrix(
            name or self.name,
            self.row_blk_sizes,
            self.col_blk_sizes,
            self.dtype,
            self.dist,
            self.matrix_type,
        )
        m.moving_pattern = self.moving_pattern
        m.keys = self.keys.copy()
        m.row_ptr = self.row_ptr.copy()
        m.ent_bin = self.ent_bin.copy()
        m.ent_slot = self.ent_slot.copy()
        m.bins = [_Bin(b.shape, b.data, b.count) for b in self.bins]
        m._shape_to_bin = dict(self._shape_to_bin)
        m._work = {k: v.copy() for k, v in self._work.items()}
        m._work_batches = [(k.copy(), a.copy(), s) for (k, a, s) in self._work_batches]
        m.valid = self.valid
        # both sides now alias the same device buffers: neither may
        # ever donate them back to the pool (conservative, permanent)
        if self.bins:
            self._bins_shared = True
            m._bins_shared = True
        return m

    def map_bin_data(self, fn) -> None:
        """Apply a jax fn to every bin's device data in place.

        Bucket-padding rows (slot >= count) are re-zeroed afterwards:
        the engine's Pallas path masks short stack groups with them and
        relies on the rows-beyond-count-are-zero invariant, which an
        arbitrary elementwise fn (fn(0) != 0) would otherwise break.
        """
        releasable = self._donatable
        all_fresh = True
        for b in self.bins:
            if b.count:
                data = fn(b.data)
                if data.shape[0] > b.count:
                    data = _rezero_pad_rows(data, b.count)
                if releasable and data is not b.data:
                    mempool.release(b.data)
                if data is b.data:
                    all_fresh = False
                b.data = data
            else:
                all_fresh = False  # empty bin: data possibly still aliased
        if all_fresh and self.bins:
            # every buffer was replaced with a fresh fn output: a
            # copy-induced shared mark no longer applies (a chain whose
            # lineage passed through copy()+scale regains donation)
            self._bins_shared = False
        self.invalidate_dense_cache()  # values changed
        self._note_mutation(self.keys)  # every stored value touched

    def device_index(self, tag, build):
        """Per-matrix device mirror of a structure-derived index array
        (or tuple of arrays) — the `acc_devmem` + `acc_ready` analog:
        ``build`` runs on the first request and whenever the sparsity
        pattern changed since (any finalize that altered structure
        invalidates — the mirror is keyed to `pattern_fingerprint`, so
        a same-pattern finalize keeps it).  Only STRUCTURE-derived
        uploads belong here; value-dependent arrays must not be
        mirrored.  Honors the residency knob like every other mirror:
        with `mempool` disabled, ``build`` runs every call (the
        historical re-upload-per-op engine)."""

        def _count(x):
            for leaf in x if isinstance(x, (tuple, list)) else (x,):
                mempool.record_h2d(
                    int(np.prod(leaf.shape))
                    * int(jnp.dtype(leaf.dtype).itemsize))

        if not mempool.enabled():
            hit = build()
            _count(hit)
            return hit
        fp = self.pattern_fingerprint()
        if self._mirror_fp != fp:
            self._dev_mirrors.clear()
            self._mirror_fp = fp
        hit = self._dev_mirrors.get(tag)
        if hit is None:
            hit = self._dev_mirrors[tag] = build()
            _count(hit)
        return hit

    def free(self) -> None:
        """Release this matrix's device storage back to the memory pool
        (the `dbcsr_release` analog): bin buffers and any cached dense
        canvas are donated when this matrix owns them exclusively
        (pool-owned, never shared through `copy`), then the matrix is
        emptied and marked invalid.  Stale outside references to the
        released buffers raise on use once recycled — they never read
        recycled data."""
        if self._donatable:
            for b in self.bins:
                mempool.release(b.data)
            cache = getattr(self, "_dense_canvas_cache", None)
            if cache is not None:
                mempool.release(cache[1])
        self.bins = []
        self._shape_to_bin = {}
        self.keys = np.empty(0, np.int64)
        self.row_ptr = np.zeros(self.nblkrows + 1, np.int64)
        self.ent_bin = np.empty(0, np.int32)
        self.ent_slot = np.empty(0, np.int32)
        self._work.clear()
        self._work_batches.clear()
        self._dev_mirrors.clear()
        self._mirror_fp = None
        self._dense_canvas_cache = None
        self._norms_cache = None
        self._note_mutation(None)  # emptied: nothing reusable remains
        self.valid = False

    def invalidate_dense_cache(self) -> None:
        """Drop the cached dense canvas (multiply engine) and the
        block-norms memo.  Correctness never depends on this — both
        caches key by bin data-array identity, so any rebind misses —
        but the caches PIN the old device arrays (id-stability), so
        every mutation funnel calls this to release them early
        (`map_bin_data` / `set_structure_from_device` do)."""
        self._dense_canvas_cache = None
        self._norms_cache = None

    def zero_data(self) -> None:
        self.map_bin_data(lambda d: jnp.zeros_like(d))

    def __repr__(self) -> str:
        return (
            f"BlockSparseMatrix({self.name!r}, {self.nblkrows}x{self.nblkcols} blocks,"
            f" {self.nblks} stored, dtype={np.dtype(self.dtype).name},"
            f" type={self.matrix_type})"
        )


class BlockIterator:
    """Explicit start/next/stop block iterator mirroring the reference
    API shape (`dbcsr_iterator_operations.F`): ``blocks_left()`` /
    ``next_block() -> (row, col, block)`` / ``stop()``.  Fetches each
    device bin once at start, like `iterate_blocks`."""

    def __init__(self, matrix: "BlockSparseMatrix"):
        if not matrix.valid:
            raise RuntimeError("finalize() before iterating")
        self._it = matrix.iterate_blocks()
        self._next = None
        self._advance()

    def _advance(self):
        try:
            self._next = next(self._it)
        except StopIteration:
            self._next = None

    def blocks_left(self) -> bool:
        return self._next is not None

    def next_block(self):
        # IndexError, not StopIteration: a StopIteration escaping from a
        # plain method into a caller's generator frame becomes
        # RuntimeError under PEP 479
        if self._next is None:
            raise IndexError("no blocks left")
        out = self._next
        self._advance()
        return out

    def stop(self) -> None:
        self._it = iter(())
        self._next = None


def _bin_entries(row_blk_sizes, col_blk_sizes, rows, cols):
    """Assign each entry a shape-bin id and an in-bin slot (key order).

    Avoids sorting the (possibly huge) entry list: distinct block SIZES
    are few (the reference enumerates them the same way,
    `dbcsr_mm_common.F:309`), so bin ids come from a small size->id
    lookup and slots from per-bin cumulative counts.
    """
    n = len(rows)
    if n == 0:
        return np.empty(0, np.int32), np.empty(0, np.int32), []
    ur = np.unique(row_blk_sizes)
    uc = np.unique(col_blk_sizes)
    if len(ur) * len(uc) > max(4 * n, 1 << 20):
        # degenerate many-distinct-sizes case: dense size table would
        # dwarf the entry list; pay the O(n log n) sort instead
        code64 = row_blk_sizes[rows].astype(np.int64) << 32 | col_blk_sizes[cols]
        uniq, inv = np.unique(code64, return_inverse=True)
        inv = inv.astype(np.int32)
        shapes = [(int(u >> 32), int(u & 0xFFFFFFFF)) for u in uniq]
    else:
        # size -> small id per entry via tiny searchsorted tables
        rid = np.searchsorted(ur, row_blk_sizes[rows])
        cid = np.searchsorted(uc, col_blk_sizes[cols])
        code = rid.astype(np.int32) * len(uc) + cid
        counts_all = np.bincount(code, minlength=len(ur) * len(uc))
        present = np.nonzero(counts_all)[0]
        remap = np.zeros(len(ur) * len(uc), np.int32)
        remap[present] = np.arange(len(present), dtype=np.int32)
        inv = remap[code]
        shapes = [(int(ur[p // len(uc)]), int(uc[p % len(uc)])) for p in present]
    nbins = len(shapes)
    if nbins == 1:
        return inv, np.arange(n, dtype=np.int32), shapes
    slots = np.empty(n, np.int32)
    if nbins <= 16:
        for b in range(nbins):
            idx = np.nonzero(inv == b)[0]
            slots[idx] = np.arange(len(idx), dtype=np.int32)
    else:
        counts = np.bincount(inv, minlength=nbins)
        starts = np.concatenate([[0], np.cumsum(counts[:-1])])
        order = np.argsort(inv, kind="stable")
        slots[order] = (np.arange(n) - np.repeat(starts, counts)).astype(np.int32)
    return inv, slots, shapes


def create(
    name: str,
    row_blk_sizes,
    col_blk_sizes,
    dtype=np.float64,
    dist: Optional[Distribution] = None,
    matrix_type: str = NO_SYMMETRY,
) -> BlockSparseMatrix:
    """Ref `dbcsr_create` (`src/work/dbcsr_work_operations.F:106`)."""
    return BlockSparseMatrix(name, row_blk_sizes, col_blk_sizes, dtype, dist, matrix_type)
