"""TAS multiply: CARMA-style split of the long dimension.

Ref `dbcsr_tas_multiply` (`dbcsr_tas_mm.F:79`): pick the long dimension
of C = op(A) op(B); split it into nsplit groups; run an ordinary
multiply per group; reduce.  The reference replicates the small matrix
into each process group and redistributes/sums afterwards
(`redistribute_and_sum`, :783); here the group loop reuses the engine's
block-index limit arguments, which bound each group's working set (the
same memory effect the grid split achieves) while keeping a fixed,
deterministic accumulation order.
"""

from __future__ import annotations

from typing import Optional, Union

import numpy as _np

from dbcsr_tpu.core import mempool as _mempool
from dbcsr_tpu.core.config import get_config
from dbcsr_tpu.core.matrix import BlockSparseMatrix
from dbcsr_tpu.core.timings import timed
from dbcsr_tpu.mm.multiply import multiply
from dbcsr_tpu.obs import metrics as _metrics
from dbcsr_tpu.obs import tracer as _trace
from dbcsr_tpu.ops.operations import scale
from dbcsr_tpu.parallel.mesh import optimize_grid
from dbcsr_tpu.tas.base import TASMatrix
from dbcsr_tpu.tas.split import (
    choose_nsplit,
    choose_nsplit_traffic,
    estimate_split_factor,
)
from dbcsr_tpu.utils.rounding import ceil_div

# ref default_nsplit_accept_ratio (`dbcsr_tas_split.F:57`): a cached
# batch split survives while within this factor of the current optimum
_NSPLIT_ACCEPT_RATIO = 3.0


def _unwrap(x: Union[TASMatrix, BlockSparseMatrix]) -> BlockSparseMatrix:
    return x.matrix if isinstance(x, TASMatrix) else x


def tas_multiply(
    transa: str,
    transb: str,
    alpha,
    matrix_a: Union[TASMatrix, BlockSparseMatrix],
    matrix_b: Union[TASMatrix, BlockSparseMatrix],
    beta,
    matrix_c: Union[TASMatrix, BlockSparseMatrix],
    filter_eps: Optional[float] = None,
    nsplit: Optional[int] = None,
    ngroups_max: int = 64,
    mesh=None,
) -> int:
    """C = alpha op(A) op(B) + beta C with long-dimension splitting.

    Returns total flops.  `nsplit=None` chooses the split from the
    split-factor estimate (ref `dbcsr_tas_mm.F:1427`); `nsplit=1`
    degenerates to a single multiply.

    With ``mesh`` the per-group multiplies run on the block-sparse
    distributed Cannon path (`parallel/sparse_dist.py`) — the
    single-controller analog of the reference's per-group process
    grids (`dbcsr_tas_split.F:304`), with the group loop bounding each
    multiply's working set.
    """
    a = _unwrap(matrix_a)
    b = _unwrap(matrix_b)
    c = _unwrap(matrix_c)
    for m in (a, b, c):
        if not m.valid:
            m.finalize()
    # op() shapes
    m_full = c.nfullrows
    n_full = c.nfullcols
    k_full = a.nfullcols if transa.upper() == "N" else a.nfullrows
    nblk_k = a.nblkcols if transa.upper() == "N" else a.nblkrows

    # batched-MM state machine (ref dbcsr_tas_mm.F:1595-1692): defer
    # filtering to the batch finalize, reuse the split decision
    explicit_nsplit = nsplit is not None
    batch = getattr(c, "_tas_batched_state", None)
    if batch is not None:
        if filter_eps is not None:
            batch["filter_eps"] = filter_eps
        filter_eps = None
        if not explicit_nsplit:
            nsplit = batch.get("nsplit")

    with timed("tas_multiply"):
        dims = {"m": m_full, "n": n_full, "k": k_full}
        long_dim = max(dims, key=dims.get)
        _trace.annotate(name=c.name, m=m_full, n=n_full, k=k_full,
                        long_dim=long_dim)

        # (the numpy/config/split/mesh imports this region used to make
        # inline are module-scope now: ~µs each, but they sat inside the
        # timed("tas_multiply") hot region of EVERY split-loop multiply)
        def _fresh_opt() -> int:
            if mesh is None:
                # one chip: no process groups to balance, and the engine
                # bounds a product's working set itself (its stacks run
                # in chunks, `acc.smm.group_chunk_groups`), so a split
                # the caller did not ask for only multiplies plans,
                # programs and host passes (on a v5e a chi batch of
                # `examples/rpa_chi.py` at 32 waters split 64, 64 and 12
                # ways compiled a hundred fused programs a batch and did
                # not finish its first in 15 minutes).  A caller whose
                # result must be built in pieces asks for groups:
                # ``nsplit``, `TASMatrix.nsplit`, `batched_mm_init`
                return 1
            long_blks = max(c.nblkrows, c.nblkcols, nblk_k)
            if mesh.shape["pr"] == mesh.shape["pc"]:
                # (rectangular grids: grouping cannot engage — the
                # grouped path needs a square Cannon grid — so nsplit
                # does not move traffic; keep the geometric estimate)
                # mesh path: pick the split that minimizes MEASURED-model
                # collective bytes (calibrated against the virtual-mesh
                # traffic counters; the role of the reference's
                # split-factor/pgrid acceptance machinery,
                # `dbcsr_tas_mm.F:1427-1464`, `dbcsr_tas_split.F:207-281`)
                g = choose_nsplit_traffic(
                    long_dim, m_full, n_full, k_full, a.nnz, b.nnz, c.nnz,
                    _np.dtype(c.dtype).itemsize,
                    mesh.shape["kl"], mesh.shape["pr"],
                    ngroups_max, long_blks,
                )
                if g is not None:
                    return g
            sf = estimate_split_factor(
                m_full, n_full, k_full, a.nnz, b.nnz, c.nnz
            ) * get_config().tas_split_factor  # ref TAS_SPLIT_FACTOR knob
            return choose_nsplit(sf, ngroups_max, long_blks)

        if nsplit is None:
            for t in (matrix_a, matrix_b, matrix_c):
                if isinstance(t, TASMatrix) and t.nsplit:
                    nsplit = t.nsplit
                    break
        if nsplit is None:
            nsplit = _fresh_opt()
        if batch is not None:
            if explicit_nsplit or batch.get("nsplit") is None:
                batch["nsplit"] = nsplit  # (re)set the batch's split
                if explicit_nsplit:
                    batch["nsplit_explicit"] = True
            elif batch.get("nsplit_explicit"):
                pass  # user-pinned split: no between-batch re-splitting
            else:
                # split re-optimization between batches (the
                # single-controller analog of the batched pgrid
                # re-optimization, `dbcsr_tensor.F:1964-2186`): keep the
                # cached split while it stays within the reference's
                # acceptance window of the current-sparsity optimum
                # (default_nsplit_accept_ratio = 3,
                # `dbcsr_tas_split.F:57,229-230`), else re-split.
                # nnz reads are O(nblks) host work, so the optimum is
                # only recomputed when the O(1) block-count triple
                # drifted beyond the acceptance ratio since last checked
                ratio = _NSPLIT_ACCEPT_RATIO
                cnt_now = (a.nblks, b.nblks, c.nblks)
                cnt_ref = batch.get("nblks_checked")
                drifted = cnt_ref is None or any(
                    now > ratio * max(ref, 1) or now * ratio < ref
                    for now, ref in zip(cnt_now, cnt_ref)
                )
                if drifted:
                    batch["nblks_checked"] = cnt_now
                    opt = _fresh_opt()
                    if not (opt / ratio <= nsplit <= opt * ratio):
                        batch["nsplit"] = nsplit = opt
                        batch["resplit_count"] = batch.get("resplit_count", 0) + 1

        _trace.annotate(nsplit=int(nsplit or 1))
        if mesh is not None:
            if batch is not None:
                # batched pgrid re-optimization (ref the reference
                # re-choosing process-grid dims between tensor batches,
                # `dbcsr_tensor.F:1964-2186`): re-factor the same
                # devices to fit the batch's nsplit/long-dim, cached in
                # the batch state and re-evaluated only when the
                # (acceptance-ratio-gated) nsplit decision changes
                key = (id(mesh), max(nsplit, 1), long_dim)
                if batch.get("pgrid_key") != key:
                    batch["pgrid_key"] = key
                    batch["pgrid_src"] = mesh  # keepalive for id(mesh)
                    batch["pgrid"] = optimize_grid(
                        mesh, max(nsplit, 1), long_dim
                    )
                    if batch["pgrid"] is not mesh:
                        batch["repgrid_count"] = (
                            batch.get("repgrid_count", 0) + 1
                        )
                mesh = batch["pgrid"]
            return _tas_multiply_mesh(
                transa, transb, alpha, a, b, beta, c, filter_eps,
                max(nsplit, 1), long_dim, nblk_k, mesh,
            )
        groups = _metrics.counter(
            "dbcsr_tpu_tas_groups_total",
            "multiplies the one-chip TAS split ran, one a group, by the "
            "long dimension it split")
        if nsplit <= 1:
            groups.inc(long_dim=long_dim)
            return multiply(transa, transb, alpha, a, b, beta, c,
                            filter_eps=filter_eps)

        # beta applies once to all of C, then groups accumulate
        if beta != 1.0:
            scale(c, beta)
        flops = 0
        if long_dim == "m":
            nblk, limit_lo, limit_hi = c.nblkrows, "first_row", "last_row"
        elif long_dim == "n":
            nblk, limit_lo, limit_hi = c.nblkcols, "first_col", "last_col"
        else:
            nblk, limit_lo, limit_hi = nblk_k, "first_k", "last_k"
        per = ceil_div(nblk, nsplit)
        # the split loop is a chained workload (core.mempool): each
        # group's multiply runs in a chain scope so engine temporaries
        # (op() transposes/desymmetrized copies) retire into the pool
        # the moment the split is done, feeding the next split's bin
        # checkouts — split panels stop costing fresh device
        # allocations, and with the device index mirrors the per-split
        # H2D collapses after the first same-pattern pass.  C itself is
        # the caller's (created outside the chain): never adopted,
        # never freed here.
        with _mempool.chain() as ch:
            for g0 in range(0, nblk, per):
                g1 = min(g0 + per, nblk)
                groups.inc(long_dim=long_dim)
                with ch.scope():
                    flops += multiply(
                        transa, transb, alpha, a, b, 1.0, c,
                        filter_eps=filter_eps,
                        **{limit_lo: g0, limit_hi: g1 - 1},
                    )
        return flops


def _tas_multiply_mesh(transa, transb, alpha, a, b, beta, c, filter_eps,
                       nsplit, long_dim, nblk_k, mesh) -> int:
    """Distributed TAS multiply with real group parallelism.

    m- or n-long products run `tas_grouped_multiply`: the 'kl' mesh
    axis carries nsplit concurrent per-group Cannons with the short
    matrix replicated into each group (ref `dbcsr_tas_mm.F:79-806`,
    `dbcsr_tas_split.F:304`); a column-long C is handled as C^T with
    row groups.  k-long products use the engine's 'kl' k-image layers +
    psum (`sparse_multiply_distributed`), which is the same grid split
    applied to the contraction dimension (`dbcsr_mm_3d.F:1037`)."""
    from dbcsr_tpu.core.kinds import is_complex
    from dbcsr_tpu.core.matrix import NO_SYMMETRY
    from dbcsr_tpu.ops.transformations import new_transposed
    from dbcsr_tpu.parallel.sparse_dist import (
        sparse_multiply_distributed,
        tas_grouped_multiply,
    )

    def _op(m, trans):
        t = trans.upper()
        if t == "N":
            return m
        return new_transposed(m, conjugate=(t == "C" and is_complex(m.dtype)))

    # chain scope for the mesh leg's temporaries: op() transposes, the
    # C^T intermediates and the result shell all retire into the pool
    # when the product is adopted into the caller's C (which was
    # created OUTSIDE this chain and is never owned by it)
    with _mempool.chain():
        a_op, b_op = _op(a, transa), _op(b, transb)
        # the grouped path runs per-group square Cannons: a rectangular
        # ('pr','pc') grid cannot take it (falls back to the all-gather
        # engine below, which supports any grid)
        grouped = (
            nsplit > 1 and mesh.shape["kl"] > 1
            and mesh.shape["pr"] == mesh.shape["pc"]
            and long_dim in ("m", "n")
        )
        if grouped and long_dim == "m":
            acc = tas_grouped_multiply(
                alpha, a_op, b_op, beta, c, mesh, name=c.name,
                filter_eps=filter_eps, nsplit=nsplit,
            )
        elif grouped:
            # column-long C: C^T = op(B)^T op(A)^T is row-long, group
            # its rows
            acc_t = tas_grouped_multiply(
                alpha, new_transposed(b_op), new_transposed(a_op), beta,
                new_transposed(c), mesh, name=c.name + "^T",
                filter_eps=filter_eps, nsplit=nsplit,
            )
            flops_t = getattr(acc_t, "_last_flops", 0)
            acc = new_transposed(acc_t)
            acc._last_flops = flops_t
        else:
            acc = sparse_multiply_distributed(
                alpha, a_op, b_op, beta, c, mesh, name=c.name,
                filter_eps=filter_eps,
            )
        flops = getattr(acc, "_last_flops", 0)
        # adopt the result structure into the caller's C object,
        # preserving its Distribution and dtype; the product is plain
        # (the sparse path desymmetrizes).  C now aliases acc's bins,
        # so acc — a chain-adopted temporary about to be freed — must
        # never donate them: the copy() shared-mark convention applied
        # to this structure adoption.
        for field in ("keys", "row_ptr", "ent_bin", "ent_slot", "bins",
                      "_shape_to_bin", "valid"):
            setattr(c, field, getattr(acc, field))
        acc._bins_shared = True
        c._bins_shared = True
        c.matrix_type = NO_SYMMETRY
        c._work.clear()
    return flops
