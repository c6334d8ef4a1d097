"""Newton–Schulz sign-function iteration.

X_{k+1} = X_k (3 I - X_k^2) / 2  — converges to sign(A) for
||I - A^2|| < 1 after Gershgorin scaling.  The second canonical
linear-scaling-DFT workload (density matrix via the sign method, the
submatrix/sign family CP2K runs on DBCSR); each step is two filtered
block-sparse multiplies plus a diagonal shift, exercising the engine
exactly the way `dbcsr_tests`' chained multiplies do.
"""

from __future__ import annotations

import math
from typing import Optional

from dbcsr_tpu.acc import precision as _precision
from dbcsr_tpu.core import mempool
from dbcsr_tpu.core.matrix import BlockSparseMatrix
from dbcsr_tpu.core.timings import timed
from dbcsr_tpu.mm import incremental as _incremental
from dbcsr_tpu.mm.multiply import multiply
from dbcsr_tpu.obs import events as _events
from dbcsr_tpu.models import integrity as _integrity
from dbcsr_tpu.ops.operations import (
    add_on_diag,
    added,
    copy,
    frobenius_norm,
    gershgorin_norm,
    scale,
)


def _product(alpha, a: BlockSparseMatrix, b: BlockSparseMatrix, name: str,
             filter_eps: Optional[float], mesh):
    """alpha * A @ B into a fresh matrix, filtered at ``filter_eps``:
    on the one-chip engine, or with a ``mesh`` on the sparse mesh engine
    (`parallel/sparse_dist.py`).  Returns (C, true flops)."""
    if mesh is not None:
        from dbcsr_tpu.parallel.sparse_dist import sparse_multiply_distributed

        c = sparse_multiply_distributed(alpha, a, b, 0.0, None, mesh,
                                        name=name, filter_eps=filter_eps)
        return c, c._last_flops
    c = BlockSparseMatrix(name, a.row_blk_sizes, b.col_blk_sizes, a.dtype,
                          a.dist)
    return c, multiply("N", "N", alpha, a, b, 0.0, c, filter_eps=filter_eps)


def sign_step(
    x: BlockSparseMatrix, filter_eps: Optional[float] = None, mesh=None
) -> BlockSparseMatrix:
    """One Newton–Schulz step: X' = X (3I - X²) / 2; with a ``mesh``
    (`parallel.make_grid`) both products run on the process grid.

    Chain-scoped (core.mempool): X² is retired to the memory pool once
    the step's second multiply consumed it, so an iteration loop keeps
    reusing the same device buffers."""
    with mempool.chain() as ch:
        x2, flops = _product(1.0, x, x, "X2", filter_eps, mesh)
        # T = 3I - X²  (in place on X²'s storage)
        scale(x2, -1.0)
        add_on_diag(x2, 3.0)
        out, flops2 = _product(0.5, x, x2, "X'", filter_eps, mesh)
        flops += flops2
        ch.retire(x2)
        ch.detach(out)
    out._last_flops = int(flops)  # true flops of the step's two products
    return out


def sign_iteration(
    a: BlockSparseMatrix,
    steps: int = 20,
    filter_eps: Optional[float] = None,
    tol: float = 1e-10,
    mesh=None,
):
    """sign(A) by Newton–Schulz; returns (X, convergence_history).

    A is Gershgorin-scaled so the iteration contracts; convergence is
    measured as ||X_k - X_{k-1}||_F and iteration stops below ``tol``.
    With a ``mesh`` every product of the chain runs on the process
    grid (`parallel.sparse_multiply_distributed`); the rest of the
    chain is the same code on what the grid's collect left.
    """
    from dbcsr_tpu.core.matrix import NO_SYMMETRY
    from dbcsr_tpu.ops.transformations import desymmetrize

    if a.matrix_type != NO_SYMMETRY:
        a = desymmetrize(a)  # iterates mix with plain multiply results
    g = gershgorin_norm(a)
    x0 = x = scale(copy(a, name="X"), 1.0 / g if g > 0 else 1.0)
    # integrity guard (models/integrity.py): checkpoint the accepted
    # iterate before each step, verify the fresh iterate's norm growth
    # bound (Newton–Schulz is a contraction for the Gershgorin-scaled
    # input, so a finite SDC flip explodes ||X'||_F), and roll back +
    # recompute on the safe engine on violation
    guard = _integrity.guard_enabled()
    history = []
    flops = 0  # true flops of every product of the chain
    # adaptive-precision chain scope: demoted Newton–Schulz steps
    # promote to native once ||X_k - X_{k-1}||_F tightens past the
    # demoted error floor (see models/purify.py)
    with mempool.chain() as ch, _precision.chain_scope(
            "sign", dtype=a.dtype,
            scale=float(max(a.nfullrows, 1)) ** 0.5,
    ) as psc:
        x_norm = frobenius_norm(x) if guard else None
        for step_i in range(steps):
            with timed("sign_step"):
                reuse0 = _incremental.stats_snapshot()
                snap = ch.snapshot(x) if guard else None
                x_new = sign_step(x, filter_eps=filter_eps, mesh=mesh)
                flops += x_new._last_flops
                # out-of-place diff: no copy, so neither iterate is ever
                # marked shared and both keep donating to the pool
                diff = added(x_new, x, 1.0, -1.0, name="diff")
                metric = frobenius_norm(diff)
                if guard:
                    nn = frobenius_norm(x_new)
                    # ||X(3I - X²)/2||_F <= (3*sqrt(N)*||X|| + ||X||³)/2
                    # (Frobenius submultiplicativity — valid on any input)
                    limit = 0.5 * (3.0 * x.nfullrows ** 0.5 * x_norm
                                   + x_norm ** 3)
                    if not (_integrity.norm_ok(nn, limit)
                            and math.isfinite(metric)):
                        _integrity.record_rollback(
                            "sign", step_i, "invariant",
                            detail=f"norm {nn:.3e} ref {x_norm:.3e}")
                        ch.retire(diff)
                        ch.retire(x_new)
                        x = ch.restore(snap)
                        seen = {}

                        def _build(x=x):
                            xn = sign_step(x, filter_eps=filter_eps, mesh=mesh)
                            return xn, added(xn, x, 1.0, -1.0, name="diff")

                        def _validate(cand, limit=limit):
                            xn, df = cand
                            seen["metric"] = frobenius_norm(df)
                            seen["nn"] = frobenius_norm(xn)
                            return (_integrity.norm_ok(seen["nn"], limit)
                                    and math.isfinite(seen["metric"]))

                        x_new, diff = _integrity.recompute_step(
                            ch, _build, _validate, "sign", step_i,
                            "invariant")
                        metric, nn = seen["metric"], seen["nn"]
                        flops += x_new._last_flops  # the recomputed step
                    x_norm = nn
                history.append(metric)
                psc.observe(metric)
                # per-iteration value-reuse fraction (delta plane)
                _events.publish("model_reuse", dict(
                    model="sign", step=step_i,
                    **_incremental.reuse_delta(reuse0)))
                ch.retire(diff)
                if x is not x0:
                    ch.retire(x)
                x = x_new
            if history[-1] < tol:
                break
        ch.detach(x)
    # what the chain did, where the mesh engine and tas/mm.py say it
    x._last_flops = int(flops)
    x._last_steps = len(history)
    return x, history
