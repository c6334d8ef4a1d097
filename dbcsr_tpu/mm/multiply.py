"""The multiply engine: C := alpha * op(A) * op(B) + beta * C.

Analog of `dbcsr_multiply_generic` (`src/mm/dbcsr_mm.F:336-1030`),
re-designed TPU-first:

* The reference discovers C's pattern inside per-thread recursive
  multiplies with hash-based block lookup (`dbcsr_mm_csr.F:178`);
  here the full symbolic product is computed up front with vectorized
  NumPy (the reference also keeps index work on CPU — SURVEY §7), so
  device work is purely static-shaped batched compute.
* Per-thread work matrices + stack flushing (`dbcsr_mm_multrec.F`,
  `dbcsr_mm_sched.F`) collapse into: one parameter stack per
  (m, n, k) shape-bin triple, sorted by C block then A entry, processed
  by the acc layer's prepared stack plans (`dbcsr_tpu.acc.smm.
  prepare_stack`/`execute_stack`, cached across same-pattern repeats)
  in mm_stack_size chunks.
* Accumulation order is fixed by the sort, giving bit-reproducible
  results per run configuration (north-star checksum requirement).

Filtering semantics follow the reference exactly (`dbcsr_mm.F:360-369`):
on-the-fly skip when ||A_ik||²·||B_kj||² < (eps/max(1, row_count_A(i)))²
with single-precision squared norms (`dbcsr_mm_cannon.F:1098-1105`,
`dbcsr_mm_csr.F:276`, `calc_norms` at `dbcsr_mm_common.F:728`), and a
final pass keeping blocks with ||C||² >= eps²
(`dbcsr_mm_multrec.F:694-748`), skipped when retain_sparsity.
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from dbcsr_tpu.core import mempool, stats
from dbcsr_tpu.acc import abft as _abft
from dbcsr_tpu.core.kinds import is_complex
from dbcsr_tpu.core.matrix import (
    NO_SYMMETRY,
    BlockSparseMatrix,
    _Bin,
    _bin_entries,
)
from dbcsr_tpu.core.timings import timed
from dbcsr_tpu.obs import costmodel as _costmodel
from dbcsr_tpu.obs import events as _events
from dbcsr_tpu.obs import flight as _flight
from dbcsr_tpu.obs import metrics as _metrics
from dbcsr_tpu.obs import tracer as _trace
from dbcsr_tpu.ops.operations import compress
from dbcsr_tpu.ops.transformations import desymmetrize, new_transposed
from dbcsr_tpu.resilience import faults as _faults
from dbcsr_tpu.utils.rounding import bucket_size


@functools.partial(jax.jit, static_argnames=())
def _scatter_scaled(dst, src, src_slots, dst_slots, beta):
    return dst.at[dst_slots].set(beta * jnp.take(src, src_slots, axis=0), mode="drop")


@jax.jit
def _scatter_scaled_window(dst, src, src_slots, dst_slots, beta, rl, rh, cl, ch):
    """Scatter blocks applying beta only to the in-window element range
    (rl..rh, cl..ch per block, inclusive) — straddling blocks of a
    windowed-beta multiply (ref: the windowed dgemm touches only the
    limited submatrix, `dbcsr_test_multiply.F:631-633`)."""
    from dbcsr_tpu.ops.operations import window_mask

    blk = jnp.take(src, src_slots, axis=0)
    mask = window_mask(blk.shape[1], blk.shape[2], rl, rh, cl, ch)
    factor = jnp.where(mask, beta, jnp.ones((), dst.dtype))
    return dst.at[dst_slots].set(blk * factor, mode="drop")


def _real_scalar(x, dtype):
    """Coerce alpha/beta for a real-dtype product, raising a clear
    TypeError (not a deep cast error) on a nonzero imaginary part."""
    arr = np.asarray(x)
    if np.iscomplexobj(arr):
        if complex(arr).imag != 0.0:
            raise TypeError(
                f"complex alpha/beta with a real matrix C "
                f"(dtype {np.dtype(dtype).name}); use a complex matrix "
                f"or real scalars"
            )
        return complex(arr).real
    return x


def _effective(matrix: BlockSparseMatrix, trans: str) -> BlockSparseMatrix:
    """Resolve op(X): desymmetrize + transpose/conjugate as needed
    (ref transpose wrappers at `dbcsr_mm.F:521-582`)."""
    trans = trans.upper()
    m = desymmetrize(matrix) if matrix.matrix_type != NO_SYMMETRY else matrix
    if trans == "N":
        return m
    if trans == "T":
        return new_transposed(m)
    if trans == "C":
        return new_transposed(m, conjugate=is_complex(m.dtype))
    raise ValueError(f"bad trans flag {trans!r}")


def multiply(
    transa: str,
    transb: str,
    alpha,
    matrix_a: BlockSparseMatrix,
    matrix_b: BlockSparseMatrix,
    beta,
    matrix_c: BlockSparseMatrix,
    retain_sparsity: bool = False,
    filter_eps: Optional[float] = None,
    first_row: Optional[int] = None,
    last_row: Optional[int] = None,
    first_col: Optional[int] = None,
    last_col: Optional[int] = None,
    first_k: Optional[int] = None,
    last_k: Optional[int] = None,
    element_limits=None,
) -> int:
    """Multiply two block-sparse matrices; returns the true flop count.

    The optional first/last row/col/k limits restrict the product to a
    block-index submatrix (0-based, inclusive).  ``element_limits``
    instead gives the reference `dbcsr_multiply` limit arguments at
    ELEMENT granularity — a 6-tuple (first_row, last_row, first_col,
    last_col, first_k, last_k) of 0-based inclusive element indices
    (None entries = open): limits that don't align with block
    boundaries are honored exactly, by cropping op(A)/op(B) at element
    level (ref `dbcsr_crop_matrix` inside `make_m2s`,
    `dbcsr_mm_cannon.F:194-220`).

    With limits, beta scales C only INSIDE the limited window — C
    elements outside keep their old values, like the reference's
    windowed dgemm (`dbcsr_test_multiply.F:631-633`).
    """
    with timed("multiply"):
        for m in (matrix_a, matrix_b, matrix_c):
            if not m.valid:
                m.finalize()
        # C may alias A or B (in-place squaring etc.): snapshot the input's
        # index before C is restructured; device arrays are immutable and
        # donation only touches C's freshly-built buffers, so a shallow
        # copy suffices.
        if matrix_a is matrix_c:
            matrix_a = matrix_a.copy()
        if matrix_b is matrix_c:
            matrix_b = matrix_b.copy()
        a = _effective(matrix_a, transa)
        b = _effective(matrix_b, transb)
        c = matrix_c
        if not np.issubdtype(np.dtype(c.dtype), np.complexfloating):
            # the reference's typed-alpha contract, surfaced clearly: a
            # complex scalar with nonzero imaginary part cannot scale a
            # real product; zero-imag complex scalars coerce
            alpha, beta = (_real_scalar(x, c.dtype) for x in (alpha, beta))
        if not np.array_equal(a.col_blk_sizes, b.row_blk_sizes):
            raise ValueError("inner blockings of op(A), op(B) differ")
        if not np.array_equal(c.row_blk_sizes, a.row_blk_sizes):
            raise ValueError("C row blocking != op(A) row blocking")
        if not np.array_equal(c.col_blk_sizes, b.col_blk_sizes):
            raise ValueError("C col blocking != op(B) col blocking")

        beta_window = None
        if element_limits is not None:
            if any(x is not None for x in (first_row, last_row, first_col,
                                           last_col, first_k, last_k)):
                raise ValueError("give block-index OR element limits, not both")
            (a, b, (first_row, last_row, first_col, last_col, first_k, last_k),
             beta_window) = _apply_element_limits(a, b, c, element_limits)
        elif any(x is not None for x in (first_row, last_row, first_col, last_col)):
            # windowed beta semantics for block limits too
            roff, coff = c.row_blk_offsets, c.col_blk_offsets
            beta_window = (
                int(roff[first_row]) if first_row is not None else 0,
                int(roff[last_row + 1]) - 1 if last_row is not None else c.nfullrows - 1,
                int(coff[first_col]) if first_col is not None else 0,
                int(coff[last_col + 1]) - 1 if last_col is not None else c.nfullcols - 1,
            )

        no_limits = all(
            x is None for x in (first_row, last_row, first_col, last_col, first_k, last_k)
        )
        # flight record + span attributes + correlation id for this
        # product (obs layer): shapes/occupancy now, driver decisions
        # and per-phase ms as the engine makes them, committed on
        # return OR error.  The product_id ties every bus event this
        # multiply causes (breaker trips, faults, failovers, recompiles)
        # to this one record across all three stores.
        product_id = _events.begin_product(
            name=c.name, mnk=[c.nfullrows, c.nfullcols, a.nfullcols])
        _flight.begin(
            op="multiply", name=c.name,
            mnk=(c.nfullrows, c.nfullcols, a.nfullcols),
            occ_a=round(a.occupation(), 4), occ_b=round(b.occupation(), 4),
            occ_c=round(c.occupation(), 4),
            filter_eps=filter_eps, retain_sparsity=retain_sparsity,
            product_id=product_id,
        )
        _trace.annotate(
            name=c.name, m=c.nfullrows, n=c.nfullcols, k=a.nfullcols,
            product_id=product_id,
        )
        try:
            flops = _multiply_body(
                a, b, c, alpha, beta, retain_sparsity, filter_eps,
                first_row, last_row, first_col, last_col, first_k, last_k,
                beta_window, no_limits,
            )
        except Exception as exc:
            err = f"{type(exc).__name__}: {exc}"
            rec = _flight.commit(error=err)
            _events.end_product(rec=rec, error=err)
            raise
        _flight.note("flops", flops)
        _flight.note("algorithm", getattr(c, "_mm_algorithm", "?"))
        _trace.annotate(algorithm=getattr(c, "_mm_algorithm", "?"))
        rec = _flight.commit()
        _events.end_product(rec=rec)
        return flops


def _multiply_body(a, b, c, alpha, beta, retain_sparsity, filter_eps,
                   first_row, last_row, first_col, last_col, first_k,
                   last_k, beta_window, no_limits) -> int:
    """The format-planned engine body of `multiply` (split out so the
    flight recorder brackets every exit path exactly once).  The
    storage format — stack or dense — is resolved by
    `mm.format_planner.choose`, the one decider both engines ask."""
    from dbcsr_tpu.mm import format_planner as _fmt

    plan = _fmt.choose(a, b, c, filter_eps=filter_eps,
                       retain_sparsity=retain_sparsity,
                       no_limits=no_limits, dense=True)
    _fmt.note_decision(plan)
    if plan.fmt == "dense":
        with timed("multiply_dense"):
            c._mm_algorithm = plan.fmt
            # canvas-path failover: the dense MXU route and the stack
            # path compute the identical product, so a canvas failure
            # (injected or real — compile gap, OOM, corrupted canvas)
            # degrades to the stack engine instead of killing the
            # multiply.  Only safe while C is still untouched: the
            # canvas path restructures C last, and the held-identity
            # check proves no restructuring happened.
            held = [b_.data for b_ in c.bins]
            try:
                flops = _dense_multiply(a, b, c, alpha, beta)
                # a canvas-path restructure makes any delta-cache entry
                # for these operands unreachable garbage: drop eagerly
                from dbcsr_tpu.mm import incremental as _inc

                _inc.note_format_executed(a, b)
                return flops
            except Exception as exc:
                if [id(b_.data) for b_ in c.bins] != [id(d) for d in held]:
                    raise  # C already restructured: unrecoverable here
                _note_dense_fallback(exc)
    c._mm_algorithm = "stack"

    with timed("multiply_index"):
        cand = _candidates(
            a, b, c, filter_eps,
            first_row, last_row, first_col, last_col, first_k, last_k,
        )
        i, j, a_ent, b_ent = cand
        # new C pattern
        old_keys = c.keys
        cand_keys = i * c.nblkcols + j
        if retain_sparsity:
            ok = mask_in_sorted(cand_keys, old_keys)
            i, j, a_ent, b_ent = i[ok], j[ok], a_ent[ok], b_ent[ok]
            cand_keys = cand_keys[ok]
            new_keys = old_keys
        else:
            new_keys = np.union1d(old_keys, np.unique(cand_keys))

    # plan-cache key: patterns + product options fully determine the
    # stack plan for UNFILTERED products.  Filtered products depend on
    # VALUES (the norm filter prunes candidates), so their key
    # additionally digests the surviving candidate list — an iterative
    # chain whose filter keeps reaching the same survivors (the
    # structure-stable steady state) then hits the cache too, paying a
    # host hash instead of the full group-sort + index re-upload.
    # Device-residency gated (mempool.enabled): the unpooled control
    # is the historical rebuild-every-multiply engine.
    plan_key = None
    if filter_eps is None or mempool.enabled():
        from dbcsr_tpu.acc import params as params_mod
        from dbcsr_tpu.acc import precision as precision_mod
        from dbcsr_tpu.core.config import get_config as _cfg

        cfg_ = _cfg()
        plan_key = (
            a.pattern_fingerprint(), b.pattern_fingerprint(),
            c.pattern_fingerprint(),
            str(np.dtype(a.dtype)), str(np.dtype(b.dtype)),
            str(np.dtype(c.dtype)),
            c.matrix_type, retain_sparsity,
            (first_row, last_row, first_col, last_col, first_k, last_k),
            (cfg_.mm_driver, cfg_.use_pallas, cfg_.flat_gather,
             cfg_.mm_stack_size, cfg_.max_kernel_dim,
             cfg_.validate_kernels, cfg_.mm_format),
            # params-table generation: a tuner promotion/demotion
            # (dbcsr_tpu.tune, or any save_entry/invalidate) bumps it,
            # so a cached plan can never serve superseded parameters
            params_mod.generation(),
            # executed-precision state: an adaptive promotion or a
            # chain-scope transition must never be served a cached
            # demoted plan (acc.precision bumps its generation on both)
            precision_mod.plan_token(),
        )
        if filter_eps is not None:
            from dbcsr_tpu.core import digests

            plan_key += ("filtered", float(filter_eps),
                         digests.index_digest(cand_keys, a_ent, b_ent))

    # delta-aware incremental path (mm.incremental): a repeated
    # beta==0 product whose operands carry a known dirty-block delta
    # since its last full execution recomputes only the affected C
    # blocks and splices the rest from the cached device-resident
    # result — bitwise-identical by construction, ABFT-certified, and
    # always falling back to the full path below on any doubt
    inc_eligible = (
        plan_key is not None and filter_eps is None and beta == 0
        and beta_window is None and not retain_sparsity and no_limits
        and mempool.enabled() and c.matrix_type == NO_SYMMETRY
    )
    if inc_eligible:
        from dbcsr_tpu.mm import incremental as _inc

        inc_flops = _inc.maybe_reuse(plan_key, a, b, c, alpha, new_keys,
                                     cand_keys, a_ent, b_ent)
        if inc_flops is not None:
            c._note_mutation(c.keys)  # spliced values installed
            stats.record_multiply(2 * c.nfullrows * c.nfullcols
                                  * a.nfullcols)
            stats.sample_memory()
            return int(inc_flops)

    with timed("multiply_c_assemble"):
        _rebuild_c(c, new_keys, beta, beta_window=beta_window)

    with timed("multiply_stacks"):
        flops = _run_stacks(c, a, b, cand_keys, a_ent, b_ent, alpha,
                            plan_key=plan_key,
                            c_zero=(beta == 0 and beta_window is None))
    # the stack launches rebound bin data after _rebuild_c's structure
    # note: stamp the completed values so epoch consumers (value
    # digests, delta caches) never see a pre-completion epoch as current
    c._note_mutation(c.keys)
    if inc_eligible:
        from dbcsr_tpu.mm import incremental as _inc

        _inc.note_executed(plan_key, a, b, c, alpha)

    if filter_eps is not None and not retain_sparsity:
        with timed("multiply_filter"):
            nblks_pre = c.nblks
            # the norms need C: on an async device this call is the
            # wait for the stack launches, and gets a span of its own
            # so that multiply_filter's self time is the host's work
            with timed("multiply_filter_norms"):
                norms = c.block_norms()
            compress(c, norms.astype(np.float64) ** 2 >= float(filter_eps) ** 2)
            note_filter_fates(nblks_pre, c.nblks)

    mflops = 2 * c.nfullrows * c.nfullcols * a.nfullcols
    stats.record_multiply(mflops)
    stats.sample_memory()
    return int(flops)


def note_filter_fates(nblks_pre: int, nblks: int) -> None:
    """Say what the norm filter made of a product's C blocks: on the
    open flight record and in `dbcsr_tpu_filter_blocks_total{fate}`
    (shared by the single-chip and mesh engines)."""
    _flight.note("filtered_blocks", nblks_pre - nblks)
    _flight.note("kept_blocks", nblks)
    fates = _metrics.counter(
        "dbcsr_tpu_filter_blocks_total",
        "C blocks of filtered products by what the norm filter "
        "made of them")
    fates.inc(nblks, fate="kept")
    fates.inc(nblks_pre - nblks, fate="dropped")


def mask_in_sorted(cand_keys: np.ndarray, sorted_keys: np.ndarray) -> np.ndarray:
    """Membership of each cand_key in sorted_keys (retain_sparsity's
    pattern lock, shared by the single-chip and mesh engines)."""
    if len(sorted_keys) == 0:
        return np.zeros(len(cand_keys), bool)
    pos = np.searchsorted(sorted_keys, cand_keys)
    return (pos < len(sorted_keys)) & (
        sorted_keys[np.minimum(pos, len(sorted_keys) - 1)] == cand_keys
    )


def _true_product_flops(a, b) -> int:
    """Exact flop count of the block-sparse product without enumerating
    candidate triples: sum_k 2 * W_m(k) * W_n(k) * k_k where W_m(k) is
    the total row extent of A's stored blocks in block-col k and W_n(k)
    the total col extent of B's stored blocks in block-row k.  O(nblks)
    — the 'true flops' of `dbcsr_mm.F:664-667`, computable up front."""
    if a.nblks == 0 or b.nblks == 0:
        return 0
    ar, ac = a.entry_coords()
    br, bc = b.entry_coords()
    wa = np.bincount(ac, weights=a.row_blk_sizes[ar].astype(np.float64),
                     minlength=a.nblkcols)
    wb = np.bincount(br, weights=b.col_blk_sizes[bc].astype(np.float64),
                     minlength=b.nblkrows)
    kk = a.col_blk_sizes.astype(np.float64)
    return int(round(2.0 * float(np.dot(wa * kk, wb))))


# the most elements one canvas of the dense executors may hold
# (3 canvases must fit HBM comfortably; 10k^2 f64 = 0.8 GB each)
_DENSE_MAX_CANVAS = 2 * 10**8


def _dense_chunking(nbr, nbc, nbk, bm, bn, bk):
    """(block-rows per m-strip, k-block-cols per k-strip, block-cols
    per n-strip) so every strip canvas (A: m-strip x k-strip, B:
    k-strip x n-strip, C: m-strip x n-strip) fits `_DENSE_MAX_CANVAS`
    elements, or None when even single-block strips cannot fit.  Wide-N
    products (one full-width C block-row over the cap) chunk the n axis
    too instead of declining dense — the cost model used to silently
    keep such products on the stack path."""
    cap = _DENSE_MAX_CANVAS
    ncb = nbc
    if bm * nbc * bn > cap:
        ncb = min(nbc, max(1, cap // (bm * bn)))
    n_el = ncb * bn
    mrb = min(nbr, max(1, cap // (bm * n_el)))
    kcb = min(nbk, max(1, cap // (bk * max(mrb * bm, n_el))))
    if (mrb * bm) * (kcb * bk) > cap or (kcb * bk) * n_el > cap \
            or (mrb * bm) * n_el > cap:
        return None
    return mrb, kcb, ncb


def _over_canvas_cap(a, b) -> bool:
    mm, nn, kk = a.nfullrows, b.nfullcols, a.nfullcols
    return max(mm * kk, kk * nn, mm * nn) > _DENSE_MAX_CANVAS


def _uniformly_blocked(m) -> bool:
    return (len(np.unique(m.row_blk_sizes)) == 1
            and len(np.unique(m.col_blk_sizes)) == 1)


def _dense_strips(a, b, c):
    """`_dense_chunking` of a product the chunked executor can take
    (uniform blockings only), else None."""
    if not all(_uniformly_blocked(m) for m in (a, b, c)):
        return None
    return _dense_chunking(
        a.nblkrows, c.nblkcols, a.nblkcols,
        int(a.row_blk_sizes[0]), int(b.col_blk_sizes[0]),
        int(a.col_blk_sizes[0]),
    )


def dense_canvas_feasible(a, b, c, *, chunked: bool) -> bool:
    """Whether the canvas executors can hold this product: A, B and C
    canvases each under `_DENSE_MAX_CANVAS`, or — where the caller has
    the chunked executor — strips that are (the reference's dense mode
    is not size-capped, `dbcsr_mm.F:593-617`).  The planner asks; the
    policy is its own."""
    return not _over_canvas_cap(a, b) or (
        chunked and _dense_strips(a, b, c) is not None)


def _note_dense_fallback(exc: BaseException) -> None:
    """Record a dense canvas-path → stack failover, the mm-layer
    sibling of `acc.smm`'s stack-driver chain — emitted through the
    same smm helpers so the counter/trace/flight schema stays
    single-sourced."""
    from dbcsr_tpu.acc import smm as _smm

    kind = _smm._classify_failure(exc)
    _smm._record_driver_failure("dense", kind, exc, ())
    _smm._record_fallback("dense", "stack", ())
    if kind == "sdc":
        # C was untouched (held-identity check) and the stack engine
        # recomputes the product: the detected canvas SDC is healed
        _abft.record_recovery("dense")
    _flight.note("dense_fallback", f"{type(exc).__name__}: {exc}"[:200])


def _dense_guard(x):
    """Fault hook + opt-in finite check for a dense-path result, BEFORE
    it is committed into C (so the dense→stack failover sees an
    untouched C).  One `active()` check when disabled."""
    if _faults.active():
        x = _faults.corrupt("dense", x)
    from dbcsr_tpu.acc import smm as _smm

    if _smm._output_checks_enabled() and _smm._output_corrupted(x):
        raise _smm.CorruptedOutputError(
            "dense path produced non-finite output")
    return x


def _carve_full_pattern(cd, nbr, nbc, bm, bn):
    """Carve a uniformly blocked product canvas into the FULL row-major
    block pattern: a pure layout permutation (the (s, s) rectangle of
    `_gather_bin_from_canvas_strided` with no ragged edge), traced
    inside the caller's program."""
    return (
        cd.reshape(nbr, bm, nbc, bn)
        .transpose(0, 2, 1, 3)
        .reshape(nbr * nbc, bm, bn)
    )


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("bm", "bn"))
def _scatter_bin_to_canvas(canvas, blocks, row_off, col_off, bm: int, bn: int):
    """Scatter an (N, bm, bn) bin onto a dense (M, K) canvas at element
    offsets — the make_dense data movement, on device.  Slots whose
    offsets are out of range are dropped (callers pass the bin's FULL
    bucket-padded buffer with out-of-range offsets for dead slots, so
    the jit shape is the stable bucket capacity, not the live count)."""
    r_idx = row_off[:, None, None] + jnp.arange(bm)[None, :, None]
    c_idx = col_off[:, None, None] + jnp.arange(bn)[None, None, :]
    return canvas.at[r_idx, c_idx].set(blocks, mode="drop")


@functools.partial(jax.jit, static_argnames=("bm", "bn"))
def _gather_bin_from_canvas(canvas, row_off, col_off, bm: int, bn: int):
    """Inverse carve: (N, bm, bn) patches from a dense canvas."""
    r_idx = row_off[:, None, None] + jnp.arange(bm)[None, :, None]
    c_idx = col_off[:, None, None] + jnp.arange(bn)[None, None, :]
    return canvas[r_idx, c_idx]


_dense_const_cache = None  # created lazily; OrderedDict LRU


def _dense_const(key, build):
    """Small device-constant LRU for the dense path's per-multiply
    h2d uploads (alpha/beta scalars, C's key vector): repeated
    same-pattern multiplies (driver reps, SCF loops) would otherwise
    pay a host->device round trip per rep per constant.  Keys embed
    the full content
    (value/dtype, or the key vector's bytes), so staleness is
    impossible; LRU-bounded like _fill_cache/_plan_cache."""
    import collections

    global _dense_const_cache
    if _dense_const_cache is None:
        _dense_const_cache = collections.OrderedDict()
    hit = _dense_const_cache.get(key)
    if hit is None:
        hit = build()
        _dense_const_cache[key] = hit
        while len(_dense_const_cache) > 64:
            _dense_const_cache.popitem(last=False)
    else:
        _dense_const_cache.move_to_end(key)
    return hit


def _dense_canvas_cached(m: BlockSparseMatrix, build) -> object:
    """Device canvas of ``m``, cached on the instance keyed by its bin
    data-array identities (jax arrays are immutable, and the cache holds
    the arrays so ids cannot be recycled): repeated dense-mode
    multiplies with unchanged operands skip the scatter entirely.
    ``build`` constructs the canvas on a miss."""
    from dbcsr_tpu.core import digests

    key = digests.buffers_key(b.data for b in m.bins)
    cache = getattr(m, "_dense_canvas_cache", None)
    if cache is not None and cache[0] == key:
        return cache[1]
    # the mutation funnels (map_bin_data / set_structure_from_device)
    # drop the attribute, so a live cache is always for current data
    canvas = build()
    m._dense_canvas_cache = (key, canvas, [b.data for b in m.bins])
    return canvas


def _to_dense_device(m: BlockSparseMatrix):
    """Densify a (possibly non-uniformly blocked) matrix on device."""
    canvas = jnp.zeros((m.nfullrows, m.nfullcols), m.dtype)
    if m.nblks == 0:
        return canvas
    rows, cols = m.entry_coords()
    roff = m.row_blk_offsets[rows]
    coff = m.col_blk_offsets[cols]
    for b_id, b in enumerate(m.bins):
        if b.count == 0:
            continue

        def _offsets(b_id=b_id, b=b):
            sel = np.nonzero(m.ent_bin == b_id)[0]
            cap = b.data.shape[0]
            # dead (bucket-padding) slots get out-of-range offsets ->
            # dropped; the full-capacity buffer keeps the jit shape
            # stable across counts
            ro = np.full(cap, m.nfullrows, np.int64)
            co = np.full(cap, m.nfullcols, np.int64)
            ro[m.ent_slot[sel]] = roff[sel]
            co[m.ent_slot[sel]] = coff[sel]
            return jnp.asarray(ro), jnp.asarray(co)

        # structure-derived offsets ride the per-matrix device mirror:
        # a repeated same-pattern densify uploads them once
        ro_d, co_d = m.device_index(("dense_off", b_id), _offsets)
        canvas = _scatter_bin_to_canvas(
            canvas, b.data, ro_d, co_d, bm=b.shape[0], bn=b.shape[1],
        )
    return canvas


def _dense_multiply(a, b, c, alpha, beta) -> int:
    """Dense mode, every blocking: densify A and B on device, one MXU
    matmul, carve C back into its own full blocking (the
    `dbcsr_make_dense`/`dbcsr_make_undense` re-blocking pair,
    `dbcsr_mm.F:593-617,770-810`, on one flat dense canvas).  Beyond
    the canvas cap a uniform blocking runs strip by strip
    (`_dense_multiply_chunked`); anything else over the cap reached
    here by a force or the occupancy rule, and keeps whole canvases."""
    if _faults.active():
        _faults.maybe_inject("dense")
    strips = _dense_strips(a, b, c) if _over_canvas_cap(a, b) else None
    if strips is not None:
        return _dense_multiply_chunked(a, b, c, alpha, beta, strips)
    t_start = time.perf_counter()
    _metrics.record_jit(
        "mm.multiply._dense_dot",
        (a.nfullrows, b.nfullcols, a.nfullcols, str(np.dtype(c.dtype))),
    )
    with timed("dense_canvas_ab"):
        ad = _dense_canvas_cached(a, lambda: _to_dense_device(a))
        bd = _dense_canvas_cached(b, lambda: _to_dense_device(b))
    acc = ad.dtype
    with timed("dense_dot"):
        cd = jax.lax.dot_general(
            ad, bd, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=acc,
        )
        dt_name = str(np.dtype(c.dtype))
        alpha_dev = _dense_const(("scalar", complex(alpha), dt_name),
                                 lambda: jnp.asarray(alpha, dtype=c.dtype))
        beta_dev = _dense_const(("scalar", complex(beta), dt_name),
                                lambda: jnp.asarray(beta, dtype=c.dtype))
        cd = alpha_dev * cd
        c_old_dense = (_to_dense_device(c)
                       if beta != 0 and c.nblks else None)
        if c_old_dense is not None:
            cd = cd + beta_dev * c_old_dense
        cd = _dense_guard(cd)
        if _abft.enabled():
            _abft.check_dense_canvas(cd, ad, bd, c_old_dense, alpha,
                                     beta, dtype=c.dtype)
        # the old-C canvas (possibly hundreds of MB) must not stay
        # alive through the carve: its uses end here
        del c_old_dense
    with timed("dense_carve"):
        carve_full_pattern(c, cd)
    # marketing flops = the dense work performed; the RETURN value is the
    # true flops of the sparse product (comparable across algorithms,
    # ref marketing-vs-true `dbcsr_mm.F:664-667`)
    dcost = _costmodel.dense_cost(
        c.nfullrows, c.nfullcols, a.nfullcols,
        itemsize=np.dtype(c.dtype).itemsize)
    stats.record_driver(
        "dense", dcost["flops"], nbytes=dcost["bytes"],
        seconds=time.perf_counter() - t_start,
        dtype=str(np.dtype(c.dtype)))
    stats.record_multiply(2 * c.nfullrows * c.nfullcols * a.nfullcols)
    return _true_product_flops(a, b)


def _near_uniform(sizes):
    """(s, q) when all block sizes equal ``s`` except a possibly-smaller
    LAST one — the shape every ceil-division blocking (the perf
    driver's (1, s) sizes, `expand_block_sizes`) produces — with ``q``
    the number of blocks of size ``s``; None for any other blocking.
    Each shape bin of the full pattern is then one rectangle of the
    canvas, split at ``q*s``, already in slot order."""
    if len(sizes) == 0:
        return None
    s, last = int(sizes[0]), int(sizes[-1])
    if last > s or not np.all(np.asarray(sizes[:-1]) == s):
        return None
    return s, len(sizes) - (last < s)


@functools.partial(jax.jit,
                   static_argnames=("rows", "cols", "bins", "replicated"))
def _gather_bin_from_canvas_strided(canvas, *, rows, cols, bins,
                                    replicated=None):
    """Every shape bin of a near-uniformly blocked canvas's full pattern
    as a layout permutation of one rectangle each — no index array.
    ``rows``/``cols`` are `_near_uniform` of the blocking; ``bins`` is
    ((bm, bn, capacity), ...) in `_bin_entries` order.  Slots are in
    key order because the full pattern is row-major; each bin comes out
    at its bucket capacity, zero tail included.  A canvas sharded over
    a mesh is first gathered to ``replicated`` (its mesh's replicated
    sharding): every device then holds all of C, as the gather leaves
    it; unconstrained, GSPMD shards the bins by slot AND inside the
    blocks.  (The name keeps the `jit__gather_bin_from_canvas*` module
    prefix the benchmark's `dense_canvas_carve_s` reads.)"""
    if replicated is not None:
        canvas = jax.lax.with_sharding_constraint(canvas, replicated)
    (rs, rq), (cs, cq) = rows, cols
    r_end, c_end = rs * rq, cs * cq
    out = []
    for bm, bn, cap in bins:
        # row strips first, columns second: the 4-D form of the whole
        # rectangle would tile-pad its (cq, cs) minor pair 5x on a TPU
        strip = canvas[:r_end] if bm == rs else canvas[r_end:]
        nr = rq if bm == rs else 1
        strip = strip.reshape(nr, bm, canvas.shape[1])
        rect = strip[:, :, :c_end] if bn == cs else strip[:, :, c_end:]
        nc = cq if bn == cs else 1
        data = (rect.reshape(nr, bm, nc, bn).transpose(0, 2, 1, 3)
                .reshape(nr * nc, bm, bn))
        if cap > nr * nc:
            data = jnp.pad(data, ((0, cap - nr * nc), (0, 0), (0, 0)))
        out.append(data)
    return tuple(out)


def carve_full_pattern(c, cd) -> None:
    """Carve a dense device canvas into ``c``'s FULL block pattern, bin
    by bin (`dbcsr_make_undense`, `dbcsr_mm.F:770-810`); shared by the
    single-chip and mesh dense modes.

    A near-uniform blocking (uniform except a smaller last row/col
    block, i.e. every ceil-division blocking) is carved by one layout
    program, `_gather_bin_from_canvas_strided`.  Anything else takes
    per-bin element-offset gathers off the canvas."""
    nbr, nbc = c.nblkrows, c.nblkcols
    new_keys = np.arange(nbr * nbc, dtype=np.int64)
    rows = new_keys // nbc
    cols = new_keys % nbc
    nb, nsl, shapes = _bin_entries(c.row_blk_sizes, c.col_blk_sizes, rows, cols)
    counts = np.bincount(nb, minlength=len(shapes))
    lead_rows = _near_uniform(c.row_blk_sizes)
    lead_cols = _near_uniform(c.col_blk_sizes)
    layout = lead_rows is not None and lead_cols is not None
    _metrics.counter(
        "dbcsr_tpu_dense_carve_total",
        "carves of a dense product canvas into C's full block pattern, "
        "by lowering: 'layout' (near-uniform blocking, a permutation) or "
        "'gather' (irregular blocking, element offsets)",
    ).inc(lowering="layout" if layout else "gather")
    if layout:
        mesh = getattr(cd.sharding, "mesh", None)
        datas = _gather_bin_from_canvas_strided(
            cd, rows=lead_rows, cols=lead_cols,
            bins=tuple((bm, bn, bucket_size(int(n)))
                       for (bm, bn), n in zip(shapes, counts)),
            replicated=(None if mesh is None else
                        jax.sharding.NamedSharding(
                            mesh, jax.sharding.PartitionSpec())),
        )
    else:
        roff = c.row_blk_offsets[rows]
        coff = c.col_blk_offsets[cols]
        datas = []
        for b_id, (bm, bn) in enumerate(shapes):
            sel = np.nonzero(nb == b_id)[0]
            count = int(counts[b_id])
            ro = np.empty(count, np.int64)
            co = np.empty(count, np.int64)
            ro[nsl[sel]] = roff[sel]
            co[nsl[sel]] = coff[sel]
            data = _gather_bin_from_canvas(
                cd, jnp.asarray(ro), jnp.asarray(co), bm=int(bm), bn=int(bn)
            )
            cap = bucket_size(count)
            if cap > count:
                data = jnp.concatenate(
                    [data,
                     jnp.zeros((cap - count, int(bm), int(bn)), data.dtype)]
                )
            datas.append(data)
    bins = [_Bin(shape, data, int(n))
            for shape, data, n in zip(shapes, datas, counts)]
    c.set_structure_from_device(new_keys, bins, binning=(nb, nsl, shapes))


@functools.partial(
    jax.jit, donate_argnums=0,
    static_argnames=("m_el", "k_el", "n_el", "bm", "bn", "bk"),
)
def _dense_strip_matmul(cd, a_data, a_ro, a_co, b_data, b_ro, b_co,
                        *, m_el, k_el, n_el, bm, bn, bk):
    """One (m-strip x k-strip) @ (k-strip x N) canvas accumulation.
    Operand strips are scattered from the FULL bin buffers with
    out-of-strip blocks carrying dropped (out-of-range) offsets, so the
    jit shape is the stable bucket capacity for every strip."""
    ad = _scatter_bin_to_canvas(
        jnp.zeros((m_el, k_el), a_data.dtype), a_data, a_ro, a_co,
        bm=bm, bn=bk,
    )
    bd = _scatter_bin_to_canvas(
        jnp.zeros((k_el, n_el), b_data.dtype), b_data, b_ro, b_co,
        bm=bk, bn=bn,
    )
    return cd + jax.lax.dot_general(
        ad, bd, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=cd.dtype,
    )


@functools.partial(
    jax.jit, donate_argnums=0,
    static_argnames=("nbc", "bm", "bn", "rows"),
)
def _dense_strip_to_blocks(cd, c_blocks, strip_pos, alpha, beta,
                           *, nbc, bm, bn, rows):
    """Carve one C m-strip canvas into its full row-major block pattern
    and merge beta*old (strip_pos: old block -> strip-local full-pattern
    position, out-of-strip dropped).  A strip is a full row-major
    pattern over ``rows`` block rows: a layout carve, no gather."""
    out = alpha * _carve_full_pattern(cd, rows, nbc, bm, bn)
    return out.at[strip_pos].add(beta * c_blocks.astype(out.dtype), mode="drop")


def _dense_multiply_chunked(a, b, c, alpha, beta, strips) -> int:
    """Dense mode beyond the canvas cap: tile over k-strips (plus
    m-strips and n-strips when the C canvas itself is too big), keeping
    every live canvas under `_DENSE_MAX_CANVAS` elements while the
    product stays on the dense MXU route (the reference's dense mode
    has no size cap, `dbcsr_mm.F:593-617`; this is its big-matrix
    realization)."""
    t_start = time.perf_counter()
    bm = int(c.row_blk_sizes[0])
    bn = int(c.col_blk_sizes[0])
    bk = int(a.col_blk_sizes[0])
    nbr, nbc, nbk = a.nblkrows, c.nblkcols, a.nblkcols
    mrb, kcb, ncb = strips  # `_dense_strips`: fits the cap, not None
    nms = -(-nbr // mrb)
    nks = -(-nbk // kcb)
    nns = -(-nbc // ncb)

    ar, ac = a.entry_coords()
    br_, bc_ = b.entry_coords()
    a_data = (a.bins[0].data[: a.nblks] if a.nblks
              else jnp.zeros((0, bm, bk), c.dtype))
    b_data = (b.bins[0].data[: b.nblks] if b.nblks
              else jnp.zeros((0, bk, bn), c.dtype))
    c_data = (c.bins[0].data[: c.nblks] if c.nblks
              else jnp.zeros((0, bm, bn), c.dtype))
    c_rows = (c.keys // nbc).astype(np.int64)
    c_cols = (c.keys % nbc).astype(np.int64)
    # dropped by mode="drop" scatters.  MUST stay out of bounds after
    # jax's int32 scatter-index narrowing (1<<40 would truncate to 0 and
    # land IN bounds); 2^30 is far beyond any canvas dim (cap 2e8) and
    # int32-safe even after + block offsets
    oor = np.int64(1) << 30

    def strip_off(coords, lo, hi, blk):
        off = (coords - lo) * blk
        return np.where((coords >= lo) & (coords < hi), off, oor)

    dt_name = str(np.dtype(c.dtype))
    alpha_dev = _dense_const(("scalar", complex(alpha), dt_name),
                             lambda: jnp.asarray(alpha, dtype=c.dtype))
    beta_dev = _dense_const(("scalar", complex(beta), dt_name),
                            lambda: jnp.asarray(beta, dtype=c.dtype))
    acc = np.dtype(c.dtype)
    # per-k-strip / per-n-strip offsets depend only on their own strip
    # index: compute/upload once, not once per (ms, ks, ns) tile (an
    # out-of-strip offset on EITHER axis drops the whole block write)
    a_ko_ks = []
    b_ro_ks = []
    for ks in range(nks):
        k0, k1 = ks * kcb, min(nbk, (ks + 1) * kcb)
        a_ko_ks.append(jnp.asarray(strip_off(ac, k0, k1, bk)))
        b_ro_ks.append(jnp.asarray(strip_off(br_, k0, k1, bk)))
    b_co_ns = []
    for ns in range(nns):
        c0, c1 = ns * ncb, min(nbc, (ns + 1) * ncb)
        b_co_ns.append(jnp.asarray(strip_off(bc_, c0, c1, bn)))
    parts = []
    for ms in range(nms):
        r0, r1 = ms * mrb, min(nbr, (ms + 1) * mrb)
        a_ro_ms = jnp.asarray(strip_off(ar, r0, r1, bm))
        tiles = []
        for ns in range(nns):
            c0, c1 = ns * ncb, min(nbc, (ns + 1) * ncb)
            cd = jnp.zeros((mrb * bm, ncb * bn), acc)
            for ks in range(nks):
                cd = _dense_strip_matmul(
                    cd, a_data, a_ro_ms, a_ko_ks[ks],
                    b_data, b_ro_ks[ks], b_co_ns[ns],
                    m_el=mrb * bm, k_el=kcb * bk, n_el=ncb * bn,
                    bm=bm, bn=bn, bk=bk,
                )
            tile_pos = np.where(
                (c_rows >= r0) & (c_rows < r1)
                & (c_cols >= c0) & (c_cols < c1),
                (c_rows - r0) * ncb + (c_cols - c0), oor,
            )
            out = _dense_strip_to_blocks(
                cd, c_data, jnp.asarray(tile_pos), alpha_dev, beta_dev,
                nbc=ncb, bm=bm, bn=bn, rows=mrb,
            )
            # (padded-rows x padded-cols) tile pattern -> live blocks
            tiles.append(out.reshape(mrb, ncb, bm, bn)
                         [: r1 - r0, : c1 - c0])
        strip = (jnp.concatenate(tiles, axis=1)
                 if len(tiles) > 1 else tiles[0])
        parts.append(strip.reshape((r1 - r0) * nbc, bm, bn))
    out = _dense_guard(
        jnp.concatenate(parts) if len(parts) > 1 else parts[0])
    new_keys = np.arange(nbr * nbc, dtype=np.int64)
    cap = bucket_size(len(new_keys))
    if cap > len(new_keys):
        out = jnp.concatenate(
            [out, jnp.zeros((cap - len(new_keys), bm, bn), out.dtype)]
        )
    c.set_structure_from_device(new_keys, [_Bin((bm, bn), out, len(new_keys))])
    # strip traffic model: every A strip is re-scattered per n-strip,
    # every B strip per m-strip, C is written once
    itemsize = np.dtype(c.dtype).itemsize
    strip_bytes = itemsize * (
        nns * nbr * bm * nbk * bk + nms * nbk * bk * nbc * bn
        + 2 * nbr * bm * nbc * bn
    )
    stats.record_stack(
        bm, bn, bk, nbr * nbc * nbk, driver="dense",
        seconds=time.perf_counter() - t_start, nbytes=strip_bytes,
        dtype=str(np.dtype(c.dtype)),
    )
    stats.record_multiply(2 * nbr * bm * nbc * bn * nbk * bk)
    return _true_product_flops(a, b)


def _apply_element_limits(a, b, c, element_limits):
    """Resolve element-granular limits (ref `dbcsr_multiply`'s full-
    index limit args).  Block-aligned limits reduce to block-index
    limits; unaligned ones additionally crop op(A)/op(B) at element
    level (ref `dbcsr_crop_matrix` in `make_m2s`,
    `dbcsr_mm_cannon.F:194-220`) so partial boundary blocks contribute
    only their in-window elements.

    Returns (a, b, block_limits, beta_window)."""
    if len(element_limits) != 6:
        raise ValueError("element_limits must be a 6-tuple")
    fr, lr, fc, lc, fk, lk = element_limits
    fr = 0 if fr is None else int(fr)
    lr = c.nfullrows - 1 if lr is None else int(lr)
    fc = 0 if fc is None else int(fc)
    lc = c.nfullcols - 1 if lc is None else int(lc)
    fk = 0 if fk is None else int(fk)
    lk = a.nfullcols - 1 if lk is None else int(lk)
    if not (0 <= fr <= lr < c.nfullrows and 0 <= fc <= lc < c.nfullcols
            and 0 <= fk <= lk < a.nfullcols):
        raise ValueError(f"element limits out of range: {element_limits}")

    def axis(lo, hi, off, n_el):
        b0 = int(np.searchsorted(off, lo, side="right") - 1)
        b1 = int(np.searchsorted(off, hi, side="right") - 1)
        aligned = off[b0] == lo and off[b1 + 1] - 1 == hi
        full = lo == 0 and hi == n_el - 1
        return b0, b1, aligned, full

    rb0, rb1, r_al, r_full = axis(fr, lr, c.row_blk_offsets, c.nfullrows)
    cb0, cb1, c_al, c_full = axis(fc, lc, c.col_blk_offsets, c.nfullcols)
    kb0, kb1, k_al, k_full = axis(fk, lk, a.col_blk_offsets, a.nfullcols)

    if not (r_al and c_al and k_al):
        from dbcsr_tpu.ops.operations import crop_matrix

        a = crop_matrix(a, row_bounds=(fr, lr), col_bounds=(fk, lk))
        b = crop_matrix(b, row_bounds=(fk, lk), col_bounds=(fc, lc))
    block_limits = (
        None if r_full else rb0, None if r_full else rb1,
        None if c_full else cb0, None if c_full else cb1,
        None if k_full else kb0, None if k_full else kb1,
    )
    beta_window = None if (r_full and c_full) else (fr, lr, fc, lc)
    return a, b, block_limits, beta_window


def _candidates(a, b, c, filter_eps, fr, lr, fc, lc, fk, lk):
    """Symbolic product: all (i, k, j) triples as parallel arrays
    (a_ent indexes op(A) entries, b_ent op(B) entries).  Uses the native
    C++ engine when available; the NumPy path below is the fallback and
    the reference implementation for tests."""
    na2 = nb2 = row_eps = None
    if filter_eps is not None:
        # squared f32 norms, per-A-row eps (ref dbcsr_mm_cannon.F:1098-1105)
        na2 = a.block_norms().astype(np.float32) ** 2
        nb2 = b.block_norms().astype(np.float32) ** 2
        row_counts = np.diff(a.row_ptr)
        with np.errstate(over="ignore"):  # huge eps -> inf is a valid threshold
            row_eps = (
                np.float32(filter_eps) / np.maximum(1, row_counts).astype(np.float32)
            ) ** 2

    from dbcsr_tpu import native

    res = native.symbolic_product(
        a.row_ptr, (a.keys % a.nblkcols).astype(np.int32),
        b.row_ptr, (b.keys % b.nblkcols).astype(np.int32),
        na2, nb2, row_eps,
        sym_c=c.matrix_type != NO_SYMMETRY,
        fr=fr, lr=lr, fc=fc, lc=lc, fk=fk, lk=lk,
    )
    if res is None:
        res = _candidates_numpy(a, b, c, na2, nb2, row_eps,
                                fr, lr, fc, lc, fk, lk)
    if (filter_eps is not None and c.matrix_type == NO_SYMMETRY
            and all(v is None for v in (fr, lr, fc, lc, fk, lk))):
        # counted where the patterns alone say what the test chose
        # from: B's blocks in row k, summed over A's blocks (i,k).  A
        # symmetric or limited product would have to enumerate twice
        # to know, so it is left out of both fates
        cols_a = (a.keys % a.nblkcols).astype(np.int64)
        structural = int(np.diff(b.row_ptr)[cols_a].sum())
        fates = _metrics.counter(
            "dbcsr_tpu_candidates_total",
            "(i,k,j) candidates of filtered products (no limits, C not "
            "symmetric) by what the norm test made of them (kept = "
            "multiplied, pruned = dropped before any flop)")
        fates.inc(len(res[0]), fate="kept")
        fates.inc(structural - len(res[0]), fate="pruned")
    return res


def _candidates_numpy(a, b, c, na2, nb2, row_eps, fr, lr, fc, lc, fk, lk):
    rows_a = np.repeat(
        np.arange(a.nblkrows, dtype=np.int64), np.diff(a.row_ptr)
    )
    cols_a = (a.keys % a.nblkcols).astype(np.int64)  # k per A entry
    cols_b = (b.keys % b.nblkcols).astype(np.int64)  # j per B entry

    a_sel = np.ones(len(a.keys), bool)
    if fr is not None:
        a_sel &= rows_a >= fr
    if lr is not None:
        a_sel &= rows_a <= lr
    if fk is not None:
        a_sel &= cols_a >= fk
    if lk is not None:
        a_sel &= cols_a <= lk
    a_entries = np.nonzero(a_sel)[0]

    counts = (b.row_ptr[cols_a[a_entries] + 1] - b.row_ptr[cols_a[a_entries]]).astype(
        np.int64
    )
    tot = int(counts.sum())
    a_ent = np.repeat(a_entries, counts)
    if tot == 0:
        z = np.empty(0, np.int64)
        return z, z, z, z
    ends = np.cumsum(counts)
    starts = ends - counts
    b_ent = (
        np.arange(tot, dtype=np.int64)
        - np.repeat(starts, counts)
        + np.repeat(b.row_ptr[cols_a[a_entries]], counts)
    )
    i = rows_a[a_ent]
    j = cols_b[b_ent]

    keep = np.ones(tot, bool)
    if fc is not None:
        keep &= j >= fc
    if lc is not None:
        keep &= j <= lc
    if c.matrix_type != NO_SYMMETRY:
        # don't compute the redundant triangle (ref symmetric skip,
        # dbcsr_mm_csr.F:281)
        keep &= i <= j
    if na2 is not None:
        keep &= na2[a_ent] * nb2[b_ent] >= row_eps[i]
    if not keep.all():
        i, j, a_ent, b_ent = i[keep], j[keep], a_ent[keep], b_ent[keep]
    return i, j, a_ent, b_ent


def _rebuild_c(c: BlockSparseMatrix, new_keys: np.ndarray, beta,
               beta_window=None) -> None:
    """Re-structure C on the (possibly grown) pattern with data
    beta-scaled.  With ``beta_window`` = (r0, r1, c0, c1) inclusive
    element bounds, beta applies only inside the window: old blocks
    fully outside are copied unscaled, straddling blocks get an
    element-masked scale (reference windowed-dgemm semantics)."""
    old_keys = c.keys
    old_bins = c.bins
    old_ent_bin = c.ent_bin
    old_ent_slot = c.ent_slot
    rows = (new_keys // c.nblkcols).astype(np.int64)
    cols = (new_keys % c.nblkcols).astype(np.int64)
    nb, nsl, shapes = _bin_entries(c.row_blk_sizes, c.col_blk_sizes, rows, cols)
    dt_name_rc = str(np.dtype(c.dtype))
    beta_dev = _dense_const(("scalar", complex(beta), dt_name_rc),
                            lambda: jnp.asarray(beta, dtype=c.dtype))
    one_dev = _dense_const(("scalar", complex(1.0), dt_name_rc),
                           lambda: jnp.asarray(1.0, dtype=c.dtype))
    pos_old = np.searchsorted(new_keys, old_keys)  # old keys ⊆ new keys

    n_old = len(old_keys)
    if beta_window is None or beta == 1 or n_old == 0:
        cls_inside = np.ones(n_old, bool)
        cls_strad = np.zeros(n_old, bool)
        blk_r0 = blk_c0 = None
    else:
        r0, r1, c0w, c1w = beta_window
        orows = (old_keys // c.nblkcols).astype(np.int64)
        ocols = (old_keys % c.nblkcols).astype(np.int64)
        roff, coff = c.row_blk_offsets, c.col_blk_offsets
        blk_r0, blk_r1 = roff[orows], roff[orows + 1] - 1
        blk_c0, blk_c1 = coff[ocols], coff[ocols + 1] - 1
        overlap = (blk_r1 >= r0) & (blk_r0 <= r1) & (blk_c1 >= c0w) & (blk_c0 <= c1w)
        cls_inside = (
            overlap & (blk_r0 >= r0) & (blk_r1 <= r1)
            & (blk_c0 >= c0w) & (blk_c1 <= c1w)
        )
        cls_strad = overlap & ~cls_inside

    bins = []
    for b_id, (bm, bn) in enumerate(shapes):
        count = int((nb == b_id).sum())
        cap = c.bin_capacity(count)
        data = mempool.zeros((cap, bm, bn), c.dtype)
        in_bin = (nb[pos_old] == b_id) if n_old else np.zeros(0, bool)

        def scatter(sel_mask, factor):
            nonlocal data
            sel = np.nonzero(sel_mask)[0]
            if not len(sel):
                return
            src_bin = old_bins[old_ent_bin[sel[0]]]
            data = _scatter_scaled(
                data, src_bin.data,
                mempool.upload_index("rebuild_src", old_ent_slot[sel]),
                mempool.upload_index("rebuild_dst", nsl[pos_old[sel]]),
                factor,
            )

        if beta != 0:
            scatter(in_bin & cls_inside, beta_dev)
        if beta_window is not None and beta != 1:
            scatter(in_bin & ~cls_inside & ~cls_strad, one_dev)
            sel = np.nonzero(in_bin & cls_strad)[0]
            if len(sel):
                r0, r1, c0w, c1w = beta_window
                rl = np.maximum(r0 - blk_r0[sel], 0)
                rh = np.minimum(r1 - blk_r0[sel], bm - 1)
                cl = np.maximum(c0w - blk_c0[sel], 0)
                ch = np.minimum(c1w - blk_c0[sel], bn - 1)
                src_bin = old_bins[old_ent_bin[sel[0]]]
                data = _scatter_scaled_window(
                    data, src_bin.data,
                    mempool.upload_index("rebuild_src", old_ent_slot[sel]),
                    mempool.upload_index("rebuild_dst", nsl[pos_old[sel]]),
                    beta_dev,
                    jnp.asarray(rl), jnp.asarray(rh),
                    jnp.asarray(cl), jnp.asarray(ch),
                )
        bins.append(_Bin((bm, bn), data, count))
    c.set_structure_from_device(new_keys, bins, binning=(nb, nsl, shapes))


# prepared-plan cache for repeated same-pattern multiplies (SCF-style
# loops; the perf driver's nrep reps): skips the host group-sort and
# the stack index upload entirely.  Keyed by pattern fingerprints +
# product options (see plan_key in multiply()); LRU-bounded by entry
# count AND by the device bytes the plans pin.
from collections import OrderedDict as _OrderedDict

_plan_cache: "_OrderedDict[tuple, _CachedSpans]" = _OrderedDict()
_plan_cache_bytes = 0  # running sum of the entries' at-insert nbytes
_PLAN_CACHE_MAX = 16
_PLAN_CACHE_MAX_BYTES = 256 * 1024 * 1024


class _CachedSpans:
    """One plan-cache entry: the per-span plan tuples plus the lazily
    built fused superstack plans per C bin (``None`` marks a bin whose
    spans cannot fuse) and the byte size snapshot the cache's running
    budget counter uses.  Plans mutate in place after insert (a
    crosspack demotion frees its payload; a failover heal can swap a
    cheap host plan for one pinning device index arrays), so every
    cache HIT refreshes the snapshot through `refresh_nbytes` — O(this
    entry's spans), vs the old global re-sum per insert."""

    __slots__ = ("spans", "super_plans", "nbytes")

    def __init__(self, spans):
        self.spans = spans
        self.super_plans: dict = {}
        self.nbytes = sum(p.nbytes() for (*_, p) in spans if p is not None)

    def refresh_nbytes(self) -> int:
        """Recompute the snapshot from the live plans; returns the
        delta for the cache's running byte counter."""
        new = sum(p.nbytes() for (*_, p) in self.spans if p is not None)
        delta = new - self.nbytes
        self.nbytes = new
        return delta

    def superstack_for(self, cbin, plans, prepare):
        """The bin's fused plan, (re)built whenever the spans' driver
        tuple changed since the cached decision — a failover/demotion
        heals plans IN PLACE, which can invalidate a built program OR
        make a previously unfusable (None) bin fusable."""
        drivers = tuple(p.driver for p in plans)
        hit = self.super_plans.get(cbin)
        if hit is not None and hit[0] == drivers:
            return hit[1]
        splan = prepare(plans)
        self.super_plans[cbin] = (drivers, splan)
        return splan


def _plan_cache_counter():
    return _metrics.counter(
        "dbcsr_tpu_plan_cache_total",
        "stack-plan cache outcomes: per multiply hit, miss or "
        "uncacheable (a filtered product with the pool off), and "
        "evicted per entry the LRU or the byte budget pushed out")


def _plan_cache_insert(key, entry: "_CachedSpans") -> None:
    """Insert + LRU/byte-budget eviction in O(evicted): the running
    byte counter replaces the old re-sum of every cached plan inside
    the eviction loop (O(cache·spans) per insert)."""
    global _plan_cache_bytes
    if not _plan_cache:
        _plan_cache_bytes = 0  # tests clear() the OrderedDict directly
    old = _plan_cache.pop(key, None)
    if old is not None:
        _plan_cache_bytes -= old.nbytes
    _plan_cache[key] = entry
    _plan_cache_bytes += entry.nbytes
    while len(_plan_cache) > _PLAN_CACHE_MAX or (
        len(_plan_cache) > 1 and _plan_cache_bytes > _PLAN_CACHE_MAX_BYTES
    ):
        _, evicted = _plan_cache.popitem(last=False)
        _plan_cache_bytes -= evicted.nbytes
        _plan_cache_counter().inc(result="evicted")


def _superstack_mode() -> str:
    """The resolved stack execution mode: config.superstack with
    "auto" meaning fused (fuse whenever a bin's spans can; single-span
    bins and unfusable bins run per-span either way).  Values are
    validated at every entry point (`Config.validate` runs for env
    application and `set_config` alike), so a typo'd control run fails
    fast instead of silently executing fused."""
    from dbcsr_tpu.core.config import get_config

    mode = get_config().superstack
    return "fused" if mode == "auto" else mode


def _run_stacks(c, a, b, cand_keys, a_ent, b_ent, alpha, plan_key=None,  # lint: disable=mutation-epoch (the caller stamps `c._note_mutation(c.keys)` once after the run — per-launch bin swaps and ABFT rollbacks are interior states of one funnel)
                c_zero=False) -> int:
    """Group candidate triples by (m,n,k) shape-bin, sort by C block,
    and execute: spans sharing a destination C bin fuse into a single
    donated-buffer launch (`acc.smm.execute_superstack`) unless
    config.superstack forces the per-span path; returns true flops."""
    if len(cand_keys) == 0:
        return 0
    from dbcsr_tpu.acc.smm import (
        execute_stack,
        execute_superstack,
        plan_exec_dtype,
        prepare_stack,
        prepare_superstack,
    )

    global _plan_cache_bytes
    cached = None
    if plan_key is not None and plan_key in _plan_cache:
        _plan_cache.move_to_end(plan_key)
        cached = _plan_cache[plan_key]
        # plans heal/demote in place: keep the byte budget honest
        _plan_cache_bytes += cached.refresh_nbytes()
    _plan_cache_counter().inc(
        result=("hit" if cached is not None
                else "miss" if plan_key is not None else "uncacheable"))
    if cached is not None:
        _flight.note("plan_cache", "hit")
        # a cache hit skips prepare_stack (where decisions are noted);
        # the flight record still names the drivers actually launched
        for _cb, _ab, _bb, m, n, k, cnt, plan in cached.spans:
            if plan is not None:
                _flight.note_driver(plan.driver, "plan-cache-hit",
                                    mnk=(m, n, k), entries=cnt)
    if cached is None:
        c_ent = np.searchsorted(c.keys, cand_keys)
        cb = c.ent_bin[c_ent]
        ab = a.ent_bin[a_ent]
        bb = b.ent_bin[b_ent]
        c_slot = c.ent_slot[c_ent]
        a_slot = a.ent_slot[a_ent]
        b_slot = b.ent_slot[b_ent]
        g = (cb.astype(np.int64) * len(a.bins) + ab) * len(b.bins) + bb
        ngroups = len(c.bins) * len(a.bins) * len(b.bins)
        from dbcsr_tpu import native

        order, gbounds = native.sort_order(g, ngroups, c_slot, a_ent,
                                           return_bounds=True)
        nonempty = np.nonzero(np.diff(gbounds))[0]
        spans = [(int(gbounds[gi]), int(gbounds[gi + 1])) for gi in nonempty]
        c_slot = c_slot[order]
        a_slot = a_slot[order]
        b_slot = b_slot[order]
        cb = cb[order]
        ab = ab[order]
        bb = bb[order]
        spans_meta = []
        for s0, s1 in spans:
            cbin, abin, bbin = int(cb[s0]), int(ab[s0]), int(bb[s0])
            m, k = a.bins[abin].shape
            _, n = b.bins[bbin].shape
            a_bin = a.bins[abin]
            b_bin = b.bins[bbin]
            plan = prepare_stack(
                c.bins[cbin].data, a_bin.data, b_bin.data,
                a_slot[s0:s1], b_slot[s0:s1], c_slot[s0:s1],
                # bucket-padded rows beyond count are zeros — the Pallas
                # path masks short groups with them
                a_pad_row=a_bin.count if a_bin.count < a_bin.data.shape[0] else None,
                b_pad_row=b_bin.count if b_bin.count < b_bin.data.shape[0] else None,
                moving=c.moving_pattern,
            )
            spans_meta.append((cbin, abin, bbin, m, n, k, s1 - s0, plan))
        cached = _CachedSpans(spans_meta)
        if plan_key is not None:
            _plan_cache_insert(plan_key, cached)
    spans_meta = cached.spans
    mode = _superstack_mode()
    # opt-in synchronized timing: block on each launch before reading
    # the clock so the recorded seconds are device-completion time
    # (the default records dispatch-side seconds — the device may still
    # be draining; stats.record_driver documents the contract)
    sync = stats.sync_timing_enabled()
    itemsize = np.dtype(c.dtype).itemsize
    dt_name = str(np.dtype(c.dtype))
    # drivers that do not donate C (host family) leave the replaced
    # buffer alive: pool-owned Cs hand it back for the next checkout
    c_releasable = c._donatable
    # Deferred ABFT: a beta==0 product's pristine C is all zeros, so
    # the whole product is re-executable from metadata alone.  The
    # per-launch probes then queue their device-side scalars WITHOUT a
    # host sync (preserving host/device pipelining) and one flush at
    # the end of the product drains them; a flush-detected mismatch
    # rolls every bin back to zeros and re-executes with immediate
    # per-launch verification (where the smm failover chain localizes
    # and recovers).  beta != 0 launches keep immediate checks — their
    # pristine C exists only as the per-launch copy.
    abft_defer = bool(c_zero) and _abft.enabled()

    def _swap_cbin(cbin, out):
        old = c.bins[cbin].data
        c.bins[cbin].data = out
        if c_releasable and out is not old:
            mempool.release(old)  # no-op for donated (deleted) buffers

    def _exec_spans(defer):
        # beta == 0 (no window): _rebuild_c left every bin as untouched
        # jnp.zeros — the host driver can then synthesize its writable
        # host buffer as np.zeros instead of fetching ~hundreds of MB
        # of zeros off the device (first touch per bin only: later
        # spans accumulate onto real contributions; a fused launch
        # counts as the whole bin's first touch)
        zero_bins = set(range(len(c.bins))) if c_zero else set()
        flops = 0
        fused_bins = 0
        i = 0
        n_spans = len(spans_meta)
        while i < n_spans:
            # spans sharing a C bin are adjacent (the group key sorts
            # by (cbin, abin, bbin)) — one slice per destination bin
            j = i
            cbin = spans_meta[i][0]
            while j < n_spans and spans_meta[j][0] == cbin:
                j += 1
            group = spans_meta[i:j]
            splan = None
            if mode != "per_span" and j - i > 1:
                splan = cached.superstack_for(
                    cbin, [sm[7] for sm in group], prepare_superstack)
            if splan is not None:
                a_datas = [a.bins[sm[1]].data for sm in group]
                b_datas = [b.bins[sm[2]].data for sm in group]
                t0 = time.perf_counter()
                out, was_fused = execute_superstack(
                    c.bins[cbin].data, a_datas, b_datas, splan, alpha,
                    c_zero=cbin in zero_bins, abft_defer=defer,
                )
                if sync:
                    jax.block_until_ready(out)
                dt_s = time.perf_counter() - t0
                _swap_cbin(cbin, out)
                zero_bins.discard(cbin)
                fused_bins += was_fused
                nseg = out.shape[0]
                span_flops = [2 * m * n * k * cnt
                              for (_, _, _, m, n, k, cnt, _) in group]
                tot_flops = float(sum(span_flops)) or 1.0
                for gi, (_cb, _ab, _bb, m, n, k, cnt, plan) \
                        in enumerate(group):
                    # the launch's seconds split across its spans by
                    # flop share; a FUSED launch reads+writes the bin's
                    # C buffer ONCE, so only the first span is charged
                    # that round trip (costmodel.superstack_bytes
                    # convention) — but a bin the resilience layer
                    # decomposed really paid the per-span round-trips,
                    # and records them as such
                    stats.record_stack(
                        m, n, k, cnt, driver=plan.driver,
                        seconds=dt_s * (span_flops[gi] / tot_flops),
                        nbytes=_costmodel.stack_bytes(
                            m, n, k, cnt,
                            nseg=(nseg if (gi == 0 or not was_fused)
                                  else 0),
                            itemsize=itemsize),
                        # EXECUTED compute dtype (demoted launches must
                        # not roofline against the request dtype's peak)
                        dtype=plan_exec_dtype(plan, dt_name), sync=sync,
                    )
                    flops += span_flops[gi]
                i = j
                continue
            for _cb, abin, bbin, m, n, k, cnt, plan in group:
                t0 = time.perf_counter()
                out = execute_stack(
                    c.bins[cbin].data, a.bins[abin].data,
                    b.bins[bbin].data, plan, alpha,
                    c_zero=cbin in zero_bins, abft_defer=defer,
                )
                if sync:
                    jax.block_until_ready(out)
                dt_s = time.perf_counter() - t0
                _swap_cbin(cbin, out)
                zero_bins.discard(cbin)
                stats.record_stack(
                    m, n, k, cnt, driver=plan.driver, seconds=dt_s,
                    nbytes=_costmodel.stack_bytes(
                        m, n, k, cnt, nseg=out.shape[0],
                        itemsize=itemsize),
                    dtype=plan_exec_dtype(plan, dt_name), sync=sync,
                )
                flops += 2 * m * n * k * cnt
            i = j
        return flops, fused_bins

    recovered_from = None
    for attempt in (0, 1):
        defer = abft_defer and attempt == 0
        if defer:
            _abft.discard_pending()
        try:
            flops, fused_bins = _exec_spans(defer)
        except BaseException:
            if defer:
                # an unrelated failure aborted the product: its queued
                # probes must never be attributed to a later one
                _abft.discard_pending()
            raise
        if not defer:
            break
        try:
            _abft.flush()
            break
        except _abft.AbftMismatchError as exc:
            from dbcsr_tpu.acc import smm as _smm

            if isinstance(exc, _abft.PrecisionExceededError):
                # adaptive-precision promote, not SDC: the cells were
                # promoted when the flush evaluated the probe; the redo
                # below re-executes with immediate verification, where
                # each still-demoted plan heals itself to native — no
                # breaker feed, no recovery attribution
                recovered_from = None
            else:
                _smm.note_deferred_sdc(exc)
                recovered_from = getattr(exc, "mismatch_drivers", None) \
                    or [getattr(exc, "driver", "?")]
            # roll every bin back to its pristine (all-zero) pre-run
            # state and redo the product with immediate verification
            for bin_ in c.bins:
                old = bin_.data
                bin_.data = mempool.zeros(old.shape, c.dtype)
                if c_releasable:
                    mempool.release(old)
    if recovered_from is not None:
        for drv in recovered_from:
            _abft.record_recovery(drv)
    if fused_bins:
        _flight.note("fused_bins", fused_bins)
    if plan_key is not None and plan_key in _plan_cache:
        # execution can heal plans in place (failover/demotion) — keep
        # the byte budget honest even for an entry never hit again
        _plan_cache_bytes += cached.refresh_nbytes()
    return flops
