"""Fused Pallas TPU kernel for parameter-stack processing.

TPU-native replacement for the reference's five CUDA kernel families
(`src/acc/libsmm_acc/kernels/smm_acc_dnt_{tiny,small,medium,largeDB1,
largeDB2}.h`): a single blocked kernel whose tuning knob is the
*grouping* R — how many stack entries one grid step processes (the
CUDA kernels' `grouping` template parameter plays the same role).

Design (vs the CUDA design, by intent):

* The stack arrives **sorted by C block** (the engine guarantees it),
  so each C block is one contiguous run of entries.  Runs are chopped
  into grid steps of R entries; a step's contributions are summed into
  a float32 VMEM accumulator that persists across the run, and the C
  block is written back once when the run ends — no atomics
  (`atomicAdd` in `smm_acc_common.h`) and bit-reproducible order.
* A/B blocks are *gathered by the Pallas pipeline itself*: the int32
  stack arrays are scalar-prefetch operands and the BlockSpec
  `index_map`s read them to pick which (1, m, k) block to DMA next —
  the Mosaic pipeline double-buffers these fetches exactly like the
  CUDA kernels' double-buffered shared-memory loads (largeDB1/2).
* Short runs are padded to a multiple of R with entries pointing at a
  guaranteed-zero block row (the engine's bucket padding), which
  contribute exact zeros — the analog of the reference's masked
  tail entries.

Only real float32/bfloat16 stacks take this path (`supports`); f64 and
complex fall back to the XLA gather/scatter-add path in
`dbcsr_tpu.acc.smm` (TPU has no native f64 MXU path to win with).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dbcsr_tpu.core.timings import device_scope

_SUPPORTED = (np.dtype(np.float32), np.dtype(jnp.bfloat16))


def supports(c_data, a_data, b_data) -> bool:
    if jnp.dtype(c_data.dtype) not in _SUPPORTED:
        return False
    if jnp.dtype(a_data.dtype) != jnp.dtype(c_data.dtype):
        return False
    if jnp.dtype(b_data.dtype) != jnp.dtype(c_data.dtype):
        return False
    from dbcsr_tpu.core.config import get_config

    # blocks bigger than max_kernel_dim blow the VMEM budget for 2*R
    # in-flight panels and take the XLA dot path instead (the role of
    # the reference's max_kernel_dim=80 cuBLAS fallback,
    # `libsmm_acc.cpp:227-249`)
    dims = a_data.shape[1:] + b_data.shape[1:] + c_data.shape[1:]
    return max(dims) <= get_config().max_kernel_dim


def _choose_grouping(run_lengths: np.ndarray) -> int:
    """Pick R (entries per grid step) from the run-length distribution —
    the one-knob analog of the CUDA `grouping` parameter."""
    avg = float(run_lengths.mean()) if len(run_lengths) else 1.0
    for r in (8, 4, 2):
        if avg >= r * 0.75:
            return r
    return 1


def build_grouped_stack(c_idx: np.ndarray, a_idx: np.ndarray, b_idx: np.ndarray,
                        a_pad_row: int, b_pad_row: int, grouping: int | None = None):
    """Chop the (sorted-by-c) stack into grid steps of R entries.

    Returns int32 arrays ai2 (S, R), bi2 (S, R), ci2 (S,) where padded
    slots point at (a_pad_row, b_pad_row) — a zero block row each.
    """
    s_total = len(c_idx)
    run_first = np.flatnonzero(np.diff(c_idx)) + 1
    run_starts = np.concatenate([[0], run_first])
    run_lens = np.diff(np.concatenate([run_starts, [s_total]]))
    r_grp = grouping or _choose_grouping(run_lens)
    steps_per_run = -(-run_lens // r_grp)
    nsteps = int(steps_per_run.sum())
    # flat destination slot of each stack entry: step base of its run
    # (in units of R) plus its position within the run
    run_of = np.repeat(np.arange(len(run_lens)), run_lens)
    pos_in_run = np.arange(s_total) - run_starts[run_of]
    step_base = np.concatenate([[0], np.cumsum(steps_per_run)])[:-1]
    dst = step_base[run_of] * r_grp + pos_in_run
    ai2 = np.full(nsteps * r_grp, a_pad_row, np.int32)
    bi2 = np.full(nsteps * r_grp, b_pad_row, np.int32)
    ai2[dst] = a_idx
    bi2[dst] = b_idx
    ci2 = np.empty(nsteps, np.int32)
    ci2[step_base[run_of] + pos_in_run // r_grp] = c_idx
    return ai2.reshape(nsteps, r_grp), bi2.reshape(nsteps, r_grp), ci2, r_grp


# ai/bi arrive FLAT (nsteps*R,) — a 2D (nsteps, R) scalar-prefetch array
# would be lane-padded to (nsteps, 128) in SMEM (1 MB budget) and blow
# the allocation 128/R-fold; 1D arrays are tiled densely
def _a_map(s, ai, bi, ci, *, r, r_grp):
    return (ai[s * r_grp + r], 0, 0)


def _b_map(s, ai, bi, ci, *, r, r_grp):
    return (bi[s * r_grp + r], 0, 0)


def _c_map(s, ai, bi, ci):
    return (ci[s], 0, 0)


def _dot_precision(dtype):
    """MXU precision per operand dtype.  HIGHEST forces true-f32
    multi-pass contraction for f32 inputs (the default single bf16
    pass loses ~1e-3 relative — caught by the validate_kernels gate on
    hardware).  bf16 operands MUST use DEFAULT: this Mosaic rejects an
    fp32 contract precision on bf16 vectors ("Bad lhs type" fatal,
    observed on-chip 2026-07-31), and bf16 inputs gain nothing from
    extra passes — the MXU multiplies bf16 exactly into the f32
    accumulator either way."""
    if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16):
        return jax.lax.Precision.DEFAULT
    return jax.lax.Precision.HIGHEST


def _smm_kernel(ai_ref, bi_ref, ci_ref, *refs, r_grp, kmerge):
    a_refs = refs[:r_grp]
    b_refs = refs[r_grp : 2 * r_grp]
    alpha_ref = refs[2 * r_grp]
    c_ref = refs[2 * r_grp + 1]
    o_ref = refs[2 * r_grp + 2]
    acc_ref = refs[2 * r_grp + 3]
    s = pl.program_id(0)
    cur = ci_ref[s]
    prev = ci_ref[jnp.maximum(s - 1, 0)]
    first = jnp.logical_or(s == 0, cur != prev)
    if kmerge and r_grp > 1:
        # k-merged variant (the in-kernel sibling of the engine's
        # xla_group R-tiling): ONE (R*k, m)^T x (R*k, n) MXU dot per
        # grid step instead of R small dots — deeper MXU pipeline,
        # R-fold fewer matmul ops.  A arrives TRANSPOSED (k, m) per
        # block so both concatenations run along the cheap sublane
        # axis, never the lane axis.
        a_cat = jnp.concatenate([a_refs[r][0] for r in range(r_grp)], axis=0)
        b_cat = jnp.concatenate([b_refs[r][0] for r in range(r_grp)], axis=0)
        contrib = jax.lax.dot_general(
            a_cat, b_cat,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_dot_precision(a_cat.dtype),
        )
    else:
        contrib = jnp.zeros(acc_ref.shape, jnp.float32)
        for r in range(r_grp):
            contrib = contrib + jax.lax.dot_general(
                a_refs[r][0],
                b_refs[r][0],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_dot_precision(a_refs[r].dtype),
            )
    contrib = alpha_ref[0, 0] * contrib

    @pl.when(first)
    def _():
        acc_ref[...] = c_ref[0].astype(jnp.float32) + contrib

    @pl.when(jnp.logical_not(first))
    def _():
        acc_ref[...] = acc_ref[...] + contrib

    o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("r_grp", "interpret", "kmerge"),
    donate_argnums=(0,),
)
def _pallas_process(c_data, a_data, b_data, ai2, bi2, ci2, alpha, *, r_grp,
                    interpret, kmerge=False):
    """One launch: ai2/bi2 flat (nsteps*R,), ci2 (nsteps,), all int32.
    With ``kmerge`` the A operand is consumed TRANSPOSED per block
    ((k, m) tiles) so the kernel's k-concatenations stay on the sublane
    axis; the transpose happens here, device-side, once per launch."""
    nsteps = ci2.shape[0]
    m, k = a_data.shape[1:]
    n = b_data.shape[2]
    kmerge = bool(kmerge and r_grp > 1)
    if kmerge:
        a_data = jnp.swapaxes(a_data, 1, 2)  # (N, k, m)
        a_block = (1, k, m)
    else:
        a_block = (1, m, k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nsteps,),
        in_specs=[
            *[
                pl.BlockSpec(a_block, functools.partial(_a_map, r=r, r_grp=r_grp))
                for r in range(r_grp)
            ],
            *[
                pl.BlockSpec((1, k, n), functools.partial(_b_map, r=r, r_grp=r_grp))
                for r in range(r_grp)
            ],
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, m, n), _c_map),
        ],
        out_specs=pl.BlockSpec((1, m, n), _c_map),
        scratch_shapes=[pltpu.VMEM((m, n), jnp.float32)],
    )
    kernel = functools.partial(_smm_kernel, r_grp=r_grp, kmerge=kmerge)
    # operand positions (incl. the 3 scalar-prefetch args):
    # 0..2 = ai2/bi2/ci2, 3..3+2R-1 = A/B, 3+2R = alpha, 3+2R+1 = c_data
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(c_data.shape, c_data.dtype),
        input_output_aliases={3 + 2 * r_grp + 1: 0},
        interpret=interpret,
    )(
        ai2, bi2, ci2,
        *([a_data] * r_grp),
        *([b_data] * r_grp),
        alpha,
        c_data,
    )


# per-launch cap on stack entries (ai+bi+ci int32 must fit the ~1 MB
# SMEM scalar-prefetch budget with headroom); longer stacks are chopped
# into sequential launches — C runs spanning a boundary continue
# correctly because the aliased C block already holds the partial sum
# and the next launch's first-step reload adds to it
_MAX_ENTRIES_PER_LAUNCH = 32768


def process_stack_pallas(
    c_data,
    a_data,
    b_data,
    a_idx: np.ndarray,
    b_idx: np.ndarray,
    c_idx: np.ndarray,
    alpha,
    a_pad_row: int | None = None,
    b_pad_row: int | None = None,
    grouping: int | None = None,
    variant: str | None = None,
):
    """Process a flat stack (host int arrays, sorted by ``c_idx``).

    ``a_pad_row``/``b_pad_row`` must index a zero row of the data
    arrays; when None, a zero row is appended on the fly.  ``grouping``
    forces R (otherwise chosen from the run-length heuristic; the
    caller passes the tuned value from `dbcsr_tpu.acc.params` when one
    exists).  ``variant="kmerge"`` selects the k-merged single-dot
    kernel (one (R*k, m)^T x (R*k, n) MXU dot per step).
    """
    if len(a_idx) == 0:
        return c_data
    if a_pad_row is None:
        a_data = jnp.concatenate([a_data, jnp.zeros((1,) + a_data.shape[1:], a_data.dtype)])
        a_pad_row = a_data.shape[0] - 1
    if b_pad_row is None:
        b_data = jnp.concatenate([b_data, jnp.zeros((1,) + b_data.shape[1:], b_data.dtype)])
        b_pad_row = b_data.shape[0] - 1
    ai2, bi2, ci2, r_grp = build_grouped_stack(
        np.asarray(c_idx), np.asarray(a_idx), np.asarray(b_idx),
        a_pad_row, b_pad_row, grouping=grouping,
    )
    launches = prepare_launches(ai2, bi2, ci2, r_grp, a_pad_row, b_pad_row)
    alpha_arr = jnp.asarray([[alpha]], dtype=jnp.float32)
    interpret = jax.devices()[0].platform != "tpu"
    for a_c, b_c, c_c in launches:
        # Mosaic fails to legalize scalar-prefetch index maps traced under
        # jax_enable_x64 (i64 SMEM index loads); the kernel only touches
        # f32/bf16 data and i32 indices, so trace with x64 off.
        with jax.enable_x64(False):
            c_data = _pallas_process(
                c_data, a_data, b_data,
                jnp.asarray(a_c), jnp.asarray(b_c), jnp.asarray(c_c),
                alpha_arr, r_grp=r_grp, interpret=interpret,
                kmerge=(variant == "kmerge"),
            )
    return c_data


# --------------------------------------------------------------------------
# Cross-packed kernel ("crosspack"): P x R MXU tiling
#
# The looped kernel runs one (m,k)x(k,n) dot per stack entry — a 23x23
# block uses <4% of one 128x128x128 MXU pass.  kmerge packs R entries
# along the CONTRACTION axis (depth R*k).  crosspack adds the spatial
# axes: P independent C-runs are packed side by side, lane p occupying
# rows [p*m, (p+1)*m) / cols [p*n, (p+1)*n) of one big
# (R*k, P*m)^T x (R*k, P*n) -> (P*m, P*n) dot whose BLOCK-DIAGONAL
# holds each lane's k-merged contribution (off-diagonal products are
# discarded — the price of packing, paid in FLOPs the idle MXU had
# anyway).  One pass now advances P*R stack entries (25 at 23^3 vs 1),
# the spatial sibling the round-3 verdict asked for next to kmerge's
# k-packing.  Reference analog: the tile_m/tile_n register-tiling knobs
# of the CUDA kernel families (`kernels/smm_acc_dnt_medium.h` tiling
# parameters) — redesigned around the MXU's fixed 128x128 geometry.
#
# Scheduling: runs (one per C block; the stack arrives sorted) are
# dealt greedily onto P lanes; each lane is the existing one-column
# state machine (f32 VMEM accumulator persisting across a run,
# write-back every step).  Lanes own DISJOINT C blocks, so each lane
# writes its own output array (Pallas multiple-outputs), and the engine
# scatters lane outputs back into c_data afterwards — no atomics, and
# bit-reproducible per-run summation order, like the base kernel.
# --------------------------------------------------------------------------


def choose_pack(m: int, n: int, k: int, max_streams: int = 40):
    """Pick (P, R): spatial lanes P and k-depth R for one MXU pass.

    P*max(m,n) and R*k each aim to fill (not exceed) 128; the stream
    count 2*P*R (+2P for C) is capped so VMEM double-buffers and the
    SMEM prefetch budget stay comfortable."""
    P = max(1, min(8, 128 // max(m, n)))
    R = max(1, min(8, 128 // k))
    while P * R * 2 + 2 * P > max_streams:
        if R >= P and R > 1:
            R -= 1
        elif P > 1:
            P -= 1
        else:
            break
    return P, R


def _deal_lanes(run_steps: np.ndarray, P: int):
    """(lane of each run, grid steps of the longest lane: the launch's
    step count).  Snake-order dealing over steps-descending runs
    (0..P-1, P-1..0, ...), the vectorized stand-in for greedy LPT —
    within one run's steps of perfectly balanced on sorted items, no
    Python loop."""
    nruns = len(run_steps)
    lane_of = np.zeros(nruns, np.int64)
    if P > 1:
        order = np.argsort(-run_steps, kind="stable")
        cyc = np.arange(nruns) % (2 * P)
        lane_of[order] = np.where(cyc < P, cyc, 2 * P - 1 - cyc)
    loads = np.bincount(lane_of, weights=run_steps, minlength=P)
    return lane_of, int(loads.max())


def build_crosspack_stack(c_idx: np.ndarray, a_idx: np.ndarray,
                          b_idx: np.ndarray, a_pad_row: int, b_pad_row: int,
                          P: int, R: int):
    """Deal the (sorted-by-c) stack onto P lanes of R-deep grid steps.

    Returns (ai (nsteps,P,R), bi (nsteps,P,R), cg (nsteps,P) global C
    block ids, cl (nsteps,P) lane-local output slots, lane_c: list of P
    int32 arrays — lane p's global C ids in lane-slot order).  Padded
    slots point at the zero rows / a dummy output slot.
    """
    s_total = len(c_idx)
    if s_total == 0:
        return (np.empty((0, P, R), np.int32), np.empty((0, P, R), np.int32),
                np.empty((0, P), np.int32), np.empty((0, P), np.int32),
                [np.empty(0, np.int32) for _ in range(P)])
    run_first = np.flatnonzero(np.diff(c_idx)) + 1
    run_starts = np.concatenate([[0], run_first])
    run_lens = np.diff(np.concatenate([run_starts, [s_total]]))
    run_steps = -(-run_lens // R)
    nruns = len(run_lens)
    lane_of, nsteps = _deal_lanes(run_steps, P)
    ai = np.full((nsteps, P, R), a_pad_row, np.int32)
    bi = np.full((nsteps, P, R), b_pad_row, np.int32)
    cg = np.zeros((nsteps, P), np.int32)
    cl = np.empty((nsteps, P), np.int32)
    lane_c = []
    run_of = np.repeat(np.arange(nruns), run_lens)
    for p in range(P):
        runs_p = np.flatnonzero(lane_of == p)  # ascending c within lane
        ent = np.flatnonzero(lane_of[run_of] == p)
        if not len(runs_p):
            cl[:, p] = 0
            lane_c.append(np.empty(0, np.int32))
            continue
        # the lane's subset keeps its sort-by-c; reuse the vectorized
        # single-lane step builder
        ai2, bi2, ci2, _ = build_grouped_stack(
            c_idx[ent], a_idx[ent], b_idx[ent], a_pad_row, b_pad_row,
            grouping=R,
        )
        sp = ai2.shape[0]
        ai[:sp, p, :] = ai2
        bi[:sp, p, :] = bi2
        cg[:sp, p] = ci2
        # lane-local slot: rank of each step's run within the lane
        cl[:sp, p] = np.searchsorted(c_idx[run_starts[runs_p]], ci2)
        # pad tail steps -> dummy slot len(runs_p): zero contributions
        # land there and the scatter never reads it
        cl[sp:, p] = len(runs_p)
        lane_c.append(c_idx[run_starts[runs_p]].astype(np.int32))
    return ai, bi, cg, cl, lane_c


def _cp_a_map(s, ai, bi, cg, cl, *, p, r, P, R):
    return (ai[(s * P + p) * R + r], 0, 0)


def _cp_b_map(s, ai, bi, cg, cl, *, p, r, P, R):
    return (bi[(s * P + p) * R + r], 0, 0)


def _cp_cin_map(s, ai, bi, cg, cl, *, p, P):
    return (cg[s * P + p], 0, 0)


def _cp_out_map(s, ai, bi, cg, cl, *, p, P):
    return (cl[s * P + p], 0, 0)


def _crosspack_epilogue(a_cols, b_cols, cl_ref, alpha_ref, c_refs, o_refs,
                        acc_ref, P):
    """Shared tail of both crosspack kernels: the big block-diagonal
    cross dot, per-lane diagonal extraction, run-boundary accumulation
    (first-step detection via cl), and per-lane write-back."""
    s = pl.program_id(0)
    m = a_cols[0].shape[1]
    n = b_cols[0].shape[1]
    a_all = jnp.concatenate(a_cols, axis=1) if P > 1 else a_cols[0]
    b_all = jnp.concatenate(b_cols, axis=1) if P > 1 else b_cols[0]
    full = jax.lax.dot_general(
        a_all, b_all,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_dot_precision(a_all.dtype),
    )
    alpha = alpha_ref[0, 0]
    for p in range(P):
        contrib = alpha * jax.lax.slice(
            full, (p * m, p * n), ((p + 1) * m, (p + 1) * n)
        )
        cur = cl_ref[s * P + p]
        prev = cl_ref[jnp.maximum(s - 1, 0) * P + p]
        first = jnp.logical_or(s == 0, cur != prev)

        @pl.when(first)
        def _(p=p, contrib=contrib):
            acc_ref[p] = c_refs[p][0].astype(jnp.float32) + contrib

        @pl.when(jnp.logical_not(first))
        def _(p=p, contrib=contrib):
            acc_ref[p] = acc_ref[p] + contrib

        o_refs[p][0] = acc_ref[p].astype(o_refs[p].dtype)


def _crosspack_kernel(ai_ref, bi_ref, cg_ref, cl_ref, *refs, P, R):
    a_refs = refs[:P * R]
    b_refs = refs[P * R:2 * P * R]
    alpha_ref = refs[2 * P * R]
    c_refs = refs[2 * P * R + 1:2 * P * R + 1 + P]
    o_refs = refs[2 * P * R + 1 + P:2 * P * R + 1 + 2 * P]
    acc_ref = refs[-1]  # VMEM (P, m, n) f32
    # lane strips: k-concats on the sublane axis (cheap), then the lane
    # concat packs strips side by side on the lane axis
    a_cols = [
        jnp.concatenate([a_refs[p * R + r][0] for r in range(R)], axis=0)
        if R > 1 else a_refs[p * R][0]
        for p in range(P)
    ]
    b_cols = [
        jnp.concatenate([b_refs[p * R + r][0] for r in range(R)], axis=0)
        if R > 1 else b_refs[p * R][0]
        for p in range(P)
    ]
    _crosspack_epilogue(a_cols, b_cols, cl_ref, alpha_ref, c_refs, o_refs,
                        acc_ref, P)


@functools.partial(
    jax.jit,
    static_argnames=("P", "R", "nc_out", "interpret"),
)
def _pallas_crosspack(c_data, a_data_t, b_data, ai, bi, cg, cl, alpha, *,
                      P, R, nc_out, interpret):
    """One crosspack launch.  ``a_data_t`` is (N, k, m) (pre-transposed,
    like kmerge).  ai/bi flat (nsteps*P*R,), cg/cl flat (nsteps*P,).
    Returns a tuple of P lane outputs, each (nc_out, m, n)."""
    nsteps = cg.shape[0] // P
    k, m = a_data_t.shape[1:]
    n = b_data.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nsteps,),
        in_specs=[
            *[
                pl.BlockSpec((1, k, m),
                             functools.partial(_cp_a_map, p=p, r=r, P=P, R=R))
                for p in range(P) for r in range(R)
            ],
            *[
                pl.BlockSpec((1, k, n),
                             functools.partial(_cp_b_map, p=p, r=r, P=P, R=R))
                for p in range(P) for r in range(R)
            ],
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *[
                pl.BlockSpec((1, m, n), functools.partial(_cp_cin_map, p=p, P=P))
                for p in range(P)
            ],
        ],
        out_specs=[
            pl.BlockSpec((1, m, n), functools.partial(_cp_out_map, p=p, P=P))
            for p in range(P)
        ],
        scratch_shapes=[pltpu.VMEM((P, m, n), jnp.float32)],
    )
    kernel = functools.partial(_crosspack_kernel, P=P, R=R)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nc_out, m, n), c_data.dtype)
            for _ in range(P)
        ],
        interpret=interpret,
    )(
        ai, bi, cg, cl,
        *([a_data_t] * (P * R)),
        *([b_data] * (P * R)),
        alpha,
        *([c_data] * P),
    )


def _crosspack_vmem_kernel(ai_ref, bi_ref, cg_ref, cl_ref, a_ref, b_ref,
                           alpha_ref, *refs, P, R):
    """VMEM-resident sibling of `_crosspack_kernel`: the whole
    (transposed-A, B) block arrays live in VMEM and lanes gather their
    blocks IN-KERNEL by dynamic leading-dim indexing — zero per-step
    HBM traffic, the regime where the operands fit on-chip (the
    double-buffered shared-memory residency of the CUDA kernels,
    `smm_acc_dnt_largeDB1.h:147-150`, taken to its TPU limit)."""
    c_refs = refs[:P]
    o_refs = refs[P:2 * P]
    acc_ref = refs[-1]
    s = pl.program_id(0)
    a_cols = [
        jnp.concatenate(
            [a_ref[ai_ref[(s * P + p) * R + r]] for r in range(R)], axis=0
        ) if R > 1 else a_ref[ai_ref[s * P * R + p * R]]
        for p in range(P)
    ]
    b_cols = [
        jnp.concatenate(
            [b_ref[bi_ref[(s * P + p) * R + r]] for r in range(R)], axis=0
        ) if R > 1 else b_ref[bi_ref[s * P * R + p * R]]
        for p in range(P)
    ]
    _crosspack_epilogue(a_cols, b_cols, cl_ref, alpha_ref, c_refs, o_refs,
                        acc_ref, P)


@functools.partial(
    jax.jit,
    static_argnames=("P", "R", "nc_out", "interpret"),
)
def _pallas_crosspack_vmem(c_data, a_data_t, b_data, ai, bi, cg, cl, alpha,
                           *, P, R, nc_out, interpret):
    """One VMEM-resident crosspack launch: operand arrays are whole
    VMEM operands (caller gates on their byte size); per-lane outputs
    as in `_pallas_crosspack`."""
    nsteps = cg.shape[0] // P
    k, m = a_data_t.shape[1:]
    n = b_data.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nsteps,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # whole A (transposed)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # whole B
            pl.BlockSpec(memory_space=pltpu.SMEM),   # alpha
            *[
                pl.BlockSpec((1, m, n), functools.partial(_cp_cin_map, p=p, P=P))
                for p in range(P)
            ],
        ],
        out_specs=[
            pl.BlockSpec((1, m, n), functools.partial(_cp_out_map, p=p, P=P))
            for p in range(P)
        ],
        scratch_shapes=[pltpu.VMEM((P, m, n), jnp.float32)],
    )
    kernel = functools.partial(_crosspack_vmem_kernel, P=P, R=R)
    # Mosaic's default scoped-VMEM limit (16 MiB on a v5e) is far below
    # what two whole-array operands need; ask for what they take plus
    # the default's worth for C blocks, accumulators and index streams
    vmem_limit = (_vmem_tiled_bytes(a_data_t.shape, a_data_t.dtype)
                  + _vmem_tiled_bytes(b_data.shape, b_data.dtype)
                  + (16 << 20))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nc_out, m, n), c_data.dtype)
            for _ in range(P)
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(
        ai, bi, cg, cl,
        a_data_t, b_data,
        alpha,
        *([c_data] * P),
    )


def _vmem_tiled_bytes(shape, dtype) -> int:
    """Bytes an (N, r, c) block array occupies in VMEM: the last two
    dims pad to the dtype's (sublane, 128) tile — (8, 128) for f32,
    (16, 128) for bf16 — so a 23x23 f32 block takes 12 KiB, 5.8x its
    2.1 KB of data."""
    n, r, c = shape
    itemsize = jnp.dtype(dtype).itemsize
    sublane = 8 * max(1, 4 // itemsize)
    return n * (-(-r // sublane) * sublane) * (-(-c // 128) * 128) * itemsize


def supports_vmem_resident(a_data, b_data) -> bool:
    """Whether both whole operand arrays (A as the kernel holds it,
    transposed) fit in three quarters of the chip's VMEM, at their
    TILED size.  The first gate counted raw bytes against "~128 MB" and
    admitted 26 MB of 23x23 f32 blocks that tile to 147 MiB: Mosaic
    refused them on a v5e ("Scoped allocation with size 73.34M and
    limit 16.00M").  Off-TPU the kernel runs interpreted; a v5e's
    128 MiB stands in."""
    if jax.devices()[0].platform == "tpu":
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
    else:
        capacity = 128 << 20
    na, m, k = a_data.shape
    need = (_vmem_tiled_bytes((na, k, m), a_data.dtype)
            + _vmem_tiled_bytes(b_data.shape, b_data.dtype))
    return need <= capacity * 3 // 4


# A v5e core has 1 MiB of scalar memory (`pltpu.get_tpu_info()
# .smem_capacity_bytes`; v4 to v6e have the same), and a crosspack
# launch scalar-prefetches its four index operands into it WHOLE.  A
# launch may spend three quarters of it on them; the rest is headroom
# for alpha's block and Mosaic's own scalars (1.1 KB at (4, 4) by the
# compiler's message) on any chip of that family.
_SMEM_BYTES = 1 << 20
_CROSS_PREFETCH_BUDGET = _SMEM_BYTES * 3 // 4


def crosspack_prefetch_bytes(nsteps: int, P: int, R: int) -> int:
    """SMEM bytes of one crosspack launch's scalar-prefetch operands
    as the chip lays them out: ai and bi (nsteps*P*R,), cg and cl
    (nsteps*P,), each a 1-D int32 array padded to whole 4 KiB
    (compiles for a described v5e: s32[104000] takes u8[417792], and
    (4, 4) at 6460 steps fits where 6480 does not).  Raw entries say
    little: runs of one or two entries dealt onto (P, R) = (4, 4) take
    four to eight slots each."""
    def tiled(n):
        return -(-n // 1024) * 4096

    return 2 * tiled(nsteps * P * R) + 2 * tiled(nsteps * P)


@functools.lru_cache(maxsize=None)
def _crosspack_max_steps(P: int, R: int, budget: int) -> int:
    """Most grid steps a crosspack launch may have: the largest
    `bucket_size` value whose prefetch operands fit ``budget`` (launch
    shapes are bucketed, so the bucket is what the kernel allocates);
    0 if not even the smallest does."""
    from dbcsr_tpu.utils.rounding import bucket_size

    steps, nxt = 0, bucket_size(1)
    while crosspack_prefetch_bytes(nxt, P, R) <= budget:
        steps, nxt = nxt, bucket_size(nxt + 1)
    return steps


def prepare_crosspack_launches(c_idx, a_idx, b_idx, a_pad_row, b_pad_row,
                               P: int, R: int,
                               budget: int = _CROSS_PREFETCH_BUDGET):
    """Chop the stack at RUN boundaries into SMEM-sized crosspack
    launches, then lane-deal each chunk.

    Unlike the base kernel, a C run cannot span launches (lane outputs
    are fresh arrays, so there is no partial sum to reload); chunk
    boundaries therefore always align to run starts.  A chunk is as
    many runs as deal onto P lanes within `_crosspack_max_steps`, so
    what the kernel prefetches (`crosspack_prefetch_bytes` of the
    bucketed step count) fits ``budget``.  Returns a list of launch
    dicts, or None if a single run alone is longer than a launch
    (callers take the base kernel, by plan).
    """
    from dbcsr_tpu.utils.rounding import bucket_size

    s_total = len(c_idx)
    if s_total == 0:
        return []
    run_first = np.flatnonzero(np.diff(c_idx)) + 1
    run_starts = np.concatenate([[0], run_first, [s_total]])
    run_steps = -(-np.diff(run_starts) // R)
    max_steps = _crosspack_max_steps(P, R, budget)
    if run_steps.max() > max_steps:
        return None
    steps_before = np.concatenate([[0], np.cumsum(run_steps)])
    launches = []
    r0, nruns = 0, len(run_steps)
    while r0 < nruns:
        # as many runs as P full lanes hold; dealing leaves the lanes
        # uneven by up to a run, so shrink until the longest lane fits
        room = P * max_steps
        while True:
            r1 = int(np.searchsorted(steps_before, steps_before[r0] + room,
                                     side="right")) - 1
            r1 = max(r1, r0 + 1)
            nsteps = _deal_lanes(run_steps[r0:r1], P)[1]
            if nsteps <= max_steps:
                break
            room = min(room - 1, room * max_steps // nsteps)
        lo, hi = int(run_starts[r0]), int(run_starts[r1])
        ai, bi, cg, cl, lane_c = build_crosspack_stack(
            c_idx[lo:hi], a_idx[lo:hi], b_idx[lo:hi],
            a_pad_row, b_pad_row, P, R,
        )
        cap = bucket_size(nsteps)
        if cap > nsteps:  # pad steps: zero entries into the dummy slot
            pad = cap - nsteps
            ai = np.concatenate([ai, np.full((pad, P, R), a_pad_row, np.int32)])
            bi = np.concatenate([bi, np.full((pad, P, R), b_pad_row, np.int32)])
            cg = np.concatenate([cg, np.zeros((pad, P), np.int32)])
            cl = np.concatenate([cl, np.repeat(cl[-1:], pad, axis=0)])
        # bucketed so the jitted launch shape recurs across patterns
        nc_out = bucket_size(max(len(c) for c in lane_c) + 1)
        launches.append({
            "ai": np.ascontiguousarray(ai.reshape(-1)),
            "bi": np.ascontiguousarray(bi.reshape(-1)),
            "cg": np.ascontiguousarray(cg.reshape(-1)),
            "cl": np.ascontiguousarray(cl.reshape(-1)),
            "lane_c": lane_c,
            "nc_out": nc_out,
            "scatter_idx": lane_scatter_index(lane_c, nc_out),
        })
        r0 = r1
    return launches


def process_stack_crosspack(
    c_data,
    a_data,
    b_data,
    a_idx: np.ndarray,
    b_idx: np.ndarray,
    c_idx: np.ndarray,
    alpha,
    a_pad_row: int | None = None,
    b_pad_row: int | None = None,
    pack: tuple | None = None,
    vmem_resident: bool = False,
    prefetch_budget: int = _CROSS_PREFETCH_BUDGET,
):
    """Cross-packed stack processing (host entry point).

    Semantics match `process_stack_pallas`: stack sorted by c_idx,
    contributions added onto ``c_data``.  ``pack`` forces (P, R).
    ``vmem_resident`` selects the whole-array-in-VMEM gather variant
    (caller responsibility: `supports_vmem_resident`).
    ``prefetch_budget`` is `prepare_crosspack_launches`'s ``budget``.
    Returns updated c_data, or None if the stack is crosspack-ineligible
    (degenerate packing or an over-long run) — callers then use the
    base kernel.
    """
    if len(a_idx) == 0:
        return c_data
    m, k = a_data.shape[1:]
    n = b_data.shape[2]
    P, R = pack or choose_pack(m, n, k)
    if P <= 1:
        return None  # no spatial packing possible; base kernel is equal
    if vmem_resident and not supports_vmem_resident(a_data, b_data):
        return None
    if a_pad_row is None:
        a_data = jnp.concatenate(
            [a_data, jnp.zeros((1,) + a_data.shape[1:], a_data.dtype)])
        a_pad_row = a_data.shape[0] - 1
    if b_pad_row is None:
        b_data = jnp.concatenate(
            [b_data, jnp.zeros((1,) + b_data.shape[1:], b_data.dtype)])
        b_pad_row = b_data.shape[0] - 1
    launches = prepare_crosspack_launches(
        np.asarray(c_idx), np.asarray(a_idx), np.asarray(b_idx),
        a_pad_row, b_pad_row, P, R, budget=prefetch_budget,
    )
    if launches is None:
        return None
    a_data_t = jnp.swapaxes(a_data, 1, 2)
    interpret = jax.devices()[0].platform != "tpu"
    alpha_arr = jnp.asarray([[alpha]], dtype=jnp.float32)
    launch_fn = _pallas_crosspack_vmem if vmem_resident else _pallas_crosspack
    for lc in launches:
        with jax.enable_x64(False):
            outs = launch_fn(
                c_data, a_data_t, b_data,
                jnp.asarray(lc["ai"]), jnp.asarray(lc["bi"]),
                jnp.asarray(lc["cg"]), jnp.asarray(lc["cl"]),
                alpha_arr, P=P, R=R, nc_out=lc["nc_out"],
                interpret=interpret,
            )
        c_data = scatter_lane_outputs(c_data, outs, lc["scatter_idx"])
    return c_data


@functools.partial(jax.jit, donate_argnums=(0,))
def _pallas_cross_scatter(c_data, outs, idx):
    """The crosspack write-back as ONE named device program (the stack
    metrics read modules `jit__pallas*`): lane p's finished C blocks
    ``outs[p]`` (nc_out, m, n) go to rows ``idx[p*nc_out:(p+1)*nc_out]``
    of the donated ``c_data``.  Lanes own disjoint C blocks, so this is
    a plain scatter-set; a slot past a lane's runs (the dummy slot, the
    bucket's tail) carries `_DROP_ROW` and is dropped.  Its shapes are
    the launch's buckets, so it compiles once per (C bin, P, nc_out).
    A change of its scope needs a new function name (`device_scope`)."""
    with device_scope("stk_cross_scatter"):
        vals = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
        return c_data.at[idx].set(vals, mode="drop")


_DROP_ROW = np.iinfo(np.int32).max  # past any C array: never written


def scatter_lane_outputs(c_data, outs, idx):
    """Write each lane's finished C blocks back into the global array
    (``idx``: the launch's ``scatter_idx``, host or device); ``c_data``
    is consumed."""
    return _pallas_cross_scatter(c_data, tuple(outs), jnp.asarray(idx))


def lane_scatter_index(lane_c, nc_out: int) -> np.ndarray:
    """(P*nc_out,) int32 for `scatter_lane_outputs`: the global C id of
    every lane slot in lane order, `_DROP_ROW` where a slot holds no
    run."""
    idx = np.full((len(lane_c), nc_out), _DROP_ROW, np.int32)
    for p, c in enumerate(lane_c):
        idx[p, :len(c)] = c
    return idx.reshape(-1)


def process_launches(c_data, a_data, b_data, launches, alpha_arr, *,
                     r_grp: int, kmerge: bool, interpret: bool):
    """Chain the prepared launches of one base-pallas plan through the
    kernel entry, accumulating into ``c_data`` (operands already carry
    their virtual zero pad row).  This is the ONE launch loop shared by
    `acc.smm._execute_plan` (a top-level dispatch per launch) and the
    fused superstack program, which traces it INSIDE its own jit so a
    whole C bin's launches ride a single dispatch."""
    for dai, dbi, dci in launches:
        c_data = _pallas_process(
            c_data, a_data, b_data, dai, dbi, dci, alpha_arr,
            r_grp=r_grp, interpret=interpret, kmerge=kmerge,
        )
    return c_data


def launch_entries(launches, r_grp: int) -> int:
    """Device-work entry count of prepared launches, INCLUDING the
    grouping and bucket padding slots: what the kernel actually
    gathers and multiplies, as opposed to the stack's true entry
    count.  The difference is the pad overhead the obs layer charges
    to the pallas driver (`dbcsr_tpu_device_entries_total`), so a
    shape whose run lengths group badly shows up as attribution, not
    as mysteriously low achieved GFLOP/s."""
    return sum(len(lc[2]) for lc in launches) * r_grp


def crosspack_launch_entries(cross_launches) -> int:
    """Device-work entry count of prepared crosspack launches (each
    gathered A column is one packed entry slot, padding included)."""
    return sum(int(lc["ai"].size) for lc in cross_launches)


def prepare_launches(ai2, bi2, ci2, r_grp: int, a_pad_row: int, b_pad_row: int):
    """Chop a grouped stack into SMEM-sized launches.

    Returns [(ai_flat (csteps*R,), bi_flat, ci (csteps,)), ...].  Chunk
    boundaries are pulled back to the start of the current C run so a
    block's accumulation stays within one launch (a mid-run split would
    round the f32 accumulator to the output dtype at the boundary —
    harmless for f32, a precision leak for bf16); a single run longer
    than the cap is split anyway.  Step counts are bucketed so jit
    shapes recur; padding steps repeat the chunk's final C block with
    zero-block entries (exact no-ops)."""
    from dbcsr_tpu.utils.rounding import bucket_size

    csteps_max = max(1, _MAX_ENTRIES_PER_LAUNCH // r_grp)
    nsteps_total = ai2.shape[0]
    out = []
    s0 = 0
    while s0 < nsteps_total:
        s1 = min(s0 + csteps_max, nsteps_total)
        if s1 < nsteps_total and ci2[s1 - 1] == ci2[s1]:
            # pull the boundary back to this run's first step
            run_start = s1 - 1
            while run_start > s0 and ci2[run_start - 1] == ci2[s1]:
                run_start -= 1
            if run_start > s0:
                s1 = run_start
        a_c, b_c, c_c = ai2[s0:s1], bi2[s0:s1], ci2[s0:s1]
        cap = bucket_size(a_c.shape[0])
        if cap > a_c.shape[0]:
            pad = cap - a_c.shape[0]
            a_c = np.concatenate([a_c, np.full((pad, r_grp), a_pad_row, np.int32)])
            b_c = np.concatenate([b_c, np.full((pad, r_grp), b_pad_row, np.int32)])
            c_c = np.concatenate([c_c, np.full(pad, c_c[-1], np.int32)])
        out.append((np.ascontiguousarray(a_c.reshape(-1)),
                    np.ascontiguousarray(b_c.reshape(-1)),
                    np.ascontiguousarray(c_c)))
        s0 = s1
    return out
