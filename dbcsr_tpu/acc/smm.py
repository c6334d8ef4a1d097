"""Batched small-matrix-multiply over parameter stacks (the hot kernel).

TPU-native equivalent of `libsmm_acc_process` / `libsmm_acc_transpose` /
`c_calculate_norms` (`src/acc/acc_libsmm.h:38-49`).  A parameter stack
is three int32 arrays of equal length S: for entry s,

    C[c_idx[s]] += alpha * A[a_idx[s]] @ B[b_idx[s]]

where A is a (Na, m, k) device array of same-shape blocks, B is
(Nb, k, n) and C is (Nc, m, n) — one array per block-shape bin (the
reference enumerates block sizes the same way, `dbcsr_mm_common.F:309`).

Key differences from the CUDA design, by intent:

* The reference relies on ``atomicAdd`` into C; TPU wants deterministic
  accumulation, so stacks arrive **sorted by c_idx** and each chunk is
  added into C by one sorted scatter-add, in place: a block's products
  are summed in stack order (bit-reproducible for fixed stack order —
  the "bit-identical checksums" north star) and a chunk touches only
  the blocks it names, never the whole bin (`_accumulate_chunk`).
* The per-(m,n,k) NVRTC JIT cache (`libsmm_acc.cpp:89-224`) becomes the
  XLA jit cache: each (m, n, k, dtype, stack-bucket) specializes once.
* Stack entries are padded up to a size bucket with ``c_idx == Nc``;
  the scatter drops an out-of-range id, giving masked no-op entries
  with static shapes.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dbcsr_tpu.acc import abft as _abft
from dbcsr_tpu.core import mempool as _mempool
from dbcsr_tpu.core.config import get_config
from dbcsr_tpu.core.kinds import real_dtype_of
from dbcsr_tpu.core.timings import device_scope, timed
from dbcsr_tpu.obs import costmodel as _costmodel
from dbcsr_tpu.obs import events as _events
from dbcsr_tpu.obs import flight as _flight
from dbcsr_tpu.obs import metrics as _metrics
from dbcsr_tpu.obs import tracer as _trace
from dbcsr_tpu.resilience import breaker as _breaker
from dbcsr_tpu.resilience import faults as _faults
from dbcsr_tpu.utils.rounding import (bucket_pow2, bucket_pow4, bucket_size,
                                      ceil_div)


def emulated_dtype_on_tpu(dtype) -> bool:
    """True when ``dtype`` is software-EMULATED on the current device
    (f64/c128 on TPU).  The single gate shared by every driver decision
    that exists to counter the emulation penalty (the xla_group default
    here, the form of its dot, `group_dot_form`, and the mesh path's
    `_stack_r0`).  Keys on `effective_platform` so the CPU suite can
    assert the TPU branch (config.platform_override seam).

    What the emulation is: a v5e keeps an f64 as two f32 halves, and its
    compiler expands an f64 ``dot_general`` where the dot stands, in
    five stages: (1) `X64SplitHigh` / `X64SplitLow` of both operands;
    (2) two `while` loops of 8 steps, one an operand, whose body
    (`select_dynamic-update-slice_fusion`, about a hundred elementwise
    ops) cuts every element into 8 f32 slices on an absolute grid of
    8-bit exponent windows (`remainder` by a power of two from the
    exponent, window = exponent >> 3, slice index = window & 7);
    (3) a `while` of 4 steps that pairs neighbouring slices into four
    more strips (a Karatsuba pairing), a `convert` of the slices to bf16
    and a relayout `copy` of B's; (4) a `while` of 16 steps of three
    bf16 `convolution`s each into f32, the only MXU work, accumulated by
    order into eight f32 accumulators; (5) a `while` of 8 steps and two
    fusions that fold the accumulators into the two halves,
    `X64Combine`.  Stages 1-3 are elementwise in the operands: beside a
    dense O(N^3) dot they are nothing, on the strips a stack chunk
    gathers they were two thirds of the dot (PERF.md, PR 35).  To see
    them: compile ``lax.dot_general`` of ``f64[256,23,184]`` by
    ``f64[256,184,23]`` for a described v5e as `tests/
    test_chip_compiles.py` does (`topologies.get_topology_desc("tpu",
    "v5e:2x2")`, ``.lower(...).compile().as_text()``)."""
    from dbcsr_tpu.core.config import effective_platform

    return (
        np.dtype(dtype) in (np.float64, np.complex128)
        and effective_platform() == "tpu"
    )


def _accum_dtype(dtype):
    """Accumulate bf16 in f32; everything else in its own precision."""
    d = jnp.dtype(dtype)
    if d == jnp.bfloat16:
        return jnp.float32
    return d


_BATCH_DOT_DIMS = (((2,), (1,)), ((0,), (0,)))


def _split_hi_lo(x, cdt):
    """Two-product operand split: ``hi = compute(x)`` plus the residue
    ``lo = compute(x - hi)`` — hi recovers the top mantissa bits, lo
    the next compute-width's worth, so hi·hi + hi·lo + lo·hi restores
    the wide product up to O(eps_compute²) (the dropped lo·lo term)."""
    hi = x.astype(cdt)
    lo = (x - hi.astype(x.dtype)).astype(cdt)
    return hi, lo


def _batch_dot(a, b, acc, prec):
    """One batched block contraction at the plan's EXECUTED precision.

    ``prec`` is the `acc.precision` spec (compute_dtype, compensated)
    or None for native.  Native keeps the historical contract (HIGHEST
    precision at the request dtype — f32 runs as true f32 on the MXU,
    bf16 data uses fast bf16 inputs with f32 accumulation via
    preferred_element_type).  Demoted casts the gathered operands to
    the compute dtype IN-KERNEL (the stored panels stay at the request
    dtype — no operand duplication, HBM traffic unchanged) and
    accumulates in ``acc`` (the wide `_accum_dtype`); compensated adds
    the two cross-term dots of the hi/lo split."""
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=_BATCH_DOT_DIMS,
        preferred_element_type=acc, precision=jax.lax.Precision.HIGHEST,
    )
    if prec is None:
        return dot(a, b)
    cdt = jnp.dtype(prec[0])
    if not prec[1]:
        # natural narrow accumulator inside the dot (f32 for f32/bf16
        # inputs), widened AFTER it: a narrow-input dot with a forced
        # wide preferred_element_type abandons the fast GEMM lowering
        # on every backend (measured ~12x on XLA-CPU), which would
        # erase the demotion win; the extra k-deep narrow accumulation
        # is inside the demotion ceiling (eps_compute * k << the x64
        # margin on block-sized k)
        narrow = jnp.promote_types(cdt, jnp.float32)
        out = jax.lax.dot_general(
            a.astype(cdt), b.astype(cdt), _BATCH_DOT_DIMS,
            preferred_element_type=narrow,
            precision=jax.lax.Precision.HIGHEST,
        )
        return out.astype(acc)
    ah, al = _split_hi_lo(a, cdt)
    bh, bl = _split_hi_lo(b, cdt)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


# bf16 slices an emulated f64 is cut into: 8 windows of 8 bits hold the
# 64 bits under an element's leading window, more than its two f32
# halves carry
SLICES = 8
# the deepest strip whose slice-pair dots are exact in f32: a slice is
# at most 2^7 of its grid's units, a product 2^14, 1 024 of them 2^24
SLICED_MAX_DEPTH = 1024
# the fewest groups a chunk of one width class holds where its tiles are
# folded into weight classes (`_fold_classes`): the fold reads them with
# the group index along lanes, which is where the compiler puts the
# tiles of such a chunk for the f64 sums too (for a v5e: chunks of 256
# groups and more); a smaller chunk's f64 sums read the dot's own
# layout, and a fold that relaid its tiles out cost the north star's
# filtered product 5% on a v5e where it saved nothing
SLICED_FOLD_MIN_GROUPS = 256


def sliced_depth(k: int) -> int:
    """The depth a stored block of inner dimension ``k`` brings to a
    sliced strip (`_slice_blocks`): k filled to whole bf16 tiles of 16
    sublanes."""
    return ceil_div(k, 16) * 16


def sliced_fold(depth: int, groups: int) -> int:
    """Slice-pair tiles of one weight class that `_sliced_dot` sums in
    f32 for a chunk of ``groups`` strips of ``depth``: the largest of 8,
    4, 2, 1 whose f tiles hold at most `SLICED_MAX_DEPTH` products of
    2^14 an element, the bound one tile is exact under; 1 (no fold) in
    a chunk of fewer than `SLICED_FOLD_MIN_GROUPS` groups."""
    f = SLICES if groups >= SLICED_FOLD_MIN_GROUPS else 1
    while f > 1 and f * depth > SLICED_MAX_DEPTH:
        f //= 2
    return f


def group_dot_form(dtype, depth: int, prec=None) -> str:
    """How the grouped chunk loop multiplies a group's strips of
    ``depth`` = r0 * k: "sliced" (`_slice_blocks`, `_sliced_dot`) where
    the dtype is real f64 that the device emulates, the plan executes
    it as it is and the depth keeps the slice products exact;
    "compiler" (`_batch_dot`) everywhere else: native dtypes, c128, the
    demoted and compensated precisions.  Decided where a plan is made
    and handed to the programs as a static argument, so that a
    program's form is part of its cache key."""
    if (prec is None and np.dtype(dtype) == np.float64
            and depth <= SLICED_MAX_DEPTH and emulated_dtype_on_tpu(dtype)):
        return "sliced"
    return "compiler"


def sliced_width(r0: int, k: int, dtype, prec=None) -> int:
    """The widest group of a plan whose blocks are ``k`` deep: ``r0``,
    halved while ``r0 * k`` passes `SLICED_MAX_DEPTH` where the dtype
    takes the sliced form (`group_dot_form` at depth ``k``).  Left as it
    is, such a plan would hand every class of the span to the compiler's
    form for its widest group's sake; a span of k = 169 keeps the sliced
    form at width 4.  No block of k * 8 <= 1 024 (every 23-block) is
    touched."""
    if group_dot_form(dtype, k, prec) != "sliced":
        return r0
    while r0 > 1 and r0 * k > SLICED_MAX_DEPTH:
        r0 //= 2
    return r0


def _f32_fixed(v, lead):
    """f32 ``v`` as a signed int64 in units of 2^(8*lead - 56), bits
    under the unit dropped; a subnormal reads 0."""
    u = jax.lax.bitcast_convert_type(v, jnp.uint32)
    eb = ((u >> 23) & 0xFF).astype(jnp.int32)
    sig = jnp.where(eb > 0, (u & 0x7FFFFF) | 0x800000, 0).astype(jnp.int64)
    up = eb - 150 - (8 * lead - 56)  # the significand's unit over ours
    mag = jnp.where(up >= 0, sig << jnp.clip(up, 0, 63),
                    sig >> jnp.clip(-up, 0, 63))
    return jnp.where(u >> 31 != 0, -mag, mag)


def _bf16_slices(data):
    """(N, r, c) f64 blocks cut into (N, SLICES, r, c) bf16 slices that
    sum to the blocks.

    The cut is the one the TPU compiler makes of the operands of an
    emulated-f64 dot (`emulated_dtype_on_tpu`), made once per stored
    block and not once per gathered slot.  An element is the two f32
    halves the device keeps it in, ``hi = f32(x)`` and
    ``lo = f32(x - hi)``.  The grid is absolute: window w holds the bits
    that weigh 2^(8w) to 2^(8w+7).  An element whose hi is under
    2^(8L+6) is written in the `SLICES` windows L, L-1, ... as signed
    digits of -128 to 127, and the digit of window w is slice w mod
    `SLICES`: a ring.  The digits are the bytes of the element as a
    64-bit integer (`_f32_fixed`, under 2^62) plus 0x80 in every byte,
    less 128 each: the one addition takes every carry.  So a slice is
    an integer of 8 bits times a power of two, exactly a bf16; the
    slices of an element sum to hi + lo exactly, since lo ends at most
    53 bits under hi's leading one and the ring holds 55 or more; and
    the elements of one slice index lie on one grid, or 2^64 apart.
    Bits under 2^-120 (windows below -15, where bf16 runs out of
    exponent) are dropped, as the device flushes a subnormal f32; from
    2^126 on, where a product overflows the two halves anyway, the
    slices are not finite."""
    f32 = jnp.float32
    hi = data.astype(f32)
    lo = (data - hi.astype(data.dtype)).astype(f32)
    # a pair the device did not leave normalised would let hi's and
    # lo's bits overlap
    top = hi + lo
    lo = lo - (top - hi)
    hi = top
    eb = (jax.lax.bitcast_convert_type(hi, jnp.uint32) >> 23) & 0xFF
    lead = (eb.astype(jnp.int32) - 125) >> 3
    fixed = _f32_fixed(hi, lead) + _f32_fixed(lo, lead)
    biased = fixed.astype(jnp.uint64) + jnp.uint64(0x8080808080808080)
    slices = []
    for i in range(SLICES):
        below = (lead - i) & (SLICES - 1)  # windows under the leading one
        byte = biased >> (8 * (SLICES - 1 - below)).astype(jnp.uint64)
        digit = (byte & jnp.uint64(0xFF)).astype(jnp.int32) - 128
        w = lead - below
        unit = jax.lax.bitcast_convert_type((8 * w + 127) << 23, f32)
        slices.append(jnp.where(w >= -15, digit.astype(f32) * unit, 0))
    return jnp.stack(slices, axis=1).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=1)
def _slice_blocks(data, depth_axis: int):
    """(N, r, c) f64 blocks as (N, kp, SLICES*o) bf16 blocks of their
    `_bf16_slices`, depth-major: ``depth_axis`` (2 for A's (m, k), 1
    for B's (k, n)) is the dimension a group's dot contracts, filled
    with zero rows to ``kp``, whole bf16 tiles of 16 sublanes; the
    other dimension o lies beside the slice index, for A filled with
    zero rows to whole f32 tiles of 8 (the rows of the product's tiles,
    which `_sliced_dot` adds up as whole tiles).  So the blocks a group
    gathers, set on end, ARE its strip: (w, kp, ..) is (w*kp, ..)
    without a relayout, which the (m, w*k) strip of the compiler's form
    pays per chunk.  Jitted so that a process traces and lowers the cut
    once per operand shape, not once per span of every fused program
    (a chain compiles 22 of eight spans each)."""
    n_blk, r, c = data.shape
    sl = _bf16_slices(data)  # (N, SLICES, r, c)
    if depth_axis == 2:  # A: (N, k, SLICES, m)
        sl = sl.transpose(0, 3, 1, 2)
        sl = jnp.pad(sl, ((0, 0),) * 3 + ((0, ceil_div(r, 8) * 8 - r),))
    else:  # B: (N, k, SLICES, n)
        sl = sl.transpose(0, 2, 1, 3)
    k = sl.shape[1]
    sl = sl.reshape(n_blk, k, -1)
    return jnp.pad(sl, ((0, 0), (0, sliced_depth(k) - k), (0, 0)))


def _halve(x, axis: int):
    """``x`` summed along ``axis`` (a power of two long) as a tree of
    halves: log2 roundings deep where a running sum has one a term."""
    while x.shape[axis] > 1:
        lower, upper = jnp.split(x, 2, axis=axis)
        x = lower + upper
    return jnp.squeeze(x, axis)


def _two_sum(a, b):
    """Two (sum, error) pairs of f32 added: the sums in f32, the
    rounding of that add kept exactly (Knuth's TwoSum) and carried
    with both errors."""
    (s1, e1), (s2, e2) = a, b
    s = s1 + s2
    z = s - s1
    return s, (e1 + e2) + ((s1 - (s - z)) + (s2 - z))


def _fold_classes(tiles, n, f):
    """The (ch, SLICES*mp, SLICES*n) slice-pair tiles of `_sliced_dot`
    as partial class tiles in f32: (ch, SLICES//f, mp, SLICES, n), and
    (ch, mp, n) the rounding errors of the folds (at f = 1 the tiles as
    they are, (ch, SLICES, mp, SLICES*n), and no error term).

    Slice i holds the windows w = i (mod 8) of every element
    (`_bf16_slices`), so tile (i, j) lies on the grid 2^(8s) with
    s = i + j (mod 8): class c is the tiles (i, (c - i) mod 8).  Entry
    [b, :, c] sums the class-c tiles of i = b*f .. b*f + f-1, f*depth
    products of 2^14 a grid: exact where they lie on one grid, while
    `sliced_fold` allows f.  But a class holds, for one pair of
    elements, the product of their leading windows beside products
    2^64 under it, whose bits an f32 sum drops (up to 2^-43 of the
    pair's product, where a single tile holds one product a pair): so
    every add of the fold keeps its rounding (`_two_sum`, one variadic
    reduce), and the errors, each under 2^-40 of its sum, come back
    summed in f32.  The class map is a roll of each A slice's row of
    tiles by i tiles along j, read from j doubled (on a v5e a diagonal
    read through one (i, j) reshape relays the doubled block out twice
    and takes twice the time)."""
    ch, smp, sn = tiles.shape
    mp = smp // SLICES
    if f == 1:  # no two tiles meet in f32: (i, j) order, no error term
        return tiles.reshape(ch, SLICES, mp, sn), None
    t = tiles.reshape(ch, SLICES, mp, SLICES, n)
    t = jnp.concatenate([t, t], axis=3)
    t = jnp.concatenate([t[:, i:i + 1, :, SLICES - i:2 * SLICES - i]
                         for i in range(SLICES)], axis=1)
    t = t.reshape(ch, SLICES // f, f, mp, SLICES, n)
    zero = jnp.float32(0)
    hi, lo = jax.lax.reduce((t, jnp.zeros_like(t)), (zero, zero), _two_sum,
                            (2,))
    return hi, lo.sum(axis=(1, 3))


def _sliced_dot(amat, bmat, m, n, acc):
    """A group's product from its sliced strips, both depth-major
    (`_slice_blocks`): ``amat`` (ch, depth, SLICES*mp) and ``bmat``
    (ch, depth, SLICES*n) in bf16 give all SLICES^2 slice-pair
    products as the (mp, n) tiles of ONE native dot.  Every tile is
    exact in f32 (`SLICED_MAX_DEPTH`; where elements of a strip lie
    2^64 apart the smaller falls under the larger's last bit), and f
    tiles of one weight class are summed in f32 with their roundings
    kept (`_fold_classes`, f = `sliced_fold` of the strip's depth and
    the chunk's groups: 8 up to depth 128).  The 64/f partial class
    tiles are summed in ``acc`` (f64) pair by pair (`_halve`): first
    the partials of a class, then the classes (at f = 1 the A slices,
    then the B slices); the folds' errors are added last."""
    tiles = jax.lax.dot_general(amat, bmat, (((1,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
    hi, lo = _fold_classes(tiles, n, sliced_fold(amat.shape[1],
                                                  amat.shape[0]))
    rows = _halve(hi.astype(acc), 1)
    out = _halve(rows.reshape(rows.shape[0], -1, SLICES, n), 2)
    if lo is not None:
        out = out + lo.astype(acc)
    return out[:, :m]


def _accumulate_chunk(c, prod, c_idx):
    """``c[c_idx[s]] += prod[s]`` for one chunk, in place in the
    loop-carried C: a sorted scatter-add that touches the blocks the
    chunk names and nothing else of the bin.  Products of one block
    are added in stack order (deterministic); an id outside the bin
    (a padded entry, a dead group: ``nseg``) is dropped."""
    with device_scope("stk_accum"):
        return c.at[c_idx].add(prod, indices_are_sorted=True, mode="drop")


def _stack_phases_xla_flat(c_data, a_data, b_data, a_idx, b_idx, c_idx, alpha,
                           prec=None):
    """Flat-gather variant: A/B are re-laid-out once per call to
    (N, m*k) so the per-entry gathers move lane-packed rows instead of
    tile-padded (m, k) blocks — the TPU HBM layout pads the last two
    dims to (sublane, 128) tiles, so gathering a 23x23 block moves ~6x
    its bytes; a 529-lane row moves ~1.2x.  The relayout is paid once
    per multiply, the gather savings S times (S >> N on the hot
    configs).  Toggle: config.flat_gather."""
    _, m, n = c_data.shape
    k = a_data.shape[2]
    with device_scope("stk_gather"):
        a_flat = a_data.reshape(a_data.shape[0], m * k)
        b_flat = b_data.reshape(b_data.shape[0], k * n)

    def body(c, idx):
        ai, bi, ci = idx
        with device_scope("stk_gather"):
            a = jnp.take(a_flat, ai, axis=0).reshape(-1, m, k)
            b = jnp.take(b_flat, bi, axis=0).reshape(-1, k, n)
        acc = _accum_dtype(c.dtype)
        with device_scope("stk_dot"):
            prod = _batch_dot(a, b, acc, prec)
            prod = (alpha.astype(acc) * prod).astype(c.dtype)
        return _accumulate_chunk(c, prod, ci), None

    with device_scope("stk_loop"):
        c_data, _ = jax.lax.scan(body, c_data, (a_idx, b_idx, c_idx))
    return c_data


# dispatch entries: the raw bodies stay callable so the fused
# superstack program can chain them inside ONE jitted program (donation
# is a top-level dispatch property, so the fused program donates
# instead).  ``prec`` (the executed-precision spec) is static: each
# demoted specialization compiles its own program, exactly like the
# reference's per-(m,n,k,dtype) kernel cache gaining a precision axis.
#
# The bodies' `stk_*` phase scopes (`core.timings.device_scope`) are
# what the benchmark reads the device trace by: `stk_pad`, `stk_gather`,
# `stk_dot`, `stk_accum` around the four steps, and `stk_loop` around
# the chunk scan for what the compiler puts at the `while` itself (its
# copies and converts of the loop-carried C and operands); an op counts
# under its innermost scope.  The bodies' names are the XLA modules'
# names (`jit__stack_phases_*`, `jit_fused_superstack`).  The persistent
# compile cache keys a program on its name and its ops but NOT on their
# metadata, where a scope lives: an executable cached before a scope
# was added, moved or renamed would be loaded in the new code's place,
# and every trace of it would read scopeless.  So whenever the scopes of
# one of these four programs change, its function gets a new name (keep
# it under `jit__stack_*` / `jit_fused*`, the benchmark's module
# patterns); do not turn `jax_compilation_cache_include_metadata_in_key`
# on instead, which would recompile everything whenever a line moves.
_process_stack_xla_flat = functools.partial(
    jax.jit, donate_argnums=0, static_argnames=("prec",))(
    _stack_phases_xla_flat)


# the layout `_stack_phases_group` gathers A and B from
GROUP_GATHER_LAYOUT = "row"


def _mnk_label(a_data, b_data) -> str:
    """A span's "mxnxk", as `obs.metrics` spells it in `by_mnk`."""
    return f"{a_data.shape[1]}x{b_data.shape[2]}x{a_data.shape[2]}"


def _note_group_span(plan, a_data, b_data) -> None:
    """Count one launched `xla_group` span by its gather layout, by the
    form of its dot and, sliced, by its classes' folds."""
    note_group_dot(plan.dot_form, _mnk_label(a_data, b_data),
                   [s[1:] for s in _group_idx_shapes(plan)], a_data.shape[2])
    _metrics.counter(
        "dbcsr_tpu_stack_gather_total",
        "xla_group spans launched (per span or inside a fused launch), "
        "by the layout their A/B gathers read: 'row' = whole blocks, "
        "one a row of the array gathered from (lane-dense (N, m*k) rows; "
        "with the sliced dot (N, kp, 8*m) blocks of bf16 slices)",
    ).inc(layout=GROUP_GATHER_LAYOUT)


def note_group_dot(dot_form: str, mnk: str, classes, k: int,
                   driver: str = "xla_group") -> None:
    """Count one launched grouped span (an `xla_group` span through
    `_note_group_span`, or one product's grouped mesh stacks:
    ``driver`` "mesh") by the form of its dot (`group_dot_form`) and
    its block shape ``mnk`` ("5x13x23"); a sliced one's width classes,
    ``classes`` = ((groups a chunk, width), ...), also by the tiles a
    weight class folds in f32 (`sliced_fold` of the strip's depth,
    w * `sliced_depth` of ``k``, and the chunk's groups)."""
    _metrics.counter(
        "dbcsr_tpu_stack_dot_total",
        "grouped spans launched, by their (m,n,k) and by how the chunk "
        "loop multiplies a group's strips: 'sliced' = bf16 slices cut "
        "once per stored block and one native dot a width class "
        "(emulated f64), 'compiler' = the compiler's dot of the "
        "gathered strips",
    ).inc(form=dot_form, mnk=mnk)
    if dot_form == "sliced":
        folds = _metrics.counter(
            "dbcsr_tpu_sliced_fold_total",
            "width classes of the sliced grouped spans launched (per "
            "span or inside a fused launch; once a product for a mesh "
            "plan's grouped stacks), by the slice-pair tiles of one "
            "weight class their dot sums in f32: 8, 4, 2 or 1",
        )
        for groups, w in classes:
            folds.inc(fold=str(sliced_fold(w * sliced_depth(k), groups)))
    from dbcsr_tpu.core import stats

    stats.record_group_dot(dot_form, driver=driver)


def _note_group_slots(tiles: "GroupTiles", driver: str = "xla_group") -> None:
    """Count what one planned `xla_group` span, or one mesh plan's
    grouped stacks (``driver`` "mesh"), launches: the slots that hold a
    stack entry against the slots of the chunks its loops run (fill =
    live / launched; the rest gathers the zero pad row)."""
    slots = _metrics.counter(
        "dbcsr_tpu_stack_slots_total",
        "slots of the xla_group spans and grouped mesh stacks planned: "
        "'live' hold a stack entry, 'launched' are gathered and "
        "multiplied (the live chunks of every width class, pad rows "
        "included)",
    )
    slots.inc(tiles.entries, kind="live")
    slots.inc(tiles.slots_launched, kind="launched")
    from dbcsr_tpu.core import stats

    stats.record_group_tiles(tiles.widths, tiles.groups, tiles.entries,
                             tiles.slots_launched, driver=driver)


def _group_idx_shapes(plan) -> tuple:
    """The shapes that key an `xla_group` plan's program: the gather
    ids of every width class."""
    return tuple(x.shape for x in plan.group_idx[1::3])


def _block_rows(data):
    """(N, r, c) blocks as (N, r*c) rows, one block per row.  A TPU
    keeps a bin of small blocks with the block index along lanes
    (`f32[N,23,23]{0,2,1}`), where gathering blocks fetches every
    element of every block on its own; a row is lane-dense, so a
    gather moves a block as a few whole lane pieces.  A temporary of
    the program that makes it, once per launch."""
    return data.reshape(data.shape[0], -1)


def _take_rows(rows, ids):
    """``rows[ids]`` for ids the plan guarantees in range
    (`build_group_tiles`): no bounds compare, no fill select."""
    return rows.at[ids].get(mode="promise_in_bounds")


def group_chunk_loop(c, a, b, live, tiles, alpha=None, prec=None,
                     dot_form="compiler"):
    """The grouped chunk loop, the one body behind `xla_group` on one
    chip and the mesh engine's ticks (`parallel/sparse_dist.py`):
    ``c[gc] += alpha * A-strip @ B-strip`` for every group the tiles
    name, into the carried ``c`` (its own accumulate dtype's panel or
    bin), and nothing else of ``c`` touched.

    ``a`` (Na, m, k) and ``b`` (Nb, k, n) are block arrays whose rows
    the tiles' ids name; every id lies inside them and the pad ids name
    an all-zero block (`build_group_tiles`).  ``tiles`` holds one
    ``(ga, gb, gc)`` triple per width class, widest first: ``ga``/``gb``
    (nchunks, CH_w, w) gather ids, ``gc`` (nchunks, CH_w) C rows, an id
    past ``c`` for a dead group (dropped).  ``live`` is the number of
    chunks that hold a group, a device scalar: the arrays' extent is
    bucketed (the program's shapes hold still while the pattern moves)
    and the chunks past ``live`` are never read.  ``alpha`` None leaves
    the products unscaled (the mesh scales its finished panel).
    ``dot_form`` is the plan's `group_dot_form`.

    Once per call A and B become `_block_rows`, or with the sliced form
    `_slice_blocks` (under `stk_dot/stk_split`: what the dot costs); then
    ONE loop carries ``c`` whatever the number of classes; a step
    gathers, multiplies and adds chunk t of every class, widest first,
    under the `stk_gather` / `stk_dot` / `stk_accum` scopes, the loop
    itself under `stk_loop`.  `_stack_phases_group` says why each
    form is what it is."""
    live = jnp.reshape(live, ())
    _, m, n = c.shape
    k = a.shape[2]
    acc = _accum_dtype(c.dtype)
    if dot_form == "sliced":
        with device_scope("stk_dot"), device_scope("stk_split"):
            a_rows = _slice_blocks(a, 2)
            b_rows = _slice_blocks(b, 1)

        def strips(ia, ib):
            ch, w = ia.shape
            ablk = _take_rows(a_rows, ia.reshape(-1))
            bblk = _take_rows(b_rows, ib.reshape(-1))
            return (ablk.reshape((ch, w * ablk.shape[1], -1)),
                    bblk.reshape((ch, w * bblk.shape[1], -1)))

        def dot(amat, bmat):
            return _sliced_dot(amat, bmat, m, n, acc)
    else:
        with device_scope("stk_gather"):
            a_rows = _block_rows(a)
            b_rows = _block_rows(b)

        def strips(ia, ib):
            ch, w = ia.shape
            ablk = _take_rows(a_rows, ia.reshape(-1)).reshape(ch, w, m, k)
            bblk = _take_rows(b_rows, ib.reshape(-1))
            amat = jnp.swapaxes(ablk, 1, 2).reshape(ch, m, w * k)
            bmat = bblk.reshape(ch, w * k, n)
            ragged = -(w * k) % 8
            if ragged:  # zeros up to whole sublanes
                amat = jnp.pad(amat, ((0, 0), (0, 0), (0, ragged)))
                bmat = jnp.pad(bmat, ((0, 0), (0, ragged), (0, 0)))
            return amat, bmat

        def dot(amat, bmat):
            return _batch_dot(amat, bmat, acc, prec)

    def body(t, c):
        for ga, gb, gc in tiles:
            with device_scope("stk_gather"):
                ia = jax.lax.dynamic_index_in_dim(ga, t, keepdims=False)
                ib = jax.lax.dynamic_index_in_dim(gb, t, keepdims=False)
                ic = jax.lax.dynamic_index_in_dim(gc, t, keepdims=False)
                amat, bmat = strips(ia, ib)
            with device_scope("stk_dot"):
                prod = dot(amat, bmat)
                if alpha is not None:
                    prod = alpha.astype(acc) * prod
                prod = prod.astype(c.dtype)
            c = _accumulate_chunk(c, prod, ic)
        return c

    with device_scope("stk_loop"):
        return jax.lax.fori_loop(0, live, body, c)


def _stack_phases_group(c_data, a_data, b_data, live, *tiles_alpha,
                        prec=None, dot_form="compiler"):
    """R-tiled ("k-merged") stack layout: entries sharing a C block are
    tiled into groups; each group's A blocks concatenate along k into
    one (m, w*k) strip, its B blocks into (w*k, n), and the whole group
    contracts in ONE dot — k grows w-fold, and a chunk's scatter-add
    into C takes one update per group, not per entry.

    This is the f64 answer to the MXU-utilization problem the reference
    solves with kernel `grouping` (`smm_acc_dnt_*.h`: one thread block
    processes `grouping` stack entries): on TPU, f64 is emulated
    (`emulated_dtype_on_tpu` lists the stages), and with the compiler's
    dot per-entry 23^3 products ran at 1.6 GFLOP/s, groups of R0 = 8 at
    6.3 (the tuner at S=100000 on a v5e, whose synthetic stack has runs
    of mean 8 entries a C block; PERF.md, PR 21).  Since PR 35 a real
    f64 span takes the sliced form (``dot_form``, `group_dot_form`):
    the operands are cut into bf16 slices once per stored block and a
    group is one native dot, where the compiler cut every gathered
    strip anew, 58 times a block in the north star's product.

    ``tiles_alpha`` is what `build_group_tiles` planned, flattened, and
    alpha; ``live`` the plan's live chunk count; the loop is
    `group_chunk_loop`, which the mesh engine's ticks run too (PR 33).
    What follows of strips, depths and block rows is the compiler's
    form (every dtype but emulated real f64); the sliced form's are in
    `_slice_blocks` and `_sliced_dot`.  A C block's groups all lie
    in one class in stack order, so its products are added in stack
    order, full groups first and the remainder of its run last
    (deterministic: the order is the plan's).  A strip whose depth w*k
    is no whole number of sublanes is filled up with zero columns: on a
    v5e the emulated-f64 dot of a ragged depth (92, 138, 276 at k = 23)
    takes 2.4-4.2x the time of depth 184 for the same entries, the zero
    columns change no bit (PERF.md, PR 31).  A and B are gathered as
    whole block rows (`_block_rows`) in the host's (group, slot) order;
    on a v5e that is 0.18 s of a filtered f64 north-star product where
    the element gather along the bins' slot-minor layout was 1.58
    (PERF.md, PR 29).  Per chunk the body touches C only through
    `_accumulate_chunk`: on a TPU the carried bin is tile-padded
    (2.4 GB for each f32 half of the north star's emulated f64), so one
    more pass over it per chunk costs 11 ms, and what the compiler puts
    at a `while` over it 0.3 s a loop (`tests/test_chip_compiles.py`
    holds the compiler to one loop and to the scatter-adds).
    """
    *flat, alpha = tiles_alpha
    tiles = [flat[i:i + 3] for i in range(0, len(flat), 3)]
    return group_chunk_loop(c_data, a_data, b_data, live, tiles, alpha, prec,
                            dot_form)


_process_stack_xla_group = functools.partial(
    jax.jit, donate_argnums=0, static_argnames=("prec", "dot_form"))(
    _stack_phases_group)


# a narrower width class is opened only where it takes this share of
# the slots the plan would launch without it
GROUP_CLASS_MIN_SAVING = 0.05
# operand bytes (A and B blocks, as values) one chunk of the grouped
# loop gathers: 2 048 slots at 23^3 in f64, where a v5e runs the north
# star's span in 0.95 s against 1.60 s at `mm_stack_size` = 30 000
# slots a chunk and 1.01 s at 1 536 (PERF.md, PR 31); never more slots
# than `mm_stack_size`
GROUP_CHUNK_BYTES = 2048 * 2 * 23 * 23 * 8


def group_chunk_groups(r0: int, m: int, n: int, k: int, itemsize: int,
                       stack_size: int) -> int:
    """Groups of ``r0`` the slots of one chunk come to."""
    slots = min(stack_size, GROUP_CHUNK_BYTES // ((m + n) * k * itemsize))
    return max(16, slots // r0)


class GroupTiles(NamedTuple):
    """What `build_group_tiles` plans for one stack, or
    `build_stacks_group_tiles` for several of one shape (then ``live``
    is an array, one count a stack, and every tile array has the
    stacks as its leading dimension)."""

    live: object   # chunks that hold a group; past it: bucket slack
    tiles: tuple   # per width class, widest first: (ga, gb, gc) host arrays
    groups: tuple  # per class: groups that hold an entry
    entries: int   # stack entries = slots that hold one

    @property
    def widths(self) -> tuple:
        return tuple(ga.shape[-1] for ga, _, _ in self.tiles)

    @property
    def slots_launched(self) -> int:
        """Slots of the chunks the loops run: what is gathered and
        multiplied, live or pad row."""
        return int(np.sum(self.live)) * sum(ga.shape[-2] * ga.shape[-1]
                                            for ga, _, _ in self.tiles)

    def flat(self) -> list:
        return [x for tile in self.tiles for x in tile]


def _narrowest_fit(widths, r0: int):
    """``fit[l]``: the narrowest of ``widths`` (r0 among them) that
    holds a run of l <= r0 entries; 0 for the empty run."""
    ws = np.sort(np.asarray(widths))
    fit = ws[np.searchsorted(ws, np.arange(r0 + 1))]
    fit[0] = 0
    return fit


def _group_widths(short_hist, other_slots: int, r0: int) -> list:
    """The width classes of a stack, widest first, from the histogram
    of its runs of at most ``r0`` entries (``short_hist[l]`` runs of l;
    longer runs launch ``other_slots`` whatever the classes).  Each
    such run is one group of the narrowest class that holds it.
    Candidates are r0 halved down to 1 (on a v5e a class of 3/4 r0
    costs more than its slots save: PERF.md, PR 31); the one that saves
    most is opened while it takes `GROUP_CLASS_MIN_SAVING` of the slots
    launched without it, so runs that fill r0 keep the single width."""
    def slots(widths):
        return other_slots + int((short_hist * _narrowest_fit(widths,
                                                              r0)).sum())

    widths = [r0]
    candidates = {r0 >> i for i in range(1, r0.bit_length())}
    now = slots(widths)
    while candidates:
        best = min(candidates, key=lambda w: (slots(widths + [w]), w))
        with_best = slots(widths + [best])
        if now - with_best < GROUP_CLASS_MIN_SAVING * now:
            break
        widths.append(best)
        candidates.discard(best)
        now = with_best
    return sorted(widths, reverse=True)


def _class_chunk_caps(counts, widths, r0: int, chunk_groups: int,
                      round_up=bucket_size) -> list:
    """``CH_w``, the groups one chunk holds of each width class, from
    the groups the fullest stack has of each (``counts``).  It follows
    the class's share of the slots in coarse steps and not its count,
    so a pattern that grows keeps its shapes and takes more chunks
    (``chunk_groups`` x r0 slots a chunk; a stack that fills no chunk
    gets one of its own size, rounded up by ``round_up``)."""
    slots = sum(cnt * w for cnt, w in zip(counts, widths))
    if slots <= chunk_groups * r0:
        # one chunk holds the stack: its size is the stack's, bucketed
        return [round_up(cnt) for cnt in counts]
    if tuple(widths) == (r0,):
        return [chunk_groups]
    step = max(1, 1 << max((chunk_groups // 16).bit_length() - 1, 0))
    per_chunk = chunk_groups * r0 / slots
    return [-(-int(np.ceil(cnt * per_chunk)) // step) * step
            for cnt in counts]


def build_stacks_group_tiles(stack_of, nstacks: int, c_idx, a_idx, b_idx,
                             r0: int, a_pad: int, b_pad: int, c_pad: int,
                             chunk_groups: int,
                             moving: bool = False) -> GroupTiles:
    """Host side of the grouped layout, for ``nstacks`` stacks that one
    program runs (one stack on one chip; one a (device, tick) on a
    mesh, where an SPMD program needs the same shapes everywhere):
    tile each C segment's run of entries into groups and the groups
    into the chunks of `group_chunk_loop`, so that what is launched is
    what holds entries.  Entry e belongs to stack ``stack_of[e]``; the
    entries come sorted by (stack, C segment).

    A run of more than ``r0`` entries (the tuned width: 6.3 GFLOP/s at
    r0 = 8 on a v5e, PR 21, on runs of mean 8) is cut into groups of r0,
    the last one filled up with zero-row ids.  A run of at most r0 is
    ONE group of the narrowest width class that holds it.  The classes
    come from the run lengths of all the stacks together
    (`_group_widths`): runs that fill r0 give one class; the north
    star's runs of mean 4.4 give three (8, 4, 2) and launch 0.65 of the
    slots; half its k range a tick, as on the 2x2 grid, gives runs of
    mean 2.5 and a fourth class, 1.  All groups of a C block lie in one
    class, in stack order.

    Each class holds a stack's groups sorted by C block, cut into
    chunks of ``CH_w`` groups (`_class_chunk_caps`, from the fullest
    stack's counts); chunk t of every class is one step of the body's
    loop.  Per class the result holds (nstacks, nchunks, CH_w, w) a/b
    gather arrays and (nstacks, nchunks, CH_w) segment ids, nchunks the
    bucketed count of chunks the fullest stack takes, and ``live``
    (nstacks,) the chunks of each stack that hold a group: 0 for a
    stack with no entry, whose loop runs no step.  Every a/b id lies in
    ``[0, a_pad]`` / ``[0, b_pad]`` where the entries' do: the body's
    gathers promise the compiler that and check nothing.  Dead groups
    carry segment id ``c_pad`` (= nseg) after the live ones, keeping
    ids sorted and dropped by the scatter-add.

    ``moving``: the stacks' counts move from call to call by more than
    the buckets' 25% (a tensor's batches, `prepare_stack`), and the
    shapes must not follow them: one width class, ``r0``, a chunk of
    one of a few sizes (powers of two) and nchunks a power of four
    (the chunks past ``live`` cost index memory only, never a step)."""
    s = len(c_idx)
    if s == 0:  # no stack runs a step: one dead chunk of the widest class
        def dead(pad, *shape):
            return np.full((nstacks, 1, bucket_size(1)) + shape, pad, np.int32)
        return GroupTiles(np.zeros(nstacks, np.int64),
                          ((dead(a_pad, r0), dead(b_pad, r0), dead(c_pad)),),
                          (0,), 0)
    new_run = np.ones(s, bool)
    new_run[1:] = (c_idx[1:] != c_idx[:-1]) | (stack_of[1:] != stack_of[:-1])
    seg_starts = np.nonzero(new_run)[0]
    seg_len = np.diff(np.append(seg_starts, s))
    run_groups = -(-seg_len // r0)
    short = run_groups == 1
    widths = [r0] if moving else _group_widths(
        np.bincount(seg_len[short], minlength=r0 + 1),
        int(run_groups[~short].sum()) * r0, r0)
    run_width = np.where(
        short, _narrowest_fit(widths, r0)[np.minimum(seg_len, r0)], r0)
    # groups in (stack, stack order); a group's row among its class's
    group_base = np.cumsum(run_groups) - run_groups
    width_of = np.repeat(run_width, run_groups)
    c_of = np.repeat(c_idx[seg_starts], run_groups)
    stack_of_group = np.repeat(stack_of[seg_starts], run_groups)
    members = [np.nonzero(width_of == w)[0] for w in widths]
    widths, members = zip(*[(w, mem) for w, mem in zip(widths, members)
                            if len(mem)])  # r0 itself may hold nothing
    per_stack = [np.bincount(stack_of_group[mem], minlength=nstacks)
                 for mem in members]
    counts = [len(mem) for mem in members]
    caps = _class_chunk_caps([int(n.max()) for n in per_stack], widths, r0,
                             chunk_groups,
                             bucket_pow2 if moving else bucket_size)
    live = np.max([-(-n // cap) for n, cap in zip(per_stack, caps)], axis=0)
    nchunks = (bucket_pow4 if moving else bucket_size)(int(live.max()),
                                                       minimum=1)
    # every class's rows in one buffer, so the ids are written once
    rows = [nchunks * cap for cap in caps]  # of one stack
    row_base = np.cumsum([0] + [nstacks * r for r in rows])
    slot_base = np.cumsum([0] + [nstacks * r * w
                                 for r, w in zip(rows, widths)])
    row_of = np.empty(len(width_of), np.int64)   # a group's row among all
    slot0_of = np.empty(len(width_of), np.int64)  # its first slot among all
    for i, (w, mem, n) in enumerate(zip(widths, members, per_stack)):
        stk = stack_of_group[mem]
        row = stk * rows[i] + np.arange(len(mem)) - (np.cumsum(n) - n)[stk]
        row_of[mem] = row_base[i] + row
        slot0_of[mem] = slot_base[i] + row * w
    # a run's groups are rows on end of one class and stack, so its
    # entries fill consecutive slots from its first group's first
    dest = np.arange(s) + np.repeat(slot0_of[group_base] - seg_starts,
                                    seg_len)
    ga = np.full(slot_base[-1], a_pad, np.int32)
    gb = np.full(slot_base[-1], b_pad, np.int32)
    gc = np.full(row_base[-1], c_pad, np.int32)
    ga[dest] = a_idx
    gb[dest] = b_idx
    gc[row_of] = c_of
    tiles = [
        (ga[slot_base[i]:slot_base[i + 1]].reshape(nstacks, nchunks, cap, w),
         gb[slot_base[i]:slot_base[i + 1]].reshape(nstacks, nchunks, cap, w),
         gc[row_base[i]:row_base[i + 1]].reshape(nstacks, nchunks, cap))
        for i, (w, cap) in enumerate(zip(widths, caps))]
    return GroupTiles(live, tuple(tiles), tuple(counts), s)


def build_group_tiles(c_idx, a_idx, b_idx, r0: int, a_pad: int, b_pad: int,
                      c_pad: int, chunk_groups: int,
                      moving: bool = False) -> GroupTiles:
    """`build_stacks_group_tiles` for ONE stack (``c_idx`` sorted
    ascending): (nchunks, CH_w, w) / (nchunks, CH_w) arrays per class
    and ``live`` a number.  ``moving``: as `build_stacks_group_tiles`
    takes it."""
    many = build_stacks_group_tiles(
        np.zeros(len(c_idx), np.int32), 1, c_idx, a_idx, b_idx, r0,
        a_pad, b_pad, c_pad, chunk_groups, moving=moving)
    return GroupTiles(int(many.live[0]),
                      tuple(tuple(x[0] for x in tile) for tile in many.tiles),
                      many.groups, many.entries)


def _stack_phases_xla(c_data, a_data, b_data, a_idx, b_idx, c_idx, alpha,
                      prec=None):
    """Process a whole stack in one device program.

    The chunk loop lives INSIDE jit as a `lax.scan` over (nchunks, L)
    index arrays — the TPU-native replacement for the reference's
    stream-cycled stack buffers (`dbcsr_mm_accdrv.F:279-326`): one
    dispatch and one compilation per (m,n,k,bucket) instead of a Python
    loop of per-chunk launches.  Entries padded with c_idx == Nc are
    dropped by the scatter-add.
    """
    def body(c, idx):
        ai, bi, ci = idx
        with device_scope("stk_gather"):
            a = jnp.take(a_data, ai, axis=0)
            b = jnp.take(b_data, bi, axis=0)
        acc = _accum_dtype(c.dtype)
        with device_scope("stk_dot"):
            prod = _batch_dot(a, b, acc, prec)
            prod = (alpha.astype(acc) * prod).astype(c.dtype)
        return _accumulate_chunk(c, prod, ci), None

    with device_scope("stk_loop"):
        c_data, _ = jax.lax.scan(body, c_data, (a_idx, b_idx, c_idx))
    return c_data


_process_stack_xla = functools.partial(
    jax.jit, donate_argnums=0, static_argnames=("prec",))(
    _stack_phases_xla)


def _append_pad_row(data):
    """Append the virtual guaranteed-zero row plans index one past the
    end of a data array (`append_a_pad`/`append_b_pad`) — the ONE
    definition of the pad convention shared by every per-span driver
    branch and the fused superstack program (they must agree bitwise)."""
    with device_scope("stk_pad"):
        return jnp.concatenate(
            [data, jnp.zeros((1,) + data.shape[1:], data.dtype)])


def pad_stack(a_idx, b_idx, c_idx, target_len: int, drop_segment: int):
    """Pad int32 stack arrays to ``target_len`` with masked no-op entries."""
    s = len(a_idx)
    if s == target_len:
        return (
            np.ascontiguousarray(a_idx, np.int32),
            np.ascontiguousarray(b_idx, np.int32),
            np.ascontiguousarray(c_idx, np.int32),
        )
    pad = target_len - s
    return (
        np.concatenate([a_idx, np.zeros(pad, np.int32)]).astype(np.int32),
        np.concatenate([b_idx, np.zeros(pad, np.int32)]).astype(np.int32),
        np.concatenate([c_idx, np.full(pad, drop_segment, np.int32)]).astype(np.int32),
    )


# (m, n, k, dtype) combos whose Pallas kernel passed first-use
# validation (ref: libsmm_acc's per-kernel JIT-time checksum gate,
# `libsmm_acc.cpp:81-85,216` — hard exit on mismatch)
_validated_kernels: set = set()
_VALIDATE_MAX_ENTRIES = 512


class KernelValidationError(RuntimeError):
    """A device kernel produced results that differ from the host oracle."""


def _validate_pallas_kernel(c_data, a_data, b_data, a_idx, b_idx, c_idx,
                            a_pad_row, b_pad_row, grouping,
                            variant=None, pack=None) -> None:
    """First-use validation of the Pallas kernel for this shape/dtype.

    Runs a prefix of the actual stack (still sorted by c_idx) on a
    zeroed C through the Pallas path and through a NumPy host oracle,
    and hard-fails on mismatch — like `validate_kernel` in
    `libsmm_acc.cpp:216` (checksum vs CPU, exit(1) at :81-85).
    """
    from dbcsr_tpu.acc.pallas_smm import (
        process_stack_crosspack,
        process_stack_pallas,
    )

    s = min(len(a_idx), _VALIDATE_MAX_ENTRIES)
    ai = np.asarray(a_idx[:s], np.int32)
    bi = np.asarray(b_idx[:s], np.int32)
    ci = np.asarray(c_idx[:s], np.int32)
    c0 = jnp.zeros_like(c_data)
    if variant in ("crosspack", "crosspack_vmem"):
        got = process_stack_crosspack(
            c0, a_data, b_data, ai, bi, ci, 1.0,
            a_pad_row=a_pad_row, b_pad_row=b_pad_row, pack=pack,
            vmem_resident=(variant == "crosspack_vmem"),
        )
        if got is None:  # prefix ineligible: nothing to validate against
            raise KernelValidationError(
                "crosspack validation prefix was ineligible for the "
                "crosspack kernel; refusing to run it unvalidated"
            )
    else:
        got = process_stack_pallas(
            c0, a_data, b_data, ai, bi, ci, 1.0,
            a_pad_row=a_pad_row, b_pad_row=b_pad_row, grouping=grouping,
            variant=variant,
        )
    a_h = np.asarray(a_data[ai]).astype(np.float64)
    b_h = np.asarray(b_data[bi]).astype(np.float64)
    ref = np.zeros(c_data.shape, np.float64)
    np.add.at(ref, ci, np.einsum("smk,skn->smn", a_h, b_h))
    scale = max(np.max(np.abs(ref)), 1.0)
    # compare ON DEVICE, fetch one scalar: this gate runs in the
    # production path, and C can be gigabytes
    cmp_dtype = (jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
    err = float(
        jnp.max(jnp.abs(got.astype(cmp_dtype) - jnp.asarray(ref, cmp_dtype)))
    ) / scale
    # dtype-aware tolerance shared with the runtime ABFT ceilings and
    # the test suite's oracle comparisons — one source of truth
    # (obs.costmodel) instead of the historical 5e-2/1e-5 literals
    depth = int(np.bincount(ci.astype(np.int64)).max()) if s else 1
    tol = _costmodel.kernel_validation_tolerance(
        str(jnp.dtype(got.dtype)), a_data.shape[2], depth)
    if not np.isfinite(err) or err > tol:
        m, k = a_data.shape[1:]
        n = b_data.shape[2]
        raise KernelValidationError(
            f"pallas SMM kernel validation failed for "
            f"(m={m}, n={n}, k={k}, dtype={c_data.dtype}): "
            f"relative error {err:.3e} > {tol:.0e} vs host oracle"
        )


class StackPlan:
    """A prepared stack: device-resident index arrays + the driver
    decision, reusable across multiplies that share sparsity patterns
    (the index arrays depend only on the patterns, not the values).
    Built by `prepare_stack`, run by `execute_stack`."""

    __slots__ = ("driver", "nseg", "xla_idx", "launches", "r_grp",
                 "group_classes", "group_launched", "a_pad_row",
                 "b_pad_row", "append_a_pad", "append_b_pad", "val_idx",
                 "group_idx", "kmerge", "pack", "cross_launches",
                 "cross_vmem", "cross_src", "host_idx", "src_idx",
                 "src_pads", "precision", "dot_form", "entries")

    def __init__(self):
        self.driver = "xla"
        self.nseg = 0
        self.entries = 0         # true stack entries (the stack's length)
        self.xla_idx = None      # (ai, bi, ci) device (nchunks, chunk)
        self.launches = None     # pallas: [(ai_flat, bi_flat, ci) device]
        self.r_grp = 1
        self.group_classes = ()  # xla_group: ((width, live groups), ...)
        self.group_launched = 0  # xla_group: slots its live chunks launch
        self.dot_form = "compiler"  # xla_group: `group_dot_form`
        self.a_pad_row = None
        self.b_pad_row = None
        self.append_a_pad = False  # pallas/group: append a zero row at execute
        self.append_b_pad = False
        self.val_idx = None      # host prefix for first-use validation
        self.group_idx = None    # xla_group: device (live chunks, then
                                 # ga, gb, gc per width class)
        self.kmerge = False      # pallas: k-merged MXU dot variant
        self.pack = None         # pallas_cross: (P, R) MXU packing
        self.cross_launches = None  # pallas_cross: launch dicts
        self.cross_vmem = False  # pallas_cross: whole-array VMEM variant
        self.cross_src = None    # pallas_cross: host (ai, bi, ci) for
                                 # the compile-failure demotion rebuild
        self.host_idx = None     # host: numpy (ai, bi, ci) for the
                                 # native C++ stack driver
        self.src_idx = None      # host (ai, bi, ci) retained for the
                                 # breaker failover rebuild (any driver)
        self.src_pads = (None, None)  # the (a_pad_row, b_pad_row)
                                 # prepare_stack was originally given
        self.precision = None    # executed-precision spec
                                 # (compute_dtype, compensated) from
                                 # acc.precision.resolve; None = native

    def nbytes(self) -> int:
        """Approximate device bytes pinned by this plan (cache budget)."""
        total = 0
        if self.xla_idx is not None:
            total += sum(int(x.size) * 4 for x in self.xla_idx)
        if self.group_idx is not None:
            total += sum(int(x.size) * 4 for x in self.group_idx)
        if self.launches is not None:
            for lc in self.launches:
                total += sum(int(x.size) * 4 for x in lc)
        if self.cross_launches is not None:
            for lc in self.cross_launches:
                total += sum(
                    int(lc[key].size) * 4
                    for key in ("ai", "bi", "cg", "cl", "scatter_idx")
                )
        if self.cross_src is not None:  # host bytes, freed on first success
            total += sum(int(x.nbytes) for x in self.cross_src)
        if self.host_idx is not None:  # host bytes
            total += sum(int(x.nbytes) for x in self.host_idx)
        if self.src_idx is not None:  # host bytes (failover payload)
            total += sum(int(x.nbytes) for x in self.src_idx)
        return total


def _note_driver(driver: str, why: str, S: int, c_data, a_data, b_data,
                 tuned=None) -> None:
    """Feed the dispatch decision (and its reason) to the flight
    recorder — `prepare_stack` is the only place the *why* is known."""
    if tuned is not None and "predicted_from" in tuned:
        why += f"+predicted_from={tuned['predicted_from']}"
    _flight.note_driver(
        driver, why,
        mnk=(a_data.shape[1], b_data.shape[2], a_data.shape[2]),
        entries=S,
    )


def _ensure_pallas_validated(c_data, a_data, b_data, plan: StackPlan) -> None:
    """First-use validation of a base-pallas plan's compiled variant,
    keyed per (m, n, k, dtype, kmerge, r_grp) — shared by the per-span
    dispatch and the fused superstack path (which must validate OUTSIDE
    its fused program, before the first fused launch of the shape).
    The plan's RESOLVED r_grp is forced so the validator exercises the
    exact compiled variant being launched (ADVICE r3)."""
    if plan.val_idx is None or not get_config().validate_kernels:
        return
    key = (
        a_data.shape[1], b_data.shape[2], a_data.shape[2],
        str(jnp.dtype(c_data.dtype)), plan.kmerge, plan.r_grp,
    )
    if key in _validated_kernels:
        return
    ai, bi, ci = plan.val_idx
    with timed("kernel_validate"):
        _validate_pallas_kernel(
            c_data, a_data, b_data, ai, bi, ci,
            None if plan.append_a_pad else plan.a_pad_row,
            None if plan.append_b_pad else plan.b_pad_row,
            plan.r_grp, variant="kmerge" if plan.kmerge else None,
        )
    _validated_kernels.add(key)


def prepare_stack(c_data, a_data, b_data, a_idx, b_idx, c_idx,
                  a_pad_row=None, b_pad_row=None,
                  moving: bool = False) -> Optional[StackPlan]:
    """Host side of stack processing: driver selection (tuned table +
    prediction), grouping/chunking/padding, and upload of the int32
    index arrays.  Returns None for an empty stack.

    The returned plan retains a host copy of the index arrays
    (``src_idx``) so `execute_stack`'s breaker failover can rebuild it
    for a different driver without the engine re-deriving the stack.
    A planning failure (injected, or a real host-side grouping bug)
    re-plans once on the safe XLA path instead of killing the
    multiply.  ``moving``: C's pattern moves from call to call (its
    `moving_pattern`, a tensor's batches), so a grouped plan takes
    shapes that do not follow the stack's counts (one width class,
    chunk sizes in powers of two, chunk counts in powers of four:
    `build_stacks_group_tiles`), and a batch reuses the last one's
    programs."""
    try:
        if _faults.active():
            _faults.maybe_inject("prepare_stack")
        plan = _prepare_stack_impl(c_data, a_data, b_data, a_idx, b_idx,
                                   c_idx, a_pad_row=a_pad_row,
                                   b_pad_row=b_pad_row, moving=moving)
    except Exception as exc:  # noqa: BLE001 — classified + recorded
        shape_key = _stack_shape_key(c_data, a_data, b_data)
        _record_driver_failure("prepare", _classify_failure(exc), exc,
                               shape_key)
        plan = _prepare_stack_impl(c_data, a_data, b_data, a_idx, b_idx,
                                   c_idx, a_pad_row=a_pad_row,
                                   b_pad_row=b_pad_row,
                                   cfg=_forced_cfg("xla"))
        _record_fallback("prepare", plan.driver if plan else "none",
                         shape_key)
    if plan is not None and plan.src_idx is None:
        plan.src_idx = (
            np.ascontiguousarray(a_idx, np.int32),
            np.ascontiguousarray(b_idx, np.int32),
            np.ascontiguousarray(c_idx, np.int32),
        )
        plan.src_pads = (a_pad_row, b_pad_row)
    return plan


def _prepare_stack_impl(c_data, a_data, b_data, a_idx, b_idx, c_idx,
                        a_pad_row=None, b_pad_row=None,
                        cfg=None, moving: bool = False
                        ) -> Optional[StackPlan]:
    """Driver selection + plan construction.  ``cfg`` overrides the
    live config — the failover path passes a copy with ``mm_driver``
    forced so one rebuild targets one specific chain driver."""
    if cfg is None:
        cfg = get_config()
    S = len(a_idx)
    if S == 0:
        return None
    # tuned preference (dbcsr_tpu.acc.params; analog of the per-GPU
    # parameter table consulted by libsmm_acc.cpp:227-249, with
    # nearest-neighbor prediction for untuned shapes standing in for
    # the predict/ ML pipeline) — resolved once here for the driver
    # choice, grouping, and the flat-gather layout decision
    from dbcsr_tpu.acc import params as params_mod

    # native host stack driver (the reference's CPU path,
    # dbcsr_mm_hostdrv.F:90 / tools/build_libsmm): explicit opt-in, or
    # a tuned-table row, on CPU backends only — on a TPU it would cost
    # a device->host->device round trip per stack, so there it demotes
    # to auto
    def _host_plan():
        plan = StackPlan()
        plan.nseg = c_data.shape[0]
        plan.entries = S
        plan.driver = "host"
        plan.a_pad_row = a_pad_row
        plan.b_pad_row = b_pad_row
        plan.host_idx = (
            np.ascontiguousarray(a_idx, np.int32),
            np.ascontiguousarray(b_idx, np.int32),
            np.ascontiguousarray(c_idx, np.int32),
        )
        return plan

    if cfg.mm_driver == "host":
        if _host_smm_available(c_data.dtype):
            _note_driver("host", "config-forced", S, c_data, a_data, b_data)
            return _host_plan()
        import warnings

        warnings.warn(
            "mm_driver='host' but the native host driver is unavailable "
            "on this backend/dtype; falling back to auto selection",
            RuntimeWarning,
            stacklevel=2,
        )
    tuned = params_mod.predict(
        a_data.shape[1], b_data.shape[2], a_data.shape[2], c_data.dtype,
        stack_size=S,
    )
    tuned_driver = tuned.get("driver") if tuned else None
    # executed-precision resolution (acc.precision): a demoted spec
    # constrains dispatch to the XLA family (the compensated/demoted
    # kernels live there); an EXPLICIT driver force wins over the
    # demotion policy — the operator asked for that exact kernel
    from dbcsr_tpu.acc import precision as precision_mod

    prec = None
    if cfg.mm_driver not in ("pallas", "pallas_cross", "host"):
        prec = precision_mod.resolve(
            a_data.shape[1], b_data.shape[2], a_data.shape[2],
            c_data.dtype, tuned=tuned,
        )
    if (cfg.mm_driver == "auto" and tuned_driver == "host"
            and (prec is None or not precision_mod.forced())
            and _host_smm_available(c_data.dtype)):
        # a tuned native-host row outranks ADAPTIVE demotion: the C++
        # driver is the measured winner on this device kind, and
        # demoting would force the stack onto the slower XLA family
        # (measured ~7x on the CPU container) — only the FORCED bench
        # modes override it
        prec = None
        # the autotuner measured the native driver fastest for this
        # shape on this (CPU) device kind — the reference's MM_DRIVER=
        # smm per-shape dispatch (dbcsr_config.F:34-38)
        _note_driver("host", "tuned", S, c_data, a_data, b_data, tuned)
        return _host_plan()
    if prec is not None:
        # executed-precision span annotation (trace_summary surfaces
        # it next to the format/algorithm attrs): what this stack will
        # actually compute in, not what was requested
        _trace.annotate(
            precision=f"{prec[0]}{'+comp' if prec[1] else ''}")
    plan = StackPlan()
    plan.nseg = c_data.shape[0]
    plan.entries = S
    # R-tiled grouped layout (see _process_stack_xla_group): the default
    # for emulated-f64 dtypes on TPU, where the per-entry dot is
    # MXU-starved; elsewhere f64 is native and per-entry is fine (same
    # platform gate as the mesh path's _stack_r0)
    want_group = cfg.mm_driver == "xla_group" or (
        cfg.mm_driver == "auto"
        and (
            tuned_driver == "xla_group"
            or (
                tuned_driver is None
                and S >= 2048
                and emulated_dtype_on_tpu(c_data.dtype)
            )
        )
    )
    if want_group:
        r0 = sliced_width(int(tuned.get("r0", 8)) if tuned else 8,
                          a_data.shape[2], c_data.dtype, prec)
        if a_pad_row is None:
            plan.append_a_pad = True
            a_pad_row = a_data.shape[0]
        if b_pad_row is None:
            plan.append_b_pad = True
            b_pad_row = b_data.shape[0]
        chunk_groups = group_chunk_groups(
            r0, a_data.shape[1], b_data.shape[2], a_data.shape[2],
            jnp.dtype(c_data.dtype).itemsize, cfg.mm_stack_size)
        tiles = build_group_tiles(
            np.asarray(c_idx), np.asarray(a_idx), np.asarray(b_idx),
            r0, a_pad_row, b_pad_row, plan.nseg, chunk_groups,
            moving=moving,
        )
        plan.driver = "xla_group"
        plan.r_grp = r0  # metadata: the widest R-tile grouping used
        plan.group_classes = tuple(zip(tiles.widths, tiles.groups))
        plan.group_launched = tiles.slots_launched
        _note_group_slots(tiles)
        plan.precision = prec
        plan.dot_form = group_dot_form(c_data.dtype, r0 * a_data.shape[2],
                                       prec)
        plan.a_pad_row = a_pad_row
        plan.b_pad_row = b_pad_row
        # the device index mirror (core.mempool): pattern-stable
        # repeats (incl. filtered products the plan cache skips)
        # re-upload nothing
        plan.group_idx = (
            _mempool.upload_index("grp_live", np.int32(tiles.live)),
            *(_mempool.upload_index(tag, x) for tile in tiles.tiles
              for tag, x in zip(("grp_a", "grp_b", "grp_c"), tile)),
        )
        _note_driver(
            "xla_group",
            "config-forced" if cfg.mm_driver == "xla_group"
            else ("tuned" if tuned_driver == "xla_group"
                  else "auto:emulated-f64-large-stack"),
            S, c_data, a_data, b_data, tuned,
        )
        return plan
    if prec is None and _pallas_supported(cfg, c_data, a_data, b_data):
        prefer_xla = (
            cfg.mm_driver == "auto" and tuned_driver in ("xla", "xla_flat")
        )
        if not prefer_xla:
            from dbcsr_tpu.acc import pallas_smm

            grouping = None
            kmerge = False
            tuned_cross = False
            if tuned and tuned.get("driver") == "pallas":
                if tuned.get("grouping"):
                    grouping = int(tuned["grouping"])
                kmerge = tuned.get("variant") == "kmerge"
                tuned_cross = tuned.get("variant") in ("crosspack",
                                                       "crosspack_vmem")
            # no guaranteed-zero row in the data array: the plan indexes
            # a virtual row one past the end, appended at execute time
            # (capacities are pattern-deterministic, so cached plans
            # remain valid across value changes)
            if a_pad_row is None:
                plan.append_a_pad = True
                a_pad_row = a_data.shape[0]
            if b_pad_row is None:
                plan.append_b_pad = True
                b_pad_row = b_data.shape[0]
            # cross-packed variant: forced by config, tuned-table
            # choice, or — on a REAL TPU — the default for untuned
            # f32 shapes (P*R entries per MXU pass; bf16 excluded, see
            # below).  A compile failure demotes the shape for the
            # session (_cross_disabled), so dispatch can never be
            # bricked by a Mosaic lowering gap; ineligible stacks fall
            # through to the base kernel
            shape_key = _stack_shape_key(c_data, a_data, b_data)
            # bf16 crosspack runs ONLY from an EXACT tuned row: a 23^3
            # bf16 crosspack launch dies with a Mosaic FATAL (process
            # abort — the in-process demotion can't catch it; observed
            # 2026-07-31, capture_loop.log), and the abort is
            # shape-specific, so neither untuned auto-crosspack nor a
            # nearest-neighbor-predicted donor row (proved on a
            # DIFFERENT shape) may select it.  The tuner subprocess is
            # the sacrificial process that proves each exact shape on
            # this backend first.
            is_bf16 = jnp.dtype(c_data.dtype) == jnp.bfloat16
            if tuned_cross and is_bf16 and "predicted_from" in tuned:
                tuned_cross = False
                grouping = None  # donor's crosspack R must not leak
                # into the base kernel (same rule as below)
            auto_cross = (
                cfg.mm_driver == "auto" and tuned is None and _on_tpu()
                and not is_bf16
            )
            want_cross = shape_key not in _cross_disabled and (
                cfg.mm_driver == "pallas_cross"
                or (cfg.mm_driver == "auto" and tuned_cross)
                or auto_cross
            )
            if tuned_cross:
                # a crosspack entry's "grouping" is the crosspack
                # k-depth R (tuned jointly with pack_p); it must not
                # leak into the base kernel if crosspack falls through
                grouping = None
            if want_cross:
                m_blk, k_blk = a_data.shape[1:]
                n_blk = b_data.shape[2]
                pack = None
                if (tuned and tuned.get("pack_p") and tuned.get("grouping")
                        and "predicted_from" not in tuned):
                    # exact tuned entry: accept, clamped to this shape's
                    # MXU geometry (defensive against a hand-edited or
                    # stale table row)
                    pack = (
                        min(int(tuned["pack_p"]),
                            max(1, 128 // max(m_blk, n_blk))),
                        min(int(tuned["grouping"]), max(1, 128 // k_blk)),
                    )
                else:
                    # nearest-neighbor-predicted donor: its pack was
                    # tuned for a DIFFERENT block shape; re-derive from
                    # this shape's geometry instead
                    pack = pallas_smm.choose_pack(m_blk, n_blk, k_blk)
                cross = None
                if pack[0] > 1:
                    cross = pallas_smm.prepare_crosspack_launches(
                        np.asarray(c_idx), np.asarray(a_idx),
                        np.asarray(b_idx), a_pad_row, b_pad_row,
                        pack[0], pack[1],
                    )
                if cross is not None:
                    plan.driver = "pallas_cross"
                    plan.pack = pack
                    plan.cross_src = (
                        np.ascontiguousarray(a_idx, np.int32),
                        np.ascontiguousarray(b_idx, np.int32),
                        np.ascontiguousarray(c_idx, np.int32),
                    )
                    # VMEM-resident gather variant: tuned-table only,
                    # and only while the operand arrays actually fit
                    plan.cross_vmem = bool(
                        tuned and tuned.get("variant") == "crosspack_vmem"
                        and pallas_smm.supports_vmem_resident(a_data, b_data)
                    )
                    plan.a_pad_row = a_pad_row
                    plan.b_pad_row = b_pad_row
                    plan.cross_launches = [
                        {
                            "ai": jnp.asarray(lc["ai"]),
                            "bi": jnp.asarray(lc["bi"]),
                            "cg": jnp.asarray(lc["cg"]),
                            "cl": jnp.asarray(lc["cl"]),
                            # one concatenated scatter per launch: lanes
                            # own disjoint C blocks, so set (not add)
                            "scatter_idx": jnp.asarray(lc["scatter_idx"]),
                            "nc_out": lc["nc_out"],
                        }
                        for lc in cross
                    ]
                    if cfg.validate_kernels:
                        s = min(S, _VALIDATE_MAX_ENTRIES)
                        plan.val_idx = (
                            np.asarray(a_idx[:s], np.int32),
                            np.asarray(b_idx[:s], np.int32),
                            np.asarray(c_idx[:s], np.int32),
                        )
                    _note_driver(
                        "pallas_cross",
                        "config-forced" if cfg.mm_driver == "pallas_cross"
                        else ("tuned" if tuned_cross
                              else "auto:untuned-f32-on-tpu"),
                        S, c_data, a_data, b_data, tuned,
                    )
                    return plan
            ai2, bi2, ci2, r_grp = pallas_smm.build_grouped_stack(
                np.asarray(c_idx), np.asarray(a_idx), np.asarray(b_idx),
                a_pad_row, b_pad_row, grouping=grouping,
            )
            plan.driver = "pallas"
            plan.r_grp = r_grp
            plan.kmerge = kmerge
            plan.a_pad_row = a_pad_row
            plan.b_pad_row = b_pad_row
            plan.launches = [
                tuple(_mempool.upload_index("pl_idx", x) for x in lc)
                for lc in pallas_smm.prepare_launches(
                    ai2, bi2, ci2, r_grp, a_pad_row, b_pad_row
                )
            ]
            if cfg.validate_kernels:
                s = min(S, _VALIDATE_MAX_ENTRIES)
                plan.val_idx = (
                    np.asarray(a_idx[:s], np.int32),
                    np.asarray(b_idx[:s], np.int32),
                    np.asarray(c_idx[:s], np.int32),
                )
            _note_driver(
                "pallas",
                "config-forced" if cfg.mm_driver in ("pallas", "pallas_cross")
                else ("tuned" if tuned_driver == "pallas"
                      else "auto:pallas-default"),
                S, c_data, a_data, b_data, tuned,
            )
            return plan
    elif cfg.mm_driver in ("pallas", "pallas_cross"):
        import warnings

        warnings.warn(
            f"mm_driver={cfg.mm_driver!r} but dtype {jnp.dtype(c_data.dtype)}"
            f" / block shape unsupported by the Pallas kernel; falling back"
            f" to XLA path",
            RuntimeWarning,
            stacklevel=2,
        )
    chunk = max(cfg.mm_stack_size, 1)
    # pad to a whole number of chunks (bucketed) and reshape to
    # (nchunks, chunk) so the scan shape reuses the jit cache
    if S <= chunk:
        chunk = bucket_size(S)
        nchunks = 1
    else:
        nchunks = bucket_size(-(-S // chunk), minimum=1)
    ai, bi, ci = pad_stack(a_idx, b_idx, c_idx, nchunks * chunk, plan.nseg)
    plan.driver = "xla_flat" if (
        cfg.flat_gather
        or (cfg.mm_driver == "auto" and tuned_driver == "xla_flat")
    ) else "xla"
    plan.precision = prec
    plan.xla_idx = (
        _mempool.upload_index("stk_a", ai.reshape(nchunks, chunk)),
        _mempool.upload_index("stk_b", bi.reshape(nchunks, chunk)),
        _mempool.upload_index("stk_c", ci.reshape(nchunks, chunk)),
    )
    if plan.driver == "xla_flat":
        why = "config.flat_gather" if cfg.flat_gather else "tuned"
    else:
        why = ("tuned" if tuned_driver == "xla"
               else ("config-forced" if cfg.mm_driver == "xla"
                     else "auto:default"))
    _note_driver(plan.driver, why, S, c_data, a_data, b_data, tuned)
    return plan


def _note_launched_entries(plan: StackPlan, mnk: str,
                           dev_entries: int) -> None:
    """Count one launched span (on its own or inside a fused launch)
    under its driver and block shape: the slots the device works
    through (chunk/group/bucket padding included) against the true
    entries among them — the pad-overhead attribution the roofline
    needs when achieved GFLOP/s (true flops) undershoots the device's
    busy rate.  Counted at the launch, so a plan-cache hit counts like
    the product that made the plan."""
    _metrics.counter(
        "dbcsr_tpu_device_entries_total",
        "stack entries actually launched per driver and (m,n,k), "
        "padding included",
    ).inc(dev_entries, driver=plan.driver, mnk=mnk)
    entries = _metrics.counter(
        "dbcsr_tpu_stack_entries_total",
        "slots of the launched spans per driver and (m,n,k): 'live' "
        "hold a true stack entry, 'pad' are the padding launched with "
        "them (live + pad = dbcsr_tpu_device_entries_total)",
    )
    entries.inc(plan.entries, driver=plan.driver, mnk=mnk, kind="live")
    entries.inc(dev_entries - plan.entries, driver=plan.driver, mnk=mnk,
                kind="pad")
    from dbcsr_tpu.core import stats

    stats.record_launched_entries(plan.driver, mnk, plan.entries,
                                  dev_entries)


def _record_stack_jit(plan: StackPlan, c_data, a_data, b_data):
    """Mirror the XLA jit cache for the stack kernels (the reference's
    per-(m,n,k) NVRTC kernel cache, `libsmm_acc.cpp:89-224`): each
    launch reports the shape/dtype signature that keys the real cache,
    so `obs.metrics` exposes compile-vs-hit counters per kernel — a
    fresh (m,n,k,dtype,bucket) bin shows up as one compile.

    Returns ``(compiled, fn_name, key)`` — compiled is True on the
    first sighting of this specialization, which is when the XLA-cost
    cross-check (`obs.costmodel.capture_xla_cost`, opt-in) fires."""
    drv = plan.driver
    dt = str(jnp.dtype(c_data.dtype))
    if drv in ("xla", "xla_flat"):
        key = (c_data.shape, a_data.shape, b_data.shape, dt,
               plan.xla_idx[0].shape, plan.precision)
        fn = ("_process_stack_xla_flat" if drv == "xla_flat"
              else "_process_stack_xla")
        dev_entries = int(plan.xla_idx[0].size)
    elif drv == "xla_group":
        key = (c_data.shape, a_data.shape, b_data.shape, dt,
               _group_idx_shapes(plan), plan.precision, plan.dot_form)
        fn = "_process_stack_xla_group"
        dev_entries = plan.group_launched
    elif drv == "pallas":
        from dbcsr_tpu.acc import pallas_smm

        key = (c_data.shape, a_data.shape, b_data.shape, dt, plan.r_grp,
               plan.kmerge, tuple(lc[0].shape for lc in plan.launches))
        fn = "_pallas_process"
        dev_entries = pallas_smm.launch_entries(plan.launches, plan.r_grp)
    elif drv == "pallas_cross":
        from dbcsr_tpu.acc import pallas_smm

        key = (c_data.shape, a_data.shape, b_data.shape, dt, plan.pack,
               plan.cross_vmem,
               tuple(lc["ai"].shape for lc in plan.cross_launches))
        fn = "_pallas_crosspack"
        dev_entries = pallas_smm.crosspack_launch_entries(
            plan.cross_launches)
    else:  # host driver: no device compilation to account
        return False, None, None
    _note_launched_entries(plan, _mnk_label(a_data, b_data), dev_entries)
    return _metrics.record_jit(f"acc.smm.{fn}", key), f"acc.smm.{fn}", key


def _capture_stack_xla_cost(fn_name, key, jit_fn, args, c_data, a_data,
                            b_data, entries: int, prec=None,
                            dot_form=None) -> None:
    """Opt-in XLA cost_analysis capture for a fresh stack-kernel
    specialization, with the analytic model of the DEVICE work (padded
    entries — XLA counts the masked pad rows too) stored alongside for
    the drift check."""
    from dbcsr_tpu.obs import costmodel

    m, k = a_data.shape[1], a_data.shape[2]
    n = b_data.shape[2]
    model = {
        "flops": costmodel.stack_flops(m, n, k, entries),
        "bytes": costmodel.stack_bytes(
            m, n, k, entries, nseg=c_data.shape[0],
            itemsize=jnp.dtype(c_data.dtype).itemsize),
    }
    kwargs = {}
    if prec is not None:
        kwargs["prec"] = prec
    if dot_form is not None:  # the grouped program alone takes one
        kwargs["dot_form"] = dot_form
    costmodel.capture_xla_cost(
        fn_name, key, jit_fn, args, model=model, kwargs=kwargs or None)


# safety-ordered stack-driver chain (the reference's unsupported-kernel
# fallback, `libsmm_acc.cpp:227-249`, made dynamic): a failing driver's
# stack re-executes on the next entry that is available and whose
# breaker admits it.  "host" is last — correct everywhere a native lib
# exists, never fast.
_FAILOVER_CHAIN = ("pallas_cross", "pallas", "xla_group", "xla_flat",
                   "xla", "host")


class CorruptedOutputError(RuntimeError):
    """A stack driver returned non-finite output blocks (detected by
    the opt-in post-execution output check)."""


def _forced_cfg(driver: str):
    """A config copy that steers `_prepare_stack_impl` to exactly one
    chain driver (xla_flat is the xla driver + flat_gather layout)."""
    cfg = get_config()
    if driver == "xla_flat":
        return dataclasses.replace(cfg, mm_driver="xla", flat_gather=True)
    if driver == "xla":
        return dataclasses.replace(cfg, mm_driver="xla", flat_gather=False)
    return dataclasses.replace(cfg, mm_driver=driver)


def _classify_failure(exc: BaseException) -> str:
    """Failure classification feeding the breaker and the
    ``dbcsr_tpu_driver_failures_total{driver,kind}`` counter."""
    if isinstance(exc, KernelValidationError):
        return "validation"
    if isinstance(exc, _abft.AbftMismatchError):
        return "sdc"
    if isinstance(exc, CorruptedOutputError):
        return "nan"
    msg = f"{type(exc).__name__}: {exc}"
    if "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower():
        return "oom"
    return "runtime"


# production finite-output checking is an import-time opt-in: a per-
# launch os.environ lookup would eat the trace-off budget (hot path)
_CHECK_OUTPUTS_ENV = os.environ.get("DBCSR_TPU_CHECK_OUTPUTS") == "1"


def _output_checks_enabled() -> bool:
    """Post-execution finite-output check: always on under fault
    injection (the chaos suites rely on NaN corruption being CAUGHT),
    opt-in for production via DBCSR_TPU_CHECK_OUTPUTS=1 at process
    start (costs one device reduction + sync per stack launch)."""
    return _CHECK_OUTPUTS_ENV or _faults.active()


def _output_corrupted(out) -> bool:
    if not jnp.issubdtype(out.dtype, jnp.inexact):
        return False
    return not bool(jnp.all(jnp.isfinite(
        jnp.sum(out, axis=tuple(range(1, out.ndim))))))


def _is_deleted(x) -> bool:
    f = getattr(x, "is_deleted", None)
    try:
        return bool(f()) if callable(f) else False
    except Exception:
        return False


def _chain_candidates(failed: str, c_data, a_data, b_data) -> list:
    """Every OTHER driver that can run this stack, safer ones first:
    the chain entries after ``failed``, then — so a failure of the
    safest available driver still has somewhere to go — the entries
    before it in DESCENDING safety order (for failed='host' that is
    xla, xla_flat, xla_group, …).  Breaker admission is checked per
    attempt."""
    try:
        i = _FAILOVER_CHAIN.index(failed)
        rest = (_FAILOVER_CHAIN[i + 1:]
                + tuple(reversed(_FAILOVER_CHAIN[:i])))
    except ValueError:  # unknown driver name: anything qualifies
        rest = _FAILOVER_CHAIN
    out = []
    for drv in rest:
        if drv == failed:
            continue
        if drv == "host":
            if _host_smm_available(c_data.dtype):
                out.append(drv)
        elif drv in ("pallas", "pallas_cross"):
            if _pallas_supported(_forced_cfg(drv), c_data, a_data, b_data):
                out.append(drv)
        else:
            out.append(drv)
    return out


def _record_driver_failure(driver: str, kind: str, exc, shape_key) -> None:
    _metrics.counter(
        "dbcsr_tpu_driver_failures_total",
        "stack-driver execution failures by driver and failure kind",
    ).inc(driver=driver, kind=kind)
    err = f"{type(exc).__name__}: {exc}"[:200]
    _events.publish(
        "driver_failure",
        {"driver": driver, "kind": kind,
         "shape": "x".join(str(x) for x in shape_key), "error": err},
        flight=("driver_failure",
                {"driver": driver, "kind": kind, "error": err}),
    )


def _record_fallback(from_driver: str, to_driver: str, shape_key) -> None:
    _metrics.counter(
        "dbcsr_tpu_driver_fallback_total",
        "stacks re-executed on a safer driver after a chain failover",
    ).inc(**{"from": from_driver, "to": to_driver})
    _events.publish(
        "driver_failover",
        {"from": from_driver, "to": to_driver,
         "shape": "x".join(str(x) for x in shape_key)},
        flight=("failover", {"from": from_driver, "to": to_driver}),
    )


def _run_candidate(base, a_data, b_data, fb_plan, alpha, c_zero,
                   checks_on: bool):
    """Execute one failover candidate (fault hooks apply to fallback
    drivers too, so injected cascades walk the whole chain).

    ``base`` is ALWAYS copied: the xla-family drivers donate their C
    argument, so a candidate that dispatches and then fails would
    otherwise consume the only pristine buffer and poison every later
    candidate (falsely tripping their breakers).  We are already on
    the failure path — one C copy per attempt is cheap insurance.

    Whenever the ABFT plane is armed (``verify`` or ``recover``) the
    candidate's output is itself probe-verified against ``base`` before
    being accepted — a recovery must never replace one
    silently-corrupted result with another.  Gating this on ``recover``
    alone left a gap: under ``verify`` a flip corrupting the pristine
    same-driver retry was accepted unprobed (and even counted as a
    recovery) — pinned by tests/test_integrity.py."""
    trial = jnp.array(base, copy=True)
    if _faults.active():
        _faults.maybe_inject("execute_stack", driver=fb_plan.driver)
    out = _execute_plan(trial, a_data, b_data, fb_plan, alpha, c_zero)
    if _faults.active():
        out = _faults.corrupt("execute_stack", out, driver=fb_plan.driver)
    if checks_on and _output_corrupted(out):
        raise CorruptedOutputError(
            f"driver {fb_plan.driver!r} produced non-finite output blocks")
    if _abft.enabled():
        _abft.check_stack(base, out, a_data, b_data, fb_plan, alpha)
    return out


def note_deferred_sdc(exc: BaseException) -> None:
    """Attribute a flush-detected (deferred) ABFT mismatch: feed the
    per-(driver, shape) breaker and the failure counters exactly as an
    immediate in-launch detection would have.  ``exc`` carries
    ``.driver``/``.shape_key`` attached by `abft.flush`."""
    drv = getattr(exc, "driver", None) or "?"
    key = getattr(exc, "shape_key", None) or (drv, "deferred")
    board = _breaker.get_board()
    board.record_failure(drv, key, kind="sdc")
    _record_driver_failure(drv, "sdc", exc, key)


def _failover_execute(c_data, a_data, b_data, plan: StackPlan, alpha,
                      c_zero, exc: Optional[BaseException], base=None):
    """Re-execute a failed (or quarantined) stack down the driver
    chain.  ``exc`` is None when the original driver was never
    attempted (breaker open); ``base`` is the pristine C buffer to
    restart from (defaults to ``c_data``).  On success the original
    plan is healed IN PLACE to the surviving driver (the established
    demotion pattern), so cached plans stop paying the failure."""
    board = _breaker.get_board()
    failed = plan.driver
    shape_key = _stack_shape_key(c_data, a_data, b_data)
    if base is None:
        # c_zero launches never copy their pristine C (it is identically
        # zero): synthesize it from metadata — valid even after the
        # failing launch donated c_data's buffer
        base = (jnp.zeros(c_data.shape, np.dtype(c_data.dtype))
                if c_zero else c_data)
    checks_on = _output_checks_enabled()
    if plan.src_idx is None or _is_deleted(base):
        # no rebuild payload, or the failing launch consumed (donated)
        # the only copy of C: recovery is impossible from here
        if exc is not None:
            raise exc
        return _execute_plan(base, a_data, b_data, plan, alpha, c_zero)
    ai, bi, ci = plan.src_idx
    pad_a, pad_b = plan.src_pads
    was_sdc = exc is not None and _classify_failure(exc) == "sdc"
    # recoveries are recorded once per COUNTED mismatch of this stack
    # (a retry that itself mismatches counts another), so the
    # mismatch/recovery counters stay balanced and health never
    # reports fully-recovered SDC as corruption that escaped
    sdc_count = 1 if was_sdc else 0
    if was_sdc:
        # SDC is transient corruption (the particle-strike model): the
        # bitwise-faithful recovery is one pristine SAME-DRIVER retry —
        # same plan, same accumulation order — before walking the chain
        # onto a driver with different numerics.  The breaker already
        # recorded the sdc failure above, so REPEATED corruption from
        # this (driver, shape) still trips quarantine.
        try:
            out = _run_candidate(base, a_data, b_data, plan, alpha,
                                 c_zero, checks_on)
        except Exception as exc2:  # noqa: BLE001 — classified + recorded
            kind2 = _classify_failure(exc2)
            if kind2 == "sdc":
                sdc_count += 1
            board.record_failure(failed, shape_key, kind=kind2)
            _record_driver_failure(failed, kind2, exc2, shape_key)
        else:
            board.record_success(failed, shape_key)
            _record_fallback(failed, failed, shape_key)
            for _ in range(sdc_count):
                _abft.record_recovery(failed)
            return out
    for drv in _chain_candidates(failed, c_data, a_data, b_data):
        if not board.allow(drv, shape_key):
            continue
        try:
            fb_plan = _prepare_stack_impl(
                base, a_data, b_data, ai, bi, ci,
                a_pad_row=pad_a, b_pad_row=pad_b, cfg=_forced_cfg(drv),
            )
            if fb_plan is None or fb_plan.driver != drv:
                continue  # selection refused the force (e.g. host gone)
            fb_plan.src_idx = plan.src_idx
            fb_plan.src_pads = plan.src_pads
            out = _run_candidate(base, a_data, b_data, fb_plan, alpha,
                                 c_zero, checks_on)
        except Exception as exc2:  # noqa: BLE001 — classified + recorded
            kind2 = _classify_failure(exc2)
            if kind2 == "sdc":
                sdc_count += 1
            board.record_failure(drv, shape_key, kind=kind2)
            _record_driver_failure(drv, kind2, exc2, shape_key)
            continue
        board.record_success(drv, shape_key)
        _record_fallback(failed, drv, shape_key)
        for _ in range(sdc_count):
            _abft.record_recovery(drv)
        _flight.note_driver(drv, f"failover:{failed}",
                            mnk=shape_key[:3], entries=len(ai))
        for slot in StackPlan.__slots__:  # heal the cached plan
            setattr(plan, slot, getattr(fb_plan, slot))
        return out
    # chain exhausted
    if exc is None:
        # quarantined entry but nothing safer is available: running the
        # original driver beats refusing the multiply
        return _execute_plan(base, a_data, b_data, plan, alpha, c_zero)
    if _classify_failure(exc) != "validation" and not _is_deleted(base):
        # last resort: one same-driver retry from the pristine buffer —
        # transient corruption (the injected-NaN case, a flaky launch)
        # heals here; proven-deterministic validation failures do not
        try:
            out = _run_candidate(base, a_data, b_data, plan, alpha,
                                 c_zero, checks_on)
        except Exception:
            raise exc
        board.record_success(failed, shape_key)
        _record_fallback(failed, failed, shape_key)
        for _ in range(sdc_count):
            _abft.record_recovery(failed)
        return out
    raise exc


def _promote_execute(c_data, a_data, b_data, plan: StackPlan, alpha,
                     c_zero, base, exc):
    """A demoted launch's probe residual breached its demotion ceiling
    (`abft.PrecisionExceededError`): the involved (m,n,k,dtype) cells
    were promoted when the probe raised, so rebuild this plan — now
    resolving to native precision — from the retained source indices,
    heal it IN PLACE (cached plans stop re-demoting), and re-execute
    from the pristine buffer.  NOT an SDC path: no breaker feed, no
    failover chain — the condemned result was wrong only by demoted
    rounding, and one native re-execution is the complete cure."""
    if base is None:
        base = (jnp.zeros(c_data.shape, np.dtype(c_data.dtype))
                if c_zero else c_data)
    if plan.src_idx is None or _is_deleted(base):
        raise exc
    shape_key = _stack_shape_key(c_data, a_data, b_data)
    _events.publish(
        "precision_promote_reexec",
        {"driver": plan.driver,
         "shape": "x".join(str(x) for x in shape_key)},
        flight=("precision_promote_reexec", {"driver": plan.driver}),
    )
    ai, bi, ci = plan.src_idx
    pad_a, pad_b = plan.src_pads
    new_plan = _prepare_stack_impl(base, a_data, b_data, ai, bi, ci,
                                   a_pad_row=pad_a, b_pad_row=pad_b)
    if new_plan is None:
        raise exc
    # belt-and-braces: under the FORCED precision modes (bench/test
    # legs) resolve would re-demote the rebuild and loop — the
    # re-execution must be native regardless of policy
    new_plan.precision = None
    new_plan.src_idx = plan.src_idx
    new_plan.src_pads = plan.src_pads
    for slot in StackPlan.__slots__:  # heal the cached plan
        setattr(plan, slot, getattr(new_plan, slot))
    return execute_stack(base, a_data, b_data, plan, alpha, c_zero=c_zero)


def execute_stack(c_data, a_data, b_data, plan: Optional[StackPlan], alpha=1.0,
                  c_zero: bool = False, abft_defer: bool = False):
    """Device side: run a prepared plan against (possibly new) data,
    guarded by the resilience layer — injected faults fire here, a
    raising/corrupting driver is recorded against its per-shape circuit
    breaker, and the stack re-executes down the failover chain
    (pallas → xla_group → xla_flat → xla → host) so one bad kernel
    never poisons the multiply.  With no faults configured and no
    recorded failures, the added cost is two attribute checks.

    ``c_zero``: caller guarantees ``c_data`` is identically zero (the
    engine's beta==0 rebuild, first touch per bin) — the host driver
    then synthesizes its writable buffer as np.zeros instead of
    fetching hundreds of MB of device zeros."""
    if plan is None:
        return c_data
    record_dispatch("per_span")
    board = _breaker.get_board()
    faults_on = _faults.active()
    abft_on = _abft.enabled()
    # the ABFT probe subsumes the finite-output check (NaN/Inf in out
    # poisons the probe scalars, so isfinite(err) fails) — don't pay a
    # second full read + sync of C for it unless faults or the explicit
    # env knob ask for the `nan`-classified path
    finite_on = faults_on or _output_checks_enabled()
    checks_on = finite_on or abft_on
    if not checks_on and not board._breakers:
        # production fast path: no faults configured, nothing ever
        # failed — the guard is three attribute checks + this try frame
        # (the per-shape key construction is deferred to the failure
        # path; str(dtype) per launch would eat the trace-off budget)
        try:
            return _execute_plan(c_data, a_data, b_data, plan, alpha, c_zero)
        except Exception as exc:  # noqa: BLE001 — classified + recorded
            shape_key = _stack_shape_key(c_data, a_data, b_data)
            kind = _classify_failure(exc)
            board.record_failure(plan.driver, shape_key, kind=kind)
            _record_driver_failure(plan.driver, kind, exc, shape_key)
            return _failover_execute(c_data, a_data, b_data, plan, alpha,
                                     c_zero, exc=exc, base=c_data)
    shape_key = _stack_shape_key(c_data, a_data, b_data)
    if not board.allow(plan.driver, shape_key):
        return _failover_execute(c_data, a_data, b_data, plan, alpha,
                                 c_zero, exc=None)
    # the xla drivers donate C: keep a pristine copy while the output
    # check may condemn a COMPLETED launch (chaos/opt-in mode only).
    # A first-touch (beta==0) launch skips the copy — the failure path
    # re-synthesizes zeros from metadata, and the ABFT probe drops the
    # base subtraction outright (half its C traffic)
    if not checks_on:
        base = c_data
    elif c_zero:
        base = None
    else:
        base = jnp.array(c_data, copy=True)
    try:
        if faults_on:
            _faults.maybe_inject("execute_stack", driver=plan.driver)
        out = _execute_plan(c_data, a_data, b_data, plan, alpha, c_zero)
        if faults_on:
            out = _faults.corrupt("execute_stack", out, driver=plan.driver)
        if finite_on and _output_corrupted(out):
            raise CorruptedOutputError(
                f"driver {plan.driver!r} produced non-finite output blocks")
        if abft_on:
            # rank-1 probe: C·v vs A·(B·v) per product — the finite-SDC
            # detector; a mismatch classifies `sdc` below and the stack
            # re-executes (pristine same-driver retry first, then the
            # chain)
            _abft.check_stack(base, out, a_data, b_data, plan, alpha,
                              c_zero=c_zero,
                              defer=abft_defer and c_zero,
                              shape_key=shape_key)
    except _abft.PrecisionExceededError as exc:
        # adaptive-precision promote, not corruption: re-execute at
        # native precision (the cells were promoted when this raised)
        return _promote_execute(c_data, a_data, b_data, plan, alpha,
                                c_zero, base, exc)
    except Exception as exc:  # noqa: BLE001 — classified + recorded
        kind = _classify_failure(exc)
        board.record_failure(plan.driver, shape_key, kind=kind)
        _record_driver_failure(plan.driver, kind, exc, shape_key)
        return _failover_execute(c_data, a_data, b_data, plan, alpha,
                                 c_zero, exc=exc, base=base)
    board.record_success(plan.driver, shape_key)
    return out


def _execute_plan(c_data, a_data, b_data, plan: Optional[StackPlan], alpha=1.0,
                  c_zero: bool = False):
    """Run one prepared plan (the driver dispatch proper; failover and
    fault hooks live in `execute_stack`)."""
    if plan is None:
        return c_data
    compiled, jit_fn_name, jit_key = _record_stack_jit(
        plan, c_data, a_data, b_data)
    want_xla_cost = compiled and _costmodel.xla_capture_enabled()
    if plan.driver == "host":
        from dbcsr_tpu import native

        ai, bi, ci = plan.host_idx
        if c_zero:
            c_np = np.zeros(c_data.shape, np.dtype(c_data.dtype))
        else:
            c_np = np.array(c_data)  # writable host copy (memcpy)
            _mempool.record_d2h(c_np.nbytes)
        a_np, b_np = np.asarray(a_data), np.asarray(b_data)
        _mempool.record_d2h(a_np.nbytes + b_np.nbytes)
        ok = native.host_smm(c_np, a_np, b_np, ai, bi, ci, alpha)
        if ok:
            _mempool.record_h2d(c_np.nbytes)
            return jnp.asarray(c_np)
        # native library vanished after planning (e.g. DBCSR_TPU_NATIVE
        # flipped): rebuild the plan in place without the host driver.
        # prepare_stack re-checks _host_smm_available, which now fails,
        # so the rebuild falls through to the XLA selection — no global
        # config mutation (the crosspack demotion pattern).
        import warnings

        warnings.warn(
            "native host driver unavailable at execute time; rebuilding "
            "as an XLA plan",
            RuntimeWarning,
            stacklevel=2,
        )
        new_plan = prepare_stack(
            c_data, a_data, b_data, ai, bi, ci,
            a_pad_row=plan.a_pad_row, b_pad_row=plan.b_pad_row,
        )
        if new_plan.driver == "host":  # cannot happen; guard recursion
            raise RuntimeError("host driver rebuild selected host again")
        for slot in StackPlan.__slots__:
            setattr(plan, slot, getattr(new_plan, slot))
        return execute_stack(c_data, a_data, b_data, plan, alpha)
    if plan.precision is not None:
        from dbcsr_tpu.acc import precision as precision_mod

        precision_mod.note_launch(str(jnp.dtype(c_data.dtype)),
                                  plan.precision)
    if plan.driver == "xla_group":
        if plan.append_a_pad:
            a_data = _append_pad_row(a_data)
        if plan.append_b_pad:
            b_data = _append_pad_row(b_data)
        alpha_dev = jnp.asarray(alpha, dtype=c_data.dtype)
        _note_group_span(plan, a_data, b_data)
        if want_xla_cost:
            _capture_stack_xla_cost(
                jit_fn_name, jit_key, _process_stack_xla_group,
                (c_data, a_data, b_data, *plan.group_idx, alpha_dev),
                c_data, a_data, b_data, plan.group_launched,
                prec=plan.precision, dot_form=plan.dot_form,
            )
        return _process_stack_xla_group(
            c_data, a_data, b_data, *plan.group_idx, alpha_dev,
            prec=plan.precision, dot_form=plan.dot_form,
        )
    if plan.driver == "pallas_cross":
        from dbcsr_tpu.acc import pallas_smm

        cfg = get_config()
        cross_variant = "crosspack_vmem" if plan.cross_vmem else "crosspack"
        try:
            if cfg.validate_kernels and plan.val_idx is not None:
                key = (
                    a_data.shape[1], b_data.shape[2], a_data.shape[2],
                    str(jnp.dtype(c_data.dtype)), cross_variant, plan.pack,
                )
                if key not in _validated_kernels:
                    ai, bi, ci = plan.val_idx
                    with timed("kernel_validate"):
                        _validate_pallas_kernel(
                            c_data, a_data, b_data, ai, bi, ci,
                            None if plan.append_a_pad else plan.a_pad_row,
                            None if plan.append_b_pad else plan.b_pad_row,
                            None, variant=cross_variant, pack=plan.pack,
                        )
                    _validated_kernels.add(key)
            a_pad = _append_pad_row(a_data) if plan.append_a_pad else a_data
            b_pad = _append_pad_row(b_data) if plan.append_b_pad else b_data
            a_data_t = jnp.swapaxes(a_pad, 1, 2)
            alpha_arr = jnp.asarray([[alpha]], dtype=jnp.float32)
            interpret = jax.devices()[0].platform != "tpu"
            P, R = plan.pack
            launch_fn = (pallas_smm._pallas_crosspack_vmem if plan.cross_vmem
                         else pallas_smm._pallas_crosspack)
            # numpy c_data would crash scatter_lane_outputs (.at[]) and
            # the demotion handler would then blacklist a perfectly
            # good kernel shape — coerce up front
            c_out = jnp.asarray(c_data)
            for lc in plan.cross_launches:
                with jax.enable_x64(False):
                    outs = launch_fn(
                        c_out, a_data_t, b_pad,
                        lc["ai"], lc["bi"], lc["cg"], lc["cl"],
                        alpha_arr, P=P, R=R, nc_out=lc["nc_out"],
                        interpret=interpret,
                    )
                c_out = pallas_smm.scatter_lane_outputs(
                    c_out, outs, lc["scatter_idx"])
            # kernel proven on this backend: drop the demotion payload
            # (host index copies kept only until the first success)
            plan.cross_src = None
            return c_out
        except KernelValidationError:
            raise  # numeric corruption: hard fail, never fall back
        except Exception as exc:
            # compile/lowering failure (e.g. a Mosaic gap on this
            # backend): demote the shape and rebuild the plan IN PLACE
            # as a base-kernel plan from the retained source indices —
            # the reference's unsupported-kernel fallback
            # (`libsmm_acc.cpp:227-249`)
            if plan.cross_src is None or _is_deleted(c_data):
                raise  # nothing to rebuild from, or C went with a scatter
            import warnings

            shape_key = _stack_shape_key(c_data, a_data, b_data)
            msg = f"{type(exc).__name__}: {exc}"
            transient = ("RESOURCE_EXHAUSTED" in msg
                         or "out of memory" in msg.lower())
            if not transient:
                # a lowering gap is deterministic — blacklist the shape;
                # resource pressure is not — fall back this time only
                _cross_disabled.add(shape_key)
            _metrics.counter(
                "dbcsr_tpu_crosspack_fallback_total",
                "crosspack plans demoted to the base kernel after the "
                "kernel failed to compile or run, by reason",
            ).inc(reason="transient" if transient else "lowering")
            warnings.warn(
                f"crosspack kernel failed on this backend for shape "
                f"{shape_key} ({msg}); falling back to the base kernel"
                + ("" if transient else " for this session"),
                RuntimeWarning,
                stacklevel=2,
            )
            ai, bi, ci = plan.cross_src
            # the rebuild must not re-select crosspack; for transient
            # failures the disable is scoped to this rebuild only
            _cross_disabled.add(shape_key)
            try:
                new_plan = prepare_stack(
                    c_data, a_data, b_data, ai, bi, ci,
                    a_pad_row=None if plan.append_a_pad else plan.a_pad_row,
                    b_pad_row=None if plan.append_b_pad else plan.b_pad_row,
                )
            finally:
                if transient:
                    _cross_disabled.discard(shape_key)
            for slot in StackPlan.__slots__:  # cached plans heal too
                setattr(plan, slot, getattr(new_plan, slot))
            return execute_stack(c_data, a_data, b_data, plan, alpha)
    if plan.driver == "pallas":
        from dbcsr_tpu.acc import pallas_smm

        _ensure_pallas_validated(c_data, a_data, b_data, plan)
        if plan.append_a_pad:
            a_data = _append_pad_row(a_data)
        if plan.append_b_pad:
            b_data = _append_pad_row(b_data)
        alpha_arr = jnp.asarray([[alpha]], dtype=jnp.float32)
        interpret = jax.devices()[0].platform != "tpu"
        with jax.enable_x64(False):
            c_data = pallas_smm.process_launches(
                c_data, a_data, b_data, plan.launches, alpha_arr,
                r_grp=plan.r_grp, kmerge=plan.kmerge, interpret=interpret,
            )
        return c_data
    alpha_dev = jnp.asarray(alpha, dtype=c_data.dtype)
    ai, bi, ci = plan.xla_idx
    fn = (_process_stack_xla_flat if plan.driver == "xla_flat"
          else _process_stack_xla)
    if want_xla_cost:
        _capture_stack_xla_cost(
            jit_fn_name, jit_key, fn,
            (c_data, a_data, b_data, ai, bi, ci, alpha_dev),
            c_data, a_data, b_data, int(ai.size),
            prec=plan.precision,
        )
    return fn(c_data, a_data, b_data, ai, bi, ci, alpha_dev,
              prec=plan.precision)


def process_stack(c_data, a_data, b_data, a_idx, b_idx, c_idx, alpha=1.0,
                  a_pad_row=None, b_pad_row=None):
    """Process a full (possibly large) stack, chunked to mm_stack_size.

    ``c_idx`` must be sorted ascending (the stack builder guarantees it);
    chunk boundaries preserve order, so accumulation into each C block
    happens in a fixed, reproducible order (ref determinism requirement:
    stack order is deterministic in `dbcsr_mm_csr.F`).

    ``a_pad_row``/``b_pad_row`` optionally name a guaranteed-zero row of
    the data arrays (the engine's bucket padding) used by the Pallas
    path to mask short groups.

    Returns the updated ``c_data`` device array.
    """
    plan = prepare_stack(c_data, a_data, b_data, a_idx, b_idx, c_idx,
                         a_pad_row=a_pad_row, b_pad_row=b_pad_row)
    return execute_stack(c_data, a_data, b_data, plan, alpha)


# ------------------------------------------------------------------ fused
# superstack execution: every span (one per (abin, bbin) pair) whose
# stack targets the SAME C bin is lowered into a single jitted program
# with a donated C argument.  The per-span path pays, for each of a
# bin's N spans, one Python→XLA dispatch round-trip plus a full
# read-modify-write of the bin's device buffer; the fused launch pays
# both exactly once per bin — the TPU-side realization of the
# reference's stack batching (amortize launch overhead across thousands
# of block products, `dbcsr_mm_accdrv.F:279-326`).

_DISPATCHES_NAME = "dbcsr_tpu_dispatches_total"
_DISPATCHES_HELP = (
    "engine dispatch round-trips by mode: one per executed span in "
    "per_span mode, one per fused C-bin (or mesh) launch in fused "
    "mode, one per tick/shift region under the pipelined distributed "
    "drivers (cannon_db ring metronome, gather_pipe chunked "
    "all-gather)")
_FUSED_SPANS_NAME = "dbcsr_tpu_fused_spans"
_FUSED_SPANS_HELP = (
    "spans (or mesh tick-chunks) carried by each single fused launch")
_FUSED_SPANS_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)

# the breaker/metrics pseudo-driver name of a fused C-bin launch: its
# failures never condemn the per-span drivers (the failing span is
# unknown from outside the program), they route the bin back to the
# per-span path where the real chain takes over
FUSED_DRIVER = "fused"


def record_dispatch(mode: str, fused_spans: Optional[int] = None) -> None:
    """Count one engine dispatch round-trip, and — for fused launches —
    how many spans it carried (the amortization histogram)."""
    _metrics.counter(_DISPATCHES_NAME, _DISPATCHES_HELP).inc(mode=mode)
    if fused_spans is not None:
        _metrics.histogram(
            _FUSED_SPANS_NAME, _FUSED_SPANS_HELP,
            buckets=_FUSED_SPANS_BUCKETS,
        ).observe(fused_spans)


_XLA_FAMILY = ("xla", "xla_flat", "xla_group")


class SuperstackPlan:
    """A prepared fused C-bin launch: the per-span `StackPlan`s (whose
    device index arrays are reused as-is) plus the cached jitted
    program that chains their kernels.  Built by `prepare_superstack`,
    run by `execute_superstack`; the engine caches it next to the
    per-span plans in `mm.multiply._plan_cache`."""

    __slots__ = ("family", "sig", "plans", "fn")

    def __init__(self, family, sig, plans, fn):
        self.family = family      # "xla" | "pallas" | "host"
        self.sig = sig
        self.plans = plans
        self.fn = fn
        # staleness note: a failover heals per-span plans IN PLACE
        # (driver changes), which invalidates this fused program — the
        # guard lives in `mm.multiply._CachedSpans.superstack_for`,
        # which keys the cached decision by the spans' driver tuple

    def nbytes(self) -> int:
        """Device bytes pinned beyond the per-span plans: none — the
        fused program reuses their index arrays."""
        return 0


def prepare_superstack(plans) -> Optional[SuperstackPlan]:
    """Lower the spans of one C bin (accumulation order preserved) into
    a fused plan, or return None when they cannot fuse.

    Fusable families — all spans must belong to ONE of:
    * the pure-XLA drivers (``xla``/``xla_flat``/``xla_group``, freely
      mixed): chained scan bodies inside one donated-C jit;
    * ``pallas``: the base kernel's launch loop traced inside one jit
      (`pallas_smm.process_launches`); first-use validation runs before
      the first fused dispatch, outside the program;
    * ``host``: the native C++ driver with ONE C fetch + writeback for
      the whole bin instead of one per span.

    ``pallas_cross`` spans keep the per-span path (their compile-
    failure demotion and lane scatters are execute-time host logic), as
    do mixed-family bins."""
    if not plans or any(p is None for p in plans):
        return None
    drivers = [p.driver for p in plans]
    if all(d in _XLA_FAMILY for d in drivers):
        family = "xla"
    elif all(d == "pallas" for d in drivers):
        family = "pallas"
    elif all(d == "host" for d in drivers):
        family = "host"
    else:
        return None
    if family == "host":
        return SuperstackPlan("host", None, list(plans), None)
    interpret = (jax.devices()[0].platform != "tpu"
                 if family == "pallas" else False)
    sig = tuple(
        (
            p.driver,
            (len(p.group_idx) if p.driver == "xla_group"
             else 3 if p.driver in _XLA_FAMILY else 3 * len(p.launches)),
            bool(p.append_a_pad), bool(p.append_b_pad),
            p.r_grp, bool(p.kmerge), p.precision, p.dot_form,
        )
        for p in plans
    )
    sig = (family, interpret, sig)
    return SuperstackPlan(family, sig, list(plans), _fused_fn(sig))


from collections import OrderedDict as _OrderedDict  # noqa: E402

# fused callables keyed by STRUCTURE (drivers, launch counts, static
# kernel params) — jax.jit handles shape/dtype specialization under
# each; LRU-bounded so pattern churn cannot pin compiled programs
_fused_fns: "_OrderedDict[tuple, object]" = _OrderedDict()
_FUSED_FN_MAX = 128


def _fused_fn(sig):
    fn = _fused_fns.get(sig)
    if fn is not None:
        _fused_fns.move_to_end(sig)
        return fn
    family, interpret, spans_sig = sig

    def fused_superstack(c_data, alpha_dev, *flat):
        from dbcsr_tpu.acc import pallas_smm

        pos = 0
        for i, (driver, n_idx, ap_a, ap_b, r_grp, kmerge, prec,
                dot_form) in enumerate(spans_sig):
            a_data = flat[pos]
            b_data = flat[pos + 1]
            idx = flat[pos + 2: pos + 2 + n_idx]
            pos += 2 + n_idx
            # device time per span, driver and (m,n,k) of one launch
            with device_scope(_span_scope(i, driver, c_data, a_data)):
                if ap_a:
                    a_data = _append_pad_row(a_data)
                if ap_b:
                    b_data = _append_pad_row(b_data)
                if driver == "xla_group":
                    c_data = _stack_phases_group(
                        c_data, a_data, b_data, *idx, alpha_dev, prec=prec,
                        dot_form=dot_form)
                elif driver == "pallas":
                    launches = [tuple(idx[3 * j: 3 * j + 3])
                                for j in range(n_idx // 3)]
                    c_data = pallas_smm.process_launches(
                        c_data, a_data, b_data, launches, alpha_dev,
                        r_grp=r_grp, kmerge=kmerge, interpret=interpret,
                    )
                else:
                    body = (_stack_phases_xla_flat if driver == "xla_flat"
                            else _stack_phases_xla)
                    c_data = body(c_data, a_data, b_data, *idx, alpha_dev,
                                  prec=prec)
        return c_data

    if any(span[-1] == "sliced" for span in spans_sig):
        # the sliced spans' scopes are new (`stk_dot/stk_split`) and a
        # program whose scopes change gets a new name (above); the
        # programs of every other dtype are what they were and keep
        # theirs, and with it their compile-cache entries
        fused_superstack.__name__ = "fused_superstack_sliced"
    fn = jax.jit(fused_superstack, donate_argnums=0)
    _fused_fns[sig] = fn
    while len(_fused_fns) > _FUSED_FN_MAX:
        _fused_fns.popitem(last=False)
    return fn


def _span_scope(i: int, driver: str, c_data, a_data) -> str:
    """`span<i>.<driver>.<m>x<n>x<k>`: the scope of span ``i`` of a fused
    bin, its block shape read off the operands while they are traced."""
    m, n = c_data.shape[1:]
    return f"span{i}.{driver}.{m}x{n}x{a_data.shape[2]}"


def _superstack_key(c_data, nspans: int) -> tuple:
    """Breaker/metrics shape key of a fused C-bin launch: the bin's
    block shape + span count + dtype (per-span (m,n,k) keys stay with
    the per-span drivers)."""
    return (c_data.shape[1], c_data.shape[2], nspans,
            str(jnp.dtype(c_data.dtype)))


def _decompose_superstack(c_data, a_datas, b_datas, plans, alpha, c_zero,
                          why: str = ""):
    """Run a fused bin's spans through the per-span engine instead —
    the fused path's failover contract: a fused launch never hard-fails
    the multiply while per-span execution (with its full driver chain)
    can still make progress.  ``c_zero`` holds for the FIRST span only
    (later spans accumulate onto its contribution)."""
    _events.publish(
        "superstack_decompose", {"why": why[:200], "spans": len(plans)},
        flight=("superstack_decompose",
                {"why": why[:200], "spans": len(plans)}),
    )
    out = c_data
    first = True
    for plan, a_d, b_d in zip(plans, a_datas, b_datas):
        out = execute_stack(out, a_d, b_d, plan, alpha,
                            c_zero=c_zero and first)
        first = False
    return out


def _record_superstack_jit(splan: SuperstackPlan, c_data, a_datas,
                           b_datas):
    """Jit-cache mirror + per-driver device-entry accounting of one
    fused launch (the fused analog of `_record_stack_jit`).  Returns
    ``(compiled, key)`` so the XLA-cost capture can fire on fresh
    specializations, like the per-span path's."""
    from dbcsr_tpu.acc import pallas_smm

    dt = str(jnp.dtype(c_data.dtype))
    idx_shapes = []
    for plan, a_d, b_d in zip(splan.plans, a_datas, b_datas):
        if plan.driver in ("xla", "xla_flat"):
            idx_shapes.append(plan.xla_idx[0].shape)
            dev_entries = int(plan.xla_idx[0].size)
        elif plan.driver == "xla_group":
            idx_shapes.append(_group_idx_shapes(plan))
            dev_entries = plan.group_launched
        else:  # pallas
            idx_shapes.append(tuple(lc[0].shape for lc in plan.launches))
            dev_entries = pallas_smm.launch_entries(plan.launches,
                                                    plan.r_grp)
        _note_launched_entries(plan, _mnk_label(a_d, b_d), dev_entries)
    key = (splan.sig, c_data.shape, dt,
           tuple(a.shape for a in a_datas),
           tuple(b.shape for b in b_datas), tuple(idx_shapes))
    return _metrics.record_jit("acc.smm._fused_superstack", key), key


def _superstack_model(splan: SuperstackPlan, c_data, a_datas,
                      b_datas) -> dict:
    """Analytic flops/bytes of one fused launch: per-span DEVICE
    entries (XLA counts the masked pad work too), bin C traffic charged
    once (`costmodel.superstack_bytes` — the convention the engine's
    per-span recording mirrors)."""
    from dbcsr_tpu.acc import pallas_smm

    spans = []
    for plan, a_d, b_d in zip(splan.plans, a_datas, b_datas):
        m, k = a_d.shape[1], a_d.shape[2]
        n = b_d.shape[2]
        if plan.driver in ("xla", "xla_flat"):
            entries = int(plan.xla_idx[0].size)
        elif plan.driver == "xla_group":
            entries = plan.group_launched
        else:
            entries = pallas_smm.launch_entries(plan.launches, plan.r_grp)
        spans.append((m, n, k, entries))
    return {
        "flops": sum(_costmodel.stack_flops(m, n, k, e)
                     for m, n, k, e in spans),
        "bytes": _costmodel.superstack_bytes(
            spans, nseg=c_data.shape[0],
            itemsize=jnp.dtype(c_data.dtype).itemsize),
    }


def _dispatch_superstack(c_data, a_datas, b_datas, splan: SuperstackPlan,
                         alpha, c_zero: bool):
    """Issue one fused launch (no failover here — `execute_superstack`
    owns the guard rails)."""
    plans = splan.plans
    if splan.family == "host":
        from dbcsr_tpu import native

        if c_zero:
            c_np = np.zeros(c_data.shape, np.dtype(c_data.dtype))
        else:
            c_np = np.array(c_data)  # ONE writable host copy per bin
            _mempool.record_d2h(c_np.nbytes)
        for plan, a_d, b_d in zip(plans, a_datas, b_datas):
            ai, bi, ci = plan.host_idx
            a_np, b_np = np.asarray(a_d), np.asarray(b_d)
            _mempool.record_d2h(a_np.nbytes + b_np.nbytes)
            ok = native.host_smm(c_np, a_np, b_np, ai, bi, ci, alpha)
            if not ok:
                raise RuntimeError(
                    "native host driver unavailable during a fused "
                    "superstack launch")
        _mempool.record_h2d(c_np.nbytes)
        return jnp.asarray(c_np)
    compiled, jit_key = _record_superstack_jit(splan, c_data, a_datas,
                                               b_datas)
    if any(p.precision is not None for p in plans):
        from dbcsr_tpu.acc import precision as precision_mod

        dt = str(jnp.dtype(c_data.dtype))
        for plan in plans:
            if plan.precision is not None:
                precision_mod.note_launch(dt, plan.precision)
    flat = []
    for plan, a_d, b_d in zip(plans, a_datas, b_datas):
        flat.append(a_d)
        flat.append(b_d)
        if plan.driver in ("xla", "xla_flat"):
            flat.extend(plan.xla_idx)
        elif plan.driver == "xla_group":
            flat.extend(plan.group_idx)
            _note_group_span(plan, a_d, b_d)
        else:
            for lc in plan.launches:
                flat.extend(lc)
    if splan.family == "pallas":
        alpha_dev = jnp.asarray([[alpha]], dtype=jnp.float32)
        with jax.enable_x64(False):
            return splan.fn(jnp.asarray(c_data), alpha_dev, *flat)
    alpha_dev = jnp.asarray(alpha, dtype=c_data.dtype)
    if compiled and _costmodel.xla_capture_enabled():
        # the fused program IS the compiled unit now: the opt-in
        # model-vs-XLA drift check captures it whole, with the
        # per-span analytic model summed (C round-trip charged once)
        _costmodel.capture_xla_cost(
            "acc.smm._fused_superstack", jit_key, splan.fn,
            (c_data, alpha_dev, *flat),
            model=_superstack_model(splan, c_data, a_datas, b_datas),
        )
    return splan.fn(c_data, alpha_dev, *flat)


def execute_superstack(c_data, a_datas, b_datas, splan: SuperstackPlan,
                       alpha=1.0, c_zero: bool = False,
                       abft_defer: bool = False):
    """Run all spans of one C bin as a single fused dispatch, guarded
    by the resilience layer: injected ``execute_superstack`` faults
    fire here, a failing fused launch is recorded against the bin's
    ``fused`` breaker and DECOMPOSES to per-span execution (where each
    span's own driver chain applies) rather than hard-failing, and an
    open fused breaker routes the bin per-span pre-emptively.

    Returns ``(new_c_buffer, fused)`` — ``fused`` is False when the
    bin actually ran per-span (breaker routing or failure decompose),
    so the caller's cost accounting can charge the per-span C
    round-trips that really happened instead of the fused convention.
    On a fused launch the program donates the old buffer, so the N−1
    intermediate copies of the per-span path never materialize."""
    plans = splan.plans
    board = _breaker.get_board()
    faults_on = _faults.active()
    abft_on = _abft.enabled()
    finite_on = faults_on or _output_checks_enabled()
    checks_on = finite_on or abft_on
    bin_key = _superstack_key(c_data, len(plans))
    if board._breakers:
        # a fused program cannot route around a quarantined member
        # kernel mid-launch, so any span whose own (driver, shape)
        # breaker is not fully closed sends the bin per-span — where
        # execute_stack's allow() gate runs the proper trial/failover.
        # state() is a read-only probe: it must not consume the
        # half-open trial admission the per-span path will claim; and
        # it must run BEFORE allow(FUSED) below, whose half-open trial
        # admission would otherwise be consumed and never resolved
        # (record_success/failure both skipped on this path), wedging
        # the fused breaker in half-open for good.
        for plan, a_d, b_d in zip(plans, a_datas, b_datas):
            if board.state(plan.driver,
                           _stack_shape_key(c_data, a_d, b_d)) \
                    != _breaker.CLOSED:
                return _decompose_superstack(
                    c_data, a_datas, b_datas, plans, alpha, c_zero,
                    why=f"span-breaker:{plan.driver}"), False
        if not board.allow(FUSED_DRIVER, bin_key):
            return _decompose_superstack(
                c_data, a_datas, b_datas, plans, alpha, c_zero,
                why="breaker-open"), False
    # first-use pallas validation happens OUTSIDE the fused program;
    # a validation failure walks the same decompose path below, where
    # execute_stack applies the hard-open breaker + chain contract.
    # The pristine copy is taken INSIDE the try: allow() above may have
    # consumed the fused half-open trial admission, and a copy failure
    # (device OOM on a big bin) must resolve that trial via
    # record_failure below — never leave the breaker wedged half-open.
    # c_data itself is still pristine then (nothing dispatched), so
    # the decompose path recovers from it.
    base = c_data
    try:
        if checks_on and splan.family != "host" and not c_zero:
            # the host family works on its own numpy copy and never
            # mutates c_data, so the original is always recoverable
            # there — don't pay a full-bin device copy for it; nor for
            # a first-touch (beta==0) bin, whose pristine C is zeros
            # the failure path re-synthesizes from metadata
            base = jnp.array(c_data, copy=True)
        if splan.family == "pallas":
            for plan, a_d, b_d in zip(plans, a_datas, b_datas):
                _ensure_pallas_validated(c_data, a_d, b_d, plan)
        # counted before the launch so a dispatch-then-fail round-trip
        # (injected faults model exactly that) still shows in the
        # per-mode comparison; the decompose's per_span dispatches are
        # counted on top — both round-trips happened
        record_dispatch("fused", fused_spans=len(plans))
        if faults_on:
            _faults.maybe_inject("execute_superstack")
        out = _dispatch_superstack(c_data, a_datas, b_datas, splan, alpha,
                                   c_zero)
        if faults_on:
            out = _faults.corrupt("execute_superstack", out)
        if finite_on and _output_corrupted(out):
            raise CorruptedOutputError(
                "fused superstack launch produced non-finite output blocks")
        if abft_on:
            # one probe covers the whole fused bin (the right side sums
            # every span); a mismatch decomposes to per-span execution,
            # where each span's own ABFT + chain recovery applies
            _abft.check_superstack(base, out, a_datas, b_datas, splan,
                                   alpha, c_zero=c_zero,
                                   defer=abft_defer and c_zero,
                                   shape_key=bin_key)
    except _abft.PrecisionExceededError:
        # adaptive-precision promote (cells already promoted): rerun
        # the bin per-span from the pristine buffer, where each span's
        # own probe + promote/re-execute handler applies — no breaker
        # feed, no SDC attribution
        if c_zero and _is_deleted(base):
            base = jnp.zeros(c_data.shape, np.dtype(c_data.dtype))
        if _is_deleted(base):
            raise
        out = _decompose_superstack(
            base, a_datas, b_datas, plans, alpha, c_zero,
            why="precision-promote")
        return out, False
    except Exception as exc:  # noqa: BLE001 — classified + recorded
        kind = _classify_failure(exc)
        board.record_failure(FUSED_DRIVER, bin_key, kind=kind)
        _record_driver_failure(FUSED_DRIVER, kind, exc, bin_key)
        if c_zero and _is_deleted(base):
            # the copy was skipped (pristine C is zeros): rebuild it
            base = jnp.zeros(c_data.shape, np.dtype(c_data.dtype))
        if _is_deleted(base):
            # the failing launch consumed (donated) the only copy of
            # the bin's C buffer: per-span recovery is impossible here
            raise
        _record_fallback(FUSED_DRIVER, "per_span", bin_key)
        out = _decompose_superstack(
            base, a_datas, b_datas, plans, alpha, c_zero,
            why=f"{type(exc).__name__}: {exc}")
        if kind == "sdc":
            _abft.record_recovery(FUSED_DRIVER)
        return out, False
    board.record_success(FUSED_DRIVER, bin_key)
    return out, True


def _on_tpu() -> bool:
    """Dispatch-decision platform gate — honors the CPU suite's
    platform_override seam; execution-level interpret= flags read the
    real platform directly (see config.effective_platform)."""
    from dbcsr_tpu.core.config import effective_platform

    return effective_platform() == "tpu"


def _host_smm_available(dtype) -> bool:
    """True when the native C++ stack driver can run this stack: CPU
    backend (no device round-trip), a dtype the C++ kernel's switch
    handles (the reference enum codes r4/r8/c4/c8 — not bf16), and the
    native library built.

    Gates on the REAL backend platform as well as `effective_platform`
    (ADVICE r5): the host driver changes where compute RUNS, not just
    policy, so `platform_override='cpu'` on a real TPU must never route
    stacks through a per-stack device->host->device round trip —
    the behavior `prepare_stack`'s own comment calls catastrophic.
    config.py's contract is that execution-level choices always follow
    the real platform; the seam only steers decisions."""
    from dbcsr_tpu.core.config import effective_platform

    if effective_platform() != "cpu":
        return False
    if jax.devices()[0].platform != "cpu":
        return False
    from dbcsr_tpu.core import kinds

    try:
        code = kinds.enum_of(dtype)
    except KeyError:
        return False
    if code not in (1, 3, 5, 7):
        return False
    from dbcsr_tpu import native

    return native.get_lib() is not None


def plan_exec_dtype(plan, request_dtype_name: str) -> str:
    """The dtype a plan's compute actually EXECUTES at: the demoted
    compute dtype for a precision-demoted plan, else the request dtype.
    Feeds `core.stats.record_stack` so the roofline rollup reports
    %-of-peak against the executed compute dtype (a demoted launch must
    not be scored against the request dtype's peak)."""
    prec = getattr(plan, "precision", None) if plan is not None else None
    return prec[0] if prec is not None else request_dtype_name


def _stack_shape_key(c_data, a_data, b_data) -> tuple:
    """(m, n, k, dtype) of a stack — the single key construction shared
    by crosspack dispatch and the demotion handler (they MUST match, or
    a demoted shape could re-select the failing kernel and recurse)."""
    return (
        a_data.shape[1], b_data.shape[2], a_data.shape[2],
        str(jnp.dtype(c_data.dtype)),
    )


# shapes whose crosspack kernel failed to COMPILE/run on this backend
# (not a numeric mismatch): dispatch demotes them to the base kernel
# for the session — the role of the reference's unsupported-kernel
# fallback (`libsmm_acc.cpp:227-249` falls back when no JIT kernel
# exists for an (m, n, k))
_cross_disabled: set = set()


def _pallas_supported(cfg, c_data, a_data, b_data) -> bool:
    if cfg.mm_driver == "xla":
        return False
    if not cfg.use_pallas and cfg.mm_driver not in ("pallas", "pallas_cross"):
        return False
    # off-TPU, pallas_call runs in INTERPRET mode — a per-step Python
    # evaluator meant for kernel testing, ~1000x slower at driver scale
    # (measured: 2000^2 23^3 bf16 north-star slice, 22 s/rep vs 0.09 s
    # for the f64 xla path on the same config).  Auto dispatch must
    # never select it; only an explicit mm_driver force (tests, kernel
    # debugging) may.
    if not _on_tpu() and cfg.mm_driver not in ("pallas", "pallas_cross"):
        return False
    try:
        from dbcsr_tpu.acc.pallas_smm import supports

        return supports(c_data, a_data, b_data)
    except Exception:
        return False


@jax.jit
def transpose_blocks(data):
    """Batched in-register block transpose: (N, m, n) -> (N, n, m).

    Ref `libsmm_acc_transpose` (`acc_libsmm.h`, kernel
    `smm_acc_transpose.h`) — used to put A panels in the (m, k)
    layout the multiply kernel wants.
    """
    return jnp.swapaxes(data, 1, 2)


@jax.jit
def _block_norms(data):
    sq = jnp.real(data * jnp.conj(data)) if jnp.iscomplexobj(data) else data * data
    return jnp.sqrt(jnp.sum(sq, axis=(1, 2), dtype=_accum_dtype(sq.dtype)))


def block_norms(data):
    """Per-block Frobenius norms, (N, m, n) -> (N,) real.

    Ref `c_calculate_norms` (`src/acc/cuda_hip/calculate_norms.cpp`),
    used for on-the-fly norm-product filtering in the stack builder.
    """
    out = np.asarray(_block_norms(data), dtype=real_dtype_of(data.dtype))
    _mempool.record_d2h(out.nbytes)
    return out
