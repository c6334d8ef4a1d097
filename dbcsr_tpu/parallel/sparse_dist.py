"""Block-sparse Cannon over the ('kl','pr','pc') mesh.

The sparse counterpart of `cannon.py` and the core re-design of
`multiply_cannon` (`dbcsr_mm_cannon.F:837`): device work and HBM
traffic scale with the number of nonzero blocks, not the dense shape.

How the reference's machinery maps here:

* `make_m2s` matrix->images predistribution (`dbcsr_mm_cannon.F:146,292`)
  -> host-side panel assembly: every device gets a zero-padded array of
  its panel's blocks, **already placed at the Cannon-skewed position**,
  so the initial skew costs no communication at all.
* per-tick index/data isend/irecv of panels (:1420-1590) ->
  `lax.ppermute` ring shifts of the whole padded panel along 'pc' (A)
  and 'pr' (B).
* hash-based C-index build + stack fill (`dbcsr_mm_csr.F:178`) -> the
  full symbolic product on host (vectorized / native engine), carved
  into one parameter stack per (device, tick), padded to a common
  static shape; padded entries point at C slot `cap_c` and are
  dropped by the accumulation.
* per-thread multrec/stacks -> per tick per device the stack programs
  of `dbcsr_tpu.acc.smm`: for emulated dtypes (`_stack_r0`) its
  grouped chunk loop on tiles planned by its rules, the very function
  one chip's `xla_group` runs; else one gather + batched-matmul +
  segment-sum.
* 2.5D layers (`dbcsr_mm_3d.F`) -> the 'kl' mesh axis partitions the
  k block range; one `psum` over 'kl' completes C
  (ref `make_layers_3D_C_reduction`, `dbcsr_mm_3d.F:1037`).

Mixed block sizes are exact via zero padding to the max block shape
(padded k columns of A meet padded zero k rows of B).  Accumulation
order is fixed (stacks sorted by C slot, ticks sequential), so results
are bit-reproducible for a given mesh shape.
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dbcsr_tpu.core.matrix import NO_SYMMETRY, BlockSparseMatrix
from dbcsr_tpu.core.timings import device_scope, timed
from dbcsr_tpu.obs import costmodel as _costmodel
from dbcsr_tpu.obs import events as _events
from dbcsr_tpu.obs import flight as _flight
from dbcsr_tpu.obs import metrics as _metrics
from dbcsr_tpu.ops.transformations import desymmetrize
from dbcsr_tpu.parallel import overlap as _overlap
from dbcsr_tpu.parallel.overlap import _HashableMesh
from dbcsr_tpu.resilience import faults as _faults
from dbcsr_tpu.utils.rounding import bucket_size


def _adopt_panels(out: BlockSparseMatrix, keys: np.ndarray,
                  blocks: np.ndarray) -> BlockSparseMatrix:
    """Vectorized collection: carve (N, BM, BN) padded panel blocks into
    `out`'s shape bins directly (replaces the per-entry put_block loop;
    the collect half of `dbcsr_merge_all`,
    `dbcsr_work_operations.F:1393`)."""
    from dbcsr_tpu.core.matrix import _Bin, _bin_entries

    rows = (keys // out.nblkcols).astype(np.int64)
    cols = (keys % out.nblkcols).astype(np.int64)
    nb, nsl, shapes = _bin_entries(out.row_blk_sizes, out.col_blk_sizes, rows, cols)
    bins = []
    for b, (bm, bn) in enumerate(shapes):
        sel = np.nonzero(nb == b)[0]
        cap = bucket_size(len(sel))
        data = np.zeros((cap, int(bm), int(bn)), blocks.dtype)
        data[nsl[sel]] = blocks[sel, : int(bm), : int(bn)]
        bins.append(_Bin((int(bm), int(bn)), jnp.asarray(data), len(sel)))
    out.set_structure_from_device(keys, bins, binning=(nb, nsl, shapes))
    return out


def _dense_blocks_host(matrix: BlockSparseMatrix, bm: int, bn: int) -> np.ndarray:
    """(nblks, bm, bn) zero-padded host copies of all blocks, key order
    (one device fetch + one vectorized scatter per shape bin)."""
    if not matrix.valid:
        raise RuntimeError("finalize() before panel assembly")
    out = np.zeros((matrix.nblks, bm, bn), np.dtype(matrix.dtype))
    for b_id, b in enumerate(matrix.bins):
        sel = np.nonzero(matrix.ent_bin == b_id)[0]
        if len(sel):
            host = np.asarray(b.data[: b.count])
            out[sel, : b.shape[0], : b.shape[1]] = host[matrix.ent_slot[sel]]
    return out


def _panel_slots(panel_ids: np.ndarray) -> np.ndarray:
    """Slot of each entry within its panel (entries pre-sorted by key
    within equal panel_ids groups).  Panels are few: ids that fit 16
    bits are sorted as such (NumPy's stable sort of them is a radix
    sort), and a panel's start comes from the counts."""
    n = len(panel_ids)
    if not n:
        return np.empty(0, np.int64)
    counts = np.bincount(panel_ids)
    ids = panel_ids.astype(np.int16) if len(counts) <= 2 ** 15 else panel_ids
    order = np.argsort(ids, kind="stable")
    slots = np.empty(n, np.int64)
    slots[order] = np.arange(n) - np.repeat(np.cumsum(counts) - counts,
                                            counts)
    return slots


def _prepare_operands(matrix_a, matrix_b, matrix_c):
    """Shared multiply prologue: desymmetrize, finalize, compatibility
    guards.  Returns (a, b, matrix_c, dtype, bm, bk, bn)."""
    a = desymmetrize(matrix_a) if matrix_a.matrix_type != NO_SYMMETRY else matrix_a
    b = desymmetrize(matrix_b) if matrix_b.matrix_type != NO_SYMMETRY else matrix_b
    for m in (a, b, matrix_c):
        if m is not None and not m.valid:
            m.finalize()
    if matrix_c is not None and matrix_c.matrix_type != NO_SYMMETRY:
        matrix_c = desymmetrize(matrix_c)
    if not np.array_equal(a.col_blk_sizes, b.row_blk_sizes):
        raise ValueError("inner blockings differ")
    if matrix_c is not None and not (
        np.array_equal(matrix_c.row_blk_sizes, a.row_blk_sizes)
        and np.array_equal(matrix_c.col_blk_sizes, b.col_blk_sizes)
    ):
        raise ValueError("C blocking incompatible with op(A), op(B)")
    dtype = np.dtype(matrix_c.dtype) if matrix_c is not None else np.dtype(a.dtype)
    bm = int(a.row_blk_sizes.max()) if a.nblkrows else 1
    bk = int(a.col_blk_sizes.max()) if a.nblkcols else 1
    bn = int(b.col_blk_sizes.max()) if b.nblkcols else 1
    return a, b, matrix_c, dtype, bm, bk, bn


def _fill_stacks(group_id, st_a, st_b, st_c, nslots, cap_c, r0=0,
                 pad_a=0, pad_b=0, chunk_groups=0):
    """Sort stack entries by (slot-group, C slot, A slot) and scatter
    into a (nslots, s_cap, 3) array whose padding rows target the
    dropped segment cap_c.  Shared by the ungrouped and grouped Cannon
    assemblies and the all-gather one (the host-side analog of
    `dbcsr_mm_accdrv.F:364-423` stack sort/binning).

    ``r0 > 0`` plans the grouped layout instead, with the one-chip
    engine's rules and code (`acc/smm.py:build_stacks_group_tiles`, one
    stack a (device, tick)): width classes from the run lengths of all
    the stacks together, a run of at most r0 one group of the narrowest
    class that holds it, ``chunk_groups`` x r0 slots a chunk, every
    class's chunk capacity and the chunk count from the fullest stack
    so that one SPMD program serves every device and tick, and ``live``
    chunks per stack.  In-group pads name the guaranteed-zero panel
    rows ``pad_a``/``pad_b``, dead groups the dropped segment cap_c.
    Returns the `GroupTiles`."""
    from dbcsr_tpu import native

    order = native.sort_order(group_id, nslots, st_c, st_a)
    group_id, st_a, st_b, st_c = (
        group_id[order], st_a[order], st_b[order], st_c[order]
    )
    if r0:
        from dbcsr_tpu.acc.smm import build_stacks_group_tiles

        return build_stacks_group_tiles(
            group_id, nslots, st_c, st_a, st_b, r0, pad_a, pad_b, cap_c,
            chunk_groups)
    counts = np.bincount(group_id, minlength=nslots)
    s_cap = bucket_size(max(int(counts.max()), 1) if len(counts) else 1)
    stacks = np.zeros((nslots, s_cap, 3), np.int32)
    stacks[:, :, 2] = cap_c
    pos = np.arange(len(group_id)) - np.repeat(
        np.concatenate([[0], np.cumsum(counts)])[:-1], counts
    )
    stacks[group_id, pos, 0] = st_a
    stacks[group_id, pos, 1] = st_b
    stacks[group_id, pos, 2] = st_c
    return stacks


def _upload_stacks(stacks, mesh, lead: tuple):
    """The filled stacks of a plan on the mesh, every leaf under
    P("kl","pr","pc") with ``lead`` = (kl, pr, pc, nticks) in front:
    the flat (.., s_cap, 3) array, or for grouped stacks the pytree
    ``(live, ((ga, gb, gc) per width class))`` whose per-tick slice
    `acc/smm.py:group_chunk_loop` takes (``live`` one chunk count a
    device and tick).  A grouped plan's live and launched slots are
    counted here, once per built plan."""
    if isinstance(stacks, np.ndarray):
        host = stacks.reshape(lead + stacks.shape[1:])
    else:
        from dbcsr_tpu.acc.smm import _note_group_slots

        _note_group_slots(stacks, driver="mesh")
        host = (stacks.live.astype(np.int32).reshape(lead),
                tuple(tuple(x.reshape(lead + x.shape[1:]) for x in tile)
                      for tile in stacks.tiles))
    return jax.device_put(host, NamedSharding(mesh, P("kl", "pr", "pc")))


def _stacks_nbytes(stacks_dev) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(stacks_dev))


def _note_program(program: str, fn: str, *arrays, **static) -> None:
    """Report one launch of a mesh product's jitted ``fn`` to the jit
    mirror (`obs/metrics.record_jit`), keyed by what keys its jit cache
    (the shapes and dtypes of ``arrays``, any pytrees, and the
    ``static`` arguments), and count in
    `dbcsr_tpu_mesh_programs_total{program}` a shape it has not run
    before: what a product whose pattern moved compiles, and what
    bucketing the capacities is judged by."""
    key = (tuple((x.shape, str(x.dtype)) for x in jax.tree.leaves(arrays)),
           tuple(sorted(static.items())))
    if _metrics.record_jit("parallel.sparse_dist." + fn, key):
        _metrics.counter(
            "dbcsr_tpu_mesh_programs_total",
            "distinct shapes of the sparse mesh engine's programs "
            "(assembly, cut = panels out of a buffer every device "
            "holds, tick, shift, finish, run = the fused serial "
            "program, collect) that products have run: each is a "
            "compile or a load from the persistent cache",
        ).inc(program=program)


def _stack_chunk_groups(r0: int, bm: int, bn: int, bk: int, dtype) -> int:
    """Groups of r0 a chunk of the grouped ticks holds: the one-chip
    engine's `group_chunk_groups` (2 048 slots at 23^3 in f64)."""
    from dbcsr_tpu.acc.smm import group_chunk_groups
    from dbcsr_tpu.core.config import get_config

    if not r0:
        return 0
    return group_chunk_groups(r0, bm, bn, bk, np.dtype(dtype).itemsize,
                              get_config().mm_stack_size)


def _stack_r0(dtype) -> int:
    """The widest group of the mesh stacks, and the one gate between
    the grouped ticks (r0 > 0: `acc/smm.py`'s tiling rules and its
    `group_chunk_loop`, the program `xla_group` runs on one chip) and
    the flat ones (0: per-entry gathers and a segment sum): group
    emulated dtypes (f64/c128 — per-entry dots are MXU-starved under
    emulation, see `acc/smm.py:_stack_phases_group`).  Auto mode
    applies this on TPU only (f64 is native elsewhere; per-entry dots
    are fine there); mm_driver='xla_group' forces it on any platform
    (how the CPU-mesh tests cover the grouped layout)."""
    from dbcsr_tpu.acc.smm import emulated_dtype_on_tpu
    from dbcsr_tpu.core.config import get_config

    driver = get_config().mm_driver
    if driver == "xla_group":
        return 8
    if driver != "auto":
        return 0
    return 8 if emulated_dtype_on_tpu(dtype) else 0


def _stack_dot_form(r0: int, bk: int, dtype) -> str:
    """The form of the grouped ticks' dot, `acc/smm.py:group_dot_form`
    at the mesh stacks' depth (flat ticks, r0 = 0: the compiler's)."""
    from dbcsr_tpu.acc.smm import group_dot_form

    return group_dot_form(dtype, r0 * bk) if r0 else "compiler"


def _note_mesh_dot(plan) -> None:
    """Count one product's grouped mesh stacks by their dot's form
    and, sliced, their width classes by their folds."""
    if plan.r0:
        from dbcsr_tpu.acc.smm import note_group_dot

        classes = [tile[0].shape[-2:] for tile in plan.stacks_dev[1]]
        note_group_dot(plan.dot_form, f"{plan.bm}x{plan.bn}x{plan.bk}",
                       classes, plan.bk, driver="mesh")


_TICK_CHUNK_ENTRIES = 32768


def _tick_chunks(s_cap: int) -> tuple:
    """(nchunk, rows_per_chunk) of a FLAT tick (r0 = 0; a grouped
    tick's chunks are the plan's, `acc/smm.py:group_chunk_groups`),
    bounding per-tick gather/product temps to ~`_TICK_CHUNK_ENTRIES`
    entries.  Small grids concentrate the whole product in ONE
    tick (a 1x1 grid: everything), and an unchunked tick materializes
    (E, bm, bn) gather/product temps — 3 x 3.5 GB f64 at the north
    star, which thrashes memory (measured: a 1x1x1 CPU-mesh rep ran 7x
    the single-chip engine, nonlinearly worse with size; the
    single-chip path chunks at mm_stack_size for exactly this reason).
    `bucket_size` capacities are {4..7}*2^k, so the power-of-two chunk
    count always divides s_cap exactly (no tail, no re-read)."""
    target = _TICK_CHUNK_ENTRIES
    nchunk = 1
    while s_cap // nchunk > target and s_cap % (nchunk * 2) == 0:
        nchunk *= 2
    return nchunk, s_cap // nchunk


@functools.lru_cache(maxsize=None)
def _ring_perms(s: int) -> tuple:
    """(shift_a, shift_b) ring permutations — A left along 'pc', B up
    along 'pr' — built once per s instead of once per traced tick body
    (shared by the fused metronome and the split shift program)."""
    return (tuple(((j + 1) % s, j) for j in range(s)),
            tuple(((i + 1) % s, i) for i in range(s)))


def _stack_contrib(a, b, c, entries, *, cap_c, acc_dtype):
    """One FLAT stack chunk's contribution (r0 = 0, the dtypes a mesh
    multiplies natively; no cell runs it): per-entry gather → batched
    matmul → sorted segment-sum, under the one-chip bodies'
    `device_scope` names (`acc/smm.py`)."""
    with device_scope("stk_gather"):
        pa = jnp.take(a, entries[:, 0], axis=0)
        pb = jnp.take(b, entries[:, 1], axis=0)
        ic = entries[:, 2]
    with device_scope("stk_dot"):
        prod = jax.lax.dot_general(
            pa, pb, (((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=acc_dtype,
        )
    with device_scope("stk_accum"):
        return c + jax.ops.segment_sum(
            prod, ic, num_segments=cap_c,
            indices_are_sorted=True,
        )


def _local_stacks(st):
    """A device's own part of the sharded stacks: every leaf without
    its (kl, pr, pc) lead, so with the ticks in front."""
    return jax.tree.map(lambda x: x.reshape(x.shape[3:]), st)


def _stack_of_tick(st, t):
    """Tick ``t``'s stack of a device's local stacks."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, t, axis=0, keepdims=False),
        st)


def _tick_contrib_chunked(a, b, c, st_tick, *, r0, cap_c, acc_dtype,
                          dot_form="compiler"):
    """One tick's full contribution into the device's C panel: ONE
    implementation shared by the fused metronome body
    (`_cannon_tick_loop`) and the split per-tick programs
    (`_stack_tick_mesh`, `_stack_tick_gather`, `_stack_tick_grouped`),
    so the two execution modes are bitwise identical by construction.

    Grouped (r0 > 0): the one-chip engine's `acc/smm.py:group_chunk_loop`
    on the local panels, whose last row is the all-zero block the
    tiles' pad ids name: whole-block row gathers, one dot a width
    class, the in-place sorted scatter-add, in one loop bounded by this
    device's and tick's own ``live`` chunk count (a sharded scalar; no
    collective sits inside the loop, so the count may differ by
    device).  Flat (r0 = 0): `_stack_contrib` in `_tick_chunks`
    sub-chunks.

    Every jitted program that traces this body is named `_stack_*`: the
    phases carry the one-chip bodies' `device_scope` names, a device
    trace splits a mesh program's time the way it splits
    `jit_fused_superstack`'s, and a program whose scopes change needs a
    new name, or a compile cache written before them answers with the
    scopeless executable (`acc/smm.py`)."""
    if r0:
        from dbcsr_tpu.acc.smm import group_chunk_loop

        live, tiles = st_tick
        return group_chunk_loop(c, a, b, live, tiles, dot_form=dot_form)
    nchunk, rows = _tick_chunks(st_tick.shape[0])
    if nchunk > 1:
        st_t = st_tick.reshape(nchunk, rows, st_tick.shape[1])
        return jax.lax.fori_loop(
            0, nchunk,
            lambda j, cc: _stack_contrib(a, b, cc, st_t[j], cap_c=cap_c,
                                         acc_dtype=acc_dtype),
            c,
        )
    return _stack_contrib(a, b, c, st_tick, cap_c=cap_c, acc_dtype=acc_dtype)


def _cannon_tick_loop(a, b, st, s, cap_c, acc_dtype, r0=0, nticks=None,
                      dot_form="compiler"):
    """The shared Cannon metronome: ticks of `_tick_contrib_chunked`,
    ring-shifting A along 'pc' and B along 'pr' between them, outside
    the chunk loop (ref the grouped_k_index loop,
    `dbcsr_mm_cannon.F:1345`).  ``st`` is the device's local stacks
    (`_local_stacks`); ``r0 > 0``: grouped stacks (`_fill_stacks`).
    ``s == 0`` disables the ring shifts (the all-gather engine's chunk
    loop: operands already complete, ticks bound peak memory only);
    ``nticks`` overrides the tick count (defaults to s).  Each tick's
    stack runs in chunks so peak temp memory stays bounded no matter
    how much product one tick carries."""
    bm, bn = a.shape[1], b.shape[2]
    c = jnp.zeros((cap_c, bm, bn), acc_dtype)
    c = jax.lax.pcast(c, ("kl", "pr", "pc"), to="varying")
    shift_a, shift_b = _ring_perms(s) if s > 1 else ((), ())

    def tick(t, carry):
        a, b, c = carry
        c = _tick_contrib_chunked(a, b, c, _stack_of_tick(st, t), r0=r0,
                                  cap_c=cap_c, acc_dtype=acc_dtype,
                                  dot_form=dot_form)
        if s > 1:
            a = jax.lax.ppermute(a, ("pc",), shift_a)
            b = jax.lax.ppermute(b, ("pr",), shift_b)
        return a, b, c

    _, _, c = jax.lax.fori_loop(0, nticks if nticks is not None else s,
                                tick, (a, b, c))
    return c


def _record_mesh_dispatch(stacks_dev, r0: int) -> None:
    """Account one mesh launch through the fused-dispatch metrics
    (`acc.smm.record_dispatch`): the whole multiply — every tick's
    chunks — rides a single SPMD program, i.e. the mesh engine is
    natively on the fused path the single-chip superstack engine
    reaches per C bin.  ``stacks_dev`` is the plan's: the flat
    (..., nticks, s_cap, 3) array, or the grouped pytree whose gather
    ids are (..., nticks, nchunks, CH_w, w)."""
    from dbcsr_tpu.acc.smm import record_dispatch

    if r0:
        ga = stacks_dev[1][0][0]
        nticks, nchunk = ga.shape[3], ga.shape[4]
    else:
        nticks = stacks_dev.shape[-3]
        nchunk, _ = _tick_chunks(stacks_dev.shape[-2])
    record_dispatch("fused", fused_spans=nticks * nchunk)


def _vcol(k: np.ndarray, kl: int, s: int):
    """k block -> (layer, panel column): the k axis is an image
    distribution of multiplicity kl over the s physical columns
    (`parallel/images.py`; ref `dbcsr_create_image_dist`,
    `dbcsr_mm_dist_operations.F:58`)."""
    from dbcsr_tpu.parallel.images import ImageDistribution

    return ImageDistribution(s, kl).split(k)


def _grid_map(dist_arr: Optional[np.ndarray], n: int, naxis: int) -> np.ndarray:
    """A block→grid-position map: the matrix's own distribution when it
    fits the mesh axis, else cyclic decimation (the reference insists on
    compatible distributions instead, `dbcsr_mm.F:585-590`; host-side
    panel assembly lets us fall back gracefully)."""
    if dist_arr is not None and len(dist_arr) == n and (
        len(dist_arr) == 0
        or (dist_arr.min(initial=0) >= 0 and dist_arr.max(initial=0) < naxis)
    ):
        return np.ascontiguousarray(dist_arr, np.int64)
    return np.arange(n, dtype=np.int64) % naxis


def _resolve_maps(a, b, matrix_c, pr: int, pc: int, kl: int):
    """Block→process maps honoring the matrices' `Distribution` objects
    (ref `dbcsr_distribution_new` row/col→proc arrays,
    `dbcsr_dist_methods.F:49`).

    Returns (rdist, cdist, k_layer, ka_col, kb_row) over block indices:
    C-row → 'pr', C-col → 'pc', k-block → (2.5D layer, A's 'pc' image,
    B's 'pr' image).  Priority: C's distribution, then A's rows / B's
    cols; falling back to cyclic images.

    Square grids (Cannon) need ONE k map shared by A's columns and B's
    rows (ref `dbcsr_mm.F:585-590` compatible-distribution rule):
    ka_col == kb_row there.  Rectangular grids run the all-gather
    engine, where A's k home (over 'pc') and B's k home (over 'pr')
    are independent (the freedom image distributions give the
    reference, `dbcsr_mm_dist_operations.F:58`).
    """
    rdist = None
    cdist = None
    for cand_dist, attr in (
        (matrix_c.dist if matrix_c is not None else None, "row_dist"),
        (a.dist, "row_dist"),
    ):
        if cand_dist is not None and cand_dist.grid.nprows == pr:
            rdist = getattr(cand_dist, attr)
            break
    for cand_dist, attr in (
        (matrix_c.dist if matrix_c is not None else None, "col_dist"),
        (b.dist, "col_dist"),
    ):
        if cand_dist is not None and cand_dist.grid.npcols == pc:
            cdist = getattr(cand_dist, attr)
            break
    nbk = a.nblkcols
    rdist = _grid_map(rdist, a.nblkrows, pr)
    cdist = _grid_map(cdist, b.nblkcols, pc)

    if pr == pc:
        s = pr
        kdist = None
        if a.dist.grid.npcols == s and len(a.dist.col_dist) == nbk:
            kdist = a.dist.col_dist
        elif b.dist.grid.nprows == s and len(b.dist.row_dist) == nbk:
            kdist = b.dist.row_dist
        if kdist is not None and (
            len(kdist) == 0
            or (kdist.min(initial=0) >= 0 and kdist.max(initial=0) < s)
        ):
            k_col = np.ascontiguousarray(kdist, np.int64)
            # 2.5D layer: deterministic round-robin within each grid
            # column (image-multiplicity decimation generalized)
            k_layer = _panel_slots(k_col) % kl
        else:
            k_layer, k_col = _vcol(np.arange(nbk, dtype=np.int64), kl, s)
        return rdist, cdist, k_layer, k_col, k_col

    # rectangular: independent k homes, one shared layer split
    ka = None
    if a.dist.grid.npcols == pc and len(a.dist.col_dist) == nbk:
        ka = a.dist.col_dist
    kb = None
    if b.dist.grid.nprows == pr and len(b.dist.row_dist) == nbk:
        kb = b.dist.row_dist
    ka_col = _grid_map(ka, nbk, pc)
    kb_row = _grid_map(kb, nbk, pr)
    k_layer = (np.arange(nbk, dtype=np.int64) // max(pr, pc)) % kl
    return rdist, cdist, k_layer, ka_col, kb_row


@functools.partial(
    jax.jit,
    static_argnames=("s", "nticks", "gather", "cap_c", "acc_name",
                     "mesh_ref", "r0", "dot_form"),
)
def _stack_run_mesh(a_panels, b_panels, stacks, c_init, alpha, beta_fac,
                    *, s, nticks, gather, cap_c, acc_name, mesh_ref, r0=0,
                    dot_form="compiler"):
    """The one mesh runner behind both sparse engines.

    ``gather=False``: square-grid skewed Cannon — s alignment ticks,
    ring-shifting A along 'pc' / B along 'pr'.
    ``gather=True``: rectangular-grid all-gather engine — A panels live
    at their k home column and are `all_gather`ed along 'pc' (B along
    'pr'), then nticks shift-free stack chunks run (the TPU-native
    realization of arbitrary nprows x npcols grids via image
    distributions, `dbcsr_mm_dist_operations.F:58`,
    `dbcsr_types.F:188-223`: one XLA collective on ICI instead of
    lcm(pr,pc) skew ticks).

    ``beta_fac`` is a per-C-slot (pr, pc, cap_c) factor: scalar beta
    everywhere normally; with block limits, 1.0 for blocks outside the
    limited window so they keep their old values (windowed-beta
    semantics shared with the single-chip engine)."""
    mesh = mesh_ref.val
    acc_dtype = jnp.dtype(acc_name)

    def body(a_p, b_p, st, c_in, alpha, beta_fac):
        a = a_p.reshape(a_p.shape[3:])  # (cap_a + xtr, bm, bk)
        b = b_p.reshape(b_p.shape[3:])
        st = _local_stacks(st)  # (nticks, ...): flat rows or group tiles
        c_in = c_in.reshape(c_in.shape[2:])  # (cap_c, bm, bn)
        fac = beta_fac.reshape(beta_fac.shape[2:])  # (cap_c,) or (cap_c,bm,bn)
        if fac.ndim == 1:
            fac = fac[:, None, None]
        if gather:
            a = jax.lax.all_gather(a, "pc", axis=0, tiled=True)
            b = jax.lax.all_gather(b, "pr", axis=0, tiled=True)
        c = _cannon_tick_loop(a, b, st, 0 if gather else s, cap_c,
                              acc_dtype, r0=r0, nticks=nticks,
                              dot_form=dot_form)
        c = jax.lax.psum(c, "kl")
        c = (alpha * c + fac * c_in.astype(acc_dtype)).astype(c_in.dtype)
        return c.reshape((1, 1) + c.shape)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P("kl", "pr", "pc"),
            P("kl", "pr", "pc"),
            P("kl", "pr", "pc"),
            P("pr", "pc"),
            P(),
            P("pr", "pc"),
        ),
        out_specs=P("pr", "pc"),
    )
    return fn(a_panels, b_panels, stacks, c_init, alpha, beta_fac)


# --------------------------------------------------------------------------
# Split per-tick programs: the double-buffered metronome
# (parallel/overlap.py) dispatches these independently so the panel
# ring shift feeding tick k+1 runs concurrently with tick k's chunk
# loop.  The per-tick body (`_tick_contrib_chunked`: on the grouped
# path `acc/smm.py:group_chunk_loop`, the one-chip engine's own loop,
# on the tick's own tiles and live count) is shared with the fused
# serial program, so the two execution modes are bitwise identical.
# --------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("cap_c", "acc_name", "mesh_ref", "r0", "dot_form"),
)
def _stack_tick_mesh(a_panels, b_panels, stacks, c_acc, t, *,
                     cap_c, acc_name, mesh_ref, r0=0, dot_form="compiler"):
    """One Cannon tick's chunked contribution into the per-layer
    accumulator ``c_acc`` (global (kl, pr, pc, cap_c, bm, bn))."""
    mesh = mesh_ref.val
    acc_dtype = jnp.dtype(acc_name)

    def body(a_p, b_p, st, c_p, t):
        a = a_p.reshape(a_p.shape[3:])
        b = b_p.reshape(b_p.shape[3:])
        c = c_p.reshape(c_p.shape[3:])   # (cap_c, bm, bn)
        c = _tick_contrib_chunked(
            a, b, c, _stack_of_tick(_local_stacks(st), t), r0=r0,
            dot_form=dot_form,
            cap_c=cap_c, acc_dtype=acc_dtype)
        return c.reshape((1, 1, 1) + c.shape)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P("kl", "pr", "pc"),
            P("kl", "pr", "pc"),
            P("kl", "pr", "pc"),
            P("kl", "pr", "pc"),
            P(),
        ),
        out_specs=P("kl", "pr", "pc"),
    )
    return fn(a_panels, b_panels, stacks, c_acc, t)


@functools.partial(jax.jit, static_argnames=("s", "mesh_ref"))
def _mesh_shift_program(a_panels, b_panels, *, s, mesh_ref):
    """One A/B panel ring shift (A left along 'pc', B up along 'pr')
    as its own SPMD program — the second operand buffer of the
    double-buffered tick."""
    shift_a, shift_b = _ring_perms(s)

    def body(a_p, b_p):
        a = a_p.reshape(a_p.shape[3:])
        b = b_p.reshape(b_p.shape[3:])
        a = jax.lax.ppermute(a, ("pc",), shift_a)
        b = jax.lax.ppermute(b, ("pr",), shift_b)
        return (a.reshape((1, 1, 1) + a.shape),
                b.reshape((1, 1, 1) + b.shape))

    fn = jax.shard_map(
        body,
        mesh=mesh_ref.val,
        in_specs=(P("kl", "pr", "pc"), P("kl", "pr", "pc")),
        out_specs=(P("kl", "pr", "pc"), P("kl", "pr", "pc")),
    )
    return fn(a_panels, b_panels)


@functools.partial(jax.jit, static_argnames=("acc_name", "mesh_ref"))
def _mesh_finish_program(c_acc, c_init, alpha, beta_fac, *,
                         acc_name, mesh_ref):
    """Layer reduction + alpha/beta merge (same op order as the fused
    program's tail): psum over 'kl', then alpha*C + beta_fac*C_in."""
    acc_dtype = jnp.dtype(acc_name)

    def body(c_p, c_in, alpha, beta_fac):
        c = c_p.reshape(c_p.shape[3:])
        c_in = c_in.reshape(c_in.shape[2:])
        fac = beta_fac.reshape(beta_fac.shape[2:])
        if fac.ndim == 1:
            fac = fac[:, None, None]
        c = jax.lax.psum(c, "kl")
        c = (alpha * c + fac * c_in.astype(acc_dtype)).astype(c_in.dtype)
        return c.reshape((1, 1) + c.shape)

    fn = jax.shard_map(
        body,
        mesh=mesh_ref.val,
        in_specs=(
            P("kl", "pr", "pc"),
            P("pr", "pc"),
            P(),
            P("pr", "pc"),
        ),
        out_specs=P("pr", "pc"),
    )
    return fn(c_acc, c_init, alpha, beta_fac)


# --------------------------------------------------------------------------
# Chunked all-gather pipeline (rectangular grids): the fused program's
# one up-front `all_gather` becomes nticks per-source-shard ring steps
# driven by the overlap metronome, so the first stack chunks contract
# while later shards are still in flight.  Tick t writes the shard
# arriving at ring distance t into the concatenated operand buffer at
# the position the fused program's tiled `all_gather` puts it, then
# contracts the plan's tick-t stack (whose entries reference only
# shards at distances <= t — `_build_mesh_plan`'s shard-arrival
# binning).  Op code is `_tick_contrib_chunked`, shared with the fused
# program: bitwise identical by construction.  Failures degrade
# through the `gather_pipe` pseudo-driver to the fused program.
# --------------------------------------------------------------------------


def _recv_perm(s: int) -> tuple:
    """Receive-from-successor ring permutation: after t steps position
    p holds the panel that originated at (p + t) % s — the per-shard
    chunk schedule of the pipelined all-gather.  The SAME table as the
    Cannon A-shift (`_ring_perms`): `_build_mesh_plan`'s arrival
    distances (dist_a/dist_b) are derived for this direction, so the
    two must never diverge."""
    return _ring_perms(s)[0]


@functools.partial(jax.jit, static_argnames=("pr", "pc", "mesh_ref"))
def _gather_shift_program(a_panels, b_panels, *, pr, pc, mesh_ref):
    """One gather chunk: rotate the rolling home A panel along 'pc'
    and the rolling B panel along 'pr' by one position, as an SPMD
    program with no data dependence on the concurrent tick program."""

    def body(a_p, b_p):
        a = a_p.reshape(a_p.shape[3:])
        b = b_p.reshape(b_p.shape[3:])
        if pc > 1:
            a = jax.lax.ppermute(a, ("pc",), _recv_perm(pc))
        if pr > 1:
            b = jax.lax.ppermute(b, ("pr",), _recv_perm(pr))
        return (a.reshape((1, 1, 1) + a.shape),
                b.reshape((1, 1, 1) + b.shape))

    fn = jax.shard_map(
        body,
        mesh=mesh_ref.val,
        in_specs=(P("kl", "pr", "pc"), P("kl", "pr", "pc")),
        out_specs=(P("kl", "pr", "pc"), P("kl", "pr", "pc")),
    )
    return fn(a_panels, b_panels)


@functools.partial(
    jax.jit,
    static_argnames=("pr", "pc", "seg_a", "seg_b", "cap_c", "acc_name",
                     "mesh_ref", "r0", "dot_form"),
)
def _stack_tick_gather(a_roll, b_roll, a_cat, b_cat, stacks, c_acc, t, *,
                       pr, pc, seg_a, seg_b, cap_c, acc_name, mesh_ref,
                       r0=0, dot_form="compiler"):
    """One gather-pipeline tick: append the shard pair at ring distance
    ``t`` into the concatenations (A at column (j+t)%pc * seg_a, B at
    row (i+t)%pr * seg_b — the tiled-all_gather layout), then contract
    tick t's stack chunk into the per-layer accumulator.  Past an
    axis's extent the wrapped shard rewrites identical bytes (benign;
    the other, longer axis still needs the step)."""
    mesh = mesh_ref.val
    acc_dtype = jnp.dtype(acc_name)

    def body(a_r, b_r, a_c, b_c, st, c_p, t):
        a_r = a_r.reshape(a_r.shape[3:])
        b_r = b_r.reshape(b_r.shape[3:])
        a_c = a_c.reshape(a_c.shape[3:])  # (pc * seg_a, bm, bk)
        b_c = b_c.reshape(b_c.shape[3:])  # (pr * seg_b, bk, bn)
        c = c_p.reshape(c_p.shape[3:])    # (cap_c, bm, bn)
        src_col = jax.lax.rem(jax.lax.axis_index("pc") + t,
                              jnp.int32(pc))
        zero = jnp.zeros((), src_col.dtype)
        a_c = jax.lax.dynamic_update_slice(
            a_c, a_r, (src_col * seg_a, zero, zero))
        src_row = jax.lax.rem(jax.lax.axis_index("pr") + t,
                              jnp.int32(pr))
        b_c = jax.lax.dynamic_update_slice(
            b_c, b_r, (src_row * seg_b, zero, zero))
        c = _tick_contrib_chunked(
            a_c, b_c, c, _stack_of_tick(_local_stacks(st), t), r0=r0,
            dot_form=dot_form,
            cap_c=cap_c, acc_dtype=acc_dtype)
        return (a_c.reshape((1, 1, 1) + a_c.shape),
                b_c.reshape((1, 1, 1) + b_c.shape),
                c.reshape((1, 1, 1) + c.shape))

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("kl", "pr", "pc"),) * 6 + (P(),),
        out_specs=(P("kl", "pr", "pc"),) * 3,
    )
    return fn(a_roll, b_roll, a_cat, b_cat, stacks, c_acc, t)


def _gather_ticks(plan: "_MeshPlan", mesh, a_panels, b_panels, c_init,
                  alpha_dev, beta_fac, mode: str, measure: bool,
                  timings: list):
    """Host-driven chunked all-gather pipeline behind the rectangular-
    grid route — bitwise identical to `_stack_run_mesh` with
    ``gather=True``.  The carried state is (a_cat, b_cat, c_acc): the
    incrementally built operand concatenations plus the accumulator."""
    from dbcsr_tpu.acc.smm import record_dispatch

    mref = _HashableMesh(mesh)
    kl, pr, pc = plan.kl, plan.s, plan.pc
    seg_a, seg_b = plan.cap_a + plan.xtr, plan.cap_b + plan.xtr
    dt_name = np.dtype(plan.dtype).name
    a_cat = _overlap.zeros_program(
        mref, (kl, pr, pc, pc * seg_a, plan.bm, plan.bk), dt_name,
        P("kl", "pr", "pc"))()
    b_cat = _overlap.zeros_program(
        mref, (kl, pr, pc, pr * seg_b, plan.bk, plan.bn), dt_name,
        P("kl", "pr", "pc"))()
    c_acc = _overlap.zeros_program(
        mref, (kl, pr, pc, plan.cap_c, plan.bm, plan.bn), plan.acc_name,
        P("kl", "pr", "pc"))()
    record_dispatch(_overlap.GATHER_DRIVER)  # the zeros programs

    def shift(aa, bb):
        return _gather_shift_program(aa, bb, pr=pr, pc=pc, mesh_ref=mref)

    def tick(aa, bb, carry, t):
        return _stack_tick_gather(
            aa, bb, carry[0], carry[1], plan.stacks_dev, carry[2],
            jnp.asarray(t, jnp.int32), pr=pr, pc=pc, seg_a=seg_a,
            seg_b=seg_b, cap_c=plan.cap_c, acc_name=plan.acc_name,
            mesh_ref=mref, r0=plan.r0, dot_form=plan.dot_form,
        )

    carry, shift_s, comp_s = _overlap.run_ticks(
        plan.nticks, a_panels, b_panels, (a_cat, b_cat, c_acc),
        shift, tick, mode=mode, engine="mesh", measure=measure,
        driver=_overlap.GATHER_DRIVER, site="gather_chunk",
    )
    if measure:
        timings.append((shift_s, comp_s))
    res = _mesh_finish_program(
        carry[2], c_init, alpha_dev, beta_fac,
        acc_name=plan.acc_name, mesh_ref=mref,
    )
    record_dispatch(_overlap.GATHER_DRIVER)
    return res


def _mesh_ticks(plan: "_MeshPlan", mesh, a_panels, b_panels, c_init,
                alpha_dev, beta_fac, mode: str, measure: bool,
                timings: list):
    """Host-driven tick loop behind the double-buffered (and
    measured-serial) sparse mesh Cannon — bitwise identical to
    `_stack_run_mesh` with ``gather=False``.  Appends the measured
    (shift_exposed_s, compute_s) split to ``timings`` — published by
    the caller only when the pipeline delivered the result
    (overlap.run_split_pipeline)."""
    from dbcsr_tpu.acc.smm import record_dispatch

    mref = _HashableMesh(mesh)
    s = plan.s
    c_acc = _overlap.zeros_program(
        mref, (plan.kl, s, plan.pc, plan.cap_c, plan.bm, plan.bn),
        plan.acc_name, P("kl", "pr", "pc"),
    )()
    record_dispatch(_overlap.DRIVER)  # the zeros program

    def shift(aa, bb):
        return _mesh_shift_program(aa, bb, s=s, mesh_ref=mref)

    def tick(aa, bb, cc, t):
        return _stack_tick_mesh(
            aa, bb, plan.stacks_dev, cc, jnp.asarray(t, jnp.int32),
            cap_c=plan.cap_c, acc_name=plan.acc_name, mesh_ref=mref,
            r0=plan.r0, dot_form=plan.dot_form,
        )

    _note_program("tick", "_stack_tick_mesh", a_panels, b_panels,
                  plan.stacks_dev, cap_c=plan.cap_c, acc=plan.acc_name,
                  r0=plan.r0, dot_form=plan.dot_form)
    _note_program("shift", "_mesh_shift_program", a_panels, b_panels)
    _note_program("finish", "_mesh_finish_program", c_acc)
    c_acc, shift_s, comp_s = _overlap.run_ticks(
        plan.nticks, a_panels, b_panels, c_acc, shift, tick,
        mode=mode, engine="mesh", measure=measure,
    )
    # tick/shift dispatches were counted as issued (run_ticks — so a
    # mid-pipeline failure still shows the round-trips it really paid,
    # the PR-4 failed-launches-count convention); the finish program
    # books its own below
    if measure:
        timings.append((shift_s, comp_s))
    res = _mesh_finish_program(
        c_acc, c_init, alpha_dev, beta_fac,
        acc_name=plan.acc_name, mesh_ref=mref,
    )
    record_dispatch(_overlap.DRIVER)
    return res


def sparse_multiply_distributed(
    alpha,
    matrix_a: BlockSparseMatrix,
    matrix_b: BlockSparseMatrix,
    beta,
    matrix_c: Optional[BlockSparseMatrix],
    mesh: Mesh,
    name: Optional[str] = None,
    retain_sparsity: bool = False,
    filter_eps: Optional[float] = None,
    first_row=None, last_row=None,
    first_col=None, last_col=None,
    first_k=None, last_k=None,
    element_limits=None,
) -> BlockSparseMatrix:
    """C = alpha*A@B + beta*C on the mesh with block-sparse panels.

    Host-resident in/out (the single-controller analog of
    `dbcsr_multiply_generic` driving `multiply_cannon`); device compute
    and inter-device traffic are fully sparse.  The optional block-index
    limits restrict the product exactly like `dbcsr_tpu.multiply`'s
    (used by the TAS group loop).  ``filter_eps``/``retain_sparsity``
    follow the single-chip engine's (= the reference's) semantics:
    on-the-fly norm-product skip with per-A-row eps
    (`dbcsr_mm_cannon.F:1098-1105`), final ||C||>=eps pass unless
    retain_sparsity, which instead locks C's pattern.
    """
    # product scope: the mesh engine's overlap decision, faults and
    # breaker events correlate to this multiply on the bus + flight
    # ring exactly like the single-chip engine's (`mm.multiply`)
    with _events.product_scope(
            "mesh_multiply", name or f"{matrix_a.name}*{matrix_b.name}",
            a=matrix_a.name, b=matrix_b.name):
        if _faults.active():
            # the collective boundary: ring shifts / psum / all_gather
            # run inside jit, so the injection point is the mesh
            # dispatch edge (the double-buffered tick pipeline adds the
            # host-level `mesh_shift` site per tick, parallel/overlap.py)
            _faults.maybe_inject("collective")
        with timed("sparse_cannon"):
            return _sparse_multiply_impl(
                alpha, matrix_a, matrix_b, beta, matrix_c, mesh, name,
                (first_row, last_row, first_col, last_col, first_k, last_k),
                retain_sparsity=retain_sparsity, filter_eps=filter_eps,
                element_limits=element_limits,
            )


# --------------------------------------------------------------------------
# Rank-resident mesh multiplies (ref: a dbcsr matrix's data areas live on
# their owning ranks permanently, `dbcsr_types.F:363-461`, backed by
# mempools `dbcsr_mem_methods.F`; a multiply moves only panels).  The
# single-controller analog: all pattern-derived index work (symbolic
# product, stack fill, panel/collect maps) is cached per pattern
# (`_mesh_plan_cache`, the mesh sibling of `mm/multiply._plan_cache`),
# panel assembly and C collection run ON DEVICE from the matrices' shape
# bins (no `_dense_blocks_host` d2h fetch, no h2d panel upload), and the
# assembled sharded panels themselves are cached keyed by the operands'
# bin data-array identities (the `_dense_canvas_cached` trick) so a
# repeated same-pattern, same-data multiply uploads nothing at all.
# --------------------------------------------------------------------------

import dataclasses
from collections import OrderedDict as _OrderedDict


@functools.partial(jax.jit, static_argnames=("nflat", "bm", "bn", "dtype_name"))
def _assemble_flat(bin_datas, flat_pos, src_slots, *, nflat, bm, bn, dtype_name):
    """Scatter shape-bin blocks into a zero (nflat, bm, bn) panel buffer
    at precomputed flat positions — the device-side make_m2s data
    movement (`dbcsr_mm_cannon.F:146,292`).  Unwritten rows (bucket pads,
    the r0 guaranteed-zero row) stay zero.  Index arrays are padded to
    bucketed lengths with out-of-range destinations (dropped) so evolving
    patterns reuse the compiled program."""
    out = jnp.zeros((nflat, bm, bn), jnp.dtype(dtype_name))
    for data, fp, ss in zip(bin_datas, flat_pos, src_slots):
        blk = jnp.take(data, ss, axis=0).astype(out.dtype)
        out = out.at[fp, : data.shape[1], : data.shape[2]].set(blk, mode="drop")
    return out


@functools.partial(jax.jit, static_argnames=("shapes", "mesh_ref"))
def _collect_bins(c_panels, own_rows, slot_rows, *, shapes, mesh_ref):
    """Carve the C panels into per-shape bins that every device holds
    whole, slots in `_bin_entries` order and pad slots zero (the
    collect half of `dbcsr_merge_all`, `dbcsr_work_operations.F:1393`,
    without the host round-trip `_adopt_panels` pays).  Maps from
    `_collect_maps`.  ``c_panels`` is (grid..., cap, bm, bn), one panel
    a device of the mesh axes its leading dims are sharded over (the
    last two or all three of 'kl', 'pr', 'pc'); three steps a bin:

    1. every device takes the rows of the blocks it owns out of its OWN
       panel, seen as (cap, bm*bn) rows (`acc/smm.py:_block_rows`: a
       gather of 3-D blocks fetches every element on its own), by
       ``own_rows`` (grid..., L): local panel slots, the pads past the
       panel, which read as zero rows;
    2. the pieces are all-gathered over those axes: a block crosses ICI
       once, and nothing else does but the bucket pads;
    3. one more row gather puts them in slot order by ``slot_rows``
       (cap_bin,): the row of the gathered pieces that holds bin slot
       i, for a pad slot a row that is a pad."""
    from dbcsr_tpu.acc.smm import _block_rows, _take_rows

    lead = c_panels.ndim - 3
    axes = ("kl", "pr", "pc")[-lead:]
    bm, bn = c_panels.shape[-2:]

    def body(c_p, own, slot):
        rows = _block_rows(c_p.reshape(c_p.shape[lead:]))
        outs = []
        for ids, perm, (bmb, bnb) in zip(own, slot, shapes):
            piece = rows.at[ids.reshape(-1)].get(mode="fill", fill_value=0)
            if (bmb, bnb) != (bm, bn):
                piece = piece.reshape(-1, bm, bn)[:, :bmb, :bnb]
                piece = piece.reshape(-1, bmb * bnb)
            pieces = jax.lax.all_gather(piece, axes, tiled=True)
            outs.append(_take_rows(pieces, perm).reshape(-1, bmb, bnb))
        return tuple(outs)

    fn = jax.shard_map(
        body,
        mesh=mesh_ref.val,
        in_specs=(P(*axes), P(*axes), P()),
        out_specs=P(),
        # an all-gather's result is the same on every device it spans,
        # which the replication check does not infer (public
        # `lax.all_gather` types its result as varying)
        check_vma=False,
    )
    return fn(c_panels, own_rows, slot_rows)


def _collect_maps(mesh, axes: tuple, nb, nsl, nbins: int, c_dev, c_local,
                  cap_local: int) -> tuple:
    """`_collect_bins`' index maps for C blocks in key order: ``nb`` /
    ``nsl`` their bin and in-bin slot (`_bin_entries`), ``c_dev`` the
    device that owns each (row-major over the mesh ``axes`` C is
    sharded over), ``c_local`` its slot in that device's panel of
    ``cap_local`` rows.  Returns (own_rows, slot_rows, counts, shipped):
    per bin the (grid..., L) local slots a device takes and the (cap,)
    rows of the gathered pieces in slot order, both on the mesh; the
    bins' block counts; and the piece slots all-gathered in all.  Every
    length is bucketed so that patterns of like counts share a program;
    L leaves every device a pad row, which reads zero and which the pad
    slots name."""
    grid = tuple(mesh.shape[ax] for ax in axes)
    ndev = int(np.prod(grid))
    own_rows, slot_rows, counts = [], [], []
    for b_id in range(nbins):
        sel = np.nonzero(nb == b_id)[0]
        dev = c_dev[sel]
        per_dev = np.bincount(dev, minlength=ndev)
        length = bucket_size(int(per_dev.max()) + 1)
        pos = _panel_slots(dev)  # key order within a device's piece
        own = np.full((ndev, length), cap_local, np.int32)
        own[dev, pos] = c_local[sel]
        perm = np.full(bucket_size(len(sel)), length - 1, np.int32)
        perm[nsl[sel]] = dev * length + pos
        own_rows.append(own.reshape(grid + (length,)))
        slot_rows.append(perm)
        counts.append(len(sel))
    shipped = sum(int(x.size) for x in own_rows)
    with timed("mesh_plan_upload"):
        own_rows = jax.device_put(tuple(own_rows),
                                  NamedSharding(mesh, P(*axes)))
        slot_rows = jax.device_put(tuple(slot_rows),
                                   NamedSharding(mesh, P()))
    return own_rows, slot_rows, tuple(counts), shipped


@dataclasses.dataclass
class _BinAsm:
    """Device-resident assembly indices for one operand: which bin each
    contributing entry lives in, its flat panel destination, and its
    in-bin source slot."""

    bin_ids: tuple  # operand bin ids, one per non-empty scatter group
    flat_pos: tuple  # jnp int32 arrays, destinations in the flat buffer
    src_slots: tuple  # jnp int32 arrays, gather slots within the bin
    nflat: int
    bm: int
    bn: int

    def nbytes(self) -> int:
        return sum(int(x.nbytes) for x in self.flat_pos) + sum(
            int(x.nbytes) for x in self.src_slots
        )


def _make_bin_asm(m: BlockSparseMatrix, flat: np.ndarray, nflat: int,
                  bm: int, bn: int) -> _BinAsm:
    """Build a `_BinAsm` from per-entry flat destinations (key order).
    Index arrays are padded to bucketed lengths (pad destinations point
    past the buffer and scatter with mode="drop") so same-size evolving
    patterns reuse the compiled assembly."""
    bin_ids, fps, sss = [], [], []
    for b_id in range(len(m.bins)):
        sel = np.nonzero(m.ent_bin == b_id)[0]
        if not len(sel):
            continue
        bin_ids.append(b_id)
        cap = bucket_size(len(sel))
        fp = np.full(cap, nflat, np.int32)  # pads: out of range -> dropped
        fp[: len(sel)] = flat[sel]
        ss = np.zeros(cap, np.int32)  # pads: any in-range gather slot
        ss[: len(sel)] = m.ent_slot[sel]
        fps.append(fp)
        sss.append(ss)
    with timed("mesh_plan_upload"):
        fps = tuple(jnp.asarray(fp) for fp in fps)
        sss = tuple(jnp.asarray(ss) for ss in sss)
    return _BinAsm(tuple(bin_ids), fps, sss, nflat, bm, bn)


def _run_bin_asm(asm: _BinAsm, m: BlockSparseMatrix, dtype) -> object:
    datas = tuple(m.bins[b].data for b in asm.bin_ids)
    _note_program("assembly", "_assemble_flat", datas, asm.flat_pos,
                  nflat=asm.nflat, bm=asm.bm, bn=asm.bn,
                  dtype=np.dtype(dtype).name)
    return _assemble_flat(
        datas, asm.flat_pos, asm.src_slots,
        nflat=asm.nflat, bm=asm.bm, bn=asm.bn, dtype_name=np.dtype(dtype).name,
    )


@dataclasses.dataclass
class _MeshPlan:
    """Everything about a mesh multiply that only depends on the
    operands' patterns, distributions, dtype and product options, and,
    for a filtered product, on its surviving candidates."""

    s: int       # 'pr' extent (== pc on Cannon grids)
    pc: int
    nticks: int  # Cannon: = s alignment steps; all-gather: chunk count
    kl: int
    r0: int
    dot_form: str  # grouped stacks: `acc/smm.py:group_dot_form`
    xtr: int
    cap_a: int
    cap_b: int
    cap_c: int
    bm: int
    bk: int
    bn: int
    dtype: object
    acc_name: str
    true_flops: int
    n_cand: int
    stacks_dev: object  # `_upload_stacks`: flat array or grouped pytree
    a_asm: _BinAsm
    b_asm: _BinAsm
    cinit_asm: Optional[_BinAsm]  # None when C had no stored blocks
    has_window: bool
    inside_all: bool
    inside_dev: object  # (s, s, cap_c) bool device array, or None
    c_keys: np.ndarray
    c_binning: tuple  # (_bin_entries result) for c_keys
    collect_own: tuple  # `_collect_maps`: per out-bin, a device's rows
    collect_perm: tuple  # per out-bin, the gathered rows in slot order
    collect_counts: tuple
    collect_shapes: tuple
    collect_shipped: int  # piece slots all-gathered, bucket pads included
    out_dist: object
    upload_bytes: int
    # (bin-data ids, sharded panels, keepalive) per operand; the ids are
    # sound because the keepalive holds the arrays (no id recycling)
    panel_cache: dict = dataclasses.field(default_factory=dict)

    def nbytes(self) -> int:
        """Device bytes this plan pins: stacks, index maps, and the
        cached panels.  The panel keepalives are NOT counted — they
        alias the owning matrix's live bin data, not extra copies."""
        n = (_stacks_nbytes(self.stacks_dev) + self.a_asm.nbytes()
             + self.b_asm.nbytes())
        if self.cinit_asm is not None:
            n += self.cinit_asm.nbytes()
        n += sum(int(x.nbytes) for x in self.collect_own)
        n += sum(int(x.nbytes) for x in self.collect_perm)
        if self.inside_dev is not None:
            n += int(self.inside_dev.nbytes)
        for _, panels, _ in self.panel_cache.values():
            n += int(panels.nbytes)
        return n


_mesh_plan_cache: "_OrderedDict[tuple, _MeshPlan]" = _OrderedDict()
# a sign chain cycles through 14 plans: as many as `mm.multiply`'s
# one-chip `_PLAN_CACHE_MAX`, so that a second chain finds them all
_MESH_PLAN_MAX = 16
_MESH_PLAN_MAX_BYTES = 512 * 1024 * 1024


def _mesh_plan_lookup(plan_key):
    """The cached plan under ``plan_key`` or None, counted by outcome:
    ``hit`` or ``miss`` of `_mesh_plan_cache`, or ``uncacheable`` where
    the key is None: a filtered product of the grouped TAS engine,
    whose plan is rebuilt every time (`_sparse_multiply_impl` keys a
    filtered plan by its surviving candidates)."""
    plan = None
    if plan_key is not None:
        plan = _mesh_plan_cache.get(plan_key)
        if plan is not None:
            _mesh_plan_cache.move_to_end(plan_key)
    _metrics.counter(
        "dbcsr_tpu_mesh_plan_total",
        "mesh plan lookups of the distributed sparse engines, by "
        "outcome: hit / miss of the plan cache (keyed by pattern, and "
        "for a filtered product by its surviving candidates too), or "
        "uncacheable (a filtered grouped-TAS product: rebuilt every "
        "product)",
    ).inc(cache="uncacheable" if plan_key is None
          else "miss" if plan is None else "hit")
    return plan


def clear_mesh_plans() -> None:
    """Release all cached mesh plans and their device-resident panels."""
    _mesh_plan_cache.clear()


def _mesh_cache_evict() -> None:
    """Hold the cache to `_MESH_PLAN_MAX` plans and, but for the most
    recent plan, to `_MESH_PLAN_MAX_BYTES`: over the bytes the least
    recently used plans first give up their cached panels and the
    operands those keep alive (one assembly rebuilds them, and a chain
    whose operands are new every product pays it anyway), and only then
    do whole plans go."""
    while len(_mesh_plan_cache) > _MESH_PLAN_MAX:
        _mesh_plan_cache.popitem(last=False)
    nbytes = sum(p.nbytes() for p in _mesh_plan_cache.values())
    for plan in list(_mesh_plan_cache.values())[:-1]:
        if nbytes <= _MESH_PLAN_MAX_BYTES:
            return
        nbytes -= sum(int(panels.nbytes)
                      for _, panels, _ in plan.panel_cache.values())
        plan.panel_cache.clear()
    while len(_mesh_plan_cache) > 1 and nbytes > _MESH_PLAN_MAX_BYTES:
        nbytes -= _mesh_plan_cache.popitem(last=False)[1].nbytes()


def _mesh_plan_insert(key, plan: _MeshPlan) -> None:
    _mesh_plan_cache[key] = plan
    _mesh_cache_evict()


@functools.lru_cache(maxsize=64)
def _panel_cut_program(mesh_ref: _HashableMesh, panel_shape: tuple, spec):
    """Cut a flat panel buffer that every device of the mesh holds
    whole into the sharded panels, each device keeping its own rows."""
    return jax.jit(lambda flat: flat.reshape(panel_shape),
                   out_shardings=NamedSharding(mesh_ref.val, spec))


def _cached_panels(plan: _MeshPlan, which: str, m: BlockSparseMatrix,
                   mesh, panel_shape, spec) -> object:
    """Sharded panels for one operand, rebuilt on device only when the
    operand's bin data changed since the cached assembly."""
    ids = tuple(id(bb.data) for bb in m.bins)
    hit = plan.panel_cache.get(which)
    if hit is not None and hit[0] == ids:
        return hit[1]
    asm = {"a": plan.a_asm, "b": plan.b_asm}[which]
    flat = _run_bin_asm(asm, m, plan.dtype)
    sharding = NamedSharding(mesh, spec)
    if flat.sharding.device_set == sharding.device_set:
        # the operand is a mesh product's result, whose bins the
        # collect left whole on every device, and so is the buffer
        # assembled from them: `jax.device_put` would fetch it to the
        # host and upload its slices (0.48 s a panel at 87 MB, the chips
        # idle: 13.4 of a sign chain's 17.8 s on the 2x2 grid, PR 37)
        _note_program("cut", "_panel_cut_program", flat)
        panels = _panel_cut_program(
            _HashableMesh(mesh), tuple(panel_shape), spec)(flat)
    else:  # staged on one device: sliced there, copied device to device
        panels = jax.device_put(flat.reshape(panel_shape), sharding)
    plan.panel_cache[which] = (ids, panels, [bb.data for bb in m.bins])
    # panels are the big rows in the byte budget and land AFTER the
    # plan's insert — re-check the cap every time one is stored
    _mesh_cache_evict()
    return panels


@dataclasses.dataclass
class _GroupedPlan:
    """Pattern-determined artifacts of a grouped TAS mesh multiply
    (the `_MeshPlan` sibling for `tas_grouped_multiply`)."""

    s: int
    g: int
    q: int
    r0: int
    dot_form: str  # grouped stacks: `acc/smm.py:group_dot_form`
    xtr: int
    cap_a: int
    cap_b: int
    cap_c: int
    bm: int
    bk: int
    bn: int
    dtype: object
    acc_name: str
    true_flops: int
    n_cand: int
    ngroups: int
    stacks_dev: object
    a_asm: _BinAsm
    b_asm: _BinAsm
    cinit_asm: Optional[_BinAsm]
    c_keys: np.ndarray
    c_binning: tuple
    collect_own: tuple
    collect_perm: tuple
    collect_counts: tuple
    collect_shapes: tuple
    collect_shipped: int
    upload_bytes: int
    panel_cache: dict = dataclasses.field(default_factory=dict)

    def nbytes(self) -> int:
        n = (_stacks_nbytes(self.stacks_dev) + self.a_asm.nbytes()
             + self.b_asm.nbytes())
        if self.cinit_asm is not None:
            n += self.cinit_asm.nbytes()
        n += sum(int(x.nbytes) for x in self.collect_own)
        n += sum(int(x.nbytes) for x in self.collect_perm)
        for _, panels, _ in self.panel_cache.values():
            n += int(panels.nbytes)
        return n


def _mesh_candidates(a, b, matrix_c, dtype, limits, retain_sparsity,
                     filter_eps) -> tuple:
    """The candidates a mesh product keeps, ``(rows, cols, a_ent,
    b_ent)``: `mm.multiply._candidates` (under ``filter_eps`` the norm
    skip, which reads the operands' values), less those outside C's
    pattern under ``retain_sparsity``."""
    from dbcsr_tpu.mm.multiply import _candidates, mask_in_sorted

    shell_c = matrix_c if matrix_c is not None else BlockSparseMatrix(
        f"{a.name}*{b.name}", a.row_blk_sizes, b.col_blk_sizes, dtype
    )
    with timed("mesh_candidates"):
        rows_t, cols_t, a_ent, b_ent = _candidates(
            a, b, shell_c, filter_eps, *limits
        )
    if retain_sparsity:
        ok = mask_in_sorted(rows_t * shell_c.nblkcols + cols_t,
                            shell_c.keys)
        rows_t, cols_t, a_ent, b_ent = (
            rows_t[ok], cols_t[ok], a_ent[ok], b_ent[ok]
        )
    return rows_t, cols_t, a_ent, b_ent


def _build_mesh_plan(a, b, matrix_c, mesh, pr, pc, kl, dtype, bm, bk, bn, r0,
                     limits, retain_sparsity, cands,
                     beta_window=None) -> _MeshPlan:
    """The host-side half of a mesh multiply on the candidates it keeps
    (`_mesh_candidates`): device and tick assignment, stack fill,
    panel/collect index maps — all of it determined by the patterns and
    the candidates, and device-uploaded exactly once.

    Square grids (pr == pc) get the skewed Cannon layout; rectangular
    grids get the all-gather layout (stack entries index the
    'pc'-gathered A / 'pr'-gathered B concatenations, no skew, ticks =
    balanced chunks instead of alignment steps)."""
    rows_t, cols_t, a_ent, b_ent = cands
    nbc = b.nblkcols  # C's block columns
    old_keys = matrix_c.keys if matrix_c is not None else np.empty(0, np.int64)
    k_of_a = (a.keys % a.nblkcols).astype(np.int64)
    k_t = k_of_a[a_ent]
    true_flops = int(
        2 * np.sum(
            a.row_blk_sizes[rows_t].astype(np.int64)
            * b.col_blk_sizes[cols_t]
            * a.col_blk_sizes[k_t]
        )
    )

    cannon = pr == pc
    nticks = pr if cannon else max(pr, pc)
    rdist, cdist, k_layer, ka_col, kb_row = _resolve_maps(
        a, b, matrix_c, pr, pc, kl
    )

    i_dev = rdist[rows_t]
    j_dev = cdist[cols_t]
    layer = k_layer[k_t]

    ar, ac = a.entry_coords()
    a_layer, a_kc = k_layer[ac], ka_col[ac]
    a_panel = ((a_layer * pr) + rdist[ar]) * pc + a_kc  # (l, i, ka)
    a_slots = _panel_slots(a_panel)
    cap_a = bucket_size(max(int(np.bincount(a_panel, minlength=kl * pr * pc).max()), 1) if a.nblks else 1)

    br, bc = b.entry_coords()
    b_layer, b_kr = k_layer[br], kb_row[br]
    b_panel = ((b_layer * pr) + b_kr) * pc + cdist[bc]  # (l, kb, j)
    b_slots = _panel_slots(b_panel)
    cap_b = bucket_size(max(int(np.bincount(b_panel, minlength=kl * pr * pc).max()), 1) if b.nblks else 1)

    if retain_sparsity:
        c_keys = old_keys
    else:
        prod_keys = np.unique(rows_t * nbc + cols_t)
        c_keys = np.union1d(old_keys, prod_keys)
    c_rows = (c_keys // nbc).astype(np.int64)
    c_cols = (c_keys % nbc).astype(np.int64)
    c_panel = rdist[c_rows] * pc + cdist[c_cols]
    c_slots = _panel_slots(c_panel)
    cap_c = bucket_size(max(int(np.bincount(c_panel, minlength=pr * pc).max()), 1) if len(c_keys) else 1)

    ent_c = np.searchsorted(c_keys, rows_t * nbc + cols_t)
    xtr = 1 if r0 else 0
    if cannon:
        # Cannon: the tick is the alignment step at which A's k column
        # meets B's k row on the (i, j) device; stacks index LOCAL
        # panel slots (panels travel via ppermute)
        tick_t = (ka_col[k_t] - i_dev - j_dev) % pr
        st_a = a_slots[a_ent]
        st_b = b_slots[b_ent]
    else:
        # all-gather: stacks index the CONCATENATED ('pc'-gathered A /
        # 'pr'-gathered B) arrays, and ticks are SHARD-ARRIVAL chunks:
        # an entry may not run before the first tick at which both its
        # A shard (ring distance of its k home column from this
        # device's column) and its B shard (distance along 'pr') have
        # arrived — the chunked gather pipeline (`_gather_ticks`)
        # contracts tick t while shard t+1 is still in flight, and the
        # fused one-collective program replays the SAME per-tick
        # stacks so the two execution modes stay bitwise identical.
        # The arrival distance is only a LOWER bound (a shard stays
        # present once arrived), so each device's c-sorted stack is
        # forward-BALANCED across the eligible ticks: tick =
        # max(arrival, balanced rank-chunk position) keeps per-tick
        # entry counts ~even — one dominant shard pair must not size
        # the shared padded tick capacity (s_cap) to itself.
        dist_a = (ka_col[k_t] - j_dev) % pc
        dist_b = (kb_row[k_t] - i_dev) % pr
        arrive = np.maximum(dist_a, dist_b)
        dev_t = (layer * pr + i_dev) * pc + j_dev
        cnt = np.bincount(dev_t, minlength=kl * pr * pc)
        order_t = np.lexsort((c_slots[ent_c], arrive, dev_t))
        starts = np.concatenate([[0], np.cumsum(cnt)])[:-1]
        rank = np.empty(len(dev_t), np.int64)
        rank[order_t] = np.arange(len(dev_t)) - starts[dev_t[order_t]]
        pos = (rank * nticks) // np.maximum(cnt[dev_t], 1)
        tick_t = np.maximum(arrive, pos)
        st_a = ka_col[k_t] * (cap_a + xtr) + a_slots[a_ent]
        st_b = kb_row[k_t] * (cap_b + xtr) + b_slots[b_ent]
    group = (((layer * pr + i_dev) * pc + j_dev) * nticks) + tick_t
    with timed("mesh_stack_fill"):
        stacks = _fill_stacks(
            group, st_a, st_b, c_slots[ent_c],
            kl * pr * pc * nticks, cap_c, r0=r0, pad_a=cap_a, pad_b=cap_b,
            chunk_groups=_stack_chunk_groups(r0, bm, bn, bk, dtype),
        )
    with timed("mesh_plan_upload"):
        stacks_dev = _upload_stacks(stacks, mesh, (kl, pr, pc, nticks))

    # ---- device-side panel assembly maps ----
    al, ai_, akc = a_panel // (pr * pc), (a_panel // pc) % pr, a_panel % pc
    # Cannon panels start SKEWED so the first tick needs no shift;
    # all-gather panels sit at their k home column directly
    aj0 = (akc - ai_) % pr if cannon else akc
    a_flat = ((al * pr + ai_) * pc + aj0) * (cap_a + xtr) + a_slots
    a_asm = _make_bin_asm(a, a_flat, kl * pr * pc * (cap_a + xtr), bm, bk)

    bl, bkr, bj = b_panel // (pr * pc), (b_panel // pc) % pr, b_panel % pc
    bi0 = (bkr - bj) % pr if cannon else bkr
    b_flat = ((bl * pr + bi0) * pc + bj) * (cap_b + xtr) + b_slots
    b_asm = _make_bin_asm(b, b_flat, kl * pr * pc * (cap_b + xtr), bk, bn)

    cinit_asm = None
    if matrix_c is not None and matrix_c.nblks:
        pos_old = np.searchsorted(c_keys, old_keys)
        cinit_flat = (
            rdist[c_rows[pos_old]] * pc + cdist[c_cols[pos_old]]
        ) * cap_c + c_slots[pos_old]
        cinit_asm = _make_bin_asm(matrix_c, cinit_flat, pr * pc * cap_c, bm, bn)

    # windowed-beta semantics: C blocks outside the limit window keep
    # their old values (factor 1.0 instead of beta)
    fr_l, lr_l, fc_l, lc_l = limits[0], limits[1], limits[2], limits[3]
    has_window = any(x is not None for x in (fr_l, lr_l, fc_l, lc_l))
    inside = np.ones(len(c_keys), bool)
    if has_window:
        if fr_l is not None:
            inside &= c_rows >= fr_l
        if lr_l is not None:
            inside &= c_rows <= lr_l
        if fc_l is not None:
            inside &= c_cols >= fc_l
        if lc_l is not None:
            inside &= c_cols <= lc_l
    inside_dev = None
    inside_bytes = 0
    if beta_window is not None:
        # ELEMENT-granular beta window (unaligned limits): straddling C
        # blocks get a per-element factor mask — beta inside the window,
        # 1 outside — the mesh analog of the windowed-beta scatter
        # (`_scatter_scaled_window`, ref `dbcsr_test_multiply.F:631-633`)
        fr_e, lr_e, fc_e, lc_e = beta_window
        roff = np.concatenate([[0], np.cumsum(a.row_blk_sizes)]).astype(np.int64)
        coff = np.concatenate([[0], np.cumsum(b.col_blk_sizes)]).astype(np.int64)
        lo_r = np.clip(fr_e - roff[c_rows], 0, bm)
        hi_r = np.clip(lr_e - roff[c_rows] + 1, 0, bm)
        lo_c = np.clip(fc_e - coff[c_cols], 0, bn)
        hi_c = np.clip(lc_e - coff[c_cols] + 1, 0, bn)
        ri = np.arange(bm)[None, :]
        ci = np.arange(bn)[None, :]
        mrow = (ri >= lo_r[:, None]) & (ri < hi_r[:, None])
        mcol = (ci >= lo_c[:, None]) & (ci < hi_c[:, None])
        canvas = np.ones((pr, pc, cap_c, bm, bn), bool)
        canvas[rdist[c_rows], cdist[c_cols], c_slots] = (
            mrow[:, :, None] & mcol[:, None, :]
        )
        inside_dev = jax.device_put(canvas, NamedSharding(mesh, P("pr", "pc")))
        inside_bytes = canvas.nbytes
        has_window = True
        inside = np.zeros(1, bool)  # keep_old must stay on
    elif has_window and not inside.all():
        canvas = np.ones((pr, pc, cap_c), bool)
        canvas[rdist[c_rows], cdist[c_cols], c_slots] = inside
        inside_dev = jax.device_put(canvas, NamedSharding(mesh, P("pr", "pc")))
        inside_bytes = canvas.nbytes

    # ---- device-side C collection maps ----
    from dbcsr_tpu.core.matrix import _bin_entries

    nb, nsl, shapes = _bin_entries(a.row_blk_sizes, b.col_blk_sizes, c_rows, c_cols)
    collect_own, collect_perm, collect_counts, collect_shipped = \
        _collect_maps(mesh, ("pr", "pc"), nb, nsl, len(shapes),
                      c_panel, c_slots, cap_c)

    from dbcsr_tpu.core.dist import Distribution, ProcessGrid

    out_dist = (
        matrix_c.dist
        if matrix_c is not None and matrix_c.dist.grid.nprows == pr
        and matrix_c.dist.grid.npcols == pc
        else Distribution(
            rdist.astype(np.int32), cdist.astype(np.int32),
            ProcessGrid(pr, pc, mesh),
        )
    )

    upload_bytes = (
        _stacks_nbytes(stacks_dev) + a_asm.nbytes() + b_asm.nbytes()
        + inside_bytes
        + (cinit_asm.nbytes() if cinit_asm is not None else 0)
        + sum(int(x.nbytes) for x in collect_own)
        + sum(int(x.nbytes) for x in collect_perm)
    )
    acc_name = "float32" if np.dtype(dtype).name == "bfloat16" else np.dtype(dtype).name
    return _MeshPlan(
        s=pr, pc=pc, nticks=nticks,
        kl=kl, r0=r0, dot_form=_stack_dot_form(r0, bk, dtype), xtr=xtr,
        cap_a=cap_a, cap_b=cap_b, cap_c=cap_c,
        bm=bm, bk=bk, bn=bn, dtype=np.dtype(dtype), acc_name=acc_name,
        true_flops=true_flops, n_cand=len(rows_t), stacks_dev=stacks_dev,
        a_asm=a_asm, b_asm=b_asm, cinit_asm=cinit_asm,
        has_window=has_window, inside_all=bool(inside.all()),
        inside_dev=inside_dev, c_keys=c_keys,
        c_binning=(nb, nsl, shapes),
        collect_own=collect_own, collect_perm=collect_perm,
        collect_counts=collect_counts, collect_shapes=tuple(shapes),
        collect_shipped=collect_shipped, out_dist=out_dist,
        upload_bytes=int(upload_bytes),
    )


def _sparse_multiply_impl(alpha, matrix_a, matrix_b, beta, matrix_c, mesh, name,
                          limits=(None,) * 6, retain_sparsity=False,
                          filter_eps=None, element_limits=None):
    t_start = time.perf_counter()
    kl, pr, pc = mesh.shape["kl"], mesh.shape["pr"], mesh.shape["pc"]
    cannon = pr == pc
    # the planner's symmetry gate reads C as the caller gave it
    # (`_prepare_operands` desymmetrizes the one the engine fills)
    c_given = matrix_c
    # accumulate in C's dtype when C is given (host-path convention)
    a, b, matrix_c, dtype, bm, bk, bn = _prepare_operands(
        matrix_a, matrix_b, matrix_c
    )
    beta_window = None
    if element_limits is not None:
        # exact element-granular limits (ref `dbcsr_crop_matrix` inside
        # make_m2s, `dbcsr_mm_cannon.F:194-220`): crop op(A)/op(B) at
        # element level, reduce to block limits, and remember the
        # element window for windowed beta on straddling C blocks —
        # the same helper the single-chip engine uses
        if any(x is not None for x in limits):
            raise ValueError("give block-index OR element limits, not both")
        from dbcsr_tpu.mm.multiply import _apply_element_limits

        shell = matrix_c if matrix_c is not None else BlockSparseMatrix(
            name or f"{a.name}*{b.name}", a.row_blk_sizes, b.col_blk_sizes,
            dtype,
        )
        a, b, limits, beta_window = _apply_element_limits(
            a, b, shell, element_limits
        )

    # ---- format decision: the planner the single-chip engine asks
    # (ref the generic driver's make_dense gate used by EVERY parallel
    # path, `dbcsr_mm.F:593-617`).  What this engine can execute is the
    # dense 2.5D Cannon, square grids only (rectangular grids keep the
    # sparse all-gather route), on whole canvases ----
    from dbcsr_tpu.mm import format_planner as _fmt

    fmt_plan = _fmt.choose(
        a, b,
        c_given if c_given is not None else BlockSparseMatrix(
            name or f"{a.name}*{b.name}", a.row_blk_sizes, b.col_blk_sizes,
            dtype),
        filter_eps=filter_eps, retain_sparsity=retain_sparsity,
        no_limits=all(x is None for x in limits),
        dense=cannon, chunked_canvas=False,
    )
    # (not `_fmt.note_decision`-ed yet: the benchmark's harness test
    # pins that a mesh window moves no decision counter, and only a
    # `benchmark` PR may edit it — ROADMAP C2)
    if fmt_plan.fmt == "dense":
        return _dense_multiply_mesh(
            alpha, a, b, beta, matrix_c, mesh, name, dtype, pr, kl
        )

    r0 = _stack_r0(dtype)
    from dbcsr_tpu.core import stats

    # ---- plan lookup: the patterns and product options key a plan.
    # A filtered product's candidates also follow the operands' values
    # (the norm skip), so they are found every product and their
    # digest joins the key, as the single-chip `_plan_cache` keys them
    # (`mm.multiply.multiply`): with A's and B's patterns in the key,
    # the (a_ent, b_ent) pairs name the survivors whole ----
    with timed("mesh_plan_build"):
        plan_key = (
            a.pattern_fingerprint(), b.pattern_fingerprint(),
            matrix_c.pattern_fingerprint() if matrix_c is not None else None,
            a.dist.fingerprint(), b.dist.fingerprint(),
            matrix_c.dist.fingerprint() if matrix_c is not None else None,
            np.dtype(dtype).name, retain_sparsity, limits, beta_window,
            _HashableMesh(mesh), r0, _stack_dot_form(r0, bk, dtype),
        )
        cands = None
        if filter_eps is not None:
            from dbcsr_tpu.core import digests

            cands = _mesh_candidates(a, b, matrix_c, dtype, limits,
                                     retain_sparsity, filter_eps)
            plan_key += ("filtered", float(filter_eps),
                         digests.index_digest(cands[2], cands[3]))
        plan = _mesh_plan_lookup(plan_key)
        if plan is None:
            if cands is None:
                cands = _mesh_candidates(a, b, matrix_c, dtype, limits,
                                         retain_sparsity, None)
            plan = _build_mesh_plan(
                a, b, matrix_c, mesh, pr, pc, kl, dtype, bm, bk, bn, r0,
                limits, retain_sparsity, cands, beta_window,
            )
            _mesh_plan_insert(plan_key, plan)
            # the plan build is the ONLY host->device traffic of a mesh
            # multiply now; plan-cache hits upload nothing
            stats.record_comm("host2dev", 1, plan.upload_bytes)
        else:
            _flight.note("plan_cache", "hit")
    cap_a, cap_b, cap_c = plan.cap_a, plan.cap_b, plan.cap_c
    xtr = plan.xtr

    # ---- device-side panel assembly (cached by bin data identity) ----
    spec3 = P("kl", "pr", "pc")
    with timed("mesh_panels"):
        a_panels = _cached_panels(
            plan, "a", a, mesh, (kl, pr, pc, cap_a + xtr, bm, bk), spec3
        )
        b_panels = _cached_panels(
            plan, "b", b, mesh, (kl, pr, pc, cap_b + xtr, bk, bn), spec3
        )

    with timed("mesh_c_init"):
        keep_old = beta != 0 or (plan.has_window and not plan.inside_all)
        if plan.cinit_asm is not None and keep_old:
            c_flat = _run_bin_asm(plan.cinit_asm, matrix_c, dtype)
        else:
            c_flat = jnp.zeros((pr * pc * cap_c, bm, bn), dtype)
        c_init = jax.device_put(
            c_flat.reshape(pr, pc, cap_c, bm, bn),
            NamedSharding(mesh, P("pr", "pc")),
        )
        if plan.inside_dev is not None:
            beta_fac = jnp.where(
                plan.inside_dev,
                jnp.asarray(beta, dtype), jnp.asarray(1, dtype),
            )
        else:
            beta_fac = jnp.full((pr, pc, cap_c), beta, dtype)
        beta_fac = jax.device_put(beta_fac, NamedSharding(mesh, P("pr", "pc")))

    # ---- run on the mesh ----
    grid = f"{kl}x{pr}x{pc}"
    # both distributed legs pipeline now: square Cannon grids through
    # the double-buffered ring metronome (cannon_db), rectangular grids
    # through the chunked all-gather (gather_pipe) — one knob, two
    # pseudo-driver breakers
    pipe_s = pr if cannon else plan.nticks
    pipe_driver = _overlap.DRIVER if cannon else _overlap.GATHER_DRIVER
    if pipe_s > 1:
        # modeled per-tick comm/compute attribution, same gauge family
        # as the dense Cannon's but labeled engine="mesh" (panel
        # capacities stand in for the dense panel dims); the gather
        # route moves the same shard pair per chunk a Cannon tick
        # ring-shifts
        model_fn = (_costmodel.mesh_tick_model if cannon
                    else _costmodel.gather_chunk_model)
        tickm = model_fn(
            cap_a + xtr, cap_b + xtr, bm, bk, bn, plan.n_cand,
            plan.nticks, kl * pr * pc, np.dtype(dtype).itemsize,
            np.dtype(dtype).name,
        )
        _overlap.publish_modeled("mesh", grid, tickm)
    mode, why = _overlap.resolve_mode(
        "mesh", grid, pipe_s, plan.nticks, driver=pipe_driver)
    _overlap.publish_decision("mesh", grid, mode, why)
    alpha_dev = jnp.asarray(alpha, dtype)
    mref = _HashableMesh(mesh)

    def serial_fn():
        _note_program("run", "_stack_run_mesh", a_panels, b_panels,
                      plan.stacks_dev, beta_fac, nticks=plan.nticks,
                      cap_c=cap_c, acc=plan.acc_name, r0=r0,
                      dot_form=plan.dot_form)
        out = _stack_run_mesh(
            a_panels, b_panels, plan.stacks_dev, c_init,
            alpha_dev, beta_fac,
            s=pr, nticks=plan.nticks, gather=not cannon, cap_c=cap_c,
            acc_name=plan.acc_name, mesh_ref=mref, r0=r0,
            dot_form=plan.dot_form,
        )
        _record_mesh_dispatch(plan.stacks_dev, r0)
        return out

    _note_mesh_dot(plan)
    measure = pipe_s > 1 and _overlap.measuring()
    with timed("mesh_ticks"):
        if _overlap.use_split_pipeline(mode, why, measure):
            # double-buffered ticks / chunked gather, or the measured
            # serial reference (same per-tick op sequence, one dispatch
            # per region — the DBCSR_TPU_SYNC_TIMING seam); both
            # guarded: an open pipeline breaker or a split-pipeline
            # failure falls back to serial_fn
            ticks_fn = _mesh_ticks if cannon else _gather_ticks
            c_out = _overlap.run_split_pipeline(
                "mesh", grid, mode,
                lambda timings: ticks_fn(
                    plan, mesh, a_panels, b_panels, c_init, alpha_dev,
                    beta_fac, mode, measure, timings),
                serial_fn, measure, driver=pipe_driver,
            )
        else:
            c_out = serial_fn()

    # ---- device-side collect into shape bins (C stays resident) ----
    out = BlockSparseMatrix(
        name or (matrix_c.name if matrix_c is not None else f"{a.name}*{b.name}"),
        a.row_blk_sizes, b.col_blk_sizes, dtype,
        dist=plan.out_dist,
    )
    with timed("mesh_collect"):
        bins = _collected_bins(plan, mref, c_out)
        out.set_structure_from_device(plan.c_keys, bins,
                                      binning=plan.c_binning)
    if filter_eps is not None and not retain_sparsity:
        # final ||C|| >= eps pass (ref multrec_filtering,
        # dbcsr_mm_multrec.F:694-748) — shared criterion with the
        # single-chip engine so filtered patterns agree exactly
        from dbcsr_tpu.mm.multiply import note_filter_fates
        from dbcsr_tpu.ops.operations import filter_matrix

        with timed("mesh_filter"):
            nblks_pre = out.nblks
            # the norms need C: on an async device this call is the
            # wait for the ticks and the collect, and gets a span of
            # its own so that mesh_filter's self time is the host's work
            with timed("mesh_filter_norms"):
                norms = out.block_norms()
            filter_matrix(out, filter_eps, norms=norms)
            note_filter_fates(nblks_pre, out.nblks)

    stats.record_stack(
        bm, bn, bk, plan.n_cand, driver="mesh",
        seconds=time.perf_counter() - t_start,
        nbytes=_costmodel.stack_bytes(
            bm, bn, bk, plan.n_cand, nseg=max(len(plan.c_keys), 1),
            itemsize=np.dtype(dtype).itemsize),
        dtype=np.dtype(dtype).name,
    )
    stats.record_multiply(2 * out.nfullrows * out.nfullcols * a.nfullcols)
    stats.sample_memory()
    # collective-traffic accounting (ref count_mpi_statistics,
    # dbcsr_mm_common.F:135): each tick ppermutes every device's A and B
    # panel; the layer reduction psums each device's C panel
    ndev = kl * pr * pc
    itemsize = np.dtype(dtype).itemsize
    if cannon and pr > 1:
        stats.record_comm(
            "ppermute", 2 * pr * ndev,
            pr * ndev * (cap_a * bm * bk + cap_b * bk * bn) * itemsize,
        )
    elif not cannon:
        # all-gather model: every device receives the other pc-1 (A)
        # / pr-1 (B) panels of its gather group once
        stats.record_comm(
            "all_gather", 2 * ndev,
            ndev * ((pc - 1) * cap_a * bm * bk + (pr - 1) * cap_b * bk * bn)
            * itemsize,
        )
    if kl > 1:
        # ring-reduce model: each of the kl-1 steps moves every
        # (pr,pc) position's C panel once
        stats.record_comm(
            "psum", (kl - 1) * pr * pc,
            (kl - 1) * pr * pc * cap_c * bm * bn * itemsize,
        )
    out._last_flops = plan.true_flops  # true flop count of this product
    out._mm_algorithm = "stack"
    # on the product's flight record, as `mm.multiply` notes them
    _flight.note("flops", plan.true_flops)
    _flight.note("algorithm", "stack")
    return out


def _collected_bins(plan, mref: _HashableMesh, c_panels) -> list:
    """The plan's C bins carved on device from the C panels
    (`_collect_bins`), whole on every device; no bin where the product
    bore no block."""
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.core.matrix import _Bin

    if not len(plan.c_keys):
        return []
    _note_program("collect", "_collect_bins", c_panels, plan.collect_own,
                  plan.collect_perm, shapes=plan.collect_shapes)
    bin_datas = _collect_bins(
        c_panels, plan.collect_own, plan.collect_perm,
        shapes=plan.collect_shapes, mesh_ref=mref,
    )
    slots = _metrics.counter(
        "dbcsr_tpu_mesh_collect_slots_total",
        "block slots of the mesh collect (`_collect_bins`), a product: "
        "'live' are C's blocks, 'shipped' the piece slots all-gathered "
        "over the grid (bucket pads included)",
    )
    slots.inc(len(plan.c_keys), kind="live")
    slots.inc(plan.collect_shipped, kind="shipped")
    stats.record_collect_slots(len(plan.c_keys), plan.collect_shipped)
    return [
        _Bin((int(shape[0]), int(shape[1])), data, int(count))
        for shape, data, count in zip(
            plan.collect_shapes, bin_datas, plan.collect_counts)
    ]


def _dense_multiply_mesh(alpha, a, b, beta, matrix_c, mesh, name, dtype,
                         s, kl) -> BlockSparseMatrix:
    """Mesh dense mode: densify the operands on device (cached element
    canvases, no host staging), run the dense 2.5D Cannon over the SAME
    ('kl','pr','pc') mesh, and carve C back into its full block pattern
    (`dbcsr_make_dense` + `use_dense_mult`, `dbcsr_mm.F:593-617,770-810`,
    inside the parallel driver).  GFLOP/s reporting stays honest: the
    true sparse-product flops are returned, the dense work lands in the
    marketing counter (`dbcsr_mm.F:664-667`)."""
    t_start = time.perf_counter()
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.core.dist import Distribution, ProcessGrid
    from dbcsr_tpu.mm.multiply import (
        _dense_canvas_cached, _to_dense_device, _true_product_flops,
        carve_full_pattern,
    )
    from dbcsr_tpu.parallel.cannon import cannon_multiply_dense

    ad = _dense_canvas_cached(a, lambda: _to_dense_device(a)).astype(dtype)
    bd = _dense_canvas_cached(b, lambda: _to_dense_device(b)).astype(dtype)
    m_el, k_el = ad.shape
    n_el = bd.shape[1]
    mp = -(-m_el // s) * s
    np_ = -(-n_el // s) * s
    kp = -(-k_el // (kl * s)) * (kl * s)
    if (mp, kp) != (m_el, k_el):
        ad = jnp.pad(ad, ((0, mp - m_el), (0, kp - k_el)))
    if (kp, np_) != (k_el, n_el):
        bd = jnp.pad(bd, ((0, kp - k_el), (0, np_ - n_el)))
    acc_name = "float32" if np.dtype(dtype).name == "bfloat16" else None
    cd = cannon_multiply_dense(
        mesh, ad, bd, acc_dtype=jnp.dtype(acc_name) if acc_name else None
    )[:m_el, :n_el].astype(dtype)
    cd = jnp.asarray(alpha, dtype) * cd
    if beta != 0 and matrix_c is not None and matrix_c.nblks:
        cd = cd + jnp.asarray(beta, dtype) * _to_dense_device(matrix_c).astype(dtype)

    out_dist = (
        matrix_c.dist
        if matrix_c is not None and matrix_c.dist.grid.nprows == s
        and matrix_c.dist.grid.npcols == s
        else Distribution(
            (np.arange(a.nblkrows) % s).astype(np.int32),
            (np.arange(b.nblkcols) % s).astype(np.int32),
            ProcessGrid(s, s, mesh),
        )
    )
    out = BlockSparseMatrix(
        name or (matrix_c.name if matrix_c is not None else f"{a.name}*{b.name}"),
        a.row_blk_sizes, b.col_blk_sizes, dtype, dist=out_dist,
    )
    carve_full_pattern(out, cd)
    bm = int(a.row_blk_sizes.max()) if a.nblkrows else 1
    bk = int(a.col_blk_sizes.max()) if a.nblkcols else 1
    bn = int(b.col_blk_sizes.max()) if b.nblkcols else 1
    stats.record_stack(bm, bn, bk, a.nblkrows * b.nblkcols * a.nblkcols,
                       driver="dense",
                       seconds=time.perf_counter() - t_start,
                       nbytes=_costmodel.dense_cost(
                           out.nfullrows, out.nfullcols, a.nfullcols,
                           itemsize=np.dtype(dtype).itemsize)["bytes"],
                       dtype=np.dtype(dtype).name)
    stats.record_multiply(2 * out.nfullrows * out.nfullcols * a.nfullcols)
    stats.sample_memory()
    out._last_flops = _true_product_flops(a, b)
    out._mm_algorithm = "dense"
    return out


@functools.partial(
    jax.jit,
    static_argnames=("s", "cap_c", "acc_name", "mesh_ref", "r0", "dot_form"),
)
def _stack_run_grouped(a_panels, b_panels, stacks, c_init, alpha, beta,
                       *, s, cap_c, acc_name, mesh_ref, r0=0,
                       dot_form="compiler"):
    """nsplit independent Cannon multiplies, one per 'kl' group, in a
    single SPMD program.  The short matrix (B) arrives replicated over
    'kl' (spec without the axis) — the `dbcsr_tas_replicate` analog —
    and groups write disjoint C slices, so there is no end reduction
    (the reference's `redistribute_and_sum`, `dbcsr_tas_mm.F:783`,
    becomes a pure collect)."""
    mesh = mesh_ref.val
    acc_dtype = jnp.dtype(acc_name)

    def body(a_p, b_p, st, c_in, alpha, beta):
        a = a_p.reshape(a_p.shape[3:])  # (cap_a, bm, bk)
        b = b_p.reshape(b_p.shape[2:])  # (cap_b, bk, bn), replicated on kl
        st = _local_stacks(st)  # (s, ...): flat rows or group tiles
        c_in = c_in.reshape(c_in.shape[3:])  # (cap_c, bm, bn)
        b = jax.lax.pcast(b, ("kl",), to="varying")
        c = _cannon_tick_loop(a, b, st, s, cap_c, acc_dtype, r0=r0,
                              dot_form=dot_form)
        c = (alpha * c + beta * c_in.astype(acc_dtype)).astype(c_in.dtype)
        return c.reshape((1, 1, 1) + c.shape)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P("kl", "pr", "pc"),
            P("pr", "pc"),
            P("kl", "pr", "pc"),
            P("kl", "pr", "pc"),
            P(),
            P(),
        ),
        out_specs=P("kl", "pr", "pc"),
    )
    return fn(a_panels, b_panels, stacks, c_init, alpha, beta)


# --------------------------------------------------------------------------
# Grouped-TAS split per-tick programs: the per-group Cannons advance in
# lockstep inside one fused program (`_stack_run_grouped`); staggering
# them through the double-buffer metronome dispatches the group
# ensemble's tick-(t+1) ring shift before tick t's contraction is
# consumed, so every group's shift overlaps every group's compute.  Op
# code (`_tick_contrib_chunked`) and per-tick order are shared with the
# fused program — bitwise identical — and failures degrade through the
# `cannon_db` pseudo-driver (keyed engine="tas") to the fused program.
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("s", "mesh_ref"))
def _grouped_shift_program(a_panels, b_panels, *, s, mesh_ref):
    """One grouped-TAS ring shift: every group's A panel moves left
    along 'pc', the group-replicated B panel up along 'pr' (B stays
    replicated over 'kl' — the `dbcsr_tas_replicate` analog — so the
    shift is one collective per (pr, pc) position, not per group)."""
    shift_a, shift_b = _ring_perms(s)

    def body(a_p, b_p):
        a = a_p.reshape(a_p.shape[3:])
        b = b_p.reshape(b_p.shape[2:])
        a = jax.lax.ppermute(a, ("pc",), shift_a)
        b = jax.lax.ppermute(b, ("pr",), shift_b)
        return (a.reshape((1, 1, 1) + a.shape),
                b.reshape((1, 1) + b.shape))

    fn = jax.shard_map(
        body,
        mesh=mesh_ref.val,
        in_specs=(P("kl", "pr", "pc"), P("pr", "pc")),
        out_specs=(P("kl", "pr", "pc"), P("pr", "pc")),
    )
    return fn(a_panels, b_panels)


@functools.partial(
    jax.jit,
    static_argnames=("cap_c", "acc_name", "mesh_ref", "r0", "dot_form"),
)
def _stack_tick_grouped(a_panels, b_panels, stacks, c_acc, t, *,
                        cap_c, acc_name, mesh_ref, r0=0, dot_form="compiler"):
    """One grouped tick's chunked contribution into the per-group
    accumulator (global (kl, s, s, q*cap_c, bm, bn); ``cap_c`` here is
    the chunk-expanded q*cap_c capacity)."""
    mesh = mesh_ref.val
    acc_dtype = jnp.dtype(acc_name)

    def body(a_p, b_p, st, c_p, t):
        a = a_p.reshape(a_p.shape[3:])
        b = b_p.reshape(b_p.shape[2:])
        b = jax.lax.pcast(b, ("kl",), to="varying")
        c = c_p.reshape(c_p.shape[3:])   # (q*cap_c, bm, bn)
        c = _tick_contrib_chunked(
            a, b, c, _stack_of_tick(_local_stacks(st), t), r0=r0,
            dot_form=dot_form,
            cap_c=cap_c, acc_dtype=acc_dtype)
        return c.reshape((1, 1, 1) + c.shape)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P("kl", "pr", "pc"),
            P("pr", "pc"),
            P("kl", "pr", "pc"),
            P("kl", "pr", "pc"),
            P(),
        ),
        out_specs=P("kl", "pr", "pc"),
    )
    return fn(a_panels, b_panels, stacks, c_acc, t)


@functools.partial(jax.jit, static_argnames=("acc_name", "mesh_ref"))
def _grouped_finish_program(c_acc, c_init, alpha, beta, *,
                            acc_name, mesh_ref):
    """Grouped alpha/beta merge (same op order as the fused program's
    tail); groups write disjoint C slices, so there is no reduction."""
    acc_dtype = jnp.dtype(acc_name)

    def body(c_p, c_in, alpha, beta):
        c = c_p.reshape(c_p.shape[3:])
        c_in = c_in.reshape(c_in.shape[3:])
        c = (alpha * c + beta * c_in.astype(acc_dtype)).astype(c_in.dtype)
        return c.reshape((1, 1, 1) + c.shape)

    fn = jax.shard_map(
        body,
        mesh=mesh_ref.val,
        in_specs=(
            P("kl", "pr", "pc"),
            P("kl", "pr", "pc"),
            P(),
            P(),
        ),
        out_specs=P("kl", "pr", "pc"),
    )
    return fn(c_acc, c_init, alpha, beta)


def _tas_ticks(plan: "_GroupedPlan", mesh, a_panels, b_panels, c_init,
               alpha_dev, beta_dev, mode: str, measure: bool,
               timings: list):
    """Host-driven staggered grouped-TAS metronome — bitwise identical
    to `_stack_run_grouped` (shared per-tick op code, same tail)."""
    from dbcsr_tpu.acc.smm import record_dispatch

    mref = _HashableMesh(mesh)
    s, q = plan.s, plan.q
    c_acc = _overlap.zeros_program(
        mref, (plan.g, s, s, q * plan.cap_c, plan.bm, plan.bn),
        plan.acc_name, P("kl", "pr", "pc"),
    )()
    record_dispatch(_overlap.DRIVER)  # the zeros program

    def shift(aa, bb):
        return _grouped_shift_program(aa, bb, s=s, mesh_ref=mref)

    def tick(aa, bb, cc, t):
        return _stack_tick_grouped(
            aa, bb, plan.stacks_dev, cc, jnp.asarray(t, jnp.int32),
            cap_c=q * plan.cap_c, acc_name=plan.acc_name, mesh_ref=mref,
            r0=plan.r0, dot_form=plan.dot_form,
        )

    c_acc, shift_s, comp_s = _overlap.run_ticks(
        s, a_panels, b_panels, c_acc, shift, tick,
        mode=mode, engine="tas", measure=measure,
        driver=_overlap.DRIVER, site="tas_tick",
    )
    if measure:
        timings.append((shift_s, comp_s))
    res = _grouped_finish_program(
        c_acc, c_init, alpha_dev, beta_dev,
        acc_name=plan.acc_name, mesh_ref=mref,
    )
    record_dispatch(_overlap.DRIVER)
    return res


def _balanced_groups(weights: np.ndarray, ngroups: int) -> np.ndarray:
    """Contiguous partition of a block axis into ngroups with ~equal
    total weight (the reference splits the long dimension contiguously
    over process groups, `dbcsr_tas_split.F:66-304`)."""
    n = len(weights)
    if n == 0:
        return np.empty(0, np.int64)
    cum = np.cumsum(weights.astype(np.float64))
    total = cum[-1] if cum[-1] > 0 else 1.0
    # group boundary: first index whose cumulative share passes g/ngroups
    frac = (cum - weights / 2) / total
    groups = np.minimum((frac * ngroups).astype(np.int64), ngroups - 1)
    return np.maximum.accumulate(groups)  # enforce monotone (contiguity)


def tas_grouped_multiply(
    alpha,
    matrix_a: BlockSparseMatrix,
    matrix_b: BlockSparseMatrix,
    beta,
    matrix_c: Optional[BlockSparseMatrix],
    mesh: Mesh,
    name: Optional[str] = None,
    filter_eps: Optional[float] = None,
    nsplit: Optional[int] = None,
) -> BlockSparseMatrix:
    """Group-parallel tall-and-skinny multiply: C's (long) row dimension
    is partitioned into ``nsplit`` groups (default: the mesh 'kl' size),
    each group runs an independent s x s sparse Cannon concurrently, and
    the small matrix B is replicated into every group.

    The TPU-native re-design of `dbcsr_tas_multiply`'s grid split
    (`dbcsr_tas_mm.F:79-806`, `dbcsr_tas_split.F:304`): the reference
    splits its MPI grid into row groups, replicates the small matrix
    per group (`dbcsr_tas_replicate`) and merges with
    `redistribute_and_sum` (:783); here groups map onto the 'kl' mesh
    axis x in-slot chunks (``nsplit`` need NOT equal the physical kl
    size, matching the reference's nnz-driven nsplit choice,
    `dbcsr_tas_split.F:207-304`), replication is an unsharded in_spec,
    and since row groups are disjoint the merge is a pure collect.
    Chunks sharing a kl position run inside one device's buffers with
    per-chunk slot offsets; their Cannons advance in lockstep under the
    same metronome.  A column-long C is handled by the caller via
    transposition (C^T row-grouped).
    """
    with _events.product_scope(
            "tas_mesh_multiply", name or f"{matrix_a.name}*{matrix_b.name}",
            a=matrix_a.name, b=matrix_b.name):
        with timed("tas_grouped_cannon"):
            return _tas_grouped_impl(
                alpha, matrix_a, matrix_b, beta, matrix_c, mesh, name,
                filter_eps, nsplit=nsplit,
            )


def _build_grouped_plan(a, b, matrix_c, mesh, g, s, dtype, bm, bk, bn, r0,
                        filter_eps, nsplit) -> _GroupedPlan:
    """Host-side half of a grouped TAS mesh multiply; everything here is
    pattern-determined and uploaded once per plan."""
    from dbcsr_tpu.mm.multiply import _candidates

    shell_c = matrix_c if matrix_c is not None else BlockSparseMatrix(
        f"{a.name}*{b.name}", a.row_blk_sizes, b.col_blk_sizes, dtype
    )
    rows_t, cols_t, a_ent, b_ent = _candidates(a, b, shell_c, filter_eps,
                                               *(None,) * 6)
    k_of_a = (a.keys % a.nblkcols).astype(np.int64)
    k_t = k_of_a[a_ent]
    true_flops = int(
        2 * np.sum(
            a.row_blk_sizes[rows_t].astype(np.int64)
            * b.col_blk_sizes[cols_t]
            * a.col_blk_sizes[k_t]
        )
    )

    # ---- group + in-group maps ----
    # ngroups honors the COMPUTED nsplit (ref nnz-driven split choice,
    # `dbcsr_tas_split.F:207-304`), independent of the physical kl size:
    # group gr lives at kl position gr // q, in-slot chunk gr % q, with
    # q = ceil(ngroups / kl).  Chunks sharing a kl position occupy
    # disjoint slot ranges of the same device buffers and their Cannons
    # advance under one metronome.
    ngroups = g if nsplit is None else max(int(nsplit), 1)
    ngroups = min(ngroups, max(a.nblkrows, 1))
    q = -(-ngroups // g)
    # balance groups by actual per-row work (candidate count), the
    # analog of the reference's nnz-driven split estimation (:1427)
    row_work = np.bincount(rows_t, minlength=a.nblkrows).astype(np.float64) + 1.0
    row_group = _balanced_groups(row_work, ngroups)
    row_kl = row_group // q       # physical kl position of a row's group
    row_ch = row_group % q        # in-slot chunk at that position
    rdist_in = _panel_slots(row_group) % s  # round-robin rows within a group
    cdist = np.arange(b.nblkcols, dtype=np.int64) % s
    k_col = np.arange(a.nblkcols, dtype=np.int64) % s  # no k images: one layer

    i_dev = rdist_in[rows_t]
    j_dev = cdist[cols_t]
    kc = k_col[k_t]
    tick_t = (kc - i_dev - j_dev) % s

    # ---- panels (capacities are PER GROUP; chunk slots are offset) ----
    ar, ac = a.entry_coords()
    a_panel = (row_group[ar] * s + rdist_in[ar]) * s + k_col[ac]  # (grp, i, kc)
    a_slots = _panel_slots(a_panel)
    cap_a = max(int(np.bincount(a_panel, minlength=ngroups * s * s).max()), 1) if a.nblks else 1

    br, bc = b.entry_coords()
    b_panel = k_col[br] * s + cdist[bc]  # (kr, j) — replicated over groups
    b_slots = _panel_slots(b_panel)
    cap_b = max(int(np.bincount(b_panel, minlength=s * s).max()), 1) if b.nblks else 1

    old_keys = matrix_c.keys if matrix_c is not None else np.empty(0, np.int64)
    prod_keys = np.unique(rows_t * shell_c.nblkcols + cols_t)
    c_keys = np.union1d(old_keys, prod_keys)
    c_rows = (c_keys // shell_c.nblkcols).astype(np.int64)
    c_cols = (c_keys % shell_c.nblkcols).astype(np.int64)
    c_panel = (row_group[c_rows] * s + rdist_in[c_rows]) * s + cdist[c_cols]
    c_slots = _panel_slots(c_panel)
    cap_c = max(int(np.bincount(c_panel, minlength=ngroups * s * s).max()), 1) if len(c_keys) else 1

    # ---- per-(kl, device, tick) stacks; chunk offsets in the slots ----
    ent_c = np.searchsorted(c_keys, rows_t * shell_c.nblkcols + cols_t)
    grp_kl = row_kl[rows_t]
    grp_ch = row_ch[rows_t]
    group_id = (((grp_kl * s + i_dev) * s + j_dev) * s) + tick_t
    st_a = (row_ch[ar][a_ent] * cap_a + a_slots[a_ent]).astype(np.int64)
    st_c = (grp_ch * cap_c + c_slots[ent_c]).astype(np.int64)
    stacks = _fill_stacks(
        group_id, st_a, b_slots[b_ent], st_c,
        g * s * s * s, q * cap_c, r0=r0, pad_a=q * cap_a, pad_b=cap_b,
        chunk_groups=_stack_chunk_groups(r0, bm, bn, bk, dtype),
    )
    stacks_dev = _upload_stacks(stacks, mesh, (g, s, s, s))

    # ---- device-side panel assembly maps (skewed start positions) ----
    xtr = 1 if r0 else 0
    agr, ai_, akc = a_panel // (s * s), (a_panel // s) % s, a_panel % s
    aj0 = (akc - ai_) % s
    a_flat = (
        ((agr // q) * s + ai_) * s + aj0
    ) * (q * cap_a + xtr) + (agr % q) * cap_a + a_slots
    a_asm = _make_bin_asm(a, a_flat, g * s * s * (q * cap_a + xtr), bm, bk)

    bkr, bj = b_panel // s, b_panel % s
    bi0 = (bkr - bj) % s
    b_flat = (bi0 * s + bj) * (cap_b + xtr) + b_slots
    b_asm = _make_bin_asm(b, b_flat, s * s * (cap_b + xtr), bk, bn)

    cinit_asm = None
    if matrix_c is not None and matrix_c.nblks:
        pos_old = np.searchsorted(c_keys, old_keys)
        cinit_flat = (
            (row_kl[c_rows[pos_old]] * s + rdist_in[c_rows[pos_old]]) * s
            + cdist[c_cols[pos_old]]
        ) * (q * cap_c) + row_ch[c_rows[pos_old]] * cap_c + c_slots[pos_old]
        cinit_asm = _make_bin_asm(matrix_c, cinit_flat, g * s * s * q * cap_c,
                                  bm, bn)

    # ---- device-side C collection maps ----
    from dbcsr_tpu.core.matrix import _bin_entries

    nb, nsl, shapes = _bin_entries(a.row_blk_sizes, b.col_blk_sizes,
                                   c_rows, c_cols)
    collect_own, collect_perm, collect_counts, collect_shipped = \
        _collect_maps(
            mesh, ("kl", "pr", "pc"), nb, nsl, len(shapes),
            (row_kl[c_rows] * s + rdist_in[c_rows]) * s + cdist[c_cols],
            row_ch[c_rows] * cap_c + c_slots, q * cap_c)

    upload_bytes = (
        _stacks_nbytes(stacks_dev) + a_asm.nbytes() + b_asm.nbytes()
        + (cinit_asm.nbytes() if cinit_asm is not None else 0)
        + sum(int(x.nbytes) for x in collect_own)
        + sum(int(x.nbytes) for x in collect_perm)
    )
    acc_name = "float32" if np.dtype(dtype).name == "bfloat16" else np.dtype(dtype).name
    return _GroupedPlan(
        s=s, g=g, q=q, r0=r0, dot_form=_stack_dot_form(r0, bk, dtype),
        xtr=xtr, cap_a=cap_a, cap_b=cap_b, cap_c=cap_c,
        bm=bm, bk=bk, bn=bn, dtype=np.dtype(dtype), acc_name=acc_name,
        true_flops=true_flops, n_cand=len(rows_t),
        ngroups=int(row_group.max()) + 1 if len(row_group) else 0,
        stacks_dev=stacks_dev, a_asm=a_asm, b_asm=b_asm, cinit_asm=cinit_asm,
        c_keys=c_keys, c_binning=(nb, nsl, shapes),
        collect_own=collect_own, collect_perm=collect_perm,
        collect_counts=collect_counts, collect_shapes=tuple(shapes),
        collect_shipped=collect_shipped, upload_bytes=int(upload_bytes),
    )


def _tas_grouped_impl(alpha, matrix_a, matrix_b, beta, matrix_c, mesh, name,
                      filter_eps, nsplit=None):
    t_start = time.perf_counter()
    g, s = mesh.shape["kl"], mesh.shape["pr"]
    if mesh.shape["pc"] != s:
        raise ValueError(
            "the grouped TAS mesh path needs a square ('pr','pc') grid; "
            "rebuild the mesh with make_grid/optimize_grid (square "
            "preferred automatically), or use sparse_multiply_distributed, "
            "whose all-gather engine supports rectangular grids"
        )
    a, b, matrix_c, dtype, bm, bk, bn = _prepare_operands(
        matrix_a, matrix_b, matrix_c
    )
    r0 = _stack_r0(dtype)
    from dbcsr_tpu.core import stats

    plan_key = None
    if filter_eps is None:
        plan_key = (
            "tas", a.pattern_fingerprint(), b.pattern_fingerprint(),
            matrix_c.pattern_fingerprint() if matrix_c is not None else None,
            np.dtype(dtype).name, nsplit, _HashableMesh(mesh), r0,
            _stack_dot_form(r0, bk, dtype),
        )
    plan = _mesh_plan_lookup(plan_key)
    if plan is None:
        with timed("mesh_plan_build"):
            plan = _build_grouped_plan(
                a, b, matrix_c, mesh, g, s, dtype, bm, bk, bn, r0,
                filter_eps, nsplit,
            )
        if plan_key is not None:
            _mesh_plan_insert(plan_key, plan)
        stats.record_comm("host2dev", 1, plan.upload_bytes)
    q, cap_a, cap_b, cap_c, xtr = plan.q, plan.cap_a, plan.cap_b, plan.cap_c, plan.xtr

    a_panels = _cached_panels(
        plan, "a", a, mesh, (g, s, s, q * cap_a + xtr, bm, bk),
        P("kl", "pr", "pc"),
    )
    b_panels = _cached_panels(
        plan, "b", b, mesh, (s, s, cap_b + xtr, bk, bn), P("pr", "pc")
    )
    if plan.cinit_asm is not None and beta != 0:
        c_flat = _run_bin_asm(plan.cinit_asm, matrix_c, dtype)
    else:
        c_flat = jnp.zeros((g * s * s * q * cap_c, bm, bn), dtype)
    c_init = jax.device_put(
        c_flat.reshape(g, s, s, q * cap_c, bm, bn),
        NamedSharding(mesh, P("kl", "pr", "pc")),
    )

    # the grouped TAS route rides the double-buffer metronome too: the
    # per-group Cannons advance in lockstep, and the split per-tick
    # programs stagger the ensemble's tick-(t+1) shift over tick t's
    # contraction — decision recorded like the other routes, serial
    # fallback is the fused lockstep program
    grid = f"{g}x{s}x{s}"
    if s > 1:
        tickm = _costmodel.mesh_tick_model(
            q * cap_a + xtr, cap_b + xtr, bm, bk, bn, plan.n_cand,
            s, g * s * s, np.dtype(dtype).itemsize, np.dtype(dtype).name,
        )
        _overlap.publish_modeled("tas", grid, tickm)
    mode, why = _overlap.resolve_mode("tas", grid, s)
    _overlap.publish_decision("tas", grid, mode, why)
    alpha_dev = jnp.asarray(alpha, dtype)
    beta_dev = jnp.asarray(beta, dtype)
    mref = _HashableMesh(mesh)

    def serial_fn():
        out = _stack_run_grouped(
            a_panels, b_panels, plan.stacks_dev, c_init,
            alpha_dev, beta_dev,
            s=s, cap_c=q * cap_c, acc_name=plan.acc_name,
            mesh_ref=mref, r0=r0, dot_form=plan.dot_form,
        )
        _record_mesh_dispatch(plan.stacks_dev, r0)
        return out

    _note_mesh_dot(plan)
    measure = s > 1 and _overlap.measuring()
    if _overlap.use_split_pipeline(mode, why, measure):
        c_out = _overlap.run_split_pipeline(
            "tas", grid, mode,
            lambda timings: _tas_ticks(
                plan, mesh, a_panels, b_panels, c_init, alpha_dev,
                beta_dev, mode, measure, timings),
            serial_fn, measure,
        )
    else:
        c_out = serial_fn()

    # ---- device-side collect (groups disjoint: no reduction) ----
    out = BlockSparseMatrix(
        name or (matrix_c.name if matrix_c is not None else f"{a.name}*{b.name}"),
        a.row_blk_sizes, b.col_blk_sizes, dtype,
        dist=matrix_c.dist if matrix_c is not None else None,
    )
    bins = _collected_bins(plan, mref, c_out)
    out.set_structure_from_device(plan.c_keys, bins, binning=plan.c_binning)
    out._tas_ngroups = plan.ngroups
    if filter_eps is not None:
        from dbcsr_tpu.ops.operations import filter_matrix

        filter_matrix(out, filter_eps)

    stats.record_stack(
        bm, bn, bk, plan.n_cand, driver="mesh",
        seconds=time.perf_counter() - t_start,
        nbytes=_costmodel.stack_bytes(
            bm, bn, bk, plan.n_cand, nseg=max(len(plan.c_keys), 1),
            itemsize=np.dtype(dtype).itemsize),
        dtype=np.dtype(dtype).name,
    )
    stats.record_multiply(2 * out.nfullrows * out.nfullcols * a.nfullcols)
    stats.sample_memory()
    ndev = g * s * s
    itemsize = np.dtype(dtype).itemsize
    if s > 1:
        # per-group panels: cap_a is the per-group maximum, cap_b the
        # replicated short matrix — the traffic the group split saves
        # shows up directly in these counters (vs the ungrouped psum of
        # the long C, sparse_multiply_distributed's 'psum' record)
        stats.record_comm(
            "ppermute", 2 * s * ndev,
            s * ndev * (q * cap_a * bm * bk + cap_b * bk * bn) * itemsize,
        )
    out._last_flops = plan.true_flops
    return out


# _HashableMesh (the static jit argument wrapper keyed by mesh
# structure) lives in `parallel/overlap.py` now, shared with the dense
# Cannon's split programs; imported at the top for compatibility.
