"""Cannon's algorithm over the ('kl','pr','pc') mesh.

TPU-native re-design of `multiply_cannon` (`dbcsr_mm_cannon.F:837`):

* The metronome loop (`grouped_k_index DO metronome`, :1345) becomes a
  `lax.fori_loop` of s ticks inside `shard_map`.
* Nonblocking isend/irecv panel exchanges with double-buffered
  calc/comm sets (:2977) become static `lax.ppermute` ring
  permutations — XLA schedules the collective concurrently with the
  local matmul, which is the comm-thread overlap
  (USE_COMM_THREAD) without host threads.
* The initial Cannon skew (A row i rotated left by i, B col j rotated
  up by j) is a single static permutation over the combined
  ('pr','pc') axis — no data-dependent communication patterns.
* The 'kl' axis implements the 2.5D algorithm (`dbcsr_mm_3d.F`):
  each layer contracts a k-slab, C is completed by one `psum` over
  'kl' (ref `make_layers_3D_C_reduction`, `dbcsr_mm_3d.F:1037`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dbcsr_tpu.core import stats
from dbcsr_tpu.parallel import overlap as _overlap
from dbcsr_tpu.parallel.overlap import _HashableMesh
from dbcsr_tpu.core.timings import timed
from dbcsr_tpu.obs import costmodel as _costmodel
from dbcsr_tpu.obs import tracer as _trace


@functools.lru_cache(maxsize=None)
def _skew_perm(s: int, kind: str):
    """Static (src, dst) pairs over the flattened ('pr','pc') axis.
    Cached per (s, kind): tick bodies and the split per-tick programs
    reference these tables on every trace — build the Python tuples
    once instead of once per trace."""
    pairs = []
    for i in range(s):
        for j in range(s):
            dst = i * s + j
            if kind == "skew_a":  # (i,j) receives A from (i, j+i)
                src = i * s + (j + i) % s
            elif kind == "skew_b":  # (i,j) receives B from (i+j, j)
                src = ((i + j) % s) * s + j
            elif kind == "shift_a":  # ring shift left along pc
                src = i * s + (j + 1) % s
            elif kind == "shift_b":  # ring shift up along pr
                src = ((i + 1) % s) * s + j
            else:
                raise AssertionError(kind)
            pairs.append((src, dst))
    return tuple(pairs)


def _local_cannon(a_loc, b_loc, s: int, acc_dtype):
    """Per-device Cannon: runs under shard_map."""
    axes = ("pr", "pc")
    if s > 1:
        a_loc = jax.lax.ppermute(a_loc, axes, _skew_perm(s, "skew_a"))
        b_loc = jax.lax.ppermute(b_loc, axes, _skew_perm(s, "skew_b"))
    c_loc = jnp.zeros((a_loc.shape[0], b_loc.shape[1]), acc_dtype)
    # mark the accumulator as device-varying so the fori_loop carry type
    # matches after the varying a@b lands in it
    c_loc = jax.lax.pcast(c_loc, ("kl", "pr", "pc"), to="varying")
    # permutation tables hoisted out of the traced tick body
    shift_a = _skew_perm(s, "shift_a")
    shift_b = _skew_perm(s, "shift_b")

    def tick(t, carry):
        a, b, c = carry
        c = c + jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=acc_dtype,
        )
        if s > 1:
            a = jax.lax.ppermute(a, axes, shift_a)
            b = jax.lax.ppermute(b, axes, shift_b)
        return a, b, c

    _, _, c_loc = jax.lax.fori_loop(0, s, tick, (a_loc, b_loc, c_loc))
    # 2.5D layer reduction (ref dbcsr_mm_3d.F:1037)
    c_loc = jax.lax.psum(c_loc, "kl")
    return c_loc


# ------------------------------------------------------------------
# Split per-tick programs: the double-buffered metronome
# (parallel/overlap.py) dispatches these independently so the ring
# shift feeding tick k+1 runs concurrently with tick k's local dot —
# per-tick op order matches `_local_cannon` exactly (bitwise identity).
# ------------------------------------------------------------------

_SPEC_A = P("pr", ("kl", "pc"))
_SPEC_B = P(("kl", "pr"), "pc")
_SPEC_C3 = P("kl", "pr", "pc")  # (kl, M, N): per-layer partial C


@functools.partial(jax.jit, static_argnames=("s", "mesh_ref", "kind_a",
                                             "kind_b"))
def _dense_permute(a, b, *, s, mesh_ref, kind_a, kind_b):
    """One A/B panel permutation (the skew, or one ring shift) as its
    own SPMD program."""
    def body(a_loc, b_loc):
        axes = ("pr", "pc")
        return (jax.lax.ppermute(a_loc, axes, _skew_perm(s, kind_a)),
                jax.lax.ppermute(b_loc, axes, _skew_perm(s, kind_b)))

    return jax.shard_map(
        body, mesh=mesh_ref.val,
        in_specs=(_SPEC_A, _SPEC_B), out_specs=(_SPEC_A, _SPEC_B),
    )(a, b)


@functools.partial(jax.jit, static_argnames=("acc_name", "mesh_ref"))
def _dense_tick(a, b, c3, *, acc_name, mesh_ref):
    """One metronome tick's local contraction: c += a @ b per device."""
    acc_dtype = jnp.dtype(acc_name)

    def body(a_loc, b_loc, c_loc):
        c = c_loc.reshape(c_loc.shape[1:])
        c = c + jax.lax.dot_general(
            a_loc, b_loc, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=acc_dtype,
        )
        return c.reshape((1,) + c.shape)

    return jax.shard_map(
        body, mesh=mesh_ref.val,
        in_specs=(_SPEC_A, _SPEC_B, _SPEC_C3), out_specs=_SPEC_C3,
    )(a, b, c3)


@functools.partial(jax.jit, static_argnames=("mesh_ref",))
def _dense_finish(c3, *, mesh_ref):
    """2.5D layer reduction (ref dbcsr_mm_3d.F:1037) of the per-layer
    partial C accumulators."""
    def body(c_loc):
        return jax.lax.psum(c_loc.reshape(c_loc.shape[1:]), "kl")

    return jax.shard_map(
        body, mesh=mesh_ref.val, in_specs=_SPEC_C3, out_specs=P("pr", "pc"),
    )(c3)


@functools.lru_cache(maxsize=64)
def _fused_cannon_program(mesh_ref, s: int, acc_name: str):
    """Cached jitted fused serial Cannon (the historical single-program
    path): a fresh `jax.jit(shard_map(partial(...)))` per call would
    retrace/recompile every multiply — on the exact path that serves as
    the cheap bitwise-reference fallback."""
    return jax.jit(
        jax.shard_map(
            functools.partial(_local_cannon, s=s,
                              acc_dtype=jnp.dtype(acc_name)),
            mesh=mesh_ref.val,
            in_specs=(_SPEC_A, _SPEC_B),
            out_specs=P("pr", "pc"),
        )
    )


def _cannon_dense_ticks(mesh, a, b, kl, s, acc_dtype, mode, measure,
                        timings):
    """The host-driven tick loop behind the double-buffered (and
    measured-serial) dense Cannon; returns C in the accumulator dtype,
    bitwise identical to the fused `_local_cannon` program.  Appends
    the measured (shift_exposed_s, compute_s) split to ``timings`` —
    published by the caller only when the pipeline delivered the
    result (overlap.run_split_pipeline)."""
    from dbcsr_tpu.acc.smm import record_dispatch

    mref = _HashableMesh(mesh)
    acc_name = jnp.dtype(acc_dtype).name
    m, n = a.shape[0], b.shape[1]
    a, b = _dense_permute(a, b, s=s, mesh_ref=mref,
                          kind_a="skew_a", kind_b="skew_b")
    record_dispatch(_overlap.DRIVER)  # the skew program
    c3 = _overlap.zeros_program(mref, (kl, m, n), acc_name, _SPEC_C3)()
    record_dispatch(_overlap.DRIVER)  # the zeros program

    def shift(aa, bb):
        return _dense_permute(aa, bb, s=s, mesh_ref=mref,
                              kind_a="shift_a", kind_b="shift_b")

    def tick(aa, bb, cc, t):
        return _dense_tick(aa, bb, cc, acc_name=acc_name, mesh_ref=mref)

    c3, shift_s, comp_s = _overlap.run_ticks(
        s, a, b, c3, shift, tick, mode=mode, engine="dense",
        measure=measure,
    )
    # tick/shift dispatches were counted as issued (run_ticks — so a
    # mid-pipeline failure still shows the round-trips it really
    # paid); the finish program books its own below
    if measure:
        timings.append((shift_s, comp_s))
    res = _dense_finish(c3, mesh_ref=mref)
    record_dispatch(_overlap.DRIVER)
    return res


def cannon_multiply_dense(mesh: Mesh, a, b, acc_dtype=None):
    """C = A @ B with A (M,K), B (K,N) dense arrays, distributed
    A: P('pr', ('kl','pc')), B: P(('kl','pr'), 'pc'), C: P('pr','pc').

    M, N must divide by s = mesh pr size; K by kl*s.  ``acc_dtype``
    overrides the accumulator dtype (bf16 data accumulates in f32, the
    acc layer's convention).
    """
    kl = mesh.shape["kl"]
    s = mesh.shape["pr"]
    if mesh.shape["pc"] != s:
        raise ValueError(
            "the dense Cannon needs a square ('pr','pc') grid; "
            "rectangular grids are supported by the block-sparse "
            "engine (sparse_multiply_distributed, all-gather path)"
        )
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError("inner dims differ")
    if m % s or n % s or k % (kl * s):
        raise ValueError(f"shapes {(m, k, n)} not divisible by grid {(kl, s, s)}")
    with timed("cannon_dense"):
        _trace.annotate(m=m, n=n, k=k, kl=kl, s=s)
        a = jax.device_put(a, NamedSharding(mesh, P("pr", ("kl", "pc"))))
        b = jax.device_put(b, NamedSharding(mesh, P(("kl", "pr"), "pc")))
        # collective-traffic accounting (host-side model of the static
        # comm pattern; the mesh engine's upload/permute counters in
        # sparse_dist follow the same convention): with s > 1 the skew
        # plus s-1 metronome ticks move every A and B shard s times
        # over 'pr'/'pc'; kl > 1 adds the 2.5D layer psum of C
        ndev = kl * s * s
        itemsize = jnp.dtype(a.dtype).itemsize
        if s > 1:
            stats.record_comm(
                "ppermute", 2 * s * ndev,
                s * (m * k + k * n) * itemsize,
            )
        if kl > 1:
            # same convention as sparse_dist's ring-reduce model: each
            # of the kl-1 steps moves every (pr,pc) position's C panel
            stats.record_comm("psum", (kl - 1) * s * s,
                              (kl - 1) * m * n * itemsize)
        grid = f"{kl}x{s}x{s}"
        if s > 1:
            # comm/compute overlap attribution per metronome tick: the
            # MODELED ratio says whether the collective is hideable on
            # this grid/shape from the static comm pattern + roofline
            # peaks (the USE_COMM_THREAD question); the double-buffered
            # path below additionally MEASURES it under
            # DBCSR_TPU_SYNC_TIMING (parallel/overlap.py)
            tick = _costmodel.cannon_tick_model(
                m, n, k, kl, s, itemsize, jnp.dtype(a.dtype).name)
            _overlap.publish_modeled("dense", grid, tick)
        acc = acc_dtype or a.dtype
        mode, why = _overlap.resolve_mode("dense", grid, s)
        _overlap.publish_decision("dense", grid, mode, why)
        mref = _HashableMesh(mesh)

        def serial_fn():
            return _fused_cannon_program(
                mref, s, jnp.dtype(acc).name)(a, b)

        measure = s > 1 and _overlap.measuring()
        if _overlap.use_split_pipeline(mode, why, measure):
            # double-buffered ticks, or the measured serial reference
            # (same per-tick op sequence, dispatched region by region
            # so the shift/compute split is observable — the
            # DBCSR_TPU_SYNC_TIMING seam); both bitwise identical to
            # the fused program and guarded: an open cannon_db breaker
            # or a split-pipeline failure falls back to serial_fn
            return _overlap.run_split_pipeline(
                "dense", grid, mode,
                lambda timings: _cannon_dense_ticks(
                    mesh, a, b, kl, s, acc, mode, measure, timings),
                serial_fn, measure,
            )
        return serial_fn()
