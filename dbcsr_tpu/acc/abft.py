"""ABFT probe checksums: catching wrong-but-finite answers.

The breaker plane (PR 3) catches crashes and NaNs; the end-of-run
checksum gate catches corruption after the fact.  What neither catches
is the dominant accelerator-fleet failure mode per the SDC literature:
a *finite* silently-corrupted product that sails through every
finite-output check, poisons an iterative chain into confident
convergence on garbage, and gets served to a tenant.  This module is
the runtime detector — the TPU-side analog of DBCSR's own checksum
utilities (``dbcsr_test_methods``'s ``dbcsr_checksum``), moved from
test-time to launch-time via algorithm-based fault tolerance.

**The probe.**  For one parameter stack ``C[ci] += alpha*A[ai]@B[bi]``
and fixed Rademacher vectors ``u`` (rows) and ``v`` (columns), the
double-sided rank-1 identity

    u · (C_new - C_old) · v  ==  alpha * Σ_s (uᵀA)[ai_s] · (B v)[bi_s]

holds exactly in real arithmetic; in floating point the two sides
disagree only by rounding, bounded by `obs.costmodel.abft_tolerance`
(accumulation-dtype epsilon × reduction depths).  The double-sided
form is what makes the probe affordable: ``uᵀA`` and ``B·v`` contract
once per *unique block* (the bucketed ``a_data``/``b_data`` panels,
read once each), and each span then costs a single k-length dot — so
the whole check is O(|A| + |B| + 2|C| + s·k) memory traffic against
the kernel's O(s·m·n·k) flops, evaluated as ONE fused dispatch and one
host sync per guarded launch.  A corrupted C element at (i, j) enters
the left side with weight ``u_i·v_j = ±1``, so single-element SDC is
never masked.

**The knob** (``DBCSR_TPU_ABFT``, `core.config.abft`):

* ``off`` — no checks (production default; zero overhead).
* ``verify`` — probe every stack/superstack launch; a mismatch raises
  `AbftMismatchError`, classified ``sdc`` by `acc.smm`, recorded
  against the per-(driver, shape) breaker, and the stack re-executes
  down the PR 3 failover chain (same-driver pristine retry first —
  SDC is transient corruption, and the retry is bitwise-faithful).
* ``recover`` — ``verify``, plus every recovery re-execution is itself
  probe-checked before its result is accepted.

Layer coverage beyond the stack boundary:

* `check_superstack` — one probe over a fused C-bin launch (the right
  side sums over the bin's spans);
* `tree_probe`/`shift_conserved` — the distributed tick pipelines'
  conservation check: a ring shift is a data permutation, so the
  global probe of the operand panels is invariant across it
  (`parallel/overlap.py`);
* `matrix_probe`/`verify_product` — whole-matrix probes for the
  serving plane's per-request verification (`serve/engine.py`).

Every check/mismatch/recovery is observable:
``dbcsr_tpu_abft_{checks,mismatches,recoveries}_total{driver}`` plus an
``abft_mismatch`` bus event correlated by product/request id.
"""

from __future__ import annotations

import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from dbcsr_tpu.core import mempool as _mempool
from dbcsr_tpu.core.config import get_config
from dbcsr_tpu.obs import costmodel as _costmodel
from dbcsr_tpu.obs import events as _events
from dbcsr_tpu.obs import metrics as _metrics


class AbftMismatchError(RuntimeError):
    """A probe checksum disagreed beyond tolerance: the launch produced
    a wrong (possibly perfectly finite) answer.  Classified ``sdc`` by
    `acc.smm._classify_failure`."""


class PrecisionExceededError(AbftMismatchError):
    """A DEMOTED launch's probe residual breached its demotion ceiling
    (`obs.costmodel.demoted_abft_tolerance`): not corruption but the
    adaptive-precision promote signal.  `acc.smm.execute_stack` answers
    it by rebuilding the plan at native precision (the involved cells
    were already promoted by `acc.precision.note_exceeded` when this
    raised) instead of walking the SDC failover chain.  Subclasses
    `AbftMismatchError` so any unaware layer still treats it as a
    condemned result rather than accepting it."""


def mode() -> str:
    return get_config().abft


def enabled() -> bool:
    """THE hot-path gate: one config-attribute read per launch."""
    return get_config().abft != "off"


def recover_enabled() -> bool:
    return get_config().abft == "recover"


# ------------------------------------------------------------- probes

def _acc_dtype(dtype):
    """Accumulation dtype of the probe math (mirrors smm._accum_dtype
    without importing smm — this module must stay import-cycle-free)."""
    d = jnp.dtype(dtype)
    if d == jnp.bfloat16 or d == jnp.float16:
        return jnp.dtype(jnp.float32)
    return d


_vec_cache: dict = {}


def probe_vector(n: int, dtype, salt: int = 0) -> object:
    """The fixed Rademacher (±1) probe vector for a given length —
    exactly representable in every dtype, deterministic per process
    lifetime (seeded), cached on device.  ``salt`` decorrelates the
    row probe ``u`` from the column probe ``v`` of a double-sided
    check."""
    acc = _acc_dtype(dtype)
    key = (int(n), str(acc), int(salt))
    hit = _vec_cache.get(key)
    if hit is not None and not hit.is_deleted():
        return hit
    rng = np.random.default_rng(0xAB5D + int(salt))
    host = rng.choice(np.asarray([-1.0, 1.0]), size=int(n))
    dev = jnp.asarray(host, dtype=acc)
    _vec_cache[key] = dev
    if len(_vec_cache) > 64:
        _vec_cache.pop(next(iter(_vec_cache)))
    return dev


@jax.jit
def _delta_probe0(out, u, v):
    """`_delta_probe` for a first-touch (beta==0) launch: the pristine
    C is identically zero, so the left side reads only ``out``."""
    acc = _acc_dtype(out.dtype)
    r = jnp.einsum("smn,m,n->s", out.astype(acc), u, v,
                   precision=jax.lax.Precision.HIGHEST)
    return r, jnp.max(jnp.abs(out.astype(acc)))


@jax.jit
def _delta_probe(base, out, u, v):
    """Left side: ``u · (out - base) · v`` per C segment — a scalar
    per segment — plus the magnitude scale the relative comparison
    needs (|out| enters because the rounding of a stored C value is
    relative to C, not to the delta)."""
    acc = _acc_dtype(out.dtype)
    r = jnp.einsum("smn,m,n->s", out.astype(acc) - base.astype(acc),
                   u, v, precision=jax.lax.Precision.HIGHEST)
    return r, jnp.max(jnp.abs(out.astype(acc)))


@functools.partial(jax.jit, static_argnames=("nseg",))
def _span_probe(a_data, b_data, ai, bi, ci, u, v, alpha, nseg: int):
    """Right side: ``alpha * Σ_s (uᵀA)[ai_s] · (B v)[bi_s]`` per C
    segment (sorted segment-sum, same accumulation discipline as the
    kernels).  ``uᵀA``/``B·v`` contract over the unique bucketed
    panels, NOT per span — the probe reads each operand block once
    however many spans reuse it."""
    acc = _acc_dtype(a_data.dtype)
    ua = jnp.einsum("amk,m->ak", a_data.astype(acc), u,
                    precision=jax.lax.Precision.HIGHEST)
    bv = jnp.einsum("bkn,n->bk", b_data.astype(acc), v,
                    precision=jax.lax.Precision.HIGHEST)
    s = jnp.einsum("sk,sk->s", jnp.take(ua, ai, axis=0),
                   jnp.take(bv, bi, axis=0),
                   precision=jax.lax.Precision.HIGHEST)
    p = jax.ops.segment_sum(s, ci, num_segments=nseg,
                            indices_are_sorted=True)
    return alpha.astype(acc) * p


@functools.partial(jax.jit, static_argnames=("nseg",))
def _stack_probe_err(base, out, a_data, b_data, ai, bi, ci, u, v,
                     alpha, nseg: int):
    """The WHOLE per-stack probe as one program returning the scalar
    pair ``[err, scale]`` — the hot-path form: one dispatch and one
    host sync per guarded launch (the unfused probe paid ~3 dispatches
    plus two blocking reads, which dominated the check's cost on small
    kernels)."""
    r, out_scale = _delta_probe(base, out, u, v)
    p = _span_probe(a_data, b_data, ai, bi, ci, u, v, alpha, nseg)
    err = jnp.max(jnp.abs(r - p))
    scale = jnp.maximum(jnp.max(jnp.abs(p)), out_scale)
    return jnp.stack([err, scale]).real


@functools.partial(jax.jit, static_argnames=("nseg",))
def _stack_probe_err0(out, a_data, b_data, ai, bi, ci, u, v, alpha,
                      nseg: int):
    """`_stack_probe_err` for a first-touch (beta==0) launch — no base
    operand, and ONE pass over C.  The comparison scale comes from the
    abs-value probe ``S_c = |alpha|·Σ_s Σ_k |uᵀA|[ai]·|B v|[bi]``: with
    Rademacher ±1 weights, ``Σ|terms|`` of BOTH compared reductions is
    bounded by S (out == ΔC here, and ``|ΔC_ij| ≤ Σ_s |A@B|_ij``), so
    ``eps·S`` rigorously bounds the legitimate rounding disagreement
    without re-reading C for a ``max|out|``."""
    acc = _acc_dtype(out.dtype)
    r = jnp.einsum("smn,m,n->s", out.astype(acc), u, v,
                   precision=jax.lax.Precision.HIGHEST)
    p = _span_probe(a_data, b_data, ai, bi, ci, u, v, alpha, nseg)
    ua = jnp.einsum("amk,m->ak", jnp.abs(a_data.astype(acc)),
                    jnp.abs(u), precision=jax.lax.Precision.HIGHEST)
    bv = jnp.einsum("bkn,n->bk", jnp.abs(b_data.astype(acc)),
                    jnp.abs(v), precision=jax.lax.Precision.HIGHEST)
    s_abs = jnp.einsum("sk,sk->s", jnp.take(ua, ai, axis=0),
                       jnp.take(bv, bi, axis=0),
                       precision=jax.lax.Precision.HIGHEST)
    S = jnp.abs(alpha.astype(acc)) * jax.ops.segment_sum(
        s_abs, ci, num_segments=nseg, indices_are_sorted=True)
    err = jnp.max(jnp.abs(r - p))
    scale = jnp.max(S)
    return jnp.stack([err, scale]).real


@jax.jit
def _compare_err(r, p, out_scale):
    """Fused tail of an accumulated (superstack) probe: ``[err,
    scale]`` in one dispatch/sync."""
    err = jnp.max(jnp.abs(r - p))
    scale = jnp.maximum(jnp.max(jnp.abs(p)), out_scale)
    return jnp.stack([err, scale]).real


def _segment_depth(ci: np.ndarray) -> int:
    """Deepest accumulation any C segment sees (ci sorted ascending)."""
    if len(ci) == 0:
        return 1
    return int(np.bincount(ci.astype(np.int64)).max())


def _record_check(driver: str) -> None:
    _metrics.counter(
        "dbcsr_tpu_abft_checks_total",
        "ABFT probe checksums evaluated, by driver/site",
    ).inc(driver=driver)


def record_mismatch(driver: str, site: str, **detail) -> None:
    """Count + publish one detected probe mismatch WITHOUT raising —
    for callers that carry their own structured error (the tick
    pipelines' conservation check)."""
    _metrics.counter(
        "dbcsr_tpu_abft_mismatches_total",
        "ABFT probe checksums that disagreed beyond tolerance (silent "
        "data corruption detected), by driver/site",
    ).inc(driver=driver)
    _events.publish("abft_mismatch",
                    dict(detail, driver=driver, site=site), flight=True)


def _mismatch(driver: str, err: float, tol: float, scale: float,
              shape, site: str = "stack") -> None:
    shape_s = "x".join(str(x) for x in shape)
    record_mismatch(driver, site, rel_err=float(err),
                    tolerance=float(tol), scale=float(scale),
                    shape=shape_s)
    raise AbftMismatchError(
        f"ABFT probe mismatch at {site} (driver {driver!r}, shape "
        f"{shape_s}): relative error {err:.3e} > tolerance "
        f"{tol:.3e} — finite silent data corruption")


def record_recovery(driver: str) -> None:
    """Count one successful re-execution that replaced an SDC-condemned
    result (smm failover, chain rollback recompute, serve re-execute)."""
    _metrics.counter(
        "dbcsr_tpu_abft_recoveries_total",
        "SDC-condemned results successfully recomputed and accepted, "
        "by driver/site",
    ).inc(driver=driver)
    _events.publish("abft_recovery", {"driver": driver}, flight=True)


def _check_scalars(err: float, scale: float, *, dtype, k: int,
                   depth: int, driver: str, shape, site: str,
                   prec=None, cells=None) -> None:
    """``prec``/``cells`` mark a launch executed at a DEMOTED compute
    dtype (`acc.precision` spec + the (m,n,k,dtype) cells involved):
    the ceiling widens to the demotion tolerance, a breach promotes the
    cells and raises `PrecisionExceededError` instead of the SDC path,
    and a pass feeds the residual back to the planner as headroom."""
    dt = str(jnp.dtype(dtype))
    if prec is not None:
        tol = _costmodel.demoted_abft_tolerance(dt, prec[0], prec[1],
                                                k, depth)
    else:
        tol = _costmodel.abft_tolerance(dt, k, depth)
    rel = err / max(scale, 1e-30)
    if not np.isfinite(err) or err > tol * max(scale, 1e-30):
        if prec is not None:
            from dbcsr_tpu.acc import precision as _precision

            _precision.note_exceeded(cells, rel, tol)
            shape_s = "x".join(str(x) for x in shape)
            raise PrecisionExceededError(
                f"demoted-precision probe residual at {site} (driver "
                f"{driver!r}, shape {shape_s}, compute {prec[0]}"
                f"{'+comp' if prec[1] else ''}): relative error "
                f"{rel:.3e} > demotion ceiling {tol:.3e} — cells "
                f"promoted to native")
        _mismatch(driver, rel, tol, scale, shape, site=site)
    elif prec is not None and cells:
        from dbcsr_tpu.acc import precision as _precision

        _precision.note_probe_ok(cells, rel)


# ------------------------------------------------ deferred verification

_tls = threading.local()


def _pending_list() -> list:
    lst = getattr(_tls, "pending", None)
    if lst is None:
        lst = _tls.pending = []
    return lst


def pending_count() -> int:
    return len(_pending_list())


def discard_pending() -> None:
    """Drop this thread's queued-but-unevaluated probe scalars — called
    before a deferring run so an earlier aborted product can never
    misattribute its corruption to this one."""
    _pending_list().clear()


def flush() -> None:
    """Evaluate every probe this thread deferred.  Deferral is the
    overlap-preserving mode: a guarded launch queues its device-side
    ``[err, scale]`` pair WITHOUT a host sync, the dispatch pipeline
    keeps running ahead of the device, and the product boundary
    (`mm.multiply._run_stacks`) pays one drain here instead of a
    pipeline stall per launch.  Every queued probe is evaluated (so
    each mismatch is counted and published), then the FIRST mismatch
    re-raises with ``.driver``/``.shape_key`` attached so the caller
    can feed the breaker plane and re-execute the product."""
    pend = _pending_list()
    if not pend:
        return
    items, pend[:] = list(pend), []
    first_sdc: Optional[AbftMismatchError] = None
    first_prec: Optional[PrecisionExceededError] = None
    mismatch_drivers: list = []
    for es_dev, meta, shape_key in items:
        es = np.asarray(es_dev)
        try:
            _check_scalars(float(es[0]), float(es[1]), **meta)
        except PrecisionExceededError as exc:
            # adaptive promote, not corruption: the cells were
            # promoted when the check raised; keep it OUT of the
            # mismatch/recovery accounting (a PrecisionExceeded never
            # recorded a mismatch, so attributing a recovery to its
            # driver would unbalance the counters)
            exc.driver = meta["driver"]
            exc.shape_key = shape_key
            if first_prec is None:
                first_prec = exc
        except AbftMismatchError as exc:
            exc.driver = meta["driver"]
            exc.shape_key = shape_key
            mismatch_drivers.append(meta["driver"])
            if first_sdc is None:
                first_sdc = exc
    if first_sdc is not None:
        # one re-execution heals EVERY mismatched launch of the
        # product: the caller records one recovery per entry here, so
        # the mismatch/recovery counters stay balanced and health
        # never reports fully-recovered SDC as escaped corruption.
        # A genuine SDC outranks a co-queued precision breach — the
        # redo runs with immediate verification, where each demoted
        # plan still heals itself.
        first_sdc.mismatch_drivers = mismatch_drivers
        raise first_sdc
    if first_prec is not None:
        raise first_prec


# ----------------------------------------------------- stack boundary

def check_stack(base, out, a_data, b_data, plan, alpha,
                c_zero: bool = False, defer: bool = False,
                shape_key=None) -> None:
    """Probe-verify one executed stack plan: ``base`` is the pristine C
    the launch started from (ignored under ``c_zero``, where it is
    identically zero by the caller's contract and may not even exist),
    ``out`` its result.  Raises `AbftMismatchError` on disagreement —
    immediately, or at the caller's `flush` when ``defer`` is set (the
    overlap-preserving mode; only callers that can re-execute the whole
    product may defer).  Silently skips plans with no retained source
    indices (cannot reconstruct the right side)."""
    src = getattr(plan, "src_idx", None)
    if src is None or (base is None and not c_zero):
        return
    ai, bi, ci = src
    nseg, m, n = out.shape
    k = a_data.shape[2]
    _record_check(plan.driver)
    u = probe_vector(m, out.dtype, salt=1)
    v = probe_vector(n, out.dtype)
    acc = _acc_dtype(out.dtype)
    idx = (
        _mempool.upload_index("abft_a", np.ascontiguousarray(ai, np.int32)),
        _mempool.upload_index("abft_b", np.ascontiguousarray(bi, np.int32)),
        _mempool.upload_index("abft_c", np.ascontiguousarray(ci, np.int32)),
    )
    alpha_dev = jnp.asarray(alpha, dtype=acc)
    if c_zero:
        es_dev = _stack_probe_err0(
            out, a_data, b_data, *idx, u, v, alpha_dev, nseg)
    else:
        es_dev = _stack_probe_err(
            base, out, a_data, b_data, *idx, u, v, alpha_dev, nseg)
    # the double-sided probe folds the u (length-m) contraction into
    # every compared scalar: widen the accumulation depth accordingly
    prec = getattr(plan, "precision", None)
    # the k-merged grouped layout contracts r0*k products per dot: the
    # demoted ceiling's narrow-accumulation term must see the MERGED
    # length or it condemns healthy grouped launches
    k_tol = k * max(getattr(plan, "r_grp", 1), 1) \
        if (prec is not None and plan.driver == "xla_group") else k
    meta = dict(dtype=out.dtype, k=k_tol,
                depth=_segment_depth(np.asarray(ci)) * max(m, n),
                driver=plan.driver, shape=(m, n, k), site="stack",
                prec=prec,
                cells=([(m, n, k, str(jnp.dtype(out.dtype)))]
                       if prec is not None else None))
    if defer:
        _pending_list().append((es_dev, meta, shape_key))
        return
    es = np.asarray(es_dev)
    _check_scalars(float(es[0]), float(es[1]), **meta)


def check_superstack(base, out, a_datas, b_datas, splan, alpha,
                     c_zero: bool = False, defer: bool = False,
                     shape_key=None) -> None:
    """Probe-verify one fused C-bin launch: the right side sums every
    span's contribution (the bin's C is read+written once, so one delta
    probe covers the whole launch).  Under ``c_zero`` the pristine bin
    is identically zero and ``base`` is never touched (it may alias a
    donated buffer)."""
    nseg, m, n = out.shape
    u = probe_vector(m, out.dtype, salt=1)
    v = probe_vector(n, out.dtype)
    acc = _acc_dtype(out.dtype)
    alpha_dev = jnp.asarray(alpha, dtype=acc)
    if c_zero:
        r, out_scale = _delta_probe0(out, u, v)
    else:
        r, out_scale = _delta_probe(base, out, u, v)
    p = jnp.zeros((nseg,), acc)
    k_max, depth = 1, 1
    prec = None  # the loosest demoted spec among the bin's spans
    cells: list = []
    dt_name = str(jnp.dtype(out.dtype))
    for plan, a_d, b_d in zip(splan.plans, a_datas, b_datas):
        src = getattr(plan, "src_idx", None)
        if src is None:
            return  # cannot reconstruct this span: skip the whole bin
        p_prec = getattr(plan, "precision", None)
        if p_prec is not None:
            cells.append((a_d.shape[1], b_d.shape[2], a_d.shape[2],
                          dt_name))
            if prec is None or (
                _costmodel.effective_epsilon(*p_prec)
                > _costmodel.effective_epsilon(*prec)
            ):
                prec = p_prec
            if plan.driver == "xla_group":
                # merged contraction length (see check_stack)
                k_max = max(k_max,
                            a_d.shape[2] * max(plan.r_grp, 1))
        ai, bi, ci = src
        p = p + _span_probe(
            a_d, b_d,
            _mempool.upload_index("abft_a",
                                  np.ascontiguousarray(ai, np.int32)),
            _mempool.upload_index("abft_b",
                                  np.ascontiguousarray(bi, np.int32)),
            _mempool.upload_index("abft_c",
                                  np.ascontiguousarray(ci, np.int32)),
            u, v, alpha_dev, nseg,
        )
        k_max = max(k_max, a_d.shape[2])
        depth += _segment_depth(np.asarray(ci))
    _record_check("fused")
    es_dev = _compare_err(r, p, out_scale)
    meta = dict(dtype=out.dtype, k=k_max, depth=depth * max(m, n),
                driver="fused", shape=(m, n, len(splan.plans)),
                site="superstack", prec=prec, cells=cells or None)
    if defer:
        _pending_list().append((es_dev, meta, shape_key))
        return
    es = np.asarray(es_dev)
    _check_scalars(float(es[0]), float(es[1]), **meta)


# ------------------------------------------------ dense-path probes

def check_dense_canvas(cd, ad, bd, c_old, alpha, beta, *, dtype,
                       driver: str = "dense") -> None:
    """Probe-verify a dense-mode product canvas: ``cd`` must equal
    ``alpha * ad @ bd + beta * c_old`` (``c_old`` None when beta == 0
    or C was empty), checked through the rank-1 identity
    ``cd·v == alpha*ad@(bd·v) + beta*(c_old·v)``.  The mm layer calls
    this after `_dense_guard`; a mismatch raises `AbftMismatchError`,
    which the dense→stack failover classifies ``sdc`` and answers by
    re-executing the product on the stack engine (where the per-stack
    probes and the chain recovery apply)."""
    acc = _acc_dtype(dtype)
    n = int(cd.shape[1])
    k = int(ad.shape[1])
    _record_check(driver)
    v = probe_vector(n, dtype)
    lhs = cd.astype(acc) @ v
    rhs = jnp.asarray(alpha, dtype=acc) * (
        ad.astype(acc) @ (bd.astype(acc) @ v))
    if c_old is not None:
        rhs = rhs + jnp.asarray(beta, dtype=acc) * (c_old.astype(acc) @ v)
    err = float(jnp.max(jnp.abs(lhs - rhs)))
    scale = float(jnp.maximum(jnp.max(jnp.abs(lhs)),
                              jnp.max(jnp.abs(rhs))))
    tol = _costmodel.abft_tolerance(str(jnp.dtype(dtype)), k, 4)
    if not np.isfinite(err) or err > tol * max(scale, 1e-30):
        _mismatch(driver, err / max(scale, 1e-30), tol, scale,
                  (cd.shape[0], n, k), site="dense")


# ------------------------------------------- distributed tick probes

def tree_probe_device(tree):
    """Device-side `tree_probe`: the same permutation-invariant
    absolute-sum as ONE queued device scalar, NO host sync — the tick
    pipelines queue one per shift and evaluate after the loop, so the
    probe never serializes the comm/compute overlap the double-buffer
    mode exists for.  Returns None when the tree has no inexact
    leaves."""
    total = None
    for leaf in jax.tree_util.tree_leaves(tree):
        if not jnp.issubdtype(leaf.dtype, jnp.inexact):
            continue
        acc = _acc_dtype(leaf.dtype)
        s = jnp.sum(jnp.abs(leaf.astype(acc)))
        total = s if total is None else total + s
    return total


def tree_probe(tree) -> float:
    """Permutation-invariant probe of a pytree of device arrays: the
    global sum of finite absolute values.  A ring shift permutes shard
    contents without changing them, so this probe is conserved across
    every shift of the tick pipelines (`parallel/overlap.run_ticks`) —
    up to resummation rounding, which `shift_conserved` tolerates.
    Blocking form of `tree_probe_device`."""
    dev = tree_probe_device(tree)
    return 0.0 if dev is None else float(dev)


def shift_conserved(before: float, after: float, dtype,
                    nelem: int) -> bool:
    """True when a shift's probe survived within resummation rounding
    of ``nelem`` accumulated terms."""
    tol = _costmodel.abft_tolerance(str(jnp.dtype(dtype)), 1, nelem)
    scale = max(abs(before), abs(after), 1e-30)
    if not np.isfinite(after):
        return False
    return abs(after - before) <= tol * scale


# ------------------------------------------------- whole-matrix probes

@functools.partial(jax.jit, static_argnames=("bm", "bn"))
def _bin_probe(out_vec, data, ro, co, v, bm: int, bn: int):
    """One shape-bin's contribution to ``M @ v``: gather each block's v
    segment, block mat-vec, scatter-add at row offsets (dead bucket
    slots carry out-of-range row offsets -> dropped; their data rows
    are zeros by the bucket-padding invariant, so the clamped v gather
    is harmless)."""
    acc = _acc_dtype(data.dtype)
    vseg = jnp.take(v, co[:, None] + jnp.arange(bn)[None, :], axis=0,
                    mode="clip")
    prod = jnp.einsum("sij,sj->si", data.astype(acc), vseg.astype(acc),
                      precision=jax.lax.Precision.HIGHEST)
    idx = ro[:, None] + jnp.arange(bm)[None, :]
    return out_vec.at[idx].add(prod, mode="drop")


def matrix_probe(m, v) -> object:
    """``M @ v`` as a device vector (nfullrows,) — the whole-matrix
    probe the serving plane verifies requests with.  ``v`` is a device
    vector of length ``nfullcols`` (or any conformable probe, e.g. the
    output of another matrix_probe).  Structure-derived offsets ride
    the per-matrix device mirror, so repeated probes of a
    pattern-stable matrix upload nothing."""
    acc = _acc_dtype(m.dtype)
    out = jnp.zeros((m.nfullrows,), acc)
    if m.nblks == 0:
        return out
    rows, cols = m.entry_coords()
    roff = m.row_blk_offsets[rows]
    coff = m.col_blk_offsets[cols]
    oor = np.int64(1) << 30  # dropped by the scatter (int32-safe)
    for b_id, b in enumerate(m.bins):
        if b.count == 0:
            continue

        def _offsets(b_id=b_id, b=b):
            sel = np.nonzero(m.ent_bin == b_id)[0]
            cap = b.data.shape[0]
            ro = np.full(cap, oor, np.int64)
            co = np.zeros(cap, np.int64)  # clamped gather; zero rows
            ro[m.ent_slot[sel]] = roff[sel]
            co[m.ent_slot[sel]] = coff[sel]
            return jnp.asarray(ro), jnp.asarray(co)

        ro_d, co_d = m.device_index(("abft_off", b_id), _offsets)
        out = _bin_probe(out, b.data, ro_d, co_d, v.astype(acc),
                         bm=b.shape[0], bn=b.shape[1])
    return out


def product_probeable(params: dict) -> bool:
    """True when a serving-plane multiply request admits the algebraic
    probe identity: no value-dependent filtering (dropped small blocks
    break ``C = alpha*A@B + beta*C`` exactly), no pattern lock, no
    windowed limits, and plain 'N' operands (the probe does not model
    op() transposes)."""
    return (
        params.get("filter_eps") is None
        and not params.get("retain_sparsity")
        and str(params.get("transa", "N")).upper() == "N"
        and str(params.get("transb", "N")).upper() == "N"
    )


def verify_product(a, b, c, alpha, beta, r_old: Optional[object],
                   *, request_id: str = "") -> None:
    """Probe-verify one completed serving-plane multiply:
    ``C_new·v == alpha * A@(B@v) + beta * (C_old·v)``.  ``r_old`` is
    the pre-execution probe of C (None means beta == 0).  Raises
    `AbftMismatchError` on disagreement."""
    n = c.nfullcols
    k = a.nfullcols
    _record_check("serve")
    v = probe_vector(n, c.dtype)
    r_c = matrix_probe(c, v)
    rhs = matrix_probe(a, matrix_probe(b, v))
    acc = _acc_dtype(c.dtype)
    rhs = jnp.asarray(alpha, dtype=acc) * rhs
    if r_old is not None:
        rhs = rhs + jnp.asarray(beta, dtype=acc) * r_old
    err = float(jnp.max(jnp.abs(r_c - rhs)))
    scale = float(jnp.maximum(jnp.max(jnp.abs(r_c)),
                              jnp.max(jnp.abs(rhs))))
    tol = _costmodel.abft_tolerance(str(np.dtype(c.dtype)), k,
                                    max(a.nblkcols, 1) * 4)
    if not np.isfinite(err) or err > tol * max(scale, 1e-30):
        record_mismatch("serve", "serve_execute",
                        rel_err=err / max(scale, 1e-30), tolerance=tol,
                        request_id=request_id,
                        shape=f"{c.nfullrows}x{c.nfullcols}x{k}")
        raise AbftMismatchError(
            f"ABFT probe mismatch on served product {request_id or '?'}: "
            f"relative error {err / max(scale, 1e-30):.3e} > {tol:.3e}")
