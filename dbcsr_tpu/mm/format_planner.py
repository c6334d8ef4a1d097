"""Storage-format planner: stack or dense, once per product.

This module is the ONLY place that decides how a product executes.
Both engines — `mm.multiply` on one chip and
`parallel.sparse_dist.sparse_multiply_distributed` on a mesh — ask
`choose`, and say in one word whether they can run a dense canvas
(``dense``: always on one chip, on a square grid on the mesh); the
rules are never re-derived anywhere else.  The two executions of the
identical product:

* ``stack`` — the shape-bucketed BCSR stack engine (the default);
* ``dense`` — one padded dense GEMM on whole-matrix canvases
  (`mm.multiply._dense_multiply`; strip-chunked beyond the canvas cap
  on one chip, the dense Cannon on a square mesh).

The funnel, first hit wins, decides only from what the product shows:

1. the structural gate (`_stack_only`): a filtered, pattern-locked,
   limited or symmetric-C product, or one under ``mm_driver="pallas"``,
   runs on the stack engine (``reason="structural"``);
2. the ``format_plan`` fault site (an injected fault degrades the plan
   to stack, ``reason="fault"`` — never cached);

   from here on the plan is cached by pattern fingerprints + dtype +
   config + platform + what the caller can execute;
3. ``mm_format`` forced (``reason="forced"``; a dense force the caller
   cannot execute falls back to stack, ``reason="ineligible"``); a
   caller with no canvas executor stops here on the stack engine
   (``reason="structural"``);
4. the dense rules (`_dense_rule`, ``reason="heuristic"``): both
   operands at or above `DENSE_OCC_THRESHOLD` occupancy (the
   reference's gate, `dbcsr_mm.F:593-617`), or — for a dtype the TPU
   only emulates — dense flops under `DENSE_FLOP_RATIO` times the true
   flops with a C that would fill anyway;
5. stack (``reason="default"``; a non-uniform blocking reports
   ``reason="structural"``).

Every one-chip decision lands on ``dbcsr_tpu_format_decision_total{
format, reason}`` and in the product's trace span/flight record
(`note_decision`; the mesh engine's call waits for a `benchmark` PR,
see its call site).

Import-light: numpy only at import; jax, config and `mm.multiply` are
reached lazily (multiply imports THIS module lazily too, so there is
no cycle).  Of `mm.multiply` the planner asks only facts and
feasibility: `_true_product_flops`, `_uniformly_blocked`,
`dense_canvas_feasible`.
"""

from __future__ import annotations

import collections
import threading
from typing import Optional

import numpy as np

# both operands at or above this occupancy go dense on any platform
# (ref MM_DENSE's gate, `dbcsr_mm.F:593-617`)
DENSE_OCC_THRESHOLD = 0.8
# TPU rule for EMULATED dtypes (f64/c128): below the occupancy
# threshold, still go dense when dense_flops < ratio * true_flops.
# The ratio is a prior, not a measurement: the one point known is the
# north star on a v5e (ratio 100), where the dense route is 4.5x the
# grouped stack route end to end (ledger, PR 27); ROADMAP A3's ladder
# measures the crossover.  0 disables the rule
DENSE_FLOP_RATIO = 250.0

_lock = threading.Lock()
_plan_cache: "collections.OrderedDict" = collections.OrderedDict()
_PLAN_CACHE_MAX = 256


class Plan:
    """One product's format decision plus the evidence it rode on."""

    __slots__ = ("fmt", "reason", "cell", "occ", "why")

    def __init__(self, fmt: str, reason: str,
                 cell: Optional[tuple] = None, occ: Optional[float] = None,
                 why: Optional[str] = None):
        self.fmt = fmt
        self.reason = reason
        self.why = why            # which dense rule fired (flight dense_why)
        self.cell = cell          # (bm, bn, bk, dtype) — uniform products
        self.occ = occ            # pair occupancy: entries/(nbr*nbc*nbk)

    def __repr__(self):
        return f"Plan({self.fmt}, reason={self.reason}, occ={self.occ})"


def _cache_get(key, cache=_plan_cache):
    with _lock:
        hit = cache.get(key)
        if hit is not None:
            cache.move_to_end(key)
        return hit


def _cache_put(key, value, cache=_plan_cache, cap=_PLAN_CACHE_MAX) -> None:
    with _lock:
        cache[key] = value
        while len(cache) > cap:
            cache.popitem(last=False)


def reset() -> None:
    """Drop cached plans and the last choice per cell (tests, config
    flips)."""
    with _lock:
        _plan_cache.clear()
        _last_choice.clear()


def _stack_only(c, cfg, filter_eps, retain_sparsity, no_limits) -> bool:
    """THE structural gate, shared by both engines: the canvas
    executors write C's full pattern in one piece, so a filtered,
    pattern-locked, limited or symmetric-C product can only run on the
    stack engine — as can one whose stack driver was pinned to Pallas
    (``mm_driver="pallas"``: kernel A/B legs)."""
    from dbcsr_tpu.core.matrix import NO_SYMMETRY

    return (filter_eps is not None or retain_sparsity or not no_limits
            or c.matrix_type != NO_SYMMETRY or cfg.mm_driver == "pallas")


def choose(a, b, c, *, filter_eps, retain_sparsity, no_limits,
           dense: bool, chunked_canvas: bool = True) -> Plan:
    """Resolve the product's execution format (see the module funnel).
    ``dense`` says whether the caller can run a dense canvas and
    ``chunked_canvas`` whether its dense executor survives the canvas
    cap by strips: all that differs between the one-chip engine and the
    mesh engine (the dense Cannon on a square grid, no strips).  Cheap
    on repeat: cached by pattern fingerprints + config + device kind."""
    from dbcsr_tpu.core.config import effective_platform, get_config
    from dbcsr_tpu.resilience import faults as _faults

    cfg = get_config()
    if _stack_only(c, cfg, filter_eps, retain_sparsity, no_limits):
        return Plan("stack", "structural")
    # fault boundary: an injected plan fault degrades to stack for THIS
    # product only (never cached — the fault is transient)
    if _faults.active():
        try:
            _faults.maybe_inject("format_plan", name=c.name)
        except BaseException:
            return Plan("stack", "fault")

    key = (
        a.pattern_fingerprint(), b.pattern_fingerprint(),
        c.pattern_fingerprint(), str(np.dtype(c.dtype)),
        (cfg.mm_format, cfg.mm_driver, effective_platform(),
         bool(dense), bool(chunked_canvas)),
    )
    plan = _cache_get(key)
    if plan is not None:
        return plan
    plan = _choose_uncached(a, b, c, cfg, dense, chunked_canvas)
    _cache_put(key, plan)
    return plan


def _choose_uncached(a, b, c, cfg, dense, chunked_canvas) -> Plan:
    from dbcsr_tpu.mm import multiply as _mm

    uniform = all(_mm._uniformly_blocked(m) for m in (a, b, c))
    cell = occ = None
    if uniform:
        bm = int(c.row_blk_sizes[0])
        bn = int(c.col_blk_sizes[0])
        bk = int(a.col_blk_sizes[0])
        cell = (bm, bn, bk, str(np.dtype(c.dtype)))
        entries = max(
            int(round(_mm._true_product_flops(a, b) / (2.0 * bm * bn * bk))),
            0)
        occ = entries / float(max(a.nblkrows * c.nblkcols * a.nblkcols, 1))

    def _plan(fmt, reason, why=None):
        return Plan(fmt, reason, cell=cell, occ=occ, why=why)

    # 3. explicit force; a caller with no canvas executor stops here
    if cfg.mm_format == "stack":
        return _plan("stack", "forced")
    if cfg.mm_format == "dense":
        return _plan("dense", "forced") if dense \
            else _plan("stack", "ineligible")
    if not dense:
        return _plan("stack", "structural")
    # 4. the dense rules: occupancy, then the emulated-dtype flop ratio
    why = _dense_rule(a, b, c, cfg, chunked_canvas)
    if why is not None:
        return _plan("dense", "heuristic", why=why)
    # 5. stack
    return _plan("stack", "default" if uniform else "structural")


def _dense_rule(a, b, c, cfg, chunked_canvas) -> Optional[str]:
    """Which dense rule sends this (structurally eligible) product to
    one dense MXU matmul, or None (ref `dbcsr_mm.F:593-617`).

    TPU extension beyond the reference's occupancy gate: for dtypes the
    chip only EMULATES (f64/c128 run as split-f32/bf16 passes), tiny
    per-block dots are so MXU-starved that one dense matmul beats the
    stack path well below occ 0.1 — at the 23^3 north-star config on a
    v5e, 0.872 s per multiply dense against 3.92 s on the grouped stack
    path (ledger, PR 27).  The result is identical either way (same
    product, same final pattern semantics); only time-to-solution
    changes."""
    from dbcsr_tpu.core.config import effective_platform
    from dbcsr_tpu.mm import multiply as _mm

    th = DENSE_OCC_THRESHOLD
    if a.occupation() >= th and b.occupation() >= th:
        return f"occupancy>={th}"
    # emulated-dtype rule (TPU only).  Guards beyond the flop ratio: an
    # explicitly forced stack driver wins, the executor must be able to
    # hold the canvases, and the product's EXPECTED block fill must be
    # near-full — dense mode stores the full pattern, which must not
    # silently densify a structurally sparse C (block-diagonal/banded
    # operands keep the stack path).
    if cfg.mm_driver != "auto" or DENSE_FLOP_RATIO <= 0:
        return None
    if np.dtype(c.dtype) not in (np.float64, np.complex128):
        return None
    if effective_platform() != "tpu":
        return None
    if not _mm.dense_canvas_feasible(a, b, c, chunked=chunked_canvas):
        return None
    if _candidate_fill(a, b) < 0.5:
        return None
    dense_flops = 2.0 * a.nfullrows * b.nfullcols * a.nfullcols
    if dense_flops < DENSE_FLOP_RATIO * _mm._true_product_flops(a, b):
        return "cost-model:emulated-dtype"
    return None


_fill_cache: "collections.OrderedDict" = collections.OrderedDict()


def _candidate_fill(a, b) -> float:
    """Fraction of C blocks the symbolic product would store.  EXACT
    (one host float32 boolean matmul over the block grids) when the
    grid volume and temp size allow — structured patterns (triangular,
    banded) are what the guard exists for, and a random-pattern
    estimate misses them; beyond the caps, fall back to the Poisson
    model.  Memoized by pattern fingerprints: repeated same-pattern
    multiplies (SCF loops) pay the matmul once."""
    nbr, nbk, nbc = a.nblkrows, a.nblkcols, b.nblkcols
    if a.nblks == 0 or b.nblks == 0 or nbr * nbc == 0:
        return 0.0
    exact_ok = (
        float(nbr) * nbk * nbc <= 1e9
        and float(nbr) * nbk + float(nbk) * nbc + float(nbr) * nbc <= 5e7
    )
    if not exact_ok:
        lam = float(a.nblks) * b.nblks / (float(nbr) * nbc * nbk)
        return 1.0 - float(np.exp(-lam))
    key = (a.pattern_fingerprint(), b.pattern_fingerprint())
    fill = _cache_get(key, _fill_cache)
    if fill is not None:
        return fill
    ar, ac = a.entry_coords()
    br, bc = b.entry_coords()
    ia = np.zeros((nbr, nbk), np.float32)
    ia[ar, ac] = 1.0
    ib = np.zeros((nbk, nbc), np.float32)
    ib[br, bc] = 1.0
    fill = float(np.count_nonzero(ia @ ib)) / (nbr * nbc)
    _cache_put(key, fill, _fill_cache, cap=64)
    return fill


# ------------------------------------------------------- observability

def note_decision(plan: Plan) -> None:
    """Count + annotate one decision (called once per multiply, on the
    product — cache hits count too: the counter measures traffic, the
    cache measures planning cost)."""
    try:
        from dbcsr_tpu.obs import flight as _flight
        from dbcsr_tpu.obs import metrics as _metrics
        from dbcsr_tpu.obs import tracer as _trace

        _metrics.counter(
            "dbcsr_tpu_format_decision_total",
            "storage-format planner decisions by chosen format and "
            "reason (mm.format_planner)",
        ).inc(format=plan.fmt, reason=plan.reason)
        _flight.note("format", plan.fmt)
        _flight.note("format_reason", plan.reason)
        if plan.occ is not None:
            _flight.note("format_occ", round(plan.occ, 4))
        if plan.why is not None:
            _flight.note("dense_why", plan.why)
        _trace.annotate(format=plan.fmt, format_reason=plan.reason)
        _note_choice_change(plan)
    except Exception:
        pass


# last (format, reason) chosen per cell: a CHANGED choice is a system
# change the causal diagnosis plane's ledger must see (obs.rca) — the
# first sight of a cell is a baseline, not a change, so startup never
# floods the ledger with one entry per cell
_last_choice: dict = {}


def _note_choice_change(plan: Plan) -> None:
    key = str(plan.cell) if plan.cell is not None else "uncelled"
    choice = (plan.fmt, plan.reason)
    with _lock:
        prev = _last_choice.get(key)
        _last_choice[key] = choice
    if prev is None or prev == choice:
        return
    from dbcsr_tpu.obs import events as _events

    _events.publish("format_decision", {
        "cell": key, "format": plan.fmt, "reason": plan.reason,
        "prev": f"{prev[0]}:{prev[1]}",
    })
