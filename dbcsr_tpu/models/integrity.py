"""Chain-invariant verification and rollback for the model workloads.

The second line of the integrity plane (`docs/resilience.md` § Chain
checkpoint/rollback): the ABFT probe (`acc/abft.py`) guards individual
stack launches, but an iterative chain — McWeeny purification,
Newton–Schulz sign / inverse-square-root — multiplies its OWN previous
output, so one silently-corrupted iterate that slips past (ABFT off,
corruption between launches, a recycled-buffer hazard) compounds into
confident convergence on garbage.  Each model therefore verifies a
cheap per-iteration invariant on the freshly produced iterate —
contraction-mapped norm growth bounds and (for purification) trace
bounds; all one-reduction checks on numbers the loops mostly already
compute — and on violation rolls back to the last accepted iterate
(`core.mempool.chain.snapshot`/`restore`) and recomputes the step on
the SAFE engine (`mm_driver='xla'`, dense mode off — the failover
chain's backstop) instead of iterating on a corrupted iterate.

Armed exactly like the engine's output checks: whenever the ABFT knob
is on (``DBCSR_TPU_ABFT`` != off) or fault injection is active; the
un-guarded loops are unchanged (zero overhead, same history).
"""

from __future__ import annotations

import contextlib
import math

from dbcsr_tpu.resilience import faults as _faults


class ChainIntegrityError(RuntimeError):
    """A chain invariant was violated AND the safe-engine recompute
    still violated it: deterministic corruption the rollback plane
    cannot heal (surface loudly, never converge on garbage)."""


def guard_enabled() -> bool:
    """Chain-invariant checking is armed by the ABFT knob or by active
    fault injection (the `acc.smm._output_checks_enabled` convention)."""
    from dbcsr_tpu.acc import abft as _abft

    return _abft.enabled() or _faults.active()


def norm_ok(new_norm: float, limit: float) -> bool:
    """Growth-bound invariant on an iterate's Frobenius norm.  Each
    model derives ``limit`` from the SUBMULTIPLICATIVITY of the
    Frobenius norm over its own step polynomial (e.g. McWeeny:
    ``||3P²-2P³|| <= 3||P||² + 2||P||³``) — a mathematically valid
    upper bound on ANY input, converging or not, so the check can
    never false-positive a legitimate iteration, while an SDC flip
    (order 2^10) on workload-scale values explodes past it.  NaN/inf
    fail the comparison by construction."""
    return (math.isfinite(float(new_norm))
            and float(new_norm) <= float(limit) * (1.0 + 1e-9) + 1.0)


def record_rollback(model: str, step: int, reason: str,
                    detail: str = "") -> None:
    """One chain rollback: counter + correlated bus event + flight."""
    from dbcsr_tpu.obs import events as _events
    from dbcsr_tpu.obs import metrics as _metrics

    _metrics.counter(
        "dbcsr_tpu_chain_rollback_total",
        "iterative-chain invariant violations rolled back to the last "
        "accepted iterate and recomputed on the safe engine, by model",
    ).inc(model=model)
    _events.publish(
        "chain_rollback",
        {"model": model, "step": step, "reason": reason,
         "detail": detail[:200]},
        flight=True,
    )


def record_recovery(model: str) -> None:
    from dbcsr_tpu.acc import abft as _abft

    _abft.record_recovery(f"chain:{model}")


def _matrices_of(cand) -> tuple:
    from dbcsr_tpu.core.matrix import BlockSparseMatrix

    if isinstance(cand, BlockSparseMatrix):
        return (cand,)
    return tuple(m for m in cand if isinstance(m, BlockSparseMatrix))


def recompute_step(ch, build, validate, model: str, step: int,
                   reason: str):
    """The rollback recompute ladder: ``build()`` once on the UNCHANGED
    engine first — the transient-SDC model (particle strike, flaky
    pass) means a clean re-run, and an unchanged engine keeps the
    recompute bitwise-faithful to the fault-free run — then, if the
    invariant still fails, once more on the forced safe engine (the
    chain backstop, for corruption that tracks a specific driver).
    Returns the first candidate ``validate`` accepts; raises
    `ChainIntegrityError` when both attempts fail."""
    cand = build()
    if validate(cand):
        record_recovery(model)
        return cand
    for m in _matrices_of(cand):
        ch.retire(m)
    with safe_engine():
        cand = build()
    if validate(cand):
        record_recovery(model)
        return cand
    raise ChainIntegrityError(
        f"{model} step {step}: {reason} invariant still violated after "
        f"the unchanged-engine AND safe-engine recomputes — "
        f"deterministic corruption, refusing to converge on garbage")


@contextlib.contextmanager
def safe_engine():
    """Force the safe stack engine for a rollback recompute: the plain
    ``xla`` driver (the failover chain's backstop) with dense mode off.
    On the CPU control this IS the auto-selected driver, so a rollback
    recompute is bitwise-identical to the clean run — the property the
    ``sdc_chain`` chaos case pins."""
    from dbcsr_tpu.core.config import get_config, set_config

    cfg = get_config()
    prev_driver, prev_format = cfg.mm_driver, cfg.mm_format
    set_config(mm_driver="xla", mm_format="stack")
    try:
        yield
    finally:
        set_config(mm_driver=prev_driver, mm_format=prev_format)
