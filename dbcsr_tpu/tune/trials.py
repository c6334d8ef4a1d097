"""Bounded, watchdog-guarded tuning trials — OFF the hot path.

One trial = one non-persisting `acc.tune.tune_smm` candidate sweep
(every launch-config leg the offline CLI times, the precision-demoted
legs included) under three guards:

* **wall budget** — ``DBCSR_TPU_TUNE_BUDGET_S`` (default 120 s) is
  enforced BETWEEN candidate legs: the sweep's candidate sink checks
  the deadline after every timed leg and stops the sweep, keeping the
  legs already measured (a bounded trial with partial evidence, not an
  error — ``budget_hit`` is stamped on the result/event).  The
  `resilience.watchdog` channel (``tune_trial``) around the whole
  sweep additionally classifies it (OK/SLOW/TRANSIENT/WEDGED) and
  keeps the streak the health model reads — it cannot preempt a single
  in-process jax leg, so one pathologically slow LEG overruns by that
  leg's length at most;
* **byte budget** — the trial stack size is clamped so the staged
  A/B/C temporaries stay under ``DBCSR_TPU_TUNE_BUDGET_BYTES``
  (default 64 MiB); temporaries run inside a `core.mempool.chain`
  scope so whatever the sweep stages is pool-owned and donated back;
* **fault boundary** — ``tune_trial`` (`resilience.sites`): an
  injected fault aborts the trial cleanly; the service counts it
  (``dbcsr_tpu_tune_trials_total{outcome="faulted"}``) and NO
  promotion can land from an aborted trial (the chaos suite's
  ``tune_storm`` case pins this).

Winner selection is **breaker-aware**: a candidate whose (driver,
shape) breaker is currently open is skipped — the tuner must never
promote a quarantined kernel, however fast it timed.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from dbcsr_tpu.tune._env import env_float as _env_float
from dbcsr_tpu.tune._env import env_int as _env_int

OK = "ok"
FAILED = "failed"
FAULTED = "faulted"
WEDGED = "wedged"

_MIN_TRIAL_STACK = 256


def budget_s() -> float:
    return max(1.0, _env_float("DBCSR_TPU_TUNE_BUDGET_S", 120.0))


def budget_bytes() -> int:
    return max(1 << 20, _env_int("DBCSR_TPU_TUNE_BUDGET_BYTES", 64 << 20))


def nrep() -> int:
    return max(1, _env_int("DBCSR_TPU_TUNE_NREP", 2))


def clamp_stack_size(m: int, n: int, k: int, dtype,
                     want: int, budget: Optional[int] = None) -> int:
    """The largest trial stack size whose staged temporaries fit the
    byte budget.  Mirrors `acc.tune`'s allocation shape: A holds
    S/16 (m, k) blocks, B S/16 (k, n) blocks, C S/8 (m, n) segments,
    plus 12 B of int32 indices per entry."""
    import numpy as np

    budget = budget_bytes() if budget is None else budget
    isz = np.dtype(dtype).itemsize
    per_entry = isz * (m * k / 16.0 + k * n / 16.0 + m * n / 8.0) + 12.0
    fit = int(budget / max(per_entry, 1.0))
    return max(_MIN_TRIAL_STACK, min(int(want), fit))


class _BudgetExhausted(Exception):
    """Internal: the wall budget elapsed — stop the sweep, keep the
    legs already measured."""


class _BudgetList(list):
    """Candidate sink that enforces the wall budget between legs: each
    append records the just-timed candidate, then aborts the sweep
    once the deadline passed (the current leg's timing is kept)."""

    def __init__(self, deadline_monotonic: float):
        super().__init__()
        self._deadline = deadline_monotonic

    def append(self, cand) -> None:
        super().append(cand)
        if time.monotonic() > self._deadline:
            raise _BudgetExhausted()


class TrialResult:
    """Outcome of one candidate sweep."""

    __slots__ = ("outcome", "cell", "entry", "candidates", "elapsed_s",
                 "error", "stack_size", "budget_hit")

    def __init__(self, outcome: str, cell: Dict, entry: Optional[Dict],
                 candidates: List[Dict], elapsed_s: float,
                 error: Optional[str], stack_size: int,
                 budget_hit: bool = False):
        self.outcome = outcome
        self.cell = cell
        self.entry = entry
        self.candidates = candidates
        self.elapsed_s = elapsed_s
        self.error = error
        self.stack_size = stack_size
        self.budget_hit = budget_hit

    @property
    def ok(self) -> bool:
        return self.outcome == OK

    def __repr__(self):
        return (f"TrialResult({self.outcome}, "
                f"cell={self.cell.get('m')}x{self.cell.get('n')}x"
                f"{self.cell.get('k')}:{self.cell.get('dtype')}, "
                f"candidates={len(self.candidates)}, "
                f"elapsed={self.elapsed_s:.2f}s)")


def _count_trial(outcome: str) -> None:
    try:
        from dbcsr_tpu.obs import metrics

        metrics.counter(
            "dbcsr_tpu_tune_trials_total",
            "online-tuner trial sweeps by outcome (dbcsr_tpu.tune)",
        ).inc(outcome=outcome)
    except Exception:
        pass


def run_trial(cell: Dict, seed: int = 7, out=None,
              deadline_s: Optional[float] = None,
              reps: Optional[int] = None) -> TrialResult:
    """Run one bounded candidate sweep for a mined cell.

    The cell dict carries ``m``/``n``/``k``/``dtype``/``stack_size``
    (the miner's schema).  Returns a `TrialResult`; ``entry`` is the
    raw sweep-best row (the SERVICE re-ranks candidates breaker-aware
    before promoting, see `select_winner`)."""
    from dbcsr_tpu.core.kinds import enum_of
    from dbcsr_tpu.resilience import faults
    from dbcsr_tpu.resilience.watchdog import Watchdog

    m, n, k = int(cell["m"]), int(cell["n"]), int(cell["k"])
    dtype = cell.get("dtype", "float64")
    want = int(cell.get("stack_size", 30000))
    trial_s = clamp_stack_size(m, n, k, dtype, want)
    mnk = f"{m}x{n}x{k}"
    sink = out if out is not None else (lambda *a: None)
    wall_budget = budget_s() if deadline_s is None else deadline_s
    candidates: List[Dict] = _BudgetList(
        time.monotonic() + wall_budget)
    entry_box: list = [None]
    fault_abort = [False]
    budget_hit = [False]

    def _sweep(_deadline: float):
        # the injectable fault boundary: a raise/oom/fail here aborts
        # the trial before any timing ran; hang wedges the watchdog
        if faults.active():
            try:
                faults.maybe_inject("tune_trial", mnk=mnk,
                                    dtype=str(dtype))
            except BaseException:
                fault_abort[0] = True
                raise
        from dbcsr_tpu.acc.tune import tune_smm

        def _run():
            entry_box[0] = tune_smm(
                m, n, k, dtype_enum=enum_of(dtype), stack_size=trial_s,
                nrep=nrep() if reps is None else reps, out=sink,
                seed=seed, persist=False, candidates_out=candidates)

        try:
            try:
                from dbcsr_tpu.core import mempool

                # pool-chained temporaries: whatever the sweep stages
                # through the pool is chain-owned and donated back at
                # exit
                with mempool.chain():
                    _run()
            except ImportError:
                _run()
        except _BudgetExhausted:
            # the wall budget elapsed mid-sweep: the legs measured so
            # far ARE the trial (bounded by design, not an error)
            budget_hit[0] = True
        return entry_box[0]

    wd = Watchdog("tune_trial", deadline_s=wall_budget)
    res = wd.guard(_sweep)
    if res.outcome == "WEDGED":
        outcome = WEDGED
    elif res.error is not None:
        outcome = FAULTED if fault_abort[0] else FAILED
    else:
        outcome = OK
    _count_trial(outcome)
    try:
        from dbcsr_tpu.obs import events as _events

        _events.publish("tune_trial", {
            "mnk": mnk, "dtype": str(dtype), "outcome": outcome,
            "stack_size": trial_s, "candidates": len(candidates),
            "budget_hit": budget_hit[0],
            "elapsed_s": round(res.elapsed_s, 3), "error": res.error,
        })
    except Exception:
        pass
    return TrialResult(outcome, cell, entry_box[0], list(candidates),
                       res.elapsed_s, res.error, trial_s,
                       budget_hit=budget_hit[0])


def _breaker_open(driver: str, m: int, n: int, k: int, dtype) -> bool:
    """Whether the live breaker board holds an OPEN breaker for this
    (driver, shape).  Never CREATES a board; shape matching is by the
    board's ``driver|MxNxKx<dtype>`` snapshot spelling (the same key
    `acc.smm` registers launches under)."""
    import sys

    import numpy as np

    br = sys.modules.get("dbcsr_tpu.resilience.breaker")
    board = getattr(br, "_board", None) if br is not None else None
    if board is None:
        return False
    want = f"{m}x{n}x{k}x{np.dtype(dtype).name}"
    for key, ent in board.snapshot().items():
        drv, _, shape = key.partition("|")
        if drv == driver and ent["state"] == "open" \
                and shape.startswith(want):
            return True
    return False


def select_winner(candidates: List[Dict], m: int, n: int, k: int,
                  dtype) -> Optional[Dict]:
    """The offline tuner's `winning_row` over the candidates whose
    (driver, shape) breaker is not open.  Returns None when every
    native candidate is quarantined (the service then promotes
    nothing)."""
    from dbcsr_tpu.acc.tune import winning_row

    return winning_row([
        cand for cand in candidates
        if not (cand.get("driver")
                and _breaker_open(cand["driver"], m, n, k, dtype))
    ])
