"""Online autotuning service: telemetry in, promoted kernel parameters
out.

The reference ships an entire offline autotune + ML-predict stack for
its batched SMM kernels (`src/acc/libsmm_acc/{tune,predict}`, ~8k LoC
of Python) because per-(m, n, k, dtype) launch parameters decide kernel
speed.  Our equivalent was a static evidence table (`acc.params`) fed
by a manual CLI sweep (`acc.tune`).  This package closes the loop and
makes tuning a continuous subsystem that runs INSIDE a serving or
long-lived process:

* `tune.miner` — scans the live telemetry history store
  (`obs.timeseries` roofline cells), and capture files when given
  some, for underperforming (driver, m, n, k, dtype) cells and ranks them by
  **wasted FLOP-seconds**, so the tuner always works the most
  expensive cell first.
* `tune.trials` — bounded, watchdog-guarded tuning trials executed OFF
  the hot path: a strict wall budget (``DBCSR_TPU_TUNE_BUDGET_S``) and
  operand byte budget (``_BUDGET_BYTES``) per trial, pool-chained
  temporaries, never while serve admission is DEGRADED/CRITICAL, and
  breaker-aware winner selection (an open breaker for a (driver,
  shape) skips that candidate).  Reuses `acc.tune`'s candidate legs —
  precision-demoted ones included — in non-persisting trial mode.
* `tune.store` — the versioned, device-kind-keyed promotion store
  layered over `acc.params`: per-row provenance (measure env, trial
  stats, generation counter), atomic promotion that bumps the params
  generation consulted by `mm.multiply`'s plan cache (no stale plan
  ever serves old parameters), and demotion-on-regression with the
  telemetry store as the judge.
* `tune.predictor` — cross-device-kind transfer (donor rows scaled by
  roofline peak ratios) and a small learned regressor trained on our
  own accumulated trial rows — the paper's `predict/` layer rebuilt on
  this repo's telemetry — used only for untuned cells and always
  outranked by real evidence.
* `tune.service` — the cycle loop tying the planes together, as a
  background thread (``DBCSR_TPU_TUNE=1`` alongside the serve engine)
  or driven synchronously (`TuneService.cycle()`, the tested form).

Operator docs: `docs/autotuning.md`.  Observability: ``tune`` health
component, ``dbcsr_tpu_tune_{trials,promotions,demotions}_total``,
``tune_promotion``/``tune_demotion``/``tune_trial`` bus events, a
timeseries collector, and a `tools/doctor.py` row.
"""

from dbcsr_tpu.tune.service import (  # noqa: F401
    TuneService,
    current_service,
    get_service,
    maybe_start_from_env,
    stop_service,
)

__all__ = [
    "TuneService",
    "current_service",
    "get_service",
    "maybe_start_from_env",
    "stop_service",
]
