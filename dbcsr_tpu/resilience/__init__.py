"""dbcsr_tpu.resilience — fault injection, driver failover, watchdog.

The robustness subsystem: DBCSR's contract is that the multiply engine
keeps producing correct results regardless of which backend executes
the small-GEMM stacks (the reference falls back from a missing JIT
kernel to the CPU path, `libsmm_acc.cpp:227-249`); on the TPU
reproduction the accelerator path additionally fails in ways the
reference never sees — Mosaic lowering fatals, emulated-dtype NaNs,
device OOM.  Three parts:

* `faults` — deterministic, seeded fault injection at the driver /
  collective / serve boundaries, configured by ``DBCSR_TPU_FAULTS``
  (e.g. ``pallas:raise@stack>=3,prob=0.5,seed=7``) or the
  `inject_faults` context manager.  Lets CI exercise every failure
  path on CPU, with no real hardware faults.
* `breaker` — per-(driver, shape-key) circuit breakers
  (closed → open → half-open with cooldown) backing the stack-driver
  failover chain wired through `acc.smm.execute_stack`: a failing
  driver is quarantined and the stack re-executes down
  pallas → xla_group → xla_flat → xla → host, so one bad kernel never
  poisons a multiply.
* `watchdog` — a single deadline-guarded executor with exponential
  backoff + jitter and structured outcome classification
  (OK / SLOW / TRANSIENT / WEDGED), adopted by the multi-process perf
  driver join and the online tuner's trials.  Wedge streaks can
  persist as JSONL so a restarted caller resumes its backoff state.

Every module here is stdlib-only at import time; jax/numpy
are reached lazily inside the few functions that need them.  With no
faults configured and no failures recorded, every hook is a single
attribute check — the same no-op contract as `obs`.
"""

from dbcsr_tpu.resilience import breaker
from dbcsr_tpu.resilience import faults
from dbcsr_tpu.resilience import watchdog

from dbcsr_tpu.resilience.breaker import (  # noqa: F401
    BreakerBoard,
    get_board,
)
from dbcsr_tpu.resilience.faults import (  # noqa: F401
    FaultError,
    FaultSpec,
    inject_faults,
)
from dbcsr_tpu.resilience.watchdog import (  # noqa: F401
    OK,
    SLOW,
    TRANSIENT,
    WEDGED,
    DeadlineExceeded,
    Watchdog,
    WatchdogResult,
)

__all__ = [
    "faults", "breaker", "watchdog",
    "FaultSpec", "FaultError", "inject_faults",
    "BreakerBoard", "get_board",
    "Watchdog", "WatchdogResult", "DeadlineExceeded",
    "OK", "SLOW", "TRANSIENT", "WEDGED",
]
