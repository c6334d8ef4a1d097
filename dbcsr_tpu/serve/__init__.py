"""dbcsr_tpu.serve — the multi-tenant serving plane.

DBCSR is a library embedded in a driver (CP2K): one caller, one
multiply at a time.  The ROADMAP's north star is a production system
serving many tenants at once — this package is that request plane,
thin glue over the engine machinery PRs 4–7 proved out:

* `session` — tenant-scoped state: named matrices owned by a
  `core.mempool.chain`-backed scope, freed wholesale on close; a
  session on one thread never adopts another tenant's buffers.
* `queue` — bounded priority admission queue driven by
  `obs.health.verdict()`: shed with a structured rejection on
  CRITICAL, queue with an enforced deadline on DEGRADED, admit on OK;
  per-tenant quotas (in-flight requests, queued bytes) and request
  deadlines classified with the watchdog outcome classes (OK/SLOW/TRANSIENT/
  WEDGED).
* `coalesce` — the cross-request batching window: same-structure
  multiply requests (identical pattern fingerprints, dtype, scalars,
  options — the stack-plan cache key, reused across tenants) arriving
  within ``serve_window_ms`` execute as ONE block-diagonal composite
  multiply, so N tenants multiplying the same sparsity pattern pay one
  fused superstack dispatch set instead of N.
* `engine` — the single-writer worker loop (sessions are producers,
  one thread executes): per-request correlation on the event bus,
  flight records, per-tenant latency stats, and the
  ``serve_admit``/``serve_execute`` fault sites so chaos schedules
  exercise shedding and mid-request failover.
* `product_cache` — the content-addressed product cache: identical
  (A, B, scalars, flags) submissions, keyed by VALUE digests and
  invalidated through the mutation-epoch machinery, return the cached
  C with zero engine dispatches; ABFT-on hits are re-certified per
  request.  See docs/serving.md § Content-addressed product cache.
* `workload` — the workload observability loop: terminal-request
  trace recorder (``DBCSR_TPU_WORKLOAD=<base>``, digest-only operand
  schema), trace model/synthesizer, and the deterministic replay
  primitives `tools/loadtest.py` turns into the measured capacity
  certificate (CAPACITY_CERT.json).  See docs/loadtest.md.

Surface: `obs.server` gains ``/serve/submit``, ``/serve/status`` and
``/serve/tenants``; `tools/serve_bench.py` is the many-client
throughput A/B and `tools/doctor.py` prints the serving row.  Knobs:
``DBCSR_TPU_SERVE_*`` (`core.config`).  See docs/serving.md.
"""

from dbcsr_tpu.serve.engine import (  # noqa: F401
    ServeEngine,
    get_engine,
    shutdown,
)
from dbcsr_tpu.serve.queue import Rejected, Request  # noqa: F401
from dbcsr_tpu.serve.session import Session, get_session  # noqa: F401

# imported for its env activation (DBCSR_TPU_WORKLOAD) and so the
# queue's guarded sys.modules hook finds the recorder
from dbcsr_tpu.serve import workload  # noqa: F401

__all__ = [
    "ServeEngine", "get_engine", "shutdown",
    "Rejected", "Request", "Session", "get_session",
]
