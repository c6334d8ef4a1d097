"""Checked registry of every non-config ``DBCSR_TPU_*`` environment knob.

Pure data, import-free: `tools/lint` parses this file with stdlib
``ast`` (never importing dbcsr_tpu), so the registry stays checkable
even when jax is broken.  The static analyzer enforces two directions:

* every literal ``DBCSR_TPU_*`` string in source must be either a
  `core/config.py` Config field knob (``DBCSR_TPU_<FIELD>``, validated
  by `Config.validate`) or an entry here (rule ``knob-registry``);
* every entry here must have a row in the generated `docs/knobs.md`
  (regenerate with ``python -m tools.lint --gen-docs``) — the docs
  table is EMITTED from this registry plus the Config fields, so the
  three previously hand-kept lists cannot drift again.

Each entry: ``owner`` (the module that reads it — informational) and
``doc`` (the one-line operator-facing description that lands in
docs/knobs.md).  Keep entries alphabetical.
"""

KNOBS = {
    "DBCSR_TPU_ATTRIBUTION": {
        "owner": "obs/attribution.py",
        "doc": "=0 disables per-request cost attribution / tenant usage "
               "metering (every hook becomes an early return).",
    },
    "DBCSR_TPU_ATTRIBUTION_N": {
        "owner": "obs/attribution.py",
        "doc": "attribution ledger capacity (per-request rows, LRU; "
               "default 1024).",
    },
    "DBCSR_TPU_ATTRIBUTION_TENANTS": {
        "owner": "obs/attribution.py",
        "doc": "per-tenant usage rollup row cap (default 512); evicted "
               "rows fold into the '(evicted)' aggregate so conservation "
               "survives tenant churn.",
    },
    "DBCSR_TPU_BENCH_DTYPE": {
        "owner": "bench.py",
        "doc": "dtype of the bench.py north-star multiply "
               "(f64/f32/bf16; default f64).",
    },
    "DBCSR_TPU_BENCH_FLIGHT": {
        "owner": "bench.py",
        "doc": "path to write the bench run's flight-recorder dump.",
    },
    "DBCSR_TPU_BENCH_METRICS": {
        "owner": "bench.py",
        "doc": "path to write the bench run's Prometheus metrics snapshot.",
    },
    "DBCSR_TPU_BENCH_NREP": {
        "owner": "bench.py",
        "doc": "repetitions of the bench north-star multiply (median "
               "reported).",
    },
    "DBCSR_TPU_BENCH_TIMINGS": {
        "owner": "bench.py",
        "doc": "emit the bench per-phase timing report (1 = stdout, "
               "path = file).",
    },
    "DBCSR_TPU_BREAKER_COOLDOWN_S": {
        "owner": "resilience/breaker.py",
        "doc": "circuit-breaker open -> half-open cooldown seconds "
               "(doubles on failed half-open trials).",
    },
    "DBCSR_TPU_BREAKER_THRESHOLD": {
        "owner": "resilience/breaker.py",
        "doc": "consecutive classified failures before a per-(driver, "
               "shape) breaker opens.",
    },
    "DBCSR_TPU_CHAIN_BLOCKS": {
        "owner": "bench.py",
        "doc": "chained-workload bench (--chain): blocks per matrix "
               "dimension.",
    },
    "DBCSR_TPU_CHAIN_FILTER_EPS": {
        "owner": "bench.py",
        "doc": "chained-workload bench: inter-iteration filter threshold.",
    },
    "DBCSR_TPU_CHAIN_ITERS": {
        "owner": "bench.py",
        "doc": "chained-workload bench: iteration count.",
    },
    "DBCSR_TPU_CHANGEPOINT": {
        "owner": "obs/changepoint.py",
        "doc": "=0 disables CUSUM change-point detection over the "
               "telemetry store (default on).",
    },
    "DBCSR_TPU_CHECK_OUTPUTS": {
        "owner": "acc/smm.py",
        "doc": "=1 forces the per-launch finite-output check (always on "
               "under fault injection).",
    },
    "DBCSR_TPU_CP_H": {
        "owner": "obs/changepoint.py",
        "doc": "CUSUM decision threshold in baseline sigmas (default 8): "
               "a series has shifted when the accumulator crosses it.",
    },
    "DBCSR_TPU_CP_REF_N": {
        "owner": "obs/changepoint.py",
        "doc": "reference-window samples frozen into a change-point "
               "baseline (default 12).",
    },
    "DBCSR_TPU_EVENTS": {
        "owner": "obs/events.py",
        "doc": "event bus control: '0'/'off' disables the bus, a path "
               "enables the JSONL sink.",
    },
    "DBCSR_TPU_EVENTS_N": {
        "owner": "obs/events.py",
        "doc": "bounded event-bus ring capacity (records).",
    },
    "DBCSR_TPU_FAULTS": {
        "owner": "resilience/faults.py",
        "doc": "fault-injection DSL: 'target:kind[@stack>=N][,prob=]"
               "[,seed=][,times=][,sleep=]', ';'-separated "
               "(docs/resilience.md).",
    },
    "DBCSR_TPU_FLEET_BACKOFF_S": {
        "owner": "serve/router.py",
        "doc": "fleet router base retry backoff seconds (doubles per "
               "attempt; default 0.05).",
    },
    "DBCSR_TPU_FLEET_CACHE_TIMEOUT_S": {
        "owner": "serve/product_cache.py",
        "doc": "fleet-shared product-cache tier: per-peer lookup "
               "timeout seconds (default 0.3); a slow/down peer costs "
               "one timeout, then the cool-off degrades lookups to "
               "local-only.",
    },
    "DBCSR_TPU_FLEET_HEARTBEAT_TIMEOUT_S": {
        "owner": "serve/router.py",
        "doc": "fleet router heartbeat probe timeout seconds "
               "(default 2).",
    },
    "DBCSR_TPU_FLEET_PEER_COOLOFF_S": {
        "owner": "serve/product_cache.py",
        "doc": "seconds a failed fleet cache peer is skipped before "
               "being probed again (default 30).",
    },
    "DBCSR_TPU_FLEET_PEERS": {
        "owner": "serve/product_cache.py",
        "doc": "comma-separated sibling-worker obs URLs for the "
               "fleet-shared product-cache tier (set per worker by "
               "serve.fleet; empty = local-only).",
    },
    "DBCSR_TPU_FLEET_RETRIES": {
        "owner": "serve/router.py",
        "doc": "routed submit attempts per request before the router "
               "marks the worker suspect and raises (default 3).",
    },
    "DBCSR_TPU_FLEET_SUBMIT_TIMEOUT_S": {
        "owner": "serve/router.py",
        "doc": "per-attempt HTTP timeout of a routed submit, seconds "
               "(default 10).",
    },
    "DBCSR_TPU_FLEET_SUSPECT_AFTER": {
        "owner": "serve/router.py",
        "doc": "consecutive missed heartbeats before a SUSPECT worker "
               "is declared DOWN (default 3).",
    },
    "DBCSR_TPU_FLIGHT_DUMP": {
        "owner": "obs/flight.py",
        "doc": "path the flight recorder dumps to at process exit.",
    },
    "DBCSR_TPU_FLIGHT_N": {
        "owner": "obs/flight.py",
        "doc": "flight-recorder ring capacity (per-product records).",
    },
    "DBCSR_TPU_HEALTH_BREAKER_CRITICAL_N": {
        "owner": "obs/health.py",
        "doc": "open breakers before the drivers component degrades to "
               "CRITICAL.",
    },
    "DBCSR_TPU_HEALTH_COLLAPSE_RATIO": {
        "owner": "obs/health.py",
        "doc": "roofline-collapse detector: fraction of the baseline "
               "roofline below which perf health degrades.",
    },
    "DBCSR_TPU_HEALTH_FALLBACK_RATE": {
        "owner": "obs/health.py",
        "doc": "driver-fallback rate per window that counts as a "
               "fallback storm.",
    },
    "DBCSR_TPU_HEALTH_LATENCY_RELTOL": {
        "owner": "obs/health.py",
        "doc": "relative dispatch-latency spike tolerance of the health "
               "model.",
    },
    "DBCSR_TPU_HEALTH_POOL_EVICTIONS": {
        "owner": "obs/health.py",
        "doc": "pool evictions per window that count as pool thrash.",
    },
    "DBCSR_TPU_HEALTH_RECOMPILE_RATE": {
        "owner": "obs/health.py",
        "doc": "jit recompiles per window that count as a recompile storm.",
    },
    "DBCSR_TPU_HEALTH_SDC_CRITICAL": {
        "owner": "obs/health.py",
        "doc": "ABFT/SDC detections per window before integrity health "
               "goes CRITICAL.",
    },
    "DBCSR_TPU_HEALTH_SHED_RATE": {
        "owner": "obs/health.py",
        "doc": "serving-plane shed fraction per window that counts as a "
               "shed storm.",
    },
    "DBCSR_TPU_HEALTH_WINDOW": {
        "owner": "obs/health.py",
        "doc": "sliding-window length (samples) of the health anomaly "
               "detectors.",
    },
    "DBCSR_TPU_ICI_GBS": {
        "owner": "obs/costmodel.py",
        "doc": "inter-chip-interconnect GB/s override for the comm cost "
               "model.",
    },
    "DBCSR_TPU_INCIDENTS": {
        "owner": "obs/incidents.py",
        "doc": "incident-bundle directory ('0' keeps bundles in memory "
               "only; default 'incidents/' under the working directory).",
    },
    "DBCSR_TPU_INCIDENT_INTERVAL_S": {
        "owner": "obs/incidents.py",
        "doc": "minimum seconds between captured incident bundles "
               "(default 60).",
    },
    "DBCSR_TPU_INCIDENT_N": {
        "owner": "obs/incidents.py",
        "doc": "maximum incident bundles captured per process "
               "(default 8).",
    },
    "DBCSR_TPU_LOADTEST_SEED": {
        "owner": "tools/loadtest.py",
        "doc": "default replay seed for the load harness (default 0): "
               "same trace + seed => bitwise-identical request stream "
               "(docs/loadtest.md).",
    },
    "DBCSR_TPU_LOADTEST_WAIT_S": {
        "owner": "tools/loadtest.py",
        "doc": "per-ticket completion wait during replay legs, seconds "
               "(default 120).",
    },
    "DBCSR_TPU_LOCKCHECK": {
        "owner": "utils/lockcheck.py",
        "doc": "=1 enables the dynamic lock-order checker: per-thread "
               "acquisition order across the instrumented locks is "
               "recorded and an order inversion raises LockOrderError "
               "(docs/static_analysis.md).",
    },
    "DBCSR_TPU_MP_PLATFORM": {
        "owner": "perf/driver.py",
        "doc": "jax_platforms value handed to spawned multi-process perf "
               "workers (default cpu).",
    },
    "DBCSR_TPU_MULTIHOST_TIMEOUT_S": {
        "owner": "parallel/multihost.py",
        "doc": "multihost world-join timeout seconds before degraded "
               "single-host fallback.",
    },
    "DBCSR_TPU_NATIVE": {
        "owner": "native/__init__.py",
        "doc": "=0 disables loading the native C++ host stack library.",
    },
    "DBCSR_TPU_OBS_HOST": {
        "owner": "obs/server.py",
        "doc": "observability HTTP server bind host.",
    },
    "DBCSR_TPU_OBS_PORT": {
        "owner": "obs/server.py",
        "doc": "observability HTTP server port (0 = ephemeral).",
    },
    "DBCSR_TPU_PARAMS_DIR": {
        "owner": "acc/params.py",
        "doc": "directory holding autotuned kernel parameter tables.",
    },
    "DBCSR_TPU_PEAK_GBS": {
        "owner": "obs/costmodel.py",
        "doc": "device HBM GB/s override for the roofline model.",
    },
    "DBCSR_TPU_PEAK_GFLOPS": {
        "owner": "obs/costmodel.py",
        "doc": "device peak GFLOP/s override for the roofline model.",
    },
    "DBCSR_TPU_PERF_DEVICES": {
        "owner": "perf/driver.py",
        "doc": "device count the multi-process perf driver spawns.",
    },
    "DBCSR_TPU_POOL": {
        "owner": "core/mempool.py",
        "doc": "=0/false/no disables the device memory pool (default on).",
    },
    "DBCSR_TPU_PROFILE": {
        "owner": "obs/profiler.py",
        "doc": "continuous profile baseline: =0 disables the fold, a "
               "path persists sealed epochs as per-process JSONL shards "
               "(default: on, in-memory ring only).",
    },
    "DBCSR_TPU_PROFILE_EPOCH_N": {
        "owner": "obs/profiler.py",
        "doc": "multiplies folded per profile-baseline epoch before it "
               "is sealed and generation-tagged (default 64).",
    },
    "DBCSR_TPU_POOL_BYTES": {
        "owner": "core/mempool.py",
        "doc": "device memory pool budget in bytes (evicts LRU beyond it).",
    },
    "DBCSR_TPU_PREC_BENCH_BS": {
        "owner": "tools/precision_bench.py",
        "doc": "precision bench: block size.",
    },
    "DBCSR_TPU_PREC_BENCH_M": {
        "owner": "tools/precision_bench.py",
        "doc": "precision bench: matrix dimension (blocks).",
    },
    "DBCSR_TPU_PREC_BENCH_OCC": {
        "owner": "tools/precision_bench.py",
        "doc": "precision bench: block occupancy.",
    },
    "DBCSR_TPU_PREC_BENCH_REPS": {
        "owner": "tools/precision_bench.py",
        "doc": "precision bench: repetitions per case.",
    },
    "DBCSR_TPU_RCA": {
        "owner": "obs/rca.py",
        "doc": "=0 disables the change ledger + causal ranking "
               "(default on).",
    },
    "DBCSR_TPU_RCA_LEDGER_N": {
        "owner": "obs/rca.py",
        "doc": "change-ledger ring capacity (default 256 entries).",
    },
    "DBCSR_TPU_RCA_WINDOW_S": {
        "owner": "obs/rca.py",
        "doc": "attribution window in seconds: how far before an "
               "estimated shift a change is still a candidate cause "
               "(default 600).",
    },
    "DBCSR_TPU_ROOFLINE": {
        "owner": "obs/costmodel.py",
        "doc": "JSON peak-table override for the roofline model "
               "(per-device-kind peaks).",
    },
    "DBCSR_TPU_SERVE_JOURNAL": {
        "owner": "serve/engine.py",
        "doc": "serving-plane request journal path (drain/restart "
               "recovery, docs/serving.md).",
    },
    "DBCSR_TPU_SERVE_TENANT_MAX": {
        "owner": "serve/engine.py",
        "doc": "cap on the engine's per-tenant latency/outcome "
               "accounting rows (least recently active evicted; "
               "default 256).",
    },
    "DBCSR_TPU_SERVE_TENANT_TTL_S": {
        "owner": "serve/engine.py",
        "doc": "idle seconds before a tenant's engine accounting rows "
               "(rolling latency window, outcome tallies) expire "
               "(default 3600).",
    },
    "DBCSR_TPU_SERVE_WAL": {
        "owner": "serve/engine.py",
        "doc": "=1 journals every admitted by-name request to "
               "DBCSR_TPU_SERVE_JOURNAL at SUBMIT time (write-ahead) "
               "instead of only at drain, tombstoned at its terminal "
               "state — what makes a SIGKILLed fleet worker's queue "
               "replayable on a peer (docs/serving.md § fleet).",
    },
    "DBCSR_TPU_SLO_CRITICAL_BURN": {
        "owner": "obs/slo.py",
        "doc": "burn-rate multiple at which an SLO objective goes "
               "CRITICAL.",
    },
    "DBCSR_TPU_SLO_LONG_S": {
        "owner": "obs/slo.py",
        "doc": "long SLO burn window seconds.",
    },
    "DBCSR_TPU_SLO_ROOFLINE_BUDGET": {
        "owner": "obs/slo.py",
        "doc": "error budget (fraction of samples) for the roofline-floor "
               "objective.",
    },
    "DBCSR_TPU_SLO_ROOFLINE_FLOOR": {
        "owner": "obs/slo.py",
        "doc": "roofline fraction below which a sample burns the "
               "roofline objective.",
    },
    "DBCSR_TPU_SLO_SDC_BUDGET": {
        "owner": "obs/slo.py",
        "doc": "error budget for silent-data-corruption detections.",
    },
    "DBCSR_TPU_SLO_SERVE_ERR_BUDGET": {
        "owner": "obs/slo.py",
        "doc": "error budget for serving-plane request failures.",
    },
    "DBCSR_TPU_SLO_SERVE_P95_BUDGET": {
        "owner": "obs/slo.py",
        "doc": "error budget for serve-latency p95 breaches.",
    },
    "DBCSR_TPU_SLO_SERVE_P95_MS": {
        "owner": "obs/slo.py",
        "doc": "serve-latency p95 objective in milliseconds.",
    },
    "DBCSR_TPU_SLO_SHORT_S": {
        "owner": "obs/slo.py",
        "doc": "short SLO burn window seconds.",
    },
    "DBCSR_TPU_SYNC_TIMING": {
        "owner": "core/stats.py",
        "doc": "=1 enables synchronized per-stack/per-tick timing (the "
               "documented sync seam; adds device fences to hot paths).",
    },
    "DBCSR_TPU_TRACE": {
        "owner": "obs/tracer.py",
        "doc": "trace control: path writes the Perfetto/Chrome JSON "
               "trace, '1' enables in-memory tracing.",
    },
    "DBCSR_TPU_TUNE": {
        "owner": "tune/service.py",
        "doc": "=1 starts the online autotuning service alongside the "
               "serving plane (serve engine start/shutdown own its "
               "lifecycle); unset/0 leaves tuning manual "
               "(docs/autotuning.md).",
    },
    "DBCSR_TPU_TUNE_BUDGET_BYTES": {
        "owner": "tune/trials.py",
        "doc": "per-trial operand byte budget: the trial stack size is "
               "clamped so staged A/B/C temporaries stay under it "
               "(default 64 MiB).",
    },
    "DBCSR_TPU_TUNE_BUDGET_S": {
        "owner": "tune/trials.py",
        "doc": "wall budget for one tuning trial's candidate sweep, "
               "seconds: checked after every timed leg (the sweep "
               "stops, keeping the legs already measured) and doubling "
               "as the tune_trial watchdog deadline.",
    },
    "DBCSR_TPU_TUNE_DEMOTE_RATIO": {
        "owner": "tune/store.py",
        "doc": "demotion-on-regression judge: a promoted row is demoted "
               "when its driver's live roofline fraction falls below "
               "this fraction of the at-promotion value (default 0.5).",
    },
    "DBCSR_TPU_TUNE_FLOOR": {
        "owner": "tune/miner.py",
        "doc": "per-device roofline-fraction floor below which a live "
               "(driver, mnk, dtype) cell counts as underperforming "
               "(default 0.25).",
    },
    "DBCSR_TPU_TUNE_INTERVAL_S": {
        "owner": "tune/service.py",
        "doc": "background tuner cycle cadence, seconds (default 60).",
    },
    "DBCSR_TPU_TUNE_MARGIN": {
        "owner": "tune/service.py",
        "doc": "minimum relative GFLOP/s uplift over the incumbent "
               "row/prediction before a trial winner is promoted "
               "(default 0.05).",
    },
    "DBCSR_TPU_TUNE_MAX_CELLS": {
        "owner": "tune/miner.py",
        "doc": "bound on the mined candidate-cell queue per cycle "
               "(default 32).",
    },
    "DBCSR_TPU_TUNE_NREP": {
        "owner": "tune/trials.py",
        "doc": "timing repetitions per candidate leg inside a tuning "
               "trial (default 2).",
    },
    "DBCSR_TPU_TS": {
        "owner": "obs/timeseries.py",
        "doc": "telemetry history store: '0'/'off' disables, a path "
               "enables the JSONL shard sink.",
    },
    "DBCSR_TPU_TS_10M_N": {
        "owner": "obs/timeseries.py",
        "doc": "10-minute rollup ring capacity.",
    },
    "DBCSR_TPU_TS_1M_N": {
        "owner": "obs/timeseries.py",
        "doc": "1-minute rollup ring capacity.",
    },
    "DBCSR_TPU_TS_INTERVAL_S": {
        "owner": "obs/timeseries.py",
        "doc": "minimum seconds between telemetry samples.",
    },
    "DBCSR_TPU_TS_RAW_N": {
        "owner": "obs/timeseries.py",
        "doc": "raw-resolution telemetry ring capacity.",
    },
    "DBCSR_TPU_WATCHDOG_LOG_MAX_BYTES": {
        "owner": "resilience/watchdog.py",
        "doc": "watchdog JSONL log rotation bound in bytes.",
    },
    "DBCSR_TPU_WORKLOAD": {
        "owner": "serve/workload.py",
        "doc": "workload-trace recorder control: unset/'0'/'off' "
               "disables it (the default — tracing every request is an "
               "operator decision), a path enables the JSONL shard "
               "sink capturing each terminal request's digest-only "
               "schema (docs/loadtest.md).",
    },
    "DBCSR_TPU_XLA_COST": {
        "owner": "obs/costmodel.py",
        "doc": "=1 captures XLA-reported cost analysis into the cost "
               "model.",
    },
}
