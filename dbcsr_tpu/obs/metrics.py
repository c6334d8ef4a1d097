"""Counter/gauge/histogram registry with snapshot + Prometheus export.

Layered over `core/stats` (the reference's STATISTICS block,
`dbcsr_mm_sched.F:390-546`): `snapshot()` folds the raw per-(m,n,k)
flop counters, collective-traffic counters and memory meters into one
machine-readable dict, alongside metrics this module owns directly —
most importantly the **JIT-recompile counters**: every stack-kernel
launch reports its specialization key via `record_jit()`, so each
jitted hot function exposes how many distinct XLA compilations it
triggered versus how often it reused one.  A stack-plan or jit-cache
churn problem (new (m,n,k)/bucket shapes arriving every multiply) is
invisible in wall time until it dominates; here it is a counter.

Label model: each metric holds values keyed by a sorted
``(label, value)`` tuple — enough for Prometheus text exposition
without pulling in a client library (the container has none; the
export format is the stable contract, see `prometheus_text()`).

Module-level imports are stdlib-only (`core.stats` is imported lazily
inside `snapshot`): `acc.smm` imports this module on its hot path.
"""

from __future__ import annotations

import json
import threading

from dbcsr_tpu.obs import events as _events
from dbcsr_tpu.obs import tracer as _trace

_lock = threading.Lock()


def _label_key(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


class Counter:
    """Monotone counter with optional labels."""

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self.values: dict = {}

    def inc(self, n: float = 1, **labels) -> None:
        key = _label_key(labels)
        self.values[key] = self.values.get(key, 0) + n

    def value(self, **labels) -> float:
        return self.values.get(_label_key(labels), 0)


class Gauge:
    """Point-in-time value with optional labels."""

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self.values: dict = {}

    def set(self, v: float, **labels) -> None:
        self.values[_label_key(labels)] = v

    def value(self, **labels) -> float:
        return self.values.get(_label_key(labels), 0)


class Histogram:
    """Fixed-bucket histogram (cumulative counts, Prometheus ``le``
    convention) + running sum/count."""

    DEFAULT_BUCKETS = (1e-4, 1e-3, 1e-2, 0.1, 0.5, 1.0, 5.0, 30.0)

    def __init__(self, name: str, help: str = "", buckets=None):
        self.name = name
        self.help = help
        self.buckets = tuple(buckets or self.DEFAULT_BUCKETS)
        self.values: dict = {}  # label key -> [counts per bucket, +inf]
        self.sums: dict = {}
        self.counts: dict = {}

    def observe(self, v: float, **labels) -> None:
        key = _label_key(labels)
        counts = self.values.setdefault(key, [0] * (len(self.buckets) + 1))
        for i, b in enumerate(self.buckets):
            if v <= b:
                counts[i] += 1
        counts[-1] += 1
        self.sums[key] = self.sums.get(key, 0.0) + v
        self.counts[key] = self.counts.get(key, 0) + 1


_counters: dict = {}
_gauges: dict = {}
_histograms: dict = {}
# per-fn specialization keys already seen (the jit-cache mirror)
_jit_seen: dict = {}


def counter(name: str, help: str = "") -> Counter:
    with _lock:
        c = _counters.get(name)
        if c is None:
            c = _counters[name] = Counter(name, help)
        return c


def counter_items(name: str) -> list:
    """Public enumeration of one counter's ``(labels_dict, value)``
    pairs — the supported way to read a labelled counter back out
    without binding to the registry's internal label-key encoding.
    Empty when the counter never incremented.  The timer table's two
    families (`_VIEWS`) are collected from `core.timings` here."""
    if name in _VIEWS:
        return [(dict(k), float(v)) for k, v in _VIEWS[name][1]()]
    with _lock:
        c = _counters.get(name)
        if c is None:
            return []
        return [(dict(k), float(v)) for k, v in c.values.items()]


def gauge(name: str, help: str = "") -> Gauge:
    with _lock:
        g = _gauges.get(name)
        if g is None:
            g = _gauges[name] = Gauge(name, help)
        return g


def histogram(name: str, help: str = "", buckets=None) -> Histogram:
    with _lock:
        h = _histograms.get(name)
        if h is None:
            h = _histograms[name] = Histogram(name, help, buckets)
        return h


def record_jit(fn: str, key) -> bool:
    """Report one launch of jitted function ``fn`` specialized by
    ``key`` (shapes/dtype/static args — whatever keys its jit cache).
    First sighting of a key counts as a compile, every later launch as
    a cache hit.  Returns True when this launch compiled.

    The mirror can only over-count compiles (e.g. after an external
    `jax.clear_caches()` the real cache recompiles while the mirror
    still records hits is the one way it under-counts; a process sees
    that rarely enough that the counter stays a faithful churn signal).
    """
    seen = _jit_seen.setdefault(fn, set())
    if key in seen:
        counter("dbcsr_tpu_jit_cache_hits_total",
                "stack-kernel launches served by an existing XLA "
                "specialization").inc(fn=fn)
        return False
    seen.add(key)
    counter("dbcsr_tpu_jit_compiles_total",
            "distinct XLA specializations triggered per jitted hot "
            "function").inc(fn=fn)
    # compiles also land on the event bus (product-correlated: "which
    # multiply triggered this recompile") and in the trace stream, so
    # tools/trace_summary.py can rank recompile offenders from the
    # JSONL alone — one publish feeds both
    _events.publish("jit_compile", {"fn": fn, "key": str(key)})
    return True


def jit_stats() -> dict:
    """{fn: {"compiles": n, "cache_hits": n}} for every function that
    reported through `record_jit`."""
    comp = _counters.get("dbcsr_tpu_jit_compiles_total")
    hits = _counters.get("dbcsr_tpu_jit_cache_hits_total")
    out: dict = {}
    for c, field in ((comp, "compiles"), (hits, "cache_hits")):
        if c is None:
            continue
        for key, v in c.values.items():
            fn = dict(key).get("fn", "?")
            out.setdefault(fn, {"compiles": 0, "cache_hits": 0})[field] = v
    return out


# ------------------------------------------------- JAX's own account
# (`record_jit` above mirrors specialisation keys and guesses a compile;
# `listen_to_compiles` books what JAX says it did)
COMPILE_SECONDS = "dbcsr_tpu_compile_seconds_total"
COMPILE_PROGRAMS = "dbcsr_tpu_compile_programs_total"
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_listening = False


class _CompileThread(threading.local):
    """Per thread: ``open`` holds, for each event that is still open,
    the seconds of the closed events inside it (JAX traces a jitted
    callee inside its caller's trace); ``retrieved`` says a cache
    retrieval was reported inside the open backend event."""

    def __init__(self):
        self.open = []
        self.retrieved = False


_compile_thread = _CompileThread()


def _on_compile_start(event, _start_time, **_kw) -> None:
    if event in _COMPILE_STAGES:
        _compile_thread.open.append(0.0)


def _on_compile_duration(event, secs, fun_name="", **_kw) -> None:
    if event == _CACHE_RETRIEVAL:
        _compile_thread.retrieved = True
        return
    stage = _COMPILE_STAGES.get(event)
    if stage is None:
        return
    nest = _compile_thread.open
    inside = nest.pop() if nest else 0.0
    if nest:
        nest[-1] += secs
    if stage == "compile" and _compile_thread.retrieved:
        stage, _compile_thread.retrieved = "cache_load", False
    from dbcsr_tpu.core import timings

    # tracing names the function `f`, lowering and the backend name its
    # module `jit(f)`, the device trace `jit_f`: one name for all
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]
    labels = {"stage": stage, "fn": fun_name,
              "phase": timings._stack[-1][0] if timings._stack else ""}
    counter(COMPILE_SECONDS,
            "seconds JAX spent to trace, lower, compile or load from the "
            "persistent cache, per jitted function and engine phase; the "
            "stages are exclusive").inc(secs - inside, **labels)
    if stage in ("compile", "cache_load"):
        counter(COMPILE_PROGRAMS,
                "programs JAX compiled or loaded from the persistent "
                "cache, per jitted function and engine phase"
                ).inc(**labels)


def listen_to_compiles() -> None:
    """Book JAX's compile events into the registry; `core.lib.init_lib`
    calls this once a process and a second call installs nothing.

    `dbcsr_tpu_compile_seconds_total{stage, fn, phase}` takes the
    seconds, `dbcsr_tpu_compile_programs_total{stage, fn, phase}` one
    bump per backend event.  ``stage`` is ``trace``, ``lower``,
    ``compile`` or ``cache_load`` and the four are exclusive: a backend
    event inside which JAX reported a cache retrieval is booked whole
    as ``cache_load``, any other as ``compile``, and an event nested in
    another (a jitted callee traced inside its caller) is taken off the
    outer one, so the stages sum to what JAX spent and ``compile`` +
    ``cache_load`` programs are its backend events.  ``fn`` is the
    jitted function's name: the event's ``fun_name`` without the
    ``jit(...)`` that lowering and the backend put around it (the
    device trace's module is ``jit_<fn>``); ``phase`` is the innermost open
    `core.timings.timed` span, ``""`` outside all of them.  The timer's
    stack is one per process, so a compile on a second thread is booked
    to whatever span the first has open: best effort across threads.

    Nothing runs unless JAX traces, compiles or loads a program: a
    steady product pays nothing for it."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring

    jax.monitoring.register_scalar_listener(_on_compile_start)
    jax.monitoring.register_event_duration_secs_listener(
        _on_compile_duration)


# ------------------------------------------ the timer table, when read
def _span_seconds() -> list:
    from dbcsr_tpu.core import timings

    return [(_label_key({"span": name, "kind": kind}), value)
            for name, st in list(timings._stats.items())
            for kind, value in (("self", st.self_time), ("total", st.total))]


def _span_calls() -> list:
    from dbcsr_tpu.core import timings

    return [(_label_key({"span": name}), st.calls)
            for name, st in list(timings._stats.items())]


# counters that are views: collected from their source when read (by
# `counter_items`, `snapshot`, `prometheus_text`), never bumped, so
# `core.timings.timed` pays nothing for being readable
_VIEWS = {
    "dbcsr_tpu_span_seconds_total": (
        "self and total host seconds per core.timings span", _span_seconds),
    "dbcsr_tpu_span_calls_total": (
        "completed calls per core.timings span", _span_calls),
}


def reset(include_stats: bool = True) -> None:
    """Clear the metric registries and the jit-recompile mirror.

    ``include_stats`` (default True) also resets the `core.stats`
    registries this module snapshots (per-(m,n,k) flops, comm traffic,
    driver rollups, memory meters) and the `costmodel` XLA-cost
    captures — so ``reset(); snapshot()`` reports a truly fresh state.
    Pass ``include_stats=False`` to clear only the obs-owned metrics
    while keeping the engine's cumulative statistics (e.g. to re-window
    counters mid-run without losing the STATISTICS block)."""
    with _lock:
        _counters.clear()
        _gauges.clear()
        _histograms.clear()
        _jit_seen.clear()
    # mempool caches Counter OBJECTS for its hot-path increments: after
    # the registry is cleared those objects are orphaned (increments
    # would vanish from scrapes), so the cache must drop with the
    # registry — on BOTH include_stats settings
    try:
        import sys

        mp = sys.modules.get("dbcsr_tpu.core.mempool")
        if mp is not None:
            mp._metric_cache.clear()
    except Exception:
        pass
    if include_stats:
        from dbcsr_tpu.core import stats
        from dbcsr_tpu.obs import costmodel

        stats.reset()
        costmodel.reset()
        try:
            from dbcsr_tpu.core import mempool

            mempool.reset_stats()
        except Exception:
            pass  # jax-free contexts (doctor --selftest parses only)
        # the attribution ledger and the incident-capture budget follow
        # the same include_stats contract (docs/observability.md §
        # Reset semantics) — AFTER stats.reset() above, so the
        # attribution re-baseline snapshots the freshly zeroed rollup
        try:
            import sys as _sys

            _attr = _sys.modules.get("dbcsr_tpu.obs.attribution")
            if _attr is not None:
                _attr.reset()
            _inc = _sys.modules.get("dbcsr_tpu.obs.incidents")
            if _inc is not None:
                _inc.reset()
            # the causal diagnosis plane joins the same contract: a
            # full reset drops profile epochs, detector baselines and
            # the change ledger; a metric re-window keeps them
            for name in ("dbcsr_tpu.obs.profiler",
                         "dbcsr_tpu.obs.changepoint",
                         "dbcsr_tpu.obs.rca"):
                mod = _sys.modules.get(name)
                if mod is not None:
                    mod.reset()
        except Exception:
            pass


def _roofline_rollup() -> dict:
    """Per-driver roofline attribution from `core.stats.driver_rollup`
    + the `costmodel` peak table, refreshing the ``dbcsr_tpu_*`` gauges
    as a side effect so scrapes and snapshots agree.  Every driver
    that executed since the last reset gets an entry; seconds are
    dispatch-side wall time (see `stats.record_driver`)."""
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.obs import costmodel

    kind = costmodel.device_kind()
    out: dict = {}
    for driver, agg in sorted(stats.driver_rollup().items()):
        dtype = max(agg["by_dtype"], key=agg["by_dtype"].get) \
            if agg["by_dtype"] else "float64"
        rl = costmodel.roofline(agg["flops"], agg["bytes"],
                                agg["seconds"], kind=kind, dtype=dtype)
        rl["stacks"] = agg["stacks"]
        # sync=true only when EVERY recorded region was timed through
        # block_until_ready (DBCSR_TPU_SYNC_TIMING at record time) —
        # a mixed aggregate must not present async dispatch rates as
        # device-completion rates
        rl["sync"] = bool(agg["stacks"]) and (
            agg["sync_stacks"] == agg["stacks"])
        out[driver] = rl
        gauge("dbcsr_tpu_achieved_gflops",
              "flops / dispatch seconds per stack driver").set(
            rl["achieved_gflops"], driver=driver)
        gauge("dbcsr_tpu_roofline_fraction",
              "achieved rate / attainable roofline rate per driver "
              "(min(peak compute, intensity*bandwidth) denominator)"
              ).set(rl["roofline_fraction"], driver=driver)
        gauge("dbcsr_tpu_arithmetic_intensity",
              "modeled flops per HBM byte per driver").set(
            rl["arithmetic_intensity"], driver=driver)
    # Cannon tick-loop overlap attribution rides on the owning driver's
    # row (engine "mesh" -> driver "mesh", engine "dense" -> "dense"):
    # per grid, the MODELED comm/compute ratio next to the MEASURED
    # comm-exposed fraction (parallel/overlap.py, DBCSR_TPU_SYNC_TIMING).
    # A standalone dense Cannon (cannon_multiply_dense called directly,
    # no record_stack row) still surfaces: its attribution lands in a
    # cannon_overlap-only row rather than being dropped.
    for engine, grids in stats.cannon_overlap_rollup().items():
        out.setdefault(engine, {})["cannon_overlap"] = grids
    return out


def _stats_snapshot() -> dict:
    """Fold core.stats' registries into plain dicts (per-driver flops,
    per-(m,n,k) stack counts, collective traffic, memory meters)."""
    from dbcsr_tpu.core import stats

    by_driver: dict = {}
    by_mnk = {}
    for (m, n, k), st in stats._by_mnk.items():
        by_mnk[f"{m}x{n}x{k}"] = {
            "stacks": st.nstacks,
            "entries": st.nentries,
            "flops": st.flops,
            "by_driver": dict(st.by_driver),
        }
        for d, f in st.by_driver.items():
            by_driver[d] = by_driver.get(d, 0) + f
    comm = {
        kind: {"messages": st.nmessages, "bytes": st.nbytes}
        for kind, st in stats._comm.items()
    }
    return {
        "flops_by_driver": by_driver,
        "by_mnk": by_mnk,
        "comm": comm,
        "totals": dict(stats._totals),
        "memory": stats.memory_high_water(),
    }


def snapshot() -> dict:
    """One machine-readable dict of everything observable right now:
    the core.stats layers + the roofline attribution rollup + this
    registry's own metrics + the jit-recompile mirror (+ captured XLA
    cost analyses when `costmodel` capture is on)."""
    from dbcsr_tpu.obs import costmodel

    def expand(metrics):
        return {
            name: {json.dumps(dict(k)): v for k, v in m.values.items()}
            for name, m in metrics.items()
        }

    snap = _stats_snapshot()
    # refresh the roofline gauges BEFORE expanding the gauge registry
    # so the snapshot's "gauges" section carries them too
    snap["roofline"] = _roofline_rollup()
    snap["device_kind"] = costmodel.device_kind()
    try:
        from dbcsr_tpu.core import mempool

        snap["pool"] = mempool.pool_stats()
        snap["transfer"] = mempool.transfer_totals()
    except Exception:
        pass  # jax-free contexts
    xc = costmodel.xla_costs()
    if xc:
        snap["xla_cost"] = xc
    snap["counters"] = expand(_counters)
    for name, (_, collect) in _VIEWS.items():
        snap["counters"][name] = {json.dumps(dict(k)): v
                                  for k, v in collect()}
    snap["gauges"] = expand(_gauges)
    snap["histograms"] = {
        name: {
            json.dumps(dict(k)): {
                "buckets": dict(zip([str(b) for b in h.buckets] + ["+Inf"],
                                    v)),
                "sum": h.sums.get(k, 0.0),
                "count": h.counts.get(k, 0),
            }
            for k, v in h.values.items()
        }
        for name, h in _histograms.items()
    }
    snap["jit"] = jit_stats()
    return snap


def _fmt_labels(key: tuple) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in key)
    return "{" + inner + "}"


def prometheus_text() -> str:
    """Prometheus text exposition (v0.0.4) of the full snapshot —
    registry metrics plus the core.stats layers rendered as
    ``dbcsr_tpu_*`` families."""
    from dbcsr_tpu.core import stats

    _roofline_rollup()  # refresh the roofline gauges before rendering
    lines: list = []

    def emit(name, kind, help, values):
        lines.append(f"# HELP {name} {help}")
        lines.append(f"# TYPE {name} {kind}")
        for key, v in values:
            lines.append(f"{name}{_fmt_labels(key)} {v}")

    # core.stats layers
    by_driver: dict = {}
    for st in stats._by_mnk.values():
        for d, f in st.by_driver.items():
            by_driver[d] = by_driver.get(d, 0) + f
    emit("dbcsr_tpu_flops_total", "counter",
         "true flops per stack driver",
         [((("driver", d),), f) for d, f in sorted(by_driver.items())])
    emit("dbcsr_tpu_comm_bytes_total", "counter",
         "collective traffic bytes per collective kind",
         [((("kind", k),), st.nbytes) for k, st in sorted(stats._comm.items())])
    emit("dbcsr_tpu_comm_messages_total", "counter",
         "collective message counts per collective kind",
         [((("kind", k),), st.nmessages)
          for k, st in sorted(stats._comm.items())])
    emit("dbcsr_tpu_multiplies_total", "counter",
         "multiply() invocations",
         [((), stats._totals["multiplies"])])
    emit("dbcsr_tpu_memory_bytes", "gauge",
         "host/device memory meters (peak and current)",
         [((("meter", k),), v)
          for k, v in sorted(stats.memory_high_water().items())])
    # registry metrics
    for name, c in sorted(_counters.items()):
        emit(name, "counter", c.help or name, sorted(c.values.items()))
    for name, (help, collect) in sorted(_VIEWS.items()):
        emit(name, "counter", help, sorted(collect()))
    for name, g in sorted(_gauges.items()):
        emit(name, "gauge", g.help or name, sorted(g.values.items()))
    for name, h in sorted(_histograms.items()):
        lines.append(f"# HELP {name} {h.help or name}")
        lines.append(f"# TYPE {name} histogram")
        for key, counts in sorted(h.values.items()):
            for b, cnt in zip([str(b) for b in h.buckets] + ["+Inf"], counts):
                lines.append(
                    f"{name}_bucket{_fmt_labels(key + (('le', b),))} {cnt}")
            lines.append(f"{name}_sum{_fmt_labels(key)} {h.sums.get(key, 0.0)}")
            lines.append(f"{name}_count{_fmt_labels(key)} {h.counts.get(key, 0)}")
    return "\n".join(lines) + "\n"
