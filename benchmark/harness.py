"""The cell harness: one process, one cell, the contract's one line.

Everything that belongs to one cell is data found by name from
`BENCHMARK.json`: `configs/<config>.json`, `traffic/<traffic>.json`
(which names `generators/<kind>.py`), and for each per-layer metric
`layers/<metric>.json` (which names `reducers/<kind>.py`).  A later PR
adds files and entries and edits none.

From the program the harness takes `dt.init_lib`, `dt.set_config` with
what the traffic file states, the generator's calls, and counters; it
sets no other knob.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import shutil
import sys
import time
import types
import warnings

from . import arithmetic, reference, xplane

OUT_DIR = ".bench_out"  # traces; inside the checkout, git-ignored
TRACE_MAX_PRODUCTS = 3
TRACE_MAX_SECONDS = 10.0
MAX_WARMUPS = 4
FAILOVER_COUNTERS = ("dbcsr_tpu_driver_failures_total",
                     "dbcsr_tpu_driver_fallback_total",
                     "dbcsr_tpu_checksum_retry_total")
HOST_SPAN_FAMILY = ("dbcsr_tpu:*", "bench:*")


class BenchError(Exception):
    """The cell cannot be run as asked; no result line is printed."""


# ------------------------------------------------------------------ data
def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@functools.lru_cache(maxsize=None)
def _load_code(path: str):
    """A generator or reducer kind, loaded from its file once."""
    name = "_bench_" + os.path.relpath(path).replace(os.sep, "_")[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads`, with everything it names loaded."""

    def __init__(self, checkout: str, name: str):
        self.spec = _read_json(os.path.join(checkout, "BENCHMARK.json"))
        self.dir = os.path.join(checkout, self.spec["paths"][0])
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
        entry = cells[name]
        self.name, self.chips = name, int(entry["chips"])
        cfg_entry = {c["name"]: c for c in self.spec["configs"]}[
            entry["config"]]
        self.config = _read_json(os.path.join(checkout, cfg_entry["file"]))
        self.traffic = _read_json(os.path.join(
            self.dir, "traffic", entry["traffic"] + ".json"))
        self.generator = _load_code(os.path.join(
            self.dir, "generators", self.traffic["generator"] + ".py"))
        self.peaks_table = _read_json(os.path.join(self.dir, "peaks.json"))

    def metrics(self, group: str) -> list:
        """The `end_to_end` or `per_layer` entries this cell reports."""
        return [m for m in self.spec[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def layer(self, metric: str):
        """(layer file, reducer module) of a per-layer metric."""
        spec = _read_json(os.path.join(self.dir, "layers", metric + ".json"))
        reducer = _load_code(os.path.join(
            self.dir, "reducers", spec["reducer"] + ".py"))
        return spec, reducer

    def peaks(self, device_kind: str) -> dict:
        if device_kind not in self.peaks_table:
            raise BenchError(
                f"device kind {device_kind!r} is not in peaks.json: add its "
                "published peaks with their source, do not guess")
        return self.peaks_table[device_kind]


# -------------------------------------------------------- program probes
class Monitor:
    """Counts JAX's own compile events from `jax.monitoring`."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    EVENTS = {"/jax/compilation_cache/cache_hits": "jax_cache_hits",
              "/jax/compilation_cache/cache_misses": "jax_cache_misses"}

    def __init__(self):
        import jax.monitoring as mon

        self.counts = {"jax_backend_compiles": 0, "jax_cache_hits": 0,
                       "jax_cache_misses": 0}
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == self.COMPILE:
            self.counts["jax_backend_compiles"] += 1

    def _event(self, event, **_kw):
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1


def read_counter(name: str, monitor: Monitor) -> list:
    """[(labels, value)] of one counter: JAX's (`jax_*`), the stack
    launches per driver (`driver_stacks`), or the program's registry."""
    if name in monitor.counts:
        return [({}, float(monitor.counts[name]))]
    if name == "driver_stacks":
        from dbcsr_tpu.core import stats

        return [({"driver": d}, float(v["stacks"]))
                for d, v in sorted(stats.driver_rollup().items())]
    from dbcsr_tpu.obs import metrics

    return metrics.counter_items(name)


def program_warnings(caught, package_dir: str) -> list:
    """RuntimeWarnings raised from the program's own files: how it says
    that it carried on after a failure it counts nowhere (a crosspack
    kernel that ran out of smem and fell back to the base kernel)."""
    return [f"{w.filename}:{w.lineno}: {str(w.message)[:300]}"
            for w in caught if issubclass(w.category, RuntimeWarning)
            and os.path.abspath(w.filename).startswith(package_dir)]


def failover_state(monitor: Monitor) -> dict:
    """What the engine bumps or sets when it carries on after a failure
    (a copy of `chip_smoke._failover_state`)."""
    from dbcsr_tpu.acc import smm

    state = {name: sum(v for _, v in read_counter(name, monitor))
             for name in FAILOVER_COUNTERS}
    state["cross_disabled"] = sorted(map(str, smm._cross_disabled))
    return state


# ---------------------------------------------------------------- running
def _log(tag: str, obj) -> None:
    print(f"BENCH {tag} " + json.dumps(obj, default=str), flush=True)


def _device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _memory(devices, key: str) -> int:
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devices)


class Run:
    """What set-up and the window leave for the report and the reducers."""

    def __init__(self):
        self.samples: dict = {}     # clock name -> [seconds, ...]
        self.records: list = []     # one per product of the window
        self.counters_before: dict = {}
        self.counters_after: dict = {}
        self.trace = None           # xplane.load(...) of the traced window
        self.trace_window = None    # (t0_ns, t1_ns) on the trace's clock
        self.metrics: dict = {}     # per-layer values, as they are reduced

    def sample(self, name: str, value: float) -> None:
        self.samples.setdefault(name, []).append(float(value))

    @property
    def products(self) -> int:
        return sum(r["ok"] for r in self.records)

    @property
    def failed(self) -> int:
        return len(self.records) - self.products

    @property
    def product_ids(self) -> list:
        return [r["product"] for r in self.records]

    @property
    def algorithms(self) -> list:
        return [r["algorithm"] for r in self.records]


def _make_digest():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bench_digest(arrays):
        return [jnp.sum(jnp.square(x)) for x in arrays]

    return lambda arrays: bench_digest(list(arrays))


def run_cell(checkout: str, workload: str, seed: int, seconds: float,
             trace: bool, *, platform: str = "tpu",
             t_process: float | None = None) -> int:
    """Run one cell and print the contract's line last.  Returns the
    exit code; prints no result line unless it is 0."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = Cell(checkout, workload)

    import jax

    devices = jax.devices()
    info = _device_info(devices)
    if info["platform"] != platform or len(devices) < cell.chips:
        print(f"benchmark: {workload} needs {cell.chips} {platform} "
              f"chip(s), JAX found {info}", file=sys.stderr)
        return 2
    # a rehearsal on another platform has no peaks, and reports no share
    peaks = (cell.peaks(info["kind"]) if platform == "tpu"
             else cell.peaks_table.get(info["kind"], {}))
    monitor = Monitor()

    import dbcsr_tpu as dt

    dt.init_lib()
    dt.set_config(**cell.traffic.get("program_config", {}))
    bench = types.SimpleNamespace(arithmetic=arithmetic, reference=reference)
    gen = cell.generator.Generator(bench, cell.config, cell.traffic, seed,
                                   devices[:cell.chips])
    _log("cell", {"workload": workload, "seed": seed, "seconds": seconds,
                  "trace": bool(trace), "device": info,
                  "compile_cache_dir": jax.config.jax_compilation_cache_dir,
                  "program_config": cell.traffic.get("program_config", {})})
    ctx = types.SimpleNamespace(
        run=Run(), cell=cell, gen=gen, peaks=peaks, info=info,
        devices=devices[:cell.chips], monitor=monitor, trace=trace,
        digest=_make_digest(), xplane=xplane, arithmetic=arithmetic,
        log=_log, family=HOST_SPAN_FAMILY,
        trace_dir=os.path.join(checkout, OUT_DIR, "trace", workload))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _set_up(ctx, t_process)
        _window(ctx, seconds)
    ctx.complaints = program_warnings(
        caught, os.path.dirname(os.path.abspath(dt.__file__)))
    print(json.dumps(_report(ctx)), flush=True)
    return 0


def _set_up(ctx, t_process: float) -> None:
    """Operands, then first products until one compiles nothing, then
    the reference check on the last of them.  All of it is `setup_s`."""
    import jax

    run, gen, monitor = ctx.run, ctx.gen, ctx.monitor
    t0 = time.perf_counter()
    ctx.log("operands", gen.make_operands())
    run.sample("setup_operands_s", time.perf_counter() - t0)
    ctx.failover0 = failover_state(monitor)
    t1 = time.perf_counter()
    ctx.want_digest, ctx.checks, warm = {}, {}, []
    for product in gen.distinct_products():
        for _ in range(MAX_WARMUPS):
            compiles = monitor.counts["jax_backend_compiles"]
            ts = time.perf_counter()
            c, flops = gen.start(product)
            jax.block_until_ready(gen.result_arrays(c))
            digest = [float(x) for x in ctx.digest(gen.result_arrays(c))]
            compiles = monitor.counts["jax_backend_compiles"] - compiles
            warm.append({"product": product, "compiles": compiles,
                         "seconds": time.perf_counter() - ts})
            if compiles == 0:
                break
        else:
            raise BenchError(f"product {product} still compiles after "
                             f"{MAX_WARMUPS} calls: {warm}")
        ctx.want_digest[product] = digest
        tc = time.perf_counter()
        check = gen.check(product, c)
        check.update(flops_program=flops, flops_true=gen.flops(product),
                     seconds=time.perf_counter() - tc)
        check["ok"] = bool(check["ok"] and flops == gen.flops(product))
        ctx.checks[product] = check
        del c
    run.sample("setup_first_products_s", time.perf_counter() - t1)
    ctx.log("warmup", warm)
    ctx.log("check", ctx.checks)
    ctx.log("compile_cache", dict(monitor.counts))
    ctx.log("stacks", {p: gen.stacks(p) for p in gen.distinct_products()})

    ctx.layers = {m["name"]: ctx.cell.layer(m["name"])
                  for m in ctx.cell.metrics("per_layer")} if ctx.trace else {}
    ctx.counters = sorted({spec["counter"] for spec, _ in ctx.layers.values()
                           if "counter" in spec})
    if ctx.trace:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        os.makedirs(ctx.trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False  # two thirds of a trace, unread
        jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)
    ctx.log("memory_at_window_start",
            {"bytes_in_use": _memory(ctx.devices, "bytes_in_use"),
             "peak_bytes_in_use": _memory(ctx.devices, "peak_bytes_in_use")})
    run.sample("setup_s", time.perf_counter() - t_process)


def _window(ctx, seconds: float) -> None:
    """The closed loop of one client: a product is started while the
    clock is under ``seconds`` and the one started is always finished.
    A traced window ends after three products or 10 s."""
    import jax
    from jax.profiler import TraceAnnotation

    run, gen, monitor, trace = ctx.run, ctx.gen, ctx.monitor, ctx.trace
    limit = min(seconds, TRACE_MAX_SECONDS) if trace else seconds

    def snapshot():
        return {name: read_counter(name, monitor) for name in ctx.counters}

    run.counters_before = snapshot()
    t_first = t_last = None
    for product in gen.schedule():
        now = time.perf_counter()
        if t_first is None:
            t_first = now
        elif now - t_first >= limit or (
                trace and len(run.records) >= TRACE_MAX_PRODUCTS):
            break
        # Two things of the benchmark's own sit inside the window and
        # are priced in PERF.md, section 2: this read (host, 6 us each, two a
        # product) and the digest below (3-4 ms of device time at
        # the north star, under 0.1% of a product).
        before = None if trace else failover_state(monitor)
        with TraceAnnotation("bench:product"):
            ts = time.perf_counter()
            with TraceAnnotation("bench:dispatch"):
                c, flops = gen.start(product)
            td = time.perf_counter()
            with TraceAnnotation("bench:fence"):
                jax.block_until_ready(gen.result_arrays(c))
            t_last = te = time.perf_counter()
        run.sample("dispatch_s", td - ts)
        run.sample("fence_wait_s", te - td)
        run.sample("multiply_s", te - ts)
        run.records.append({
            "product": product, "algorithm": gen.algorithm(c),
            # dispatched now, read after the window
            "digest": ctx.digest(gen.result_arrays(c)),
            "ok": flops == gen.flops(product) and (
                before is None or failover_state(monitor) == before)})
        del c
    ctx.window_s = t_last - t_first
    run.counters_after = snapshot()
    ctx.failover1 = failover_state(monitor)
    if trace:
        jax.profiler.stop_trace()


def _report(ctx) -> dict:
    """Outside the window: determinism, failovers, memory, and the
    contract's line (end-to-end metrics, or per-layer ones when traced)."""
    run, gen, cell = ctx.run, ctx.gen, ctx.cell
    for i, rec in enumerate(run.records):
        if [float(x) for x in rec.pop("digest")] != \
                ctx.want_digest[rec["product"]]:
            ctx.log("digest_differs", {"product_index": i})
            rec["ok"] = False
    if ctx.failover1 != ctx.failover0:
        ctx.log("failover", {"before": ctx.failover0,
                             "after": ctx.failover1})
    if ctx.complaints:
        ctx.log("failover_warnings", ctx.complaints)
    correct = (run.failed == 0 and run.products > 0
               and ctx.failover1 == ctx.failover0 and not ctx.complaints
               and all(ch["ok"] for ch in ctx.checks.values()))
    flops_done = sum(gen.flops(r["product"]) for r in run.records if r["ok"])
    peak = _memory(ctx.devices, "peak_bytes_in_use")
    device = dict(ctx.info, memory_peak_bytes=peak)
    ctx.log("multiply_s", arithmetic.quartiles(run.samples["multiply_s"]))
    ctx.log("window", {"seconds": ctx.window_s, "products": run.products,
                       "failed": run.failed, "flops": flops_done,
                       "algorithms": sorted(set(map(str, run.algorithms)))})
    values = {
        "true_gflops": flops_done / ctx.window_s * 1e-9,
        "multiply_s": arithmetic.median(run.samples["multiply_s"]),
        "peak_hbm_gib": peak / 2.0 ** 30,
        "setup_s": run.samples["setup_s"][0],
    }
    line = {"correct": bool(correct), "attempted": len(run.records),
            "failed": run.failed, "device": device}
    if not ctx.trace:
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.metrics("end_to_end")}
        return line
    ctx.log("end_to_end_of_traced_run_not_reported", values)
    _reduce_trace(ctx, ctx.trace_dir)
    line["metrics"] = {}
    for m in cell.metrics("per_layer"):
        spec, reducer = ctx.layers[m["name"]]
        value = reducer.reduce(spec, ctx)
        if value is not None:  # a reader that finds nothing says nothing
            run.metrics[m["name"]] = float(value)
            line["metrics"][m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
    device.update(ctx.device_trace)
    line["breakdown"] = ctx.breakdown
    return line


def _reduce_trace(ctx, trace_dir: str) -> None:
    """Load the traced window and fill what every trace reducer shares:
    the window on the trace's clock, busy seconds per device, the
    breakdown."""
    xp, run = ctx.xplane, ctx.run
    t0 = time.perf_counter()
    path = xp.find_xplane(trace_dir)
    run.trace = xp.load(path, xp.wanted_line)
    products = [ev for _, evs in xp.host_spans(run.trace, ["bench:product"])
                for ev in evs]
    if not products:
        raise BenchError("the trace holds no bench:product span")
    w0 = min(ev[1] for ev in products)
    w1 = max(ev[1] + ev[2] for ev in products)
    run.trace_window = (w0, w1)
    planes = xp.device_planes(run.trace)[:len(ctx.devices)]
    if not planes:
        raise BenchError("the trace holds no device plane")
    busy, op_time, idle, by_module = [], {}, {}, []
    for _, plane in planes:
        ops = xp.clip(xp.line_events(plane, xp.OPS_LINE), run.trace_window)
        busy.append(xp.union_ns(ops) * 1e-9)
        by_module.append({})
        for mod, name, dur in xp.ops_by_module(plane, run.trace_window):
            key = f"{mod}/{xp.op_kind(name)}"
            op_time[key] = op_time.get(key, 0.0) + dur / len(planes)
            by_module[-1][mod] = by_module[-1].get(mod, 0.0) + dur
        for name, ns in xp.attribute_gaps(
                xp.gaps(ops, run.trace_window), run.trace,
                ctx.family).items():
            idle[name] = idle.get(name, 0.0) + ns / len(planes)
    ctx.busy_per_device = busy
    ctx.device_trace = {"busy_s": sum(busy) / len(busy),
                        "window_s": (w1 - w0) * 1e-9}
    ctx.breakdown = {"device_ops": xp.top(op_time),
                     "idle_gaps": xp.top(idle)}
    n = max(len(run.records), 1)
    ctx.log("device_seconds_per_product_by_module",
            [dict(xp.top(mods, 12, 1e-9 / n)) for mods in by_module])
    ctx.log("trace", {"file_bytes": os.path.getsize(path),
                      "read_s": time.perf_counter() - t0,
                      "busy_s_per_device": busy,
                      "window_s": ctx.device_trace["window_s"]})


def main(argv=None, *, t_process: float | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        return run_cell(checkout, args.workload, args.seed, args.seconds,
                        bool(args.trace), t_process=t_process)
    except BenchError as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 1
