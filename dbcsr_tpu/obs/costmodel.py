"""Analytic FLOP/byte cost model, roofline peaks, and XLA cross-check.

The attribution layer the reference builds into its STATISTICS block
(`dbcsr_mm_sched.F:390-546` true-vs-marketing flops) and that CP2K uses
to say *how far from peak* a run is — rebuilt as three pieces:

* **Analytic model** — `stack_flops`/`stack_bytes` model one parameter
  stack (gather A+B per entry, C read+written once per segment — the
  same HBM-traffic convention as `acc/bench.py`), `dense_cost` one
  dense-canvas matmul.  `core.stats` aggregates these per driver, so
  `obs.metrics.snapshot()` can report achieved GFLOP/s, arithmetic
  intensity and roofline fraction per stack driver.
* **Roofline peak table** — per-`device_kind` peak compute (per dtype)
  and memory/interconnect bandwidth.  The built-ins are order-of-
  magnitude engineering estimates, not vendor numbers; override with
  ``DBCSR_TPU_ROOFLINE`` (a JSON dict merged over the table) or the
  scalar ``DBCSR_TPU_PEAK_GFLOPS`` / ``DBCSR_TPU_PEAK_GBS`` /
  ``DBCSR_TPU_ICI_GBS`` env knobs.  `roofline()` computes the
  attainable rate ``min(peak, intensity * bw)`` and the achieved
  fraction of it.
* **XLA cross-check** — with ``DBCSR_TPU_XLA_COST=1`` (or
  `enable_xla_capture()`), the first launch of each jitted stack-kernel
  specialization additionally captures XLA's own
  ``lowered.compile().cost_analysis()`` / ``memory_analysis()`` numbers
  (one extra AOT compile per specialization — opt-in for exactly that
  reason), stored next to the analytic model's prediction so drift
  between the two is a queryable artifact (`xla_costs()`, and
  `metrics.snapshot()["xla_cost"]`).

Module-level imports are stdlib-only: `core.stats` imports this module
on the multiply hot path, and must stay importable without jax.
"""

from __future__ import annotations

import json
import os


# ---------------------------------------------------------------- model

def stack_flops(m: int, n: int, k: int, entries: int) -> int:
    """True flops of one parameter stack: 2*m*n*k per entry (the
    reference's 'true flops', `dbcsr_mm.F:664-667`)."""
    return 2 * m * n * k * entries


def stack_bytes(m: int, n: int, k: int, entries: int, *,
                nseg: int | None = None, itemsize: int = 8) -> int:
    """Modeled HBM traffic of one stack: gather one A (m,k) and one B
    (k,n) block per entry, read+write each C segment once.  A lower
    bound — TPU tile padding and revisited gathers only add to it; the
    same convention as the `acc/bench.py` GB/s line, so kernel
    micro-bench and engine rollups are comparable."""
    if nseg is None:
        nseg = entries
    return itemsize * (entries * (m * k + k * n) + 2 * nseg * m * n)


def superstack_bytes(span_shapes, *, nseg: int, itemsize: int = 8) -> int:
    """Modeled HBM traffic of one FUSED C-bin launch: every span still
    gathers its own A/B blocks, but the bin's C buffer is read+written
    exactly once for the whole launch — the N−1 C round-trips the
    per-span path pays are the traffic fusion eliminates, so charging
    them would overstate bytes and understate the fused roofline
    fraction.  ``span_shapes`` is an iterable of (m, n, k, entries)
    sharing one (m, n); equals the sum of per-span `stack_bytes` where
    only the first span passes ``nseg`` and the rest pass ``nseg=0``
    (the convention `mm.multiply._run_stacks` records)."""
    gather = 0
    m = n = 0
    for m, n, k, entries in span_shapes:
        gather += entries * (m * k + k * n)
    return itemsize * (gather + 2 * nseg * m * n)


def dense_cost(m: int, n: int, k: int, *, itemsize: int = 8) -> dict:
    """FLOPs/bytes of one dense (m,k)x(k,n) canvas matmul: read A and
    B once, write (and read, for beta-merge) C once."""
    flops = 2 * m * n * k
    nbytes = itemsize * (m * k + k * n + 2 * m * n)
    return {"flops": flops, "bytes": nbytes,
            "intensity": flops / nbytes if nbytes else 0.0}


def intensity(flops: float, nbytes: float) -> float:
    """Arithmetic intensity in flops/byte."""
    return float(flops) / float(nbytes) if nbytes else 0.0


# machine epsilon of the ACCUMULATION dtype each engine dtype uses
# (bf16 accumulates in f32, acc/smm._accum_dtype) — stdlib-only so the
# tolerance stays computable without jax/numpy imported
_ACC_EPS = {
    "float64": 2.220446049250313e-16,
    "complex128": 2.220446049250313e-16,
    "float32": 1.1920929e-07,
    "complex64": 1.1920929e-07,
    "bfloat16": 1.1920929e-07,  # f32 accumulation
    "float16": 9.765625e-04,
}


# machine epsilon of each COMPUTE dtype's own representation (the
# input-rounding term of a demoted or reduced-precision kernel) — the
# companion of _ACC_EPS, which maps bf16 to its f32 ACCUMULATION
# epsilon instead.  Stdlib-only like everything in this module.
_COMPUTE_EPS = {
    "float64": 2.220446049250313e-16,
    "complex128": 2.220446049250313e-16,
    "float32": 1.1920929e-07,
    "complex64": 1.1920929e-07,
    "bfloat16": 2.0 ** -8,
    "float16": 2.0 ** -11,
}


def effective_epsilon(compute: str, compensated: bool) -> float:
    """Effective per-product relative rounding of a DEMOTED compute
    scheme: the compute dtype's own epsilon, or — under two-product
    compensation (the hi/lo split of `acc.smm`, which restores every
    cross term and drops only lo·lo plus the split residue) — its
    square, with a x4 margin for the three extra roundings the
    compensated recombination performs."""
    eps = _COMPUTE_EPS.get(str(compute), 2.0 ** -8)
    return 4.0 * eps * eps if compensated else eps


def abft_tolerance(dtype: str, k: int, depth: int) -> float:
    """Relative tolerance of an ABFT probe-checksum comparison: the
    rank-1 probe ``C·v`` vs ``A·(B·v)`` evaluates the same bilinear
    form along two association orders, so the legitimate disagreement
    is pure rounding — bounded by the accumulation dtype's epsilon
    times the reduction lengths (``k`` inner-product terms per entry,
    ``depth`` entries accumulated per C segment).  The constant is an
    engineering margin (false positives trigger a failover walk, far
    more expensive than a slightly blunter detector); injected/real SDC
    perturbs O(1) values, orders of magnitude above this floor."""
    eps = _ACC_EPS.get(str(dtype), 1.1920929e-07)
    k = max(int(k), 1)
    depth = max(int(depth), 1)
    return 64.0 * eps * (k + 1) * float(depth + 1) ** 0.5


def demoted_abft_tolerance(dtype: str, compute: str, compensated: bool,
                           k: int, depth: int) -> float:
    """Probe ceiling of a launch executed at a DEMOTED compute dtype:
    the per-product demotion error is relative to each product term,
    and the probe's comparison scale already bounds the sum of |terms|
    (the S_c scale of the beta==0 probe form, the max-|p| scale of the
    delta form), so the demotion term is the effective compute epsilon
    times the same x64 engineering margin as the native tolerance —
    the (k, depth) reduction factors are NOT re-applied to it (they
    are absorbed by the scale).  EXCEPT: the uncompensated kernel
    accumulates INSIDE the dot at the compute family's natural narrow
    accumulator (`acc.smm._batch_dot`), and a ``k``-deep narrow sum
    legitimately contributes up to ~k*eps_acc relative to sum|terms| —
    callers pass the MERGED contraction length (r0*k for the k-merged
    xla_group layout), or the ceiling would condemn healthy grouped
    launches.  The native accumulation tolerance floors the result (a
    demoted launch can never be held to a tighter bound than a native
    one)."""
    tol = 64.0 * effective_epsilon(compute, compensated)
    if not compensated:
        acc_eps = _ACC_EPS.get(str(compute), 1.1920929e-07)
        tol += 8.0 * acc_eps * max(int(k), 1)
    return max(tol, abft_tolerance(dtype, k, depth))


def kernel_validation_tolerance(dtype: str, k: int, depth: int) -> float:
    """Relative tolerance of a kernel-vs-host-oracle ELEMENTWISE-max
    validation (the first-use Pallas gate in
    `acc.smm._validate_pallas_kernel` and its test-suite mirrors): an
    accumulation term ~eps_acc*sqrt((k+1)*(depth+1)) for the k-deep
    dot times depth-deep segment sum, plus an input-rounding term for
    dtypes whose own epsilon exceeds their accumulation epsilon (bf16
    inputs round at 2^-8 while accumulating in f32) — one dtype-aware
    source of truth replacing the historical `5e-2 if bf16 else 1e-5`
    literals.  Deliberately NOT `abft_tolerance`: that bound carries
    the probe comparison's x64 margin and scale-absorption reasoning,
    which would loosen this elementwise gate ~100x and let a subtly
    miscompiled kernel through first-use validation."""
    eps_acc = _ACC_EPS.get(str(dtype), 1.1920929e-07)
    eps_in = _COMPUTE_EPS.get(str(dtype), eps_acc)
    k = max(int(k), 1)
    depth = max(int(depth), 1)
    return max(2.0 * eps_acc * float((k + 1) * (depth + 1)) ** 0.5,
               4.0 * eps_in * float(k + 1) ** 0.5)


# ------------------------------------------------------- roofline table

# Per-device_kind peaks.  Matching is by lowercase substring of
# jax's `device.device_kind` ("TPU v5 lite", "TPU v4", "cpu", ...).
# "gflops" is peak compute per chip per dtype; f64/c128 entries model
# the EMULATED split-f32/bf16 passes on TPU (no native f64 unit).
# "gbs" is HBM bandwidth, "ici_gbs" per-device interconnect bandwidth
# (the Cannon ring rides ICI).  All are engineering estimates meant to
# anchor a fraction-of-peak signal, not vendor benchmarks — override
# via DBCSR_TPU_ROOFLINE / DBCSR_TPU_PEAK_* for calibrated numbers.
_PEAKS: dict = {
    "tpu v6": {"gflops": {"bfloat16": 918000.0, "float32": 229000.0,
                          "float64": 7000.0},
               "gbs": 1640.0, "ici_gbs": 448.0},
    "tpu v5p": {"gflops": {"bfloat16": 459000.0, "float32": 115000.0,
                           "float64": 5000.0},
                "gbs": 2765.0, "ici_gbs": 600.0},
    "tpu v5 lite": {"gflops": {"bfloat16": 197000.0, "float32": 49000.0,
                               "float64": 3000.0},
                    "gbs": 819.0, "ici_gbs": 200.0},
    "tpu v4": {"gflops": {"bfloat16": 275000.0, "float32": 69000.0,
                          "float64": 4000.0},
               "gbs": 1228.0, "ici_gbs": 300.0},
    # the CI container: one CPU core through XLA-CPU (BASELINE.md's
    # committed north-star engine number is ~3 GFLOP/s f64)
    "cpu": {"gflops": {"bfloat16": 100.0, "float32": 100.0,
                       "float64": 50.0},
            "gbs": 20.0, "ici_gbs": 20.0},
}
_DEFAULT_PEAK = {"gflops": {"float64": 100.0, "float32": 200.0,
                            "bfloat16": 200.0},
                 "gbs": 100.0, "ici_gbs": 100.0}

_env_table = None  # parsed DBCSR_TPU_ROOFLINE, cached


def _env_overrides() -> dict:
    global _env_table
    if _env_table is None:
        raw = os.environ.get("DBCSR_TPU_ROOFLINE", "")
        try:
            _env_table = json.loads(raw) if raw else {}
        except ValueError:
            _env_table = {}
    return _env_table


def device_kind() -> str:
    """Best-effort `device_kind` of the default device.  Never forces
    backend initialization (same guard as `obs.tracer._process_index`):
    before any jax work has run it reports "unknown"."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return "unknown"
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None or not getattr(xb, "_backends", None):
        return "unknown"
    try:
        return str(jax.devices()[0].device_kind)
    except Exception:
        return "unknown"


def _longest_match(table: dict, kind: str) -> str | None:
    return max((key for key in table if key in kind), key=len, default=None)


def peaks_key(kind: str | None = None) -> str | None:
    """The `_PEAKS` row a device kind selects (longest lowercase
    substring match), or None for a kind no row names."""
    return _longest_match(_PEAKS, (kind or device_kind()).lower())


def peaks_for(kind: str | None = None) -> dict:
    """Peak entry for a device kind: longest-matching table row, with
    env overrides folded in.  A TPU kind no row names is an error — the
    format planner's cost curves read these, and a guessed peak would
    steer them silently; other unknown kinds (a backend not yet
    initialized reports "unknown") get the conservative generic entry."""
    kind = (kind or device_kind()).lower()
    table = dict(_PEAKS)
    for key, row in _env_overrides().items():
        base = dict(table.get(key.lower(), _DEFAULT_PEAK))
        gf = dict(base.get("gflops", {}))
        gf.update(row.get("gflops", {}))
        base.update(row)
        base["gflops"] = gf
        table[key.lower()] = base
    best = _longest_match(table, kind)
    if best is None and "tpu" in kind:
        raise KeyError(
            f"no peaks row for TPU device kind {kind!r}: add it to "
            f"obs.costmodel._PEAKS (have {sorted(_PEAKS)})")
    entry = dict(table[best] if best else _DEFAULT_PEAK)
    env_gf = os.environ.get("DBCSR_TPU_PEAK_GFLOPS")
    if env_gf:
        entry["gflops"] = {d: float(env_gf) for d in
                           set(entry["gflops"]) | {"float64", "float32"}}
    env_bw = os.environ.get("DBCSR_TPU_PEAK_GBS")
    if env_bw:
        entry["gbs"] = float(env_bw)
    env_ici = os.environ.get("DBCSR_TPU_ICI_GBS")
    if env_ici:
        entry["ici_gbs"] = float(env_ici)
    return entry


def peak_gflops(kind: str | None = None, dtype: str = "float64") -> float:
    """Peak compute for a dtype on a device kind.  Complex dtypes map
    to their real component peak / 4 (a complex MAC is 4 real MACs;
    the engine counts 2*m*n*k 'entry flops' regardless of dtype)."""
    entry = peaks_for(kind)
    gf = entry["gflops"]
    dtype = str(dtype)
    if dtype in gf:
        return float(gf[dtype])
    if dtype == "complex64":
        return float(gf.get("float32", _DEFAULT_PEAK["gflops"]["float32"])) / 4
    if dtype == "complex128":
        return float(gf.get("float64", _DEFAULT_PEAK["gflops"]["float64"])) / 4
    if dtype == "float16":
        return float(gf.get("bfloat16", gf.get("float32", 100.0)))
    return float(gf.get("float32", _DEFAULT_PEAK["gflops"]["float32"]))


def roofline(flops: float, nbytes: float, seconds: float,
             kind: str | None = None, dtype: str = "float64") -> dict:
    """Roofline attribution of one measured region: achieved GFLOP/s,
    arithmetic intensity, the attainable rate at that intensity
    (``min(peak_compute, intensity * peak_bandwidth)``), and the
    achieved fraction of it."""
    kind = kind or device_kind()
    entry = peaks_for(kind)
    peak = peak_gflops(kind, dtype)
    inten = intensity(flops, nbytes)
    attainable = min(peak, inten * entry["gbs"]) if nbytes else peak
    achieved = flops / seconds / 1e9 if seconds > 0 else 0.0
    return {
        "device_kind": kind,
        "dtype": str(dtype),
        "achieved_gflops": achieved,
        "arithmetic_intensity": inten,
        "peak_gflops": peak,
        "peak_gbs": entry["gbs"],
        "attainable_gflops": attainable,
        "roofline_fraction": achieved / attainable if attainable else 0.0,
        "bytes_moved": int(nbytes),
        "flops": int(flops),
        "seconds": seconds,
    }


def _tick_balance(flops: float, comm_bytes: float, dtype: str,
                  kind: str | None) -> dict:
    """Comm/compute balance of one metronome tick against the roofline
    peaks: ``overlap_ratio`` = modeled comm time / compute time — below
    1.0 the collective hides fully behind the local contraction (the
    comm-thread overlap the reference gets from USE_COMM_THREAD)."""
    kind = kind or device_kind()
    peak = peak_gflops(kind, dtype) * 1e9
    ici = peaks_for(kind)["ici_gbs"] * 1e9
    t_comp = flops / peak if peak else 0.0
    t_comm = comm_bytes / ici if ici else 0.0
    return {
        "tick_flops": int(flops),
        "tick_comm_bytes": int(comm_bytes),
        "t_compute_s": t_comp,
        "t_comm_s": t_comm,
        "overlap_ratio": (t_comm / t_comp) if t_comp > 0 else 0.0,
    }


def cannon_tick_model(m: int, n: int, k: int, kl: int, s: int,
                      itemsize: int, dtype: str,
                      kind: str | None = None) -> dict:
    """Per-device, per-tick comm/compute balance of the dense Cannon:
    each metronome tick contracts a local (m/s, k/(kl*s)) x
    (k/(kl*s), n/s) panel while ring-shifting both operand shards over
    ICI."""
    m_loc, n_loc, k_loc = m / s, n / s, k / (kl * s)
    flops = 2.0 * m_loc * n_loc * k_loc
    comm_bytes = (m_loc * k_loc + k_loc * n_loc) * itemsize
    return _tick_balance(flops, comm_bytes, dtype, kind)


def mesh_tick_model(cap_a: int, cap_b: int, bm: int, bk: int, bn: int,
                    entries: int, nticks: int, ndev: int,
                    itemsize: int, dtype: str,
                    kind: str | None = None) -> dict:
    """Per-device, per-tick comm/compute balance of the block-sparse
    mesh Cannon: each tick ring-shifts a full padded A panel
    (``cap_a`` blocks of (bm, bk)) and B panel (``cap_b`` of (bk, bn))
    while contracting this tick's share of the symbolic product's
    ``entries`` (true flops split evenly over devices x ticks — the
    stack fill balances by construction)."""
    flops = 2.0 * bm * bn * bk * entries / max(ndev * nticks, 1)
    comm_bytes = (cap_a * bm * bk + cap_b * bk * bn) * itemsize
    return _tick_balance(flops, comm_bytes, dtype, kind)


def gather_chunk_model(cap_a: int, cap_b: int, bm: int, bk: int, bn: int,
                       entries: int, nticks: int, ndev: int,
                       itemsize: int, dtype: str,
                       kind: str | None = None) -> dict:
    """Per-device, per-chunk comm/compute balance of the CHUNKED
    all-gather pipeline on rectangular grids: each of the ``nticks``
    ring steps moves one padded A shard (``cap_a`` blocks of (bm, bk))
    and one B shard over ICI while the tick contracts its
    shard-arrival share of the product's ``entries`` (the same shard
    pair per step a Cannon tick ring-shifts — `mesh_tick_model`'s
    balance applied to the gather schedule, so the two routes share
    one gauge family)."""
    return mesh_tick_model(cap_a, cap_b, bm, bk, bn, entries, nticks,
                           ndev, itemsize, dtype, kind)


# ------------------------------------------------------- XLA cross-check

_xla_costs: dict = {}  # fn -> {key_str: {model + xla numbers}}
_capture = None  # resolved lazily from env; enable_xla_capture overrides


def xla_capture_enabled() -> bool:
    global _capture
    if _capture is None:
        _capture = os.environ.get("DBCSR_TPU_XLA_COST", "").lower() in (
            "1", "true", "yes")
    return _capture


def enable_xla_capture(on: bool = True) -> None:
    """Programmatic toggle for the per-specialization XLA cost capture
    (the env knob is ``DBCSR_TPU_XLA_COST=1``)."""
    global _capture
    _capture = bool(on)


def capture_xla_cost(fn_name: str, key, jit_fn, args, *,
                     kwargs: dict | None = None,
                     model: dict | None = None) -> dict | None:
    """Capture XLA's own cost/memory analysis for one fresh jit
    specialization, storing it next to the analytic ``model`` numbers.

    Costs one extra AOT ``lower().compile()`` of the same computation
    (the dispatch-path cache is separate), so call sites gate on
    `xla_capture_enabled()` AND on `metrics.record_jit` returning True
    — once per specialization, never on the steady-state path.
    Best-effort: any backend/API failure records nothing."""
    try:
        compiled = jit_fn.lower(*args, **(kwargs or {})).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        rec = {
            "xla_flops": float(ca.get("flops", 0.0)),
            "xla_bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
        try:
            ma = compiled.memory_analysis()
            rec["xla_argument_bytes"] = int(
                getattr(ma, "argument_size_in_bytes", 0))
            rec["xla_output_bytes"] = int(
                getattr(ma, "output_size_in_bytes", 0))
            rec["xla_temp_bytes"] = int(
                getattr(ma, "temp_size_in_bytes", 0))
        except Exception:
            pass
        if model:
            rec["model"] = dict(model)
            if model.get("flops") and rec["xla_flops"]:
                rec["flops_ratio"] = rec["xla_flops"] / model["flops"]
        _xla_costs.setdefault(fn_name, {})[str(key)] = rec
        return rec
    except Exception:
        return None


def xla_costs() -> dict:
    """{fn: {specialization_key: {model vs XLA numbers}}} for every
    capture since the last `reset()`."""
    return {fn: dict(d) for fn, d in _xla_costs.items()}


def reset() -> None:
    _xla_costs.clear()
