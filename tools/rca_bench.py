#!/usr/bin/env python
"""Causal-diagnosis certification: deliberate regressions, diagnosed.

Exercises the diagnosis plane (`dbcsr_tpu.obs.{profiler,changepoint,
rca}`) END TO END against regressions whose true cause is known by
construction, then certifies that the plane's steady-state hot-path
cost is inside the <1% budget:

* **mistuned_params_row** — a steady workload dispatching through the
  fast native-host driver gets a deliberately bad row promoted into
  the (hermetic) params table via the REAL promotion path
  (`tune.store.promote`), steering its cell onto the ~7x-slower XLA
  group driver.  The latency change-point must fire and the ranked
  causal report must name ``tune_promotion`` top-1 **with the exact
  promoted generation**.

* **mis_crossover_format** — the same plane, different cause class: a
  low-occupancy workload the planner serves from stacked blocks is
  forced whole-panel dense mid-run through the ``DBCSR_TPU_MM_FORMAT``
  knob.  The report must name ``knob_change`` top-1 and identify the
  knob by name.

Both injections also check that the continuous profile baseline's
``diff_around`` localizes the regression to a phase row (the flight
phases the regressed driver/format actually moved).

* **overhead** — the identical steady workload with the plane OFF
  (baseline) vs ON (candidate), multiplies/s, gated by
  ``tools/perf_gate.gate`` at ``rel_tol=0.01``: diagnosis must cost
  under 1% of hot-path throughput (beyond measured noise).

Hermetic: params table in a temp dir, telemetry sampling forced to
every product boundary, no obs server.  The output certificate
(``--out``, default RCA_CERT.json at the repo root) is what
``tools/doctor.py --diagnose`` renders in artifact mode; exit 0 iff
every injection names its true cause top-1 AND the overhead gate
passes.

Usage: python tools/rca_bench.py [--nblk 12] [--reps 16] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU-only by design (the delta_bench convention): the committed cert
# is the CPU control; on a real TPU the same injections recertify.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# hermetic params table: the deliberately-bad promotion must never
# land in the user's real table
os.environ.setdefault("DBCSR_TPU_PARAMS_DIR",
                      tempfile.mkdtemp(prefix="rca_bench_params_"))
# sample at every product boundary: the change-point must resolve the
# injection instant to one multiply, not one 10 s cadence window
os.environ.setdefault("DBCSR_TPU_TS_INTERVAL_S", "0")
# short reference window + small profile epochs: the bench runs tens
# of multiplies, not thousands
os.environ.setdefault("DBCSR_TPU_CP_REF_N", "8")
os.environ.setdefault("DBCSR_TPU_PROFILE_EPOCH_N", "8")


def _build_pair(nblk: int, bsize: int, occ: float, seed: int):
    """A, B at one block size/occupancy: a random block pattern."""
    import numpy as np

    import dbcsr_tpu as dt

    rng = np.random.default_rng(seed)
    bs = [bsize] * nblk
    pattern = [(i, j) for i in range(nblk) for j in range(nblk)
               if rng.random() < occ] or [(0, 0)]

    def _fill(name):
        m = dt.create(name, bs, bs)
        rows = np.asarray([i for i, j in pattern], dtype=np.int64)
        cols = np.asarray([j for i, j in pattern], dtype=np.int64)
        blocks = rng.integers(-4, 5, size=(len(pattern), bsize, bsize)
                              ).astype(np.float64)
        m.put_blocks(rows, cols, blocks)
        m.finalize()
        return m

    return _fill("rcaA"), _fill("rcaB"), bs


def _sync(c) -> None:
    try:
        import jax

        for bn_ in getattr(c, "bins", ()):
            if getattr(bn_, "count", 0) and \
                    hasattr(bn_.data, "block_until_ready"):
                jax.block_until_ready(bn_.data)
    except Exception:
        pass


def _run(a, b, bs, reps: int) -> float:
    """reps multiplies; returns total wall seconds."""
    import dbcsr_tpu as dt

    t0 = time.perf_counter()
    for _ in range(reps):
        c = dt.create("rcaC", bs, bs)
        dt.multiply("N", "N", 1.0, a, b, 0.0, c)
        _sync(c)
    return time.perf_counter() - t0


def _fresh_plane() -> None:
    """Reset every diagnosis-plane ring between injections so each
    report attributes ONLY its own regression."""
    from dbcsr_tpu.mm import format_planner as fp
    from dbcsr_tpu.obs import metrics

    metrics.reset(include_stats=True)
    fp.reset()


def _latest_report() -> dict | None:
    from dbcsr_tpu.obs import rca

    reps = rca.reports(limit=1)
    return reps[-1] if reps else None


def _profile_top(report: dict | None) -> dict | None:
    diff = (report or {}).get("profile_diff") or {}
    return diff.get("top") if diff.get("ok") else None


def inject_mistuned_row(nblk: int, reps: int) -> dict:
    """Promote a deliberately bad driver row for the live cell and
    demand the causal report convicts that exact promotion."""
    from dbcsr_tpu.core.config import get_config
    from dbcsr_tpu.tune import store

    _fresh_plane()
    a, b, bs = _build_pair(nblk, bsize=16, occ=0.6, seed=11)
    base_s = _run(a, b, bs, reps)

    bad = {
        "m": 16, "n": 16, "k": 16, "dtype": "float64",
        "driver": "xla_group", "r0": 8, "gflops": 9999.0,
        "stack_size": get_config().mm_stack_size,
        # "onchip" provenance so predict() trusts the row outright —
        # exactly the failure mode of a miscalibrated tuner
        "env": "onchip",
    }
    ledger_rec = store.promote(bad, trial={"note": "rca_bench injection"})
    gen = int(ledger_rec["generation"])

    regressed_s = _run(a, b, bs, reps)

    report = _latest_report()
    causes = (report or {}).get("causes") or []
    top = causes[0] if causes else {}
    ok = bool(report) \
        and report.get("top_cause") == "tune_promotion" \
        and int(top.get("generation") or -1) == gen
    # undo: the displaced (empty) incumbent comes back, generation
    # bumps again, later injections see a clean table
    store.demote(16, 16, 16, "float64", bad["stack_size"],
                 reason="rca_bench cleanup")
    return {
        "name": "mistuned_params_row",
        "expected_kind": "tune_promotion",
        "expected_generation": gen,
        "top_cause": (report or {}).get("top_cause"),
        "top_cause_generation": top.get("generation"),
        "baseline_s": round(base_s, 4),
        "regressed_s": round(regressed_s, 4),
        "slowdown": round(regressed_s / base_s, 2) if base_s else None,
        "profile_top": _profile_top(report),
        "ok": ok,
        "report": report,
    }


def inject_format_knob(nblk: int, reps: int) -> dict:
    """Flip DBCSR_TPU_MM_FORMAT to whole-panel dense on a low-occupancy
    workload and demand the report convicts the knob by name."""
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.mm import format_planner as fp

    _fresh_plane()
    # different block size from injection A: nearest-row prediction
    # must not resurrect A's (demoted) cell here
    a, b, bs = _build_pair(nblk, bsize=8, occ=0.12, seed=23)
    base_s = _run(a, b, bs, reps)

    prev_env = os.environ.get("DBCSR_TPU_MM_FORMAT")
    os.environ["DBCSR_TPU_MM_FORMAT"] = "dense"
    set_config(mm_format="dense")
    fp.reset()  # retire the planner's cached (stack) plans
    try:
        regressed_s = _run(a, b, bs, reps)
        report = _latest_report()
        causes = (report or {}).get("causes") or []
        top = causes[0] if causes else {}
        ok = bool(report) \
            and report.get("top_cause") == "knob_change" \
            and top.get("knob") == "DBCSR_TPU_MM_FORMAT" \
            and top.get("value") == "dense"
    finally:
        if prev_env is None:
            os.environ.pop("DBCSR_TPU_MM_FORMAT", None)
        else:
            os.environ["DBCSR_TPU_MM_FORMAT"] = prev_env
        set_config(mm_format="auto")
        fp.reset()
    return {
        "name": "mis_crossover_format",
        "expected_kind": "knob_change",
        "expected_knob": "DBCSR_TPU_MM_FORMAT",
        "top_cause": (report or {}).get("top_cause"),
        "top_cause_knob": top.get("knob"),
        "baseline_s": round(base_s, 4),
        "regressed_s": round(regressed_s, 4),
        "slowdown": round(regressed_s / base_s, 2) if base_s else None,
        "profile_top": _profile_top(report),
        "ok": ok,
        "report": report,
    }


def measure_overhead(nblk: int, reps: int, legs: int = 12) -> dict:
    """Plane OFF vs ON on the identical steady workload, perf-gated at
    1%: always-on diagnosis must be free at hot-path granularity.

    Measured at the PRODUCTION cadences (10 s telemetry sampling, 64
    multiplies per profile epoch), not the bench's forensic settings:
    the injections force every-product sampling and tiny epochs to pin
    change-points to a single multiply, but steady state pays only the
    per-multiply profile fold plus the ledger's event-bus tap — the
    per-sample scan and the epoch seal amortize across their windows.
    The headline fraction compares MIN wall per leg across interleaved
    legs (the noise-robust CPU estimator — scheduler dips only ever
    inflate a wall, never deflate it); the perf gate itself runs on
    the full per-leg throughput samples, so measured noise widens its
    threshold honestly."""
    from dbcsr_tpu.obs import changepoint, profiler, rca

    import perf_gate

    prev = {k: os.environ.get(k)
            for k in ("DBCSR_TPU_TS_INTERVAL_S",
                      "DBCSR_TPU_PROFILE_EPOCH_N")}
    os.environ["DBCSR_TPU_TS_INTERVAL_S"] = "10"
    os.environ["DBCSR_TPU_PROFILE_EPOCH_N"] = "64"
    profiler.reset()  # pick up the production epoch cadence
    # heavier blocks than the injection workloads: the plane's fixed
    # ~10 us/multiply fold must be charged against a REPRESENTATIVE
    # ms-scale multiply, not a toy one where it reads as percents
    a, b, bs = _build_pair(nblk + 8, bsize=32, occ=0.6, seed=31)
    _run(a, b, bs, 2)  # warm compile caches (untimed)

    def _leg(on: bool) -> float:
        for mod in (profiler, changepoint, rca):
            mod.set_enabled(on)
        try:
            return _run(a, b, bs, reps) / reps  # wall s/multiply
        finally:
            for mod in (profiler, changepoint, rca):
                mod.set_enabled(True)

    off_walls, on_walls = [], []
    for _ in range(legs):  # interleaved: drift hits both legs alike
        off_walls.append(_leg(False))
        on_walls.append(_leg(True))

    for k, v in prev.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    profiler.reset()
    gate = perf_gate.gate(
        [{"metric": "rca_plane_steady_state", "value": 1.0 / w}
         for w in off_walls],
        [{"metric": "rca_plane_steady_state", "value": 1.0 / w}
         for w in on_walls],
        rel_tol=0.01, gate_on="value")
    off_w, on_w = min(off_walls), min(on_walls)
    return {
        "metric": "rca_plane_steady_state",
        "off_ms_per_multiply": round(off_w * 1e3, 4),
        "on_ms_per_multiply": round(on_w * 1e3, 4),
        "overhead_frac": round(max(0.0, on_w / off_w - 1.0), 4),
        "rel_tol": 0.01,
        "legs": legs,
        "gate": "PASS" if gate.get("exit_code") == 0 else "FAIL",
        "gate_report": gate,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--nblk", type=int, default=12,
                    help="blocks per matrix dimension (default 12)")
    ap.add_argument("--reps", type=int, default=16,
                    help="multiplies per workload phase (default 16)")
    ap.add_argument("--skip-overhead", action="store_true",
                    help="injections only (fast iteration)")
    ap.add_argument("--out",
                    help="certificate path (default RCA_CERT.json at "
                         "the repo root)")
    args = ap.parse_args(argv)

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_path = args.out or os.path.join(repo_root, "RCA_CERT.json")

    from dbcsr_tpu.acc import params as params_mod
    from dbcsr_tpu import obs

    injections = [
        inject_mistuned_row(args.nblk, args.reps),
        inject_format_knob(args.nblk, args.reps),
    ]
    for inj in injections:
        top = inj.get("profile_top") or {}
        print(f" {inj['name']}: top_cause={inj['top_cause']} "
              f"slowdown=x{inj['slowdown']} "
              f"phase={top.get('driver', '?')}|{top.get('phase', '?')} "
              f"-> {'OK' if inj['ok'] else 'FAIL'}")

    overhead = None
    if not args.skip_overhead:
        _fresh_plane()
        overhead = measure_overhead(args.nblk, max(args.reps, 24))
        print(f" overhead: off={overhead['off_ms_per_multiply']}ms "
              f"on={overhead['on_ms_per_multiply']}ms per multiply, "
              f"frac={overhead['overhead_frac']} "
              f"gate={overhead['gate']}")

    ok = all(inj["ok"] for inj in injections) \
        and (overhead is None or overhead["gate"] == "PASS")
    cert = {
        "schema": obs.OBS_SCHEMA_VERSION,
        "bench": "rca_bench",
        "t_unix": time.time(),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "device_kind": params_mod.device_kind(),
        "nblk": args.nblk,
        "reps": args.reps,
        "injections": injections,
        "overhead": overhead,
        "ok": ok,
    }
    with open(out_path, "w") as fh:
        json.dump(cert, fh, indent=1, default=str)
        fh.write("\n")
    print(f" certificate: {out_path}  ok={ok}")
    print(json.dumps({"bench": "rca_bench", "ok": ok,
                      "injections": [
                          {k: inj[k] for k in
                           ("name", "top_cause", "slowdown", "ok")}
                          for inj in injections],
                      "overhead": {k: overhead[k] for k in
                                   ("off_ms_per_multiply",
                                    "on_ms_per_multiply",
                                    "overhead_frac", "gate")}
                      if overhead else None}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
