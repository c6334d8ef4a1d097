#!/usr/bin/env python
"""Online-autotuner A/B: static mistuned table vs tuner-promoted row.

Leg pair (the tier-2.14 committed evidence, perf_gate-gated):

* ``static`` — a block-sparse multiply workload dispatched against a
  parameter table holding a deliberately MISTUNED row for the
  workload's (m, n, k, f64) cell (driver ``xla_group`` at a bad
  grouping — a plausible stale row from another environment);
* ``tuned`` — the SAME workload after one real closed-loop tuner pass:
  the telemetry store samples the static leg, `tune.miner` mines the
  cell from the live roofline series, `tune.service` runs a bounded
  trial and PROMOTES the breaker-aware winner through the store (the
  params generation bumps, retiring the static leg's cached plans).

The legs run the identical sequence (same seeds, same matrices).  The
operand blocks are INTEGER-VALUED, so every candidate driver's f64
accumulation is exact and the final C is **bitwise identical** across
the legs whatever row dispatch picks up — asserted per iteration (exit
1 on mismatch); this is what makes a cross-driver speed A/B honestly
checksum-pinnable.  ``value`` is the leg's true-flop GFLOP/s.

The output JSON (last stdout line) is a perf_gate-compatible capture
row with both legs under ``ab``.  The whole run uses a
TEMPORARY params dir — the committed device tables are never touched.

Usage: python tools/tune_bench.py [--nblk 12] [--bsize 23] [--occ 0.5]
           [--iters 6] [--seed 7]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU-only by design: the committed A/B row is the CPU control — the
# mine -> trial -> promote loop and the dispatch steering it proves are
# real scheduling properties on this world too.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# bounded trial: clamp the sweep stack so the whole closed loop stays
# inside a CI-friendly budget (the knobs under test, not a bypass)
os.environ.setdefault("DBCSR_TPU_TUNE_BUDGET_BYTES", str(16 << 20))
os.environ.setdefault("DBCSR_TPU_TUNE_NREP", "2")


def _sync(mat) -> None:
    import jax

    for b in getattr(mat, "bins", ()):
        if getattr(b, "count", 0) and hasattr(b.data, "block_until_ready"):
            jax.block_until_ready(b.data)


def _make_workload(nblk: int, bsize: int, occ: float, seed: int):
    """Integer-valued A, B (exact f64 accumulation under ANY driver /
    grouping — the bitwise contract's foundation) and an empty C."""
    import numpy as np

    import dbcsr_tpu as dt
    from dbcsr_tpu.ops.test_methods import make_random_matrix

    bs = [bsize] * nblk
    a = make_random_matrix("A", bs, bs, occupation=occ,
                           rng=np.random.default_rng(seed))
    b = make_random_matrix("B", bs, bs, occupation=occ,
                           rng=np.random.default_rng(seed + 1))
    for mat in (a, b):
        mat.map_bin_data(lambda d: __import__("numpy").trunc(d * 4.0))
    c = dt.create("C", bs, bs)
    return a, b, c


def run_leg(name: str, a, b, c, iters: int):
    """Warm twice (compile + plan caches), then time ``iters`` reps.
    Returns (walls, digests, flops_per_product)."""
    import numpy as np

    import dbcsr_tpu as dt
    from dbcsr_tpu.ops.test_methods import to_dense

    flops = 0
    for _ in range(2):
        flops = max(flops, dt.multiply("N", "N", 1.0, a, b, 0.0, c))
    _sync(c)
    walls, digests = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        dt.multiply("N", "N", 1.0, a, b, 0.0, c)
        _sync(c)
        walls.append(time.perf_counter() - t0)
        digests.append(hashlib.sha1(
            np.ascontiguousarray(np.asarray(to_dense(c))).tobytes()
        ).hexdigest())
    return walls, digests, int(flops)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nblk", type=int, default=12)
    ap.add_argument("--bsize", type=int, default=23)
    ap.add_argument("--occ", type=float, default=0.5)
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)

    import numpy as np  # noqa: F401

    from dbcsr_tpu.acc import params as params_mod
    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.obs import OBS_SCHEMA_VERSION, costmodel
    from dbcsr_tpu.obs import timeseries as ts
    from dbcsr_tpu.tune import miner
    from dbcsr_tpu.tune import service as tune_service

    m = args.bsize
    stack_key = int(get_config().mm_stack_size)
    prev_params_dir = os.environ.get("DBCSR_TPU_PARAMS_DIR")
    prev_driver = get_config().mm_driver
    prev_inc = get_config().incremental
    # auto: the tuned row must be what steers.  incremental=full: a
    # repeated identical product is otherwise served by the delta
    # plane's cached C (zero kernel work — the tier-2.13 axis), which
    # would hide the kernel-parameter axis this A/B measures
    set_config(mm_driver="auto", incremental="full")
    tmpdir = tempfile.mkdtemp(prefix="tune_bench_params_")
    os.environ["DBCSR_TPU_PARAMS_DIR"] = tmpdir
    params_mod.invalidate()
    try:
        # the deliberately mistuned static row: xla_group at r0=4 for a
        # cell this CPU runs much faster elsewhere (a stale row's
        # claimed rate rides along; the service's promotion bar is the
        # LIVE observed rate, so the claim cannot defend the row)
        params_mod.save_entry({
            "m": m, "n": m, "k": m, "dtype": "float64",
            "stack_size": stack_key, "driver": "xla_group", "r0": 4,
            "grouping": None, "gflops": 1.0, "env": "cpu"})

        a, b, c = _make_workload(args.nblk, args.bsize, args.occ,
                                 args.seed)
        ts.set_enabled(True)
        walls_s, digests_s, flops = run_leg("static", a, b, c,
                                            args.iters)
        ts.sample(reason="tune_bench_static")

        # mine the cell from the LIVE telemetry (no capture files: the
        # committed artifacts must not leak into the hermetic A/B)
        cells = [cl for cl in miner.mine(query=ts.query,
                                         capture_paths=[])
                 if (cl["m"], cl["n"], cl["k"]) == (m, m, m)]
        mined = bool(cells)
        if not mined:
            # the floor criterion depends on the host's peak table; if
            # this world's fraction sits above the floor, surface the
            # donor-estimate criterion by restating the row's claim at
            # the observed shortfall — logged, never silent
            print("tune_bench: cell not mined via roofline floor; "
                  "falling back to donor-estimate criterion",
                  file=sys.stderr)
            obs_rate = flops / min(walls_s) / 1e9
            params_mod.save_entry({
                "m": m, "n": m, "k": m, "dtype": "float64",
                "stack_size": stack_key, "driver": "xla_group", "r0": 4,
                "grouping": None, "gflops": round(obs_rate * 4, 3),
                "env": "cpu"})
            ts.sample(reason="tune_bench_remine")
            cells = [cl for cl in miner.mine(query=ts.query,
                                             capture_paths=[])
                     if (cl["m"], cl["n"], cl["k"]) == (m, m, m)]
        if not cells:
            print("FAIL: miner never surfaced the mistuned cell",
                  file=sys.stderr)
            return 1

        svc = tune_service.TuneService(interval_s=3600,
                                       seed=args.seed)
        gen0 = params_mod.generation()
        out = svc.cycle(cells=cells)
        print(f"  tuner cycle: {out['outcome']} "
              f"promoted={out.get('promoted')}", file=sys.stderr)
        if out.get("outcome") != "promoted":
            print(f"FAIL: tuner did not promote ({out})",
                  file=sys.stderr)
            return 1
        gen1 = params_mod.generation()

        walls_t, digests_t, _ = run_leg("tuned", a, b, c, args.iters)
        ts.sample(reason="tune_bench_tuned")
        promoted_row = params_mod.lookup(m, m, m, "float64",
                                         stack_size=stack_key)
    finally:
        set_config(mm_driver=prev_driver, incremental=prev_inc)
        if prev_params_dir is None:
            os.environ.pop("DBCSR_TPU_PARAMS_DIR", None)
        else:
            os.environ["DBCSR_TPU_PARAMS_DIR"] = prev_params_dir
        params_mod.invalidate()
        import shutil

        shutil.rmtree(tmpdir, ignore_errors=True)

    bitwise = digests_s == digests_t
    kind = costmodel.device_kind()
    stamps = {
        "unit": "GFLOP/s",
        "device": str(jax.devices()[0]),
        "device_fallback": jax.devices()[0].platform == "cpu",
        "device_kind": kind,
        "jax_version": jax.__version__,
        "obs_schema": OBS_SCHEMA_VERSION,
    }
    side = args.nblk * args.bsize
    metric = (f"tune_ab GFLOP/s ({side}^2 BCSR, "
              f"{args.bsize}x{args.bsize} blocks, occ={args.occ}, f64, "
              f"mistuned xla_group r0=4 vs tuner-promoted)")
    legs = {}
    for name, walls in (("static", walls_s), ("tuned", walls_t)):
        legs[name] = dict(
            stamps,
            metric=metric,
            value=round(flops / min(walls) / 1e9, 6),
            table=name,
            mm_driver="auto",
            iters=args.iters,
            true_flops=flops,
            wall_s=round(sum(walls), 6),
            wall_min_s=round(min(walls), 6),
        )
    speedup = min(walls_s) / min(walls_t) if min(walls_t) else 0.0
    for name, leg in legs.items():
        print(f"  {name:>7}: {leg['value']} GFLOP/s "
              f"(min {leg['wall_min_s']} s)", file=sys.stderr)
    row = dict(
        stamps,
        metric=metric,
        value=legs["tuned"]["value"],
        table="tuned",
        mm_driver="auto",
        speedup_tuned=round(float(speedup), 4),
        checksum_bitwise_match=bitwise,
        mined_cell={k2: cells[0].get(k2) for k2 in
                    ("m", "n", "k", "dtype", "observed_gflops",
                     "target_gflops", "wasted_flop_seconds", "reason",
                     "source")},
        promoted_driver=(promoted_row or {}).get("driver"),
        promoted_gflops=(promoted_row or {}).get("gflops"),
        params_generation=[gen0, gen1],
        ab={"static": legs["static"], "tuned": legs["tuned"]},
    )
    print(json.dumps(row))
    if not bitwise:
        print("FAIL: tuned leg not bitwise-identical to static leg",
              file=sys.stderr)
        return 1
    if speedup <= 1.0:
        print(f"FAIL: tuner-promoted leg not faster "
              f"(speedup={speedup:.3f})", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
