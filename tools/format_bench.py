#!/usr/bin/env python
"""Storage-format occupancy-sweep A/B: stack vs dense vs composite vs
the adaptive planner (``mm_format=auto``), with the autotuner's
learned-crossover loop closed live.

One sweep = the SAME logical product family at a ladder of block
occupancies, executed once per forced storage format plus once with
the planner left to choose.  Two pattern families:

* ``uniform`` — random occupancy at each ladder point: the stack/dense
  crossover axis;
* ``banded`` — a block-band (fixed bandwidth): the composite panel
  format's home turf, where whole-panel dense padding drowns.

Block values are INTEGER-VALUED floats, so every format's float64
accumulation is exact and the C digests must be **bitwise identical**
across all legs at every ladder point (exit 1 on mismatch) — format
choice is a performance decision, never a numerics decision.

Then the tentpole's learning loop runs FOR REAL: every ladder point
where the planner's first choice fell off the fixed-format envelope
becomes a mined format cell (`tune.trials.run_format_trial` A/Bs the
formats off the hot path, the service merge-promotes the winner's
format columns into the params table, the generation bump retires the
planner's cached plans), and the auto leg re-runs as ``auto_learned``.

Envelope gate (exit 1 on violation): at every ladder point the LEARNED
auto leg must be within ``--tol`` (default 10%) of the best FIXED
format that actually executed — measured on the format CHOICE: when
the auto leg executed the same format as the best fixed leg the gap
is 0 by construction (re-timing an identical code path samples
scheduler jitter, not the planner), and only a genuinely different
choice is charged its measured shortfall.  A forced format that
structurally declines (``composite`` on a dense-full panel) falls back
to stack and competes as what it ran (recorded in ``executed``).

Hermetic: the params table lands in a temp dir — the bench's learned
promotions never pollute the user's real table.

The output JSON (last stdout line) is a perf_gate-compatible capture
row; per-point legs live under ``sweep``.

Usage: python tools/format_bench.py [--nblk 24] [--bsize 16]
           [--occs 0.15,0.45,0.9] [--band 2] [--reps 5] [--seed 7]
           [--tol 0.10]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU-only by design (the delta_bench convention): the committed row is
# the CPU control; on a real TPU the same sweep recalibrates the table.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# hermetic params table: learned promotions stay in the bench sandbox
os.environ.setdefault("DBCSR_TPU_PARAMS_DIR",
                      tempfile.mkdtemp(prefix="format_bench_params_"))

FIXED = ("stack", "dense", "composite")


def _build_pair(family: str, nblk: int, bsize: int, occ: float,
                band: int, seed: int):
    """A, B with integer-valued blocks (exact f64 accumulation →
    bitwise-comparable C across formats)."""
    import numpy as np

    import dbcsr_tpu as dt

    rng = np.random.default_rng(seed)
    bs = [bsize] * nblk

    def _fill(name, pattern):
        m = dt.create(name, bs, bs)
        rows = np.asarray([i for i, j in pattern], dtype=np.int64)
        cols = np.asarray([j for i, j in pattern], dtype=np.int64)
        blocks = rng.integers(-4, 5, size=(len(pattern), bsize, bsize)
                              ).astype(np.float64)
        m.put_blocks(rows, cols, blocks)
        m.finalize()
        return m

    if family == "banded":
        pattern = [(i, j) for i in range(nblk) for j in range(nblk)
                   if abs(i - j) <= band]
    else:
        pattern = [(i, j) for i in range(nblk) for j in range(nblk)
                   if rng.random() < occ]
        pattern = pattern or [(0, 0)]
    return _fill("fmtA", pattern), _fill("fmtB", list(pattern))


def _digest(c) -> str:
    import numpy as np

    from dbcsr_tpu import to_dense

    return hashlib.sha1(np.ascontiguousarray(
        np.asarray(to_dense(c))).tobytes()).hexdigest()


def _sync(c) -> None:
    try:
        import jax

        for bn_ in getattr(c, "bins", ()):
            if getattr(bn_, "count", 0) and \
                    hasattr(bn_.data, "block_until_ready"):
                jax.block_until_ready(bn_.data)
    except Exception:
        pass


def run_leg(fmt: str, a, b, bs, reps: int) -> dict:
    """One forced-format (or auto) leg over a prebuilt A, B pair."""
    import dbcsr_tpu as dt
    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.mm import format_planner as fp

    prev = get_config().mm_format
    set_config(mm_format=fmt)
    fp.reset()
    try:
        walls, flops, executed = [], 0, "stack"

        def _rep() -> None:
            nonlocal flops, executed, c
            c = dt.create("fmtC", bs, bs)
            t0 = time.perf_counter()
            got = dt.multiply("N", "N", 1.0, a, b, 0.0, c)
            _sync(c)
            walls.append(time.perf_counter() - t0)
            flops = max(flops, int(got))
            executed = getattr(c, "_mm_algorithm", "stack")

        c = None
        _rep()  # warm (untimed cache fill)
        walls.clear()
        _rep()
        # Small products have sub-ms walls where scheduler jitter swamps
        # the format signal: scale reps so each leg accumulates ~150 ms
        # of measured work before taking the min.
        want = max(reps, 1)
        if walls[0] < 0.03:
            want = max(want, min(25, int(0.15 / max(walls[0], 1e-4))))
        for _ in range(want - 1):
            _rep()
        wall_min = min(walls)
        return {
            "executed": executed,
            "wall_min_s": round(wall_min, 6),
            "gflops": round(flops / wall_min / 1e9, 4) if wall_min
            else 0.0,
            "true_flops": flops,
            "digest": _digest(c),
        }
    finally:
        set_config(mm_format=prev)
        fp.reset()


def _choice_gap(legs: dict, auto_leg: dict) -> float:
    """How far the planner's CHOICE fell off the fixed-format
    envelope.  When the auto leg executed the same format as the best
    fixed leg, the choice is envelope-optimal by construction and the
    gap is 0 — re-measuring an identical code path only samples
    scheduler jitter, not the planner.  Only a genuinely different
    format choice is charged its measured shortfall."""
    fixed_best = max(FIXED, key=lambda f: legs[f]["gflops"])
    best = legs[fixed_best]
    if not best["gflops"] or auto_leg["executed"] == best["executed"]:
        return 0.0
    return (best["gflops"] - auto_leg["gflops"]) / best["gflops"]


def learn_cell(point: dict, legs: dict, bsize: int, nblk: int,
               seed: int) -> dict | None:
    """Close the loop for one off-envelope point: mined-style cell →
    off-hot-path format trial → merge promotion (generation bump
    retires cached plans).  Returns the promotion record or None."""
    from dbcsr_tpu.tune import service as tsvc
    from dbcsr_tpu.tune import trials as ttrials

    fixed_best = max(legs[f]["gflops"] for f in FIXED)
    # the planner's occupancy unit is product-TRIPLE density, not the
    # pattern fill — recover it from the product's true flops
    triple_occ = legs["auto"]["true_flops"] / (
        2.0 * bsize ** 3 * nblk ** 3)
    cell = {
        "m": bsize, "n": bsize, "k": bsize, "dtype": "float64",
        "driver": "format", "stack_size": 0,
        "format": legs["auto"]["executed"],
        "occ": round(triple_occ, 4), "grid": [nblk] * 3,
        "observed_gflops": legs["auto"]["gflops"],
        "target_gflops": fixed_best,
        "wasted_flop_seconds": 0.0, "source": "format_bench",
        "reason": f"auto fell {point['auto_gap']:.1%} off the envelope",
    }
    trial = ttrials.run_format_trial(cell, seed=seed, reps=2)
    if not trial.ok or trial.entry is None:
        print(f"  learn: trial {trial.outcome} "
              f"(error={trial.error}, candidates={trial.candidates})",
              file=sys.stderr)
        return None
    svc = tsvc.TuneService(interval_s=3600)
    promoted = svc._maybe_promote_format(cell, trial)
    if promoted is None:
        print(f"  learn: held (trial entry {trial.entry}, "
              f"bar={legs['auto']['gflops']})", file=sys.stderr)
        return None
    return {"cell": f"{bsize}x{bsize}x{bsize}:float64",
            "format": promoted["entry"].get("format"),
            "format_occ": promoted["entry"].get("format_occ"),
            "generation": promoted["generation"],
            "trial_candidates": trial.candidates}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nblk", type=int, default=24)
    ap.add_argument("--bsize", type=int, default=16)
    ap.add_argument("--occs", default="0.15,0.45,0.9",
                    help="uniform-family occupancy ladder")
    ap.add_argument("--band", type=int, default=2,
                    help="banded-family half bandwidth (blocks)")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--tol", type=float, default=0.10,
                    help="max fraction a fixed format may beat auto by")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)

    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.obs import OBS_SCHEMA_VERSION, costmodel

    # the incremental plane would splice the repeated identical
    # products and the bench would time splices, not formats
    prev_inc = get_config().incremental
    set_config(incremental="full")

    points = [("uniform", float(o)) for o in args.occs.split(",")]
    points.append(("banded", -1.0))
    bs = [args.bsize] * args.nblk

    # ---- phase 1: the sweep (fixed formats + first-pass auto)
    sweep, pairs = [], []
    bitwise = True
    for family, occ in points:
        a, b = _build_pair(family, args.nblk, args.bsize, occ,
                           args.band, args.seed)
        pairs.append((a, b))
        legs = {f: run_leg(f, a, b, bs, args.reps)
                for f in FIXED + ("auto",)}
        nnz = len(a.entry_coords()[0])
        stored_occ = round(nnz / float(args.nblk * args.nblk), 4)
        same = len({legs[f]["digest"] for f in legs}) == 1
        bitwise = bitwise and same
        gap = _choice_gap(legs, legs["auto"])
        sweep.append({"family": family, "occ": stored_occ,
                      "bitwise": same, "auto_gap": round(gap, 4),
                      "legs": legs})

    # ---- phase 2: learn the mis-crossovers, re-run auto
    learned = []
    for point in sweep:
        if point["auto_gap"] > args.tol:
            rec = learn_cell(point, point["legs"], args.bsize,
                             args.nblk, args.seed)
            if rec is not None:
                learned.append(dict(rec, family=point["family"],
                                    occ=point["occ"]))
    worst_gap = 0.0
    for point, (a, b) in zip(sweep, pairs):
        leg = run_leg("auto", a, b, bs, args.reps)
        point["legs"]["auto_learned"] = leg
        same = leg["digest"] == point["legs"]["stack"]["digest"]
        bitwise = bitwise and same
        point["bitwise"] = point["bitwise"] and same
        gap = _choice_gap(point["legs"], leg)
        point["auto_learned_gap"] = round(gap, 4)
        worst_gap = max(worst_gap, gap)
        label = (f"{point['family']} occ={point['occ']}")
        print(f"  {label:>22}: " + ", ".join(
            f"{f}={point['legs'][f]['gflops']}"
            f"({point['legs'][f]['executed']})"
            for f in FIXED + ("auto", "auto_learned"))
            + f"  bitwise={'OK' if point['bitwise'] else 'MISMATCH'}"
            f"  gap={point['auto_gap']:.1%}->{gap:.1%}",
            file=sys.stderr)
        for f in FIXED + ("auto", "auto_learned"):
            point["legs"][f].pop("digest", None)

    kind = costmodel.device_kind()
    top = max((p for p in sweep if p["family"] == "uniform"),
              key=lambda p: p["occ"])
    m = args.nblk * args.bsize

    def _geomean(vals):
        vals = [v for v in vals if v > 0]
        return math.exp(sum(math.log(v) for v in vals) / len(vals)) \
            if vals else 0.0

    # perf_gate legs: the best SINGLE fixed format over the whole
    # sweep (what a format knob without a planner buys you) vs the
    # learned planner.  Geomean across ladder points — one fixed
    # format cannot win both ends of the occupancy axis, which is
    # exactly the planner's claim.
    geo = {f: _geomean([p["legs"][f]["gflops"] for p in sweep])
           for f in FIXED + ("auto_learned",)}
    best_fixed = max(FIXED, key=lambda f: geo[f])
    ab_metric = (f"format_ab sweep geomean GFLOP/s ({m}^2 BCSR, "
                 f"{args.bsize}x{args.bsize} blocks, f64, "
                 f"{len(sweep)}-point occupancy sweep)")
    env = {
        "device": str(jax.devices()[0]),
        "device_fallback": jax.devices()[0].platform == "cpu",
        "device_kind": kind,
        "jax_version": jax.__version__,
        "obs_schema": OBS_SCHEMA_VERSION,
    }
    ab = {
        "fixed": dict(env, metric=ab_metric, unit="GFLOP/s",
                      value=round(geo[best_fixed], 4),
                      format=best_fixed),
        "auto": dict(env, metric=ab_metric, unit="GFLOP/s",
                     value=round(geo["auto_learned"], 4),
                     format="auto+tuned"),
    }
    row = {
        "metric": (f"format_ab learned-auto GFLOP/s ({m}^2 BCSR, "
                   f"{args.bsize}x{args.bsize} blocks, f64, "
                   f"occ={top['occ']}, planner=auto+tuned)"),
        "value": top["legs"]["auto_learned"]["gflops"],
        "unit": "GFLOP/s",
        "device": str(jax.devices()[0]),
        "device_fallback": jax.devices()[0].platform == "cpu",
        "device_kind": kind,
        "jax_version": jax.__version__,
        "obs_schema": OBS_SCHEMA_VERSION,
        "checksum_bitwise_match": bitwise,
        "auto_worst_gap": round(worst_gap, 4),
        "tol": args.tol,
        "speedup_auto": round(geo["auto_learned"] / geo[best_fixed], 4)
        if geo[best_fixed] else 0.0,
        "best_fixed_format": best_fixed,
        "ab": ab,
        "learned": learned,
        "sweep": sweep,
    }
    set_config(incremental=prev_inc)
    print(json.dumps(row))
    if not bitwise:
        print("FAIL: C digests differ across storage formats",
              file=sys.stderr)
        return 1
    if worst_gap > args.tol:
        print(f"FAIL: a fixed format beats learned auto by "
              f"{worst_gap:.1%} (> {args.tol:.0%}) — the planner fell "
              f"off the envelope", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
