#!/usr/bin/env python
"""ABFT-overhead A/B: ``DBCSR_TPU_ABFT=verify`` vs ``off``.

Times the north-star-shaped CPU workload (23x23-block BCSR f64
multiplies at the BASELINE.json block shape and occupancy) under both
ABFT modes and reports per leg:

* ``value`` — true-flop GFLOP/s of the leg's FASTEST rep (higher is
  better, the number ``tools/perf_gate.py`` gates on: the gate's
  default 10 % relative tolerance IS the acceptance bound on ABFT
  overhead);
* ``wall_s`` / ``wall_min_s`` / ``reps`` and the derived
  ``overhead_frac`` on the row.

Methodology: both legs run the IDENTICAL multiply sequence on the
SAME operand objects (beta == 0 rebuilds C every rep, so the legs
cannot contaminate each other; sharing keeps the cache/memory
footprint identical — separate per-leg operands measurably inflate
the apparent overhead with L3 eviction artifacts), every rep blocks
on C's device bins before the clock stops (the dispatch pipeline is
async — an unsynced timer flatters whichever leg defers more work),
and the compared walls are each leg's per-rep minimum (the standard
noise-floor estimator).  The ``verify`` leg's final C is asserted
**bitwise identical** to the control's (exit 1 on mismatch): probes
only read, they never perturb the product.

The output JSON (last stdout line) is a perf_gate-compatible capture
row with both legs under ``ab``.

Usage: python tools/abft_bench.py [--nblk 160] [--bsize 23] [--occ 0.1]
           [--reps 6] [--seed 7]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU-only by design: the committed A/B row is the CPU control — the
# probe's relative cost is a scheduling/flops property, real here.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _sync(mat) -> None:
    """Block until every device bin of ``mat`` has materialized.  The
    dispatch pipeline is async: without this barrier a leg's timer
    stops with kernel work still queued, flattering whichever leg
    defers more of its work past the multiply() return."""
    import jax

    for b in getattr(mat, "bins", ()):
        if getattr(b, "count", 0) and hasattr(b.data, "block_until_ready"):
            jax.block_until_ready(b.data)


def run_ab(nblk: int, bsize: int, occ: float, reps: int, seed: int):
    import numpy as np

    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.mm.multiply import multiply
    from dbcsr_tpu.obs import metrics
    from dbcsr_tpu.ops.test_methods import make_random_matrix, to_dense

    bs = [bsize] * nblk
    a = make_random_matrix("A", bs, bs, occupation=occ,
                           rng=np.random.default_rng(seed))
    b = make_random_matrix("B", bs, bs, occupation=occ,
                           rng=np.random.default_rng(seed + 1))
    c = make_random_matrix("C", bs, bs, occupation=0.3,
                           rng=np.random.default_rng(seed + 2))

    flops_rep = {}
    walls = {"off": [], "verify": []}
    denses = {}
    checks = 0
    for mode in ("off", "verify"):
        # incremental off: rep 2+ of the identical product would be a
        # zero-delta cache hit in BOTH legs, measuring the cache
        # instead of the probe overhead this A/B exists for
        set_config(abft=mode, incremental="off")
        flops_rep[mode] = multiply("N", "N", 1.0, a, b, 0.0, c)  # warm
        _sync(c)
        metrics.reset()  # count probe checks over the timed reps only
        for _ in range(reps):
            t0 = time.perf_counter()
            multiply("N", "N", 1.0, a, b, 0.0, c)
            _sync(c)
            walls[mode].append(time.perf_counter() - t0)
        denses[mode] = np.asarray(to_dense(c))
        if mode == "verify":
            checks = sum(v for _, v in metrics.counter_items(
                "dbcsr_tpu_abft_checks_total"))
    legs = {}
    for mode in ("off", "verify"):
        wall = sum(walls[mode])
        wall_min = min(walls[mode])
        m = nblk * bsize
        legs[mode] = {
            "metric": (f"abft_overhead_ab GFLOP/s ({m}^2 BCSR, "
                       f"{bsize}x{bsize} blocks, occ={occ}, f64, "
                       f"best of {reps} reps)"),
            "value": round(flops_rep[mode] / wall_min / 1e9, 6)
            if wall_min else 0.0,
            "unit": "GFLOP/s",
            "abft_mode": mode,
            "reps": reps,
            "true_flops": int(flops_rep[mode] * reps),
            "wall_s": round(wall, 6),
            "wall_min_s": round(wall_min, 6),
        }
    legs["verify"]["abft_checks"] = int(checks)
    bitwise = bool((denses["off"] == denses["verify"]).all())
    return legs, bitwise


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nblk", type=int, default=160)
    ap.add_argument("--bsize", type=int, default=23)
    ap.add_argument("--occ", type=float, default=0.1)
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)

    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.obs import OBS_SCHEMA_VERSION, costmodel

    prev = get_config().abft
    try:
        legs, bitwise = run_ab(args.nblk, args.bsize, args.occ,
                               args.reps, args.seed)
    finally:
        set_config(abft=prev)

    for mode in ("off", "verify"):
        print(f"  {mode:>7}: {legs[mode]['value']} GFLOP/s "
              f"(min {legs[mode]['wall_min_s']} s, "
              f"{legs[mode].get('abft_checks', 0)} checks)",
              file=sys.stderr)
    if not legs["verify"].get("abft_checks"):
        print("FAIL: the verify leg evaluated zero probe checks",
              file=sys.stderr)
        return 1
    kind = costmodel.device_kind()
    dev = str(jax.devices()[0])
    stamps = {
        "unit": "GFLOP/s",
        "device": dev,
        "device_fallback": jax.devices()[0].platform == "cpu",
        "device_kind": kind,
        "jax_version": jax.__version__,
        "obs_schema": OBS_SCHEMA_VERSION,
    }
    for leg in legs.values():
        leg.update(stamps)
    v = legs["verify"]
    overhead = (legs["off"]["wall_min_s"] and
                (v["wall_min_s"] - legs["off"]["wall_min_s"])
                / legs["off"]["wall_min_s"])
    row = dict(
        stamps,
        metric=v["metric"],
        value=v["value"],
        abft_mode="verify",
        overhead_frac=round(float(overhead), 4),
        abft_checks=v["abft_checks"],
        checksum_bitwise_match=bitwise,
        ab={"off": legs["off"], "verify": v},
    )
    print(json.dumps(row))
    if not bitwise:
        print("FAIL: verify and off legs are not bitwise identical",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
