#!/usr/bin/env python
"""SCF-shaped delta A/B: incremental multiply + serve product cache.

Leg pair (the tier-2.13 committed evidence, perf_gate-gated):

* ``full`` — ``DBCSR_TPU_INCREMENTAL=full``: every product recomputed
  from scratch (the control; the delta machinery still tracks, so the
  leg carries the bookkeeping cost honestly);
* ``incremental`` — ``=auto``: the same update/multiply sequence with
  the delta-aware path live — per iteration ~``--delta`` of A's
  stored blocks get new values (same sparsity pattern, the SCF
  shape), and only the C blocks whose accumulation reads a dirty A
  block are recomputed; the rest splice from the cached
  device-resident result.

Both legs run the IDENTICAL sequence (same seeds, same update
subsets) with the stack driver HELD CONSTANT (default ``mm_driver=
xla``, the device-resident TPU-production lowering — the
`tools/precision_bench.py` convention: a CPU box would otherwise
auto-pick the native host driver, whose per-launch full-bin H2D
upload costs O(C) regardless of how few entries execute and buries
the delta axis under a transfer the TPU path never pays).  Every
iteration's C is asserted **bitwise identical** across the legs
(exit 1 on mismatch) — the incremental path's whole contract.
``value`` is the leg's effective true-flop GFLOP/s over the FULL
product's work (work-normalized: the incremental leg does less
arithmetic for the same logical product, which is the point).

A third serve-layer leg then submits the identical (A, B, alpha,
flags) product twice through `dbcsr_tpu.serve` and asserts the repeat
is returned from the content-addressed product cache with ZERO engine
dispatches and a bitwise-identical C.

The output JSON (last stdout line) is a perf_gate-compatible capture
row with both legs under ``ab``.

Usage: python tools/delta_bench.py [--nblk 40] [--bsize 32] [--occ 0.6]
           [--iters 8] [--delta 0.25] [--seed 7] [--driver xla]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU-only by design: the committed A/B row is the CPU control — the
# saved work is real arithmetic on this world too.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _sync(mat) -> None:
    """Block until every device bin of ``mat`` materialized (the
    dispatch pipeline is async; an unsynced timer flatters whichever
    leg defers more work)."""
    import jax

    for b in getattr(mat, "bins", ()):
        if getattr(b, "count", 0) and hasattr(b.data, "block_until_ready"):
            jax.block_until_ready(b.data)


def run_leg(mode: str, nblk: int, bsize: int, occ: float, iters: int,
            delta: float, seed: int):
    """One leg: warm 3 reps, then ``iters`` update+multiply rounds.
    Returns (walls, digests, full_flops, reuse_totals)."""
    import hashlib

    import numpy as np

    import dbcsr_tpu as dt
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.mm import incremental as inc
    from dbcsr_tpu.ops.test_methods import make_random_matrix, to_dense

    set_config(incremental=mode)
    inc.reset()
    bs = [bsize] * nblk
    a = make_random_matrix("A", bs, bs, occupation=occ,
                           rng=np.random.default_rng(seed))
    b = make_random_matrix("B", bs, bs, occupation=occ,
                           rng=np.random.default_rng(seed + 1))
    c = dt.create("C", bs, bs)
    rows, cols = a.entry_coords()
    n_dirty = max(1, int(round(len(rows) * delta)))
    sub = np.arange(n_dirty)  # fixed subset: the SCF "active" blocks
    full_flops = 0
    for _ in range(3):  # prime plan + result caches (untimed)
        # max, not last: in auto mode a warm rep can already be an
        # incremental hit returning only the EXECUTED subset flops —
        # the work-normalized GFLOP/s must use the full product's
        full_flops = max(full_flops, dt.multiply("N", "N", 1.0, a, b, 0.0, c))
    _sync(c)
    walls, digests = [], []
    for it in range(iters):
        r2 = np.random.default_rng(seed * 1000 + it)
        blocks = r2.standard_normal((n_dirty, bsize, bsize))
        a.put_blocks(rows[sub], cols[sub], blocks)
        a.finalize()
        _sync(a)
        t0 = time.perf_counter()
        dt.multiply("N", "N", 1.0, a, b, 0.0, c)
        _sync(c)
        walls.append(time.perf_counter() - t0)
        digests.append(hashlib.sha1(
            np.ascontiguousarray(np.asarray(to_dense(c))).tobytes()
        ).hexdigest())
    return walls, digests, int(full_flops), inc.stats_snapshot()


def run_serve_leg(nblk: int, bsize: int, occ: float, seed: int) -> dict:
    """Identical submission twice through the serve plane: the repeat
    must come from the content-addressed product cache with zero
    engine dispatches and a bitwise-identical C."""
    import numpy as np

    import dbcsr_tpu as dt
    from dbcsr_tpu import serve
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.ops.test_methods import make_random_matrix, to_dense

    bs = [bsize] * nblk
    a = make_random_matrix("SA", bs, bs, occupation=occ,
                           rng=np.random.default_rng(seed + 10))
    b = make_random_matrix("SB", bs, bs, occupation=occ,
                           rng=np.random.default_rng(seed + 11))
    eng = serve.get_engine()
    sess = eng.open_session("delta-bench")
    sess.put("A", a, adopt=False)
    sess.put("B", b, adopt=False)
    sess.put("C1", dt.create("C1", bs, bs))
    sess.put("C2", dt.create("C2", bs, bs))
    t0 = time.perf_counter()
    r1 = eng.submit(sess, a="A", b="B", c="C1", beta=0.0)
    r1.wait(timeout=120)
    t_first = time.perf_counter() - t0
    m0 = stats._totals["multiplies"]
    t0 = time.perf_counter()
    r2 = eng.submit(sess, a="A", b="B", c="C2", beta=0.0)
    r2.wait(timeout=120)
    t_repeat = time.perf_counter() - t0
    dispatches = stats._totals["multiplies"] - m0
    c1 = np.asarray(to_dense(sess.get("C1")))
    c2 = np.asarray(to_dense(sess.get("C2")))
    out = {
        "hit": bool((r2.result or {}).get("cached") == 1),
        "dispatches_on_hit": int(dispatches),
        "bitwise": bool((c1 == c2).all()),
        "first_ms": round(t_first * 1e3, 3),
        "repeat_ms": round(t_repeat * 1e3, 3),
        "saved_flops": int((r2.result or {}).get("saved_flops", 0)),
    }
    eng.shutdown()
    sess.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nblk", type=int, default=40)
    ap.add_argument("--bsize", type=int, default=32)
    ap.add_argument("--occ", type=float, default=0.6)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--delta", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--driver", default="xla",
                    help="mm_driver held constant across the legs")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)

    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.obs import OBS_SCHEMA_VERSION, costmodel

    prev = get_config().incremental
    prev_driver = get_config().mm_driver
    set_config(mm_driver=args.driver)
    legs = {}
    try:
        for mode, leg_name in (("full", "full"), ("auto", "incremental")):
            walls, digests, flops, totals = run_leg(
                mode, args.nblk, args.bsize, args.occ, args.iters,
                args.delta, args.seed)
            m = args.nblk * args.bsize
            wall, wall_min = sum(walls), min(walls)
            legs[leg_name] = {
                "metric": (f"delta_ab effective GFLOP/s ({m}^2 BCSR, "
                           f"{args.bsize}x{args.bsize} blocks, "
                           f"occ={args.occ}, f64, "
                           f"{args.delta:.0%} of A dirty/iter)"),
                "value": round(flops / wall_min / 1e9, 6)
                if wall_min else 0.0,
                "unit": "GFLOP/s",
                "incremental_mode": mode,
                "mm_driver": args.driver,
                "iters": args.iters,
                "true_flops_full": int(flops),
                "wall_s": round(wall, 6),
                "wall_min_s": round(wall_min, 6),
                "digests": digests,
                "reuse": totals,
            }
        serve_leg = run_serve_leg(args.nblk, args.bsize, args.occ,
                                  args.seed)
    finally:
        set_config(incremental=prev, mm_driver=prev_driver)

    full, incr = legs["full"], legs["incremental"]
    bitwise = full.pop("digests") == incr.pop("digests")
    totals = incr["reuse"]
    blocks = totals["reused_blocks"] + totals["recomputed_blocks"]
    reuse_fraction = round(totals["reused_blocks"] / blocks, 6) \
        if blocks else 0.0
    for name, leg in legs.items():
        print(f"  {name:>12}: {leg['value']} GFLOP/s "
              f"(min {leg['wall_min_s']} s, reuse {leg['reuse']})",
              file=sys.stderr)
    print(f"  serve cache: {serve_leg}", file=sys.stderr)

    kind = costmodel.device_kind()
    stamps = {
        "unit": "GFLOP/s",
        "device": str(jax.devices()[0]),
        "device_fallback": jax.devices()[0].platform == "cpu",
        "device_kind": kind,
        "jax_version": jax.__version__,
        "obs_schema": OBS_SCHEMA_VERSION,
    }
    for leg in legs.values():
        leg.update(stamps)
    speedup = (full["wall_min_s"] / incr["wall_min_s"]
               if incr["wall_min_s"] else 0.0)
    row = dict(
        stamps,
        metric=incr["metric"],
        value=incr["value"],
        incremental_mode="auto",
        mm_driver=args.driver,
        speedup_incremental=round(float(speedup), 4),
        reuse_fraction=reuse_fraction,
        saved_flops=int(totals["saved_flops"]),
        checksum_bitwise_match=bitwise,
        serve_cache=serve_leg,
        ab={"full": full, "incremental": incr},
    )
    print(json.dumps(row))
    ok = (bitwise and serve_leg["hit"]
          and serve_leg["dispatches_on_hit"] == 0
          and serve_leg["bitwise"])
    if not ok:
        print("FAIL: bitwise identity or serve-cache contract violated",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
