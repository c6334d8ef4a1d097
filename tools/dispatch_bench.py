"""Dispatch-overhead microbench: per-span vs fused superstack launches.

CPU-runnable (no hardware needed): runs the scaled north-star stack
workload — (1, B)-patterned blockings like `bench.py`'s 10k case, so
every C bin receives MULTIPLE spans (one per k block size) and fusion
has something to fuse — once per stack execution mode, and reports

* host wall µs per multiply (steady-state, plan-cache hits),
* engine dispatch round-trips per multiply
  (``dbcsr_tpu_dispatches_total``, split by mode),
* the fused-launch span histogram, and
* a checksum identity check across modes (fusion must be bit-exact).

The device path is forced to ``mm_driver="xla"`` by default: the
tuned-table host driver has no device dispatches to count, and the XLA
driver is the CPU-runnable stand-in for every TPU stack driver's
dispatch behavior (override with ``--mm-driver``).

The win is SCALE-DEPENDENT on CPU: what fusion eliminates is the
per-span read-modify-write of the destination bin's whole C buffer
(plus N−1 dispatch round-trips), so it grows with the bin buffer —
measured at the 10k north star: 5.2 s fused vs 5.9 s per-span
(~12%); at the 6000 default ~15%; below ~5k on this host XLA-CPU's
chained-program scheduling noise can exceed the saving.  Use sizes
near production scale when producing evidence.

Output is one ``BENCH_*``-compatible JSON object (``metric`` /
``value`` / ``unit`` with the per-mode breakdown inline); ``value`` is
the fused mode's steady-state multiplies/second — higher is better, so
`tools/perf_gate.py` can gate captures of this bench directly:

    python tools/dispatch_bench.py > DISPATCH_r01.json
    python tools/perf_gate.py DISPATCH_r01.json DISPATCH_r02.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(m: int = 6000, n: int = 6000, k: int = 6000, block: int = 23,
        occ: float = 0.1, nrep: int = 3, dtype_enum: int = 3,
        mm_driver: str = "xla", seed: int = 12341313) -> dict:
    """Run the A/B and return the result dict (importable; the tier-1
    smoke test drives this directly at a small size)."""
    import numpy as np

    import dbcsr_tpu.mm.multiply as mm
    from dbcsr_tpu import create, multiply
    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.core.kinds import dtype_of
    from dbcsr_tpu.obs import metrics
    from dbcsr_tpu.ops.test_methods import checksum, make_random_matrix
    from dbcsr_tpu.perf.driver import expand_block_sizes

    dtype = dtype_of(dtype_enum)
    m_sizes = expand_block_sizes(m, [(1, block)])
    n_sizes = expand_block_sizes(n, [(1, block)])
    k_sizes = expand_block_sizes(k, [(1, block)])
    rng = np.random.default_rng(seed)
    a = make_random_matrix("a", m_sizes, k_sizes, dtype=dtype,
                           occupation=occ, rng=rng)
    b = make_random_matrix("b", k_sizes, n_sizes, dtype=dtype,
                           occupation=occ, rng=rng)

    cfg0 = {f: getattr(get_config(), f) for f in ("superstack", "mm_driver")}
    modes = {}
    try:
        for mode in ("per_span", "fused"):
            # incremental off: rep 2+ of the identical product would
            # otherwise be a zero-delta cache hit with no dispatches —
            # this A/B measures the dispatch machinery, not the cache
            set_config(superstack=mode, mm_driver=mm_driver,
                       incremental="off")
            mm._plan_cache.clear()
            metrics.reset()

            def one_multiply():
                c = create("c", m_sizes, n_sizes, dtype=dtype)
                multiply("N", "N", 1.0, a, b, 0.0, c)
                for bin_ in c.bins:
                    bin_.data.block_until_ready()
                return c

            c = one_multiply()  # warm-up: compile + plan build
            cs_warm = checksum(c)
            n_cbins = len(c.bins)
            base = metrics.snapshot()["counters"].get(
                "dbcsr_tpu_dispatches_total", {})
            t0 = time.perf_counter()
            for _ in range(nrep):
                c = one_multiply()
            dt = time.perf_counter() - t0
            # checksummed on the LAST timed rep: the steady-state
            # plan-cache-hit path is the one being benchmarked, so the
            # bit-exactness contract must cover it, not just warm-up
            cs = checksum(c)
            if cs != cs_warm:
                raise AssertionError(
                    f"{mode}: cache-hit checksum {cs!r} != warm-up "
                    f"{cs_warm!r}")
            snap = metrics.snapshot()
            cur = snap["counters"].get("dbcsr_tpu_dispatches_total", {})
            per_mode = {
                json.loads(key)["mode"]: (v - base.get(key, 0)) / nrep
                for key, v in cur.items()
            }
            modes[mode] = {
                "host_us_per_multiply": dt / nrep * 1e6,
                "multiplies_per_s": nrep / dt,
                "dispatches_per_multiply": sum(per_mode.values()),
                "dispatches_by_mode": per_mode,
                "fused_spans": snap["histograms"].get(
                    "dbcsr_tpu_fused_spans", {}),
                "checksum": cs,
                "c_bins": n_cbins,
            }
    finally:
        set_config(**cfg0)
        mm._plan_cache.clear()

    fused = modes["fused"]
    per_span = modes["per_span"]
    checksums_identical = fused["checksum"] == per_span["checksum"]
    out = {
        "metric": (
            f"dispatch_bench steady-state multiply rate, fused superstack "
            f"mode ({m}x{n}x{k}, {block}-blocks, occ={occ}, "
            f"dtype={np.dtype(dtype).name}, mm_driver={mm_driver})"),
        "value": round(fused["multiplies_per_s"], 3),
        "unit": "multiply/s",
        "stack_mode": "fused",
        "mm_driver": mm_driver,
        "nrep": nrep,
        "host_us_per_multiply": {
            mode: round(r["host_us_per_multiply"], 1)
            for mode, r in modes.items()
        },
        "dispatches_per_multiply": {
            mode: r["dispatches_per_multiply"] for mode, r in modes.items()
        },
        "c_bins": fused["c_bins"],
        "fused_dispatches_per_multiply": fused["dispatches_by_mode"].get(
            "fused", 0),
        "dispatch_reduction": (
            per_span["dispatches_per_multiply"]
            / fused["dispatches_per_multiply"]
            if fused["dispatches_per_multiply"] else None),
        "host_overhead_speedup": round(
            per_span["host_us_per_multiply"] / fused["host_us_per_multiply"],
            4),
        "checksums_identical": checksums_identical,
        "checksum": fused["checksum"],
        "modes": modes,
    }
    if not checksums_identical:
        out["error"] = "fused and per_span checksums differ"
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--m", type=int, default=6000)
    ap.add_argument("--n", type=int, default=0, help="default: m")
    ap.add_argument("--k", type=int, default=0, help="default: m")
    ap.add_argument("--block", type=int, default=23)
    ap.add_argument("--occ", type=float, default=0.1)
    ap.add_argument("--nrep", type=int, default=3)
    ap.add_argument("--dtype", type=int, default=3,
                    help="kind enum (3=f64, 1=f32, 9=bf16)")
    ap.add_argument("--mm-driver", default="xla")
    ap.add_argument("--out", default=None, help="write JSON here too")
    args = ap.parse_args(argv)

    # a CPU harness by default: it counts dispatches; its times are
    # host times of the CPU backend, not device numbers
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from dbcsr_tpu.core.lib import init_lib

    init_lib()
    res = run(m=args.m, n=args.n or args.m, k=args.k or args.m,
              block=args.block, occ=args.occ, nrep=args.nrep,
              dtype_enum=args.dtype, mm_driver=args.mm_driver)
    line = json.dumps(res)
    print(line)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    return 0 if res.get("checksums_identical") else 1


if __name__ == "__main__":
    sys.exit(main())
