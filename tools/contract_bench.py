#!/usr/bin/env python
"""Contraction-shaped north-star A/B: the upper layers on the fused,
device-resident hot path.

Two paired experiments, one committed row (tier 2.10):

* **pipeline** — a rank-3 tensor contraction (``T(i,j,k) M(k,l) ->
  C(i,j,l)``, the 3-center-integral pattern) routed over a
  RECTANGULAR (1x2x3) grid, so `tensor.contract` -> `tas_multiply`
  lands on the all-gather route, run with ``cannon_overlap=serial``
  (the fused one-collective program, gather wait fully exposed) vs
  ``double_buffer`` (the chunked per-source-shard gather pipeline,
  `parallel/sparse_dist._gather_ticks`) under
  ``DBCSR_TPU_SYNC_TIMING=1``.  Reported per leg: the MEASURED
  comm-exposed fraction (the ``dbcsr_tpu_cannon_overlap_measured``
  gauge) and its higher-is-better complement ``value`` (hidden-comm
  fraction) that `tools/perf_gate.py` gates on.

* **chain** — the TAS split loop as a chained workload: repeated
  ``tas_multiply(nsplit=K)`` over fixed tall-and-skinny operands
  (the batched post-SCF regime), memory pool + device index mirrors
  ON (`core.mempool.chain` residency, what `tas/mm.py` now does
  internally) vs OFF (the restage-every-multiply control).  Reported
  per leg: GFLOP/s (``value``) and per-iteration restage bytes
  (h2d+d2h deltas) — with residency on, per-split H2D collapses to
  ~zero after iteration 1 instead of staying proportional to the
  split count.  Like `bench.py --chain`, the device-side ``xla``
  driver is forced: the CPU-tuned native host driver computes ON
  host, so its per-multiply C round-trips are algorithmic, not
  restage overhead (on the TPU target every auto driver is
  device-side).

Checksums are asserted **bitwise identical** within each pair (exit 1
on mismatch): pipelining reorders dispatches and residency reorders
allocations; neither may change arithmetic.

The output JSON (last stdout line) carries all four legs under ``ab``
(``serial``/``pipelined`` and ``unchained``/``chained``) with distinct
``metric`` strings per pair, a ``cannon_mode`` stamp on the row and
the pipeline legs.

Usage: python tools/contract_bench.py [--nblk 6] [--bsize 5]
           [--occ 0.6] [--nrep 4] [--iters 6] [--nsplit 6] [--seed 7]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from statistics import median

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU-runnable by design (the committed A/B row is the CPU control);
# a real accelerator world runs the same code on its own devices.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _hostdev  # noqa: E402

# the rectangular-grid route needs a (1, 2, 3) world
_hostdev.ensure_virtual_devices(6)
# the measurement seam: per-tick dispatch + sub-region timing
os.environ["DBCSR_TPU_SYNC_TIMING"] = "1"


def _rand_tensor(name, blk_sizes, occ, seed):
    import itertools

    import numpy as np

    from dbcsr_tpu.tensor import create_tensor

    rng = np.random.default_rng(seed)
    t = create_tensor(name, blk_sizes)
    for idx in itertools.product(*(range(len(n)) for n in blk_sizes)):
        if rng.random() < occ:
            t.put_block(idx, rng.standard_normal(t.block_shape(idx)))
    return t.finalize()


def run_pipeline_leg(mode: str, tensors, mesh, grid: str, nrep: int):
    """One contraction leg over the rectangular grid; returns the
    perf_gate leg dict + the dense result for the bitwise assert."""
    import numpy as np

    from dbcsr_tpu.core import stats
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.obs import metrics
    from dbcsr_tpu.parallel.sparse_dist import clear_mesh_plans
    from dbcsr_tpu.tensor import create_tensor
    from dbcsr_tpu.tensor.contract import contract

    a, b, blks = tensors
    si, sj, sk, sl = blks
    set_config(cannon_overlap=mode)

    def one():
        clear_mesh_plans()
        c = create_tensor("c", [si, sj, sl])
        c.finalize()
        contract(1.0, a, b, 0.0, c,
                 contract_a=(2,), notcontract_a=(0, 1),
                 contract_b=(0,), notcontract_b=(1,),
                 map_1=(0, 1), map_2=(2,), mesh=mesh)
        return c

    c = one()  # warmup/compile
    exposed, walls = [], []
    for _ in range(nrep):
        # fresh rollup per rep: a silently degraded rep publishes no
        # measurement, and a stale sample left by the warmup/previous
        # rep (or the other leg) must never become committed evidence
        metrics.reset()
        t0 = time.perf_counter()
        c = one()
        walls.append(time.perf_counter() - t0)
        row = stats.cannon_overlap_rollup().get("mesh", {}).get(grid, {})
        if "measured_exposed" not in row or row.get("mode") != mode:
            raise RuntimeError(
                f"leg {mode}: this rep recorded no measured overlap for "
                f"grid {grid} (degraded pipeline? rollup: "
                f"{stats.cannon_overlap_rollup()})")
        exposed.append(row["measured_exposed"])
    exp_med = median(exposed)
    return {
        "metric": "tensor_contract gather-pipeline hidden-comm fraction "
                  "(rank-3 x matrix, 1x2x3 rect grid, f64)",
        "value": round(1.0 - exp_med, 6),
        "unit": "hidden-comm fraction",
        "cannon_mode": mode,
        "exposed_fraction": round(exp_med, 6),
        "exposed_samples": [round(x, 6) for x in exposed],
        "wall_s": round(median(walls), 6),
    }, np.asarray(c.to_dense())


def run_chain_leg(pooled: bool, iters: int, nsplit: int, nblk_tall: int,
                  seed: int):
    """One TAS chained-workload leg; returns the perf_gate leg dict +
    the final C dense array for the bitwise assert."""
    import numpy as np

    import dbcsr_tpu as dt
    from dbcsr_tpu.core import mempool, stats
    from dbcsr_tpu.mm import multiply as mm_multiply
    from dbcsr_tpu.ops.test_methods import to_dense
    from dbcsr_tpu.tas import tas_multiply

    mempool.set_enabled(pooled)
    mempool.clear()
    mempool.reset_stats()
    mm_multiply._plan_cache.clear()
    # mixed blockings so the split multiplies hit the fused superstack
    # (several (abin, bbin) span families per C bin)
    ls = [5, 4, 5, 4] * nblk_tall
    ss = [5, 4, 5]
    rng = np.random.default_rng(seed)
    a = dt.make_random_matrix("a", ls, ss, occupation=0.6, rng=rng)
    b = dt.make_random_matrix("b", ss, ss, occupation=0.8, rng=rng)
    per_iter_s, per_iter_bytes = [], []
    flops0 = stats.total_flops()
    c = None
    for _ in range(iters):
        c = dt.create("c", ls, ss)
        tr0 = mempool.transfer_totals()
        t0 = time.perf_counter()
        tas_multiply("N", "N", 1.0, a, b, 0.0, c, nsplit=nsplit)
        per_iter_s.append(time.perf_counter() - t0)
        tr1 = mempool.transfer_totals()
        per_iter_bytes.append(
            int((tr1["h2d"] - tr0["h2d"]) + (tr1["d2h"] - tr0["d2h"])))
    flops = stats.total_flops() - flops0
    secs = sum(per_iter_s)
    dense = np.asarray(to_dense(c))
    return {
        "metric": f"tas_contract chain GFLOP/s (tall-and-skinny split "
                  f"loop, nsplit={nsplit}, {iters} iters, f64)",
        "value": round(flops / secs / 1e9, 6) if secs else 0.0,
        "unit": "GFLOP/s",
        "chain_pool": pooled,
        "seconds": round(secs, 4),
        "per_iter_seconds": [round(s, 4) for s in per_iter_s],
        "per_iter_bytes": per_iter_bytes,
        "flops": int(flops),
    }, dense


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nblk", type=int, default=6,
                    help="blocks per tensor dim (pipeline part)")
    ap.add_argument("--bsize", type=int, default=5)
    ap.add_argument("--occ", type=float, default=0.6)
    ap.add_argument("--nrep", type=int, default=4)
    ap.add_argument("--iters", type=int, default=6,
                    help="chain-part iterations")
    ap.add_argument("--nsplit", type=int, default=6)
    ap.add_argument("--tall", type=int, default=8,
                    help="chain-part tall-dim repeat factor (x4 blocks)")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)
    import numpy as np

    from dbcsr_tpu.core import mempool
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.obs import OBS_SCHEMA_VERSION, costmodel
    from dbcsr_tpu.parallel import make_grid

    # production-shaped: stack engine + device-side driver (see module
    # docstring; matches bench.py --chain)
    set_config(mm_format="stack", mm_driver="xla")

    # ---- pipeline A/B (rectangular-grid gather route) ----
    bs = [args.bsize] * args.nblk
    mix = ([args.bsize, args.bsize - 1] * args.nblk)[:args.nblk]
    a3 = _rand_tensor("a3", [bs, mix, bs], args.occ, args.seed)
    m2 = _rand_tensor("m2", [bs, mix], min(1.0, args.occ + 0.2),
                      args.seed + 1)
    mesh = make_grid(6, layers=1)  # (kl=1, pr=2, pc=3): rectangular
    grid = "x".join(str(mesh.shape[ax]) for ax in ("kl", "pr", "pc"))

    legs = {}
    dense = {}
    for name, mode in (("serial", "serial"),
                       ("pipelined", "double_buffer")):
        legs[name], dense[name] = run_pipeline_leg(
            mode, (a3, m2, (bs, mix, bs, mix)), mesh, grid, args.nrep)
        print(f"  {name:>10}: exposed={legs[name]['exposed_fraction']:.4f} "
              f"hidden={legs[name]['value']:.4f} "
              f"wall={legs[name]['wall_s'] * 1e3:.1f} ms",
              file=sys.stderr)
    pipe_bitwise = bool((dense["serial"] == dense["pipelined"]).all())

    # ---- chain A/B (TAS split loop, device residency on/off) ----
    # absorb every XLA compile (incl. the pool's donated-rezero
    # variants) before either timed leg
    for warm in (False, True):
        run_chain_leg(warm, iters=2, nsplit=args.nsplit,
                      nblk_tall=args.tall, seed=args.seed)
    for name, pooled in (("unchained", False), ("chained", True)):
        legs[name], dense[name] = run_chain_leg(
            pooled, iters=args.iters, nsplit=args.nsplit,
            nblk_tall=args.tall, seed=args.seed)
        print(f"  {name:>10}: {legs[name]['value']} GFLOP/s "
              f"per-iter bytes {legs[name]['per_iter_bytes']}",
              file=sys.stderr)
    mempool.set_enabled(True)
    chain_bitwise = bool(np.array_equal(dense["unchained"],
                                        dense["chained"]))

    kind = costmodel.device_kind()
    stamps = {
        "device": str(jax.devices()[0]),
        "device_fallback": jax.devices()[0].platform == "cpu",
        "device_kind": kind,
        "jax_version": jax.__version__,
        "obs_schema": OBS_SCHEMA_VERSION,
        "mm_driver": "xla",
    }
    for leg in legs.values():
        leg.update(stamps)
    pipe = legs["pipelined"]
    chained = legs["chained"]
    row = dict(
        stamps,
        metric=pipe["metric"],
        value=pipe["value"],
        unit="hidden-comm fraction",
        cannon_mode="double_buffer",
        exposed_serial=legs["serial"]["exposed_fraction"],
        exposed_pipelined=pipe["exposed_fraction"],
        chain_gflops_unchained=legs["unchained"]["value"],
        chain_gflops_chained=chained["value"],
        # restage collapse: steady-state (iters 2..N) bytes per
        # iteration vs the chain's first (cold) iteration — and the
        # unchained control's steady state, which stays proportional
        # to the split count
        restage_bytes_iter1=chained["per_iter_bytes"][0],
        restage_bytes_steady=max(chained["per_iter_bytes"][1:]),
        restage_bytes_unchained_steady=max(
            legs["unchained"]["per_iter_bytes"][1:]),
        checksum=float(np.sum(dense["pipelined"])),
        checksum_bitwise_match=bool(pipe_bitwise and chain_bitwise),
        ab=legs,
    )
    print(json.dumps(row))
    ok = True
    if not pipe_bitwise:
        print("FAIL: pipelined and serial contraction legs are not "
              "bitwise identical", file=sys.stderr)
        ok = False
    if not chain_bitwise:
        print("FAIL: chained and unchained TAS legs are not bitwise "
              "identical", file=sys.stderr)
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
