#!/usr/bin/env python
"""Overlapped-vs-serial Cannon tick A/B on a 2x2 mesh.

Runs the block-sparse distributed multiply twice — once with
``cannon_overlap=serial`` (the fused shift-after-compute reference
ordering, timed tick-by-tick) and once with
``cannon_overlap=double_buffer`` (tick k+1's ring shift dispatched
before tick k's contraction, `parallel/overlap.py`) — under
``DBCSR_TPU_SYNC_TIMING=1`` so each leg's shift/compute sub-regions
are measured, and reports the MEASURED comm-overlap per leg:

* ``exposed_fraction`` — shift seconds not hidden behind compute over
  total tick-loop seconds (the ``dbcsr_tpu_cannon_overlap_measured``
  gauge; lower is better);
* ``value`` — the hidden fraction (1 - exposed), the higher-is-better
  number `tools/perf_gate.py` gates on (serial leg = baseline,
  double-buffer leg = candidate).

Checksums of the two legs are asserted **bitwise identical** (exit 1
on mismatch): double buffering reorders dispatches, never arithmetic.

The output JSON (last stdout line) is a perf_gate-compatible capture
row with both legs under ``ab`` and a ``cannon_mode`` stamp.

Usage: python tools/overlap_bench.py [--nblk 24] [--bsize 5]
           [--occ 0.4] [--nrep 5] [--seed 7]
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

_hostdev.ensure_virtual_devices(4)
# the measurement seam: per-tick dispatch + sub-region timing
os.environ["DBCSR_TPU_SYNC_TIMING"] = "1"


def run_leg(mode: str, a, b, mesh, grid: str, nrep: int):
    import numpy as np

    from dbcsr_tpu.core import stats
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.ops.test_methods import checksum, to_dense
    from dbcsr_tpu.parallel import sparse_multiply_distributed
    from dbcsr_tpu.parallel.sparse_dist import clear_mesh_plans

    from dbcsr_tpu.obs import metrics

    set_config(cannon_overlap=mode)
    clear_mesh_plans()
    out = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh)  # warmup
    exposed, walls = [], []
    for _ in range(nrep):
        # fresh rollup per rep: a silently degraded rep publishes no
        # measurement, and a stale sample left by the warmup/previous
        # rep (or the other leg) must never become committed evidence
        metrics.reset()
        t0 = time.perf_counter()
        out = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh)
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
        "metric": "cannon_overlap_ab hidden-comm fraction "
                  f"({a.nblkrows}^2 blk BCSR, 2x2 mesh, f64)",
        "value": round(1.0 - exp_med, 6),
        "unit": "hidden-comm fraction",
        "cannon_mode": mode,
        "exposed_fraction": round(exp_med, 6),
        "exposed_samples": [round(x, 6) for x in exposed],
        "wall_s": round(median(walls), 6),
        "checksum": checksum(out),
    }, np.asarray(to_dense(out))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nblk", type=int, default=24)
    ap.add_argument("--bsize", type=int, default=5)
    ap.add_argument("--occ", type=float, default=0.4)
    ap.add_argument("--nrep", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)
    import numpy as np

    from dbcsr_tpu.obs import OBS_SCHEMA_VERSION
    from dbcsr_tpu.obs import costmodel
    from dbcsr_tpu.ops.test_methods import make_random_matrix
    from dbcsr_tpu.parallel import make_grid

    rng = np.random.default_rng(args.seed)
    bs = [args.bsize] * args.nblk
    a = make_random_matrix("A", bs, bs, occupation=args.occ, rng=rng)
    b = make_random_matrix("B", bs, bs, occupation=args.occ, rng=rng)
    # layers pinned to 1: an inherited DBCSR_TPU_NUM_LAYERS_3D must not
    # reshape the world into a rectangular (no-Cannon) grid
    mesh = make_grid(4, layers=1)  # (kl=1, pr=2, pc=2)
    grid = "x".join(str(mesh.shape[a]) for a in ("kl", "pr", "pc"))

    legs = {}
    dense = {}
    for mode in ("serial", "double_buffer"):
        legs[mode], dense[mode] = run_leg(mode, a, b, mesh, grid, args.nrep)
        print(f"  {mode:>14}: exposed={legs[mode]['exposed_fraction']:.4f} "
              f"hidden={legs[mode]['value']:.4f} "
              f"wall={legs[mode]['wall_s'] * 1e3:.1f} ms",
              file=sys.stderr)

    bitwise = bool((dense["serial"] == dense["double_buffer"]).all())
    kind = costmodel.device_kind()
    dev = str(jax.devices()[0])
    stamps = {
        "unit": "hidden-comm fraction",
        "device": dev,
        "device_fallback": jax.devices()[0].platform == "cpu",
        "device_kind": kind,
        "jax_version": jax.__version__,
        "obs_schema": OBS_SCHEMA_VERSION,
    }
    for leg in legs.values():
        leg.update(stamps)
    db = legs["double_buffer"]
    row = dict(
        stamps,
        metric=db["metric"],
        value=db["value"],
        cannon_mode="double_buffer",
        exposed_serial=legs["serial"]["exposed_fraction"],
        exposed_double_buffer=db["exposed_fraction"],
        checksum=db["checksum"],
        checksum_bitwise_match=bitwise,
        speedup_wall=round(legs["serial"]["wall_s"] / db["wall_s"], 4)
        if db["wall_s"] else None,
        ab={"serial": legs["serial"], "double_buffer": db},
    )
    print(json.dumps(row))
    if not bitwise:
        print("FAIL: overlapped and serial legs are not bitwise identical",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
