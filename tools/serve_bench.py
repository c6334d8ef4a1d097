#!/usr/bin/env python
"""Many-client serving throughput A/B: coalesced vs serialized.

Drives the serving plane (`dbcsr_tpu.serve`) with N tenant threads
each submitting R same-structure multiply requests (identical sparsity
pattern, per-tenant values), twice — once with cross-request
coalescing OFF (every request its own engine multiply; the serialized
control) and once ON (same-structure requests grouped into
block-diagonal composite multiplies within the batching window) — and
reports per leg:

* ``value`` — requests per engine dispatch (`dbcsr_tpu_dispatches_
  total` delta / requests; higher is better, the number
  `tools/perf_gate.py` gates on): coalescing's whole point is that N
  tenants multiplying the same pattern pay ~one dispatch set;
* ``throughput_rps`` / ``wall_s`` — end-to-end completion rate;
* ``dispatches_per_request``, ``coalesced_groups``.

Every request's C is fetched densely after each leg and the two legs
are asserted **bitwise identical** (exit 1 on mismatch): coalescing
reorders nothing inside a product's accumulation (docs/serving.md).

The output JSON (last stdout line) is a perf_gate-compatible capture
row with both legs under ``ab``.

Usage: python tools/serve_bench.py [--tenants 4] [--requests 6]
           [--nblk 8] [--bsize 5] [--occ 0.5] [--seed 7]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU-runnable by design (the committed A/B row is the CPU control);
# the serving plane schedules dispatches the same way on any backend.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _dispatch_total() -> float:
    from dbcsr_tpu.obs import metrics

    return sum(v for _, v in
               metrics.counter_items("dbcsr_tpu_dispatches_total"))


def _build_one(tenant: int, nblk: int, bsize: int, occ: float,
               seed: int):
    """Tenant ``tenant``'s (a, b, c): ONE shared sparsity pattern
    across tenants (pattern rng seeded by ``seed`` only) with
    tenant-specific values — the same-structure workload coalescing
    exists for."""
    import numpy as np

    from dbcsr_tpu.ops.test_methods import make_random_matrix

    bs = [bsize] * nblk
    a = make_random_matrix("A", bs, bs, occupation=occ,
                           rng=np.random.default_rng(seed))
    b = make_random_matrix("B", bs, bs, occupation=occ,
                           rng=np.random.default_rng(seed + 1))
    c = make_random_matrix("C", bs, bs, occupation=0.3,
                           rng=np.random.default_rng(seed + 2))
    a.map_bin_data(lambda d: d * (1.0 + 0.25 * tenant))
    b.map_bin_data(lambda d: d * (2.0 - 0.125 * tenant))
    return a, b, c


def run_leg(mode: str, n_tenants: int, n_requests: int, nblk: int,
            bsize: int, occ: float, seed: int):
    import numpy as np

    from dbcsr_tpu import serve
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.obs import metrics
    from dbcsr_tpu.ops.test_methods import to_dense

    coalesce = mode == "coalesced"
    set_config(serve_coalesce=coalesce, serve_window_ms=25.0,
               serve_coalesce_max=max(2, n_tenants),
               serve_tenant_inflight=max(16, n_requests + 2))
    eng = serve.ServeEngine(start=True)
    sessions = []
    tickets: list = []
    lock = threading.Lock()
    nreq = n_tenants * n_requests
    d0 = _dispatch_total()
    t0 = time.perf_counter()

    def client(i: int) -> None:
        sess = eng.open_session(f"bench-tenant{i}")
        with lock:
            sessions.append(sess)
        for rep in range(n_requests):
            a, b, c = _build_one(i, nblk, bsize, occ, seed + 31 * rep)
            sess.put(f"A{rep}", a)
            sess.put(f"B{rep}", b)
            sess.put(f"C{rep}", c)
            t = eng.submit(sess, a=f"A{rep}", b=f"B{rep}", c=f"C{rep}",
                           alpha=1.0, beta=0.0)
            with lock:
                tickets.append(((i, rep), t, c))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_tenants)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    for _, t, _ in tickets:
        if not t.wait(timeout=300) or t.state != "done":
            raise RuntimeError(f"leg {mode}: request not served: "
                               f"{t.info()}")
    wall = time.perf_counter() - t0
    dispatches = _dispatch_total() - d0
    coalesced_groups = 0
    ctr = metrics._counters.get("dbcsr_tpu_serve_coalesced_total")
    if ctr is not None:
        coalesced_groups = int(sum(ctr.values.values()))
    denses = {key: np.asarray(to_dense(c)) for key, _, c in tickets}
    eng.shutdown()
    for s in sessions:
        s.close()
    per_req = dispatches / nreq if nreq else 0.0
    return {
        "metric": (f"serve_coalesce_ab requests/dispatch "
                   f"({n_tenants} tenants x {n_requests} reqs, "
                   f"{nblk}x{bsize} blk BCSR f64)"),
        "value": round(nreq / dispatches, 6) if dispatches else 0.0,
        "unit": "requests/dispatch",
        "serve_mode": mode,
        "requests": nreq,
        "dispatches": int(dispatches),
        "dispatches_per_request": round(per_req, 4),
        "coalesced_groups": coalesced_groups,
        "wall_s": round(wall, 6),
        "throughput_rps": round(nreq / wall, 4) if wall else 0.0,
    }, denses


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--nblk", type=int, default=8)
    ap.add_argument("--bsize", type=int, default=5)
    ap.add_argument("--occ", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)

    from dbcsr_tpu.obs import OBS_SCHEMA_VERSION, costmodel, metrics

    legs = {}
    denses = {}
    for mode in ("serialized", "coalesced"):
        metrics.reset()
        legs[mode], denses[mode] = run_leg(
            mode, args.tenants, args.requests, args.nblk, args.bsize,
            args.occ, args.seed)
        leg = legs[mode]
        print(f"  {mode:>10}: {leg['requests']} reqs, "
              f"{leg['dispatches']} dispatches "
              f"({leg['dispatches_per_request']}/req), "
              f"{leg['throughput_rps']} req/s, "
              f"groups={leg['coalesced_groups']}", file=sys.stderr)

    keys = sorted(denses["serialized"])
    bitwise = all(
        (denses["serialized"][k] == denses["coalesced"][k]).all()
        for k in keys)
    kind = costmodel.device_kind()
    dev = str(jax.devices()[0])
    stamps = {
        "unit": "requests/dispatch",
        "device": dev,
        "device_fallback": jax.devices()[0].platform == "cpu",
        "device_kind": kind,
        "jax_version": jax.__version__,
        "obs_schema": OBS_SCHEMA_VERSION,
    }
    for leg in legs.values():
        leg.update(stamps)
    co = legs["coalesced"]
    row = dict(
        stamps,
        metric=co["metric"],
        value=co["value"],
        serve_mode="coalesced",
        requests=co["requests"],
        dispatches_serialized=legs["serialized"]["dispatches"],
        dispatches_coalesced=co["dispatches"],
        checksum_bitwise_match=bitwise,
        speedup_dispatch=round(
            legs["serialized"]["dispatches"] / co["dispatches"], 4)
        if co["dispatches"] else None,
        speedup_wall=round(legs["serialized"]["wall_s"] / co["wall_s"], 4)
        if co["wall_s"] else None,
        ab={"serialized": legs["serialized"], "coalesced": co},
    )
    print(json.dumps(row))
    if not bitwise:
        print("FAIL: coalesced and serialized legs are not bitwise "
              "identical", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
