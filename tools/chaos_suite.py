"""Chaos suite: the multiply corpus under a randomized fault schedule.

Runs a corpus of multiply configurations (mixed blockings, dtypes,
alpha/beta, symmetric operands, dense-mode shapes) twice each — once
clean for the reference checksum, once under a randomized, seed-logged
fault schedule drawn from every injectable site and kind
(`dbcsr_tpu.resilience.faults`) — and asserts the checksums still
match: the resilience layer's whole contract is that injected driver
failures are invisible in the product.

Checksum acceptance is RELATIVE, dtype-aware (f32 1e-5, f64 1e-11 —
the reference's own gate is threshold-based,
`dbcsr_performance_multiply.F:656-675`): a failover legitimately lands
on a different driver whose accumulation order differs in the last
ulps; bitwise identity across drivers is pinned separately by
`tests/test_resilience.py` with controlled driver pairs.

The seed is printed on every run (and chosen from the clock when not
given), so any failing schedule replays exactly:

    python tools/chaos_suite.py                # random seed, 8 rounds
    python tools/chaos_suite.py --seed 7       # replay schedule 7
    python tools/chaos_suite.py --rounds 20 --verbose

Exit status: 0 = all checksums matched, 1 = at least one mismatch or
an unrecovered failure.  Tier-2 entry point: the ``chaos``-marked test
in `tests/test_resilience.py` runs a short schedule of this corpus
(`pytest -m chaos`); this script is the unbounded local/nightly form.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU-only by design: chaos runs must be schedulable in CI without
# hardware.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the dynamic lock-order checker rides every chaos schedule: the
# randomized fault timing is exactly the interleaving explorer that
# surfaces an A->B / B->A inversion (dbcsr_tpu/utils/lockcheck.py)
os.environ.setdefault("DBCSR_TPU_LOCKCHECK", "1")
# the mesh_overlap corpus case needs a real 2x2 grid, the tas_contract
# case a rectangular 1x2x3 one plus a (2,2,2) grouped world: give the
# CPU backend 8 virtual devices (no-op when XLA_FLAGS already set them)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _hostdev  # noqa: E402

_hostdev.ensure_virtual_devices(8)

# the schedule draw and corruption targets derive from the checked
# fault-site registry (the analyzer's `fault-site-docs` rule rejects a
# hand-kept tuple here — registry drift was exactly the failure mode).
# Loaded standalone by file path, like the watchdog in the capture
# loop: the registry is pure data and must stay readable before the
# package (and jax) come up.
import importlib.util  # noqa: E402

_sites_spec = importlib.util.spec_from_file_location(
    "_chaos_sites", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "dbcsr_tpu", "resilience", "sites.py"))
_sites = importlib.util.module_from_spec(_sites_spec)
_sites_spec.loader.exec_module(_sites)

# NOTE: a logged --seed replays exactly only against the same tree —
# the draw order (and the corpus) are part of the schedule, and the
# registry derivation reordered the draw relative to pre-PR-14 logs
SITES = _sites.chaos_sites()
KINDS = ("raise", "oom", "nan", "flip")
# targets whose OUTPUT a nan/flip spec can corrupt: the faults.corrupt
# call sites plus the driver labels they carry (a ``pallas:nan`` spec
# fires on the execute_stack corrupt hook via its driver label).  The
# whole suite runs with DBCSR_TPU_ABFT=verify, so a finite flip here
# must be detected and recovered like any other fault.
CORRUPTIBLE = _sites.chaos_corrupt_targets()


def corpus():
    """The multiply test corpus: (name, kwargs for one product)."""
    import numpy as np

    return [
        ("uniform_f64", dict(bs=[5] * 8, dtype=np.float64, occ=0.5)),
        ("uniform_f32", dict(bs=[4] * 6, dtype=np.float32, occ=0.6)),
        ("mixed_blocking", dict(bs=[3, 5, 7, 4, 6, 2], dtype=np.float64,
                                occ=0.7)),
        ("near_full", dict(bs=[5] * 6, dtype=np.float64, occ=0.95)),
        ("complex", dict(bs=[4] * 5, dtype=np.complex128, occ=0.5)),
        ("beta_accumulate", dict(bs=[5] * 6, dtype=np.float64, occ=0.5,
                                 alpha=2.0, beta=0.5)),
        # chained case: a short McWeeny purification inside a device-
        # residency chain (core.mempool) — faults that fire mid-chain
        # must not corrupt pool-donated buffers (the PR-4 decompose
        # caveat extended to recycled device storage)
        ("mcweeny_chain", dict(bs=[4] * 6, dtype=np.float64, occ=0.4,
                               chain_steps=3)),
        # distributed case: the block-sparse Cannon on a 2x2 mesh with
        # the double-buffered tick pipeline forced on — a mesh_shift
        # fault firing mid-shift must degrade the multiply to the
        # serial fused program with the checksum intact
        # (breaker-integrated like the fused superstack's decompose)
        ("mesh_overlap", dict(bs=[4] * 8, dtype=np.float64, occ=0.5,
                              mesh=4, cannon_overlap="double_buffer")),
        # upper-layer pipeline case: a rank-3 tensor contraction over
        # the RECTANGULAR (1x2x3) grid — the chunked all-gather
        # pipeline, fault site `gather_chunk` at each per-shard ring
        # step — plus a grouped-TAS multiply on the (2,2,2) world,
        # fault site `tas_tick` at the staggered group-ensemble
        # tick/shift edge.  Both pipelines forced on: a fault at
        # either dispatch edge must degrade that multiply to its
        # serial fused program with the checksum intact (the
        # gather_pipe / cannon_db breaker contract)
        ("tas_contract", dict(bs=[4] * 6, dtype=np.float64, occ=0.6,
                              contract_mesh=6, tas_mesh=8,
                              cannon_overlap="double_buffer")),
        # serving-plane case: many concurrent clients through
        # dbcsr_tpu.serve with injected serve_admit/serve_execute
        # faults — shed submissions are retried until admitted, a
        # faulted coalesced group must degrade to serialized with
        # results intact, and every shed/degrade/failure must land on
        # the event bus with a correlated request id (asserted inside
        # the case, plus --events for fault correlation)
        ("serve_storm", dict(bs=[4] * 6, dtype=np.float64, occ=0.5,
                             serve_tenants=3, serve_requests=2)),
        # cost-attribution case: the serve storm with the attribution
        # ledger re-baselined first — beyond the storm contract, the
        # tenant-cost conservation invariant must hold EXACTLY when
        # the dust settles: per-tenant billings sum to the grand
        # totals, and the grand flops/bytes equal the engine rollup
        # bit-for-bit whatever the schedule shed, degraded, faulted
        # (including at the `attribution` site itself) or retried
        ("usage_storm", dict(bs=[4] * 6, dtype=np.float64, occ=0.5,
                             usage_tenants=3, usage_requests=2)),
        # finite-SDC case: flip faults injected mid-McWeeny chain must
        # be detected (stack ABFT probe with the knob on; chain
        # invariant rollback with it off) and recovered BITWISE-equal
        # to the clean run — pinned inside the case with paired legs
        # in a pristine fault context (the outer schedule then applies
        # to the returned checksum leg like every other case)
        ("sdc_chain", dict(bs=[4] * 6, dtype=np.float64, occ=0.4,
                           purify_steps=3)),
        # delta-aware incremental multiply case: an SCF-shaped loop
        # (same pattern, ~25% of A's blocks updated per iteration)
        # whose repeated products splice from the cached result —
        # flip/raise faults injected mid-incremental-multiply must
        # force the fallback full recompute, bitwise-identical to a
        # clean run (the mm.incremental safety-ladder contract)
        ("delta_chain", dict(bs=[4] * 6, dtype=np.float64, occ=0.5,
                             delta_iters=3)),
        # online-autotuner case: the tuner promoting a trial winner
        # MID-TRAFFIC while a serve workload runs, against a temp
        # params dir seeded with a mistuned row.  Paired legs in a
        # pristine inner fault context pin the contract: a clean cycle
        # must promote (and the serve results stay equal), and a
        # tune_trial-faulted cycle must promote NOTHING while the
        # workload's checksums still match.  Integer-valued operands
        # make every driver's accumulation exact, so the checksum is
        # bitwise-stable whatever row dispatch picks up
        ("tune_storm", dict(bs=[4] * 6, dtype=np.float64, occ=0.5,
                            tune_requests=2)),
        # workload-replay case: a trace recorded in-process through the
        # serve recorder, then replayed via the deterministic replay
        # path (`serve.workload`) under injected serve_admit/
        # serve_execute/replay_submit faults — every stream entry must
        # land EXACTLY once (bounded retries, no request lost or
        # duplicated, audited against the replay ledger), the faulted
        # leg's per-request checksums must equal the clean replay
        # BITWISE (integer-valued operands), and a capacity
        # certificate built while faults are active must come out
        # degraded and be REFUSED by `tools.loadtest.publish`
        ("replay_storm", dict(bs=[4] * 6, dtype=np.float64, occ=0.5,
                              replay_tenants=2, replay_requests=3)),
        # fleet case: a REAL multi-process serve fleet (serve.fleet
        # spawns the workers, serve.router routes) — SIGKILL one
        # worker mid-queue under deterministically injected
        # fleet_route/fleet_handoff faults, fail its write-ahead
        # journal over onto the surviving peer, and pin the
        # exactly-once contract fleet-wide: every admitted request
        # reaches exactly one terminal state (replay-ledger audit),
        # result checksums are BITWISE equal to a clean single-worker
        # run, and a rolling restart of every worker loses zero
        # requests.  Paired legs in pristine/deterministic inner fault
        # contexts (the fleet sites are chaos: False — multi-process
        # topology, the multihost_init precedent)
        ("fleet_storm", dict(bs=[4] * 6, dtype=np.float64, occ=0.5,
                             fleet_workers=2, fleet_requests=3)),
    ]


def random_schedule(rng: random.Random) -> str:
    """One randomized fault schedule (1-3 specs) over the sites/kinds.

    Schedules are constrained to RECOVERABLE shapes: at most ONE
    site-wide ``execute_stack`` spec per schedule, bounded to
    ``times<=2`` — an unconditional every-launch-of-every-driver
    failure is unrecoverable by construction (there is no driver left
    to fall back to; the suite asserts the resilience contract, not
    magic).  Driver-targeted / prepare / dense specs may be unbounded:
    the chain re-executes elsewhere, prepare re-plans on the safe
    path, dense degrades to the stack engine."""
    specs = []
    have_sitewide = False
    for _ in range(rng.randint(1, 3)):
        site = rng.choice(SITES)
        kind = rng.choice(KINDS)
        if site == "execute_stack":
            if have_sitewide:
                continue
            have_sitewide = True
        if kind in ("nan", "flip") and site not in CORRUPTIBLE:
            kind = "raise"  # nothing to corrupt at this site
        opts = [f"seed={rng.randint(0, 2**16)}"]
        if site == "execute_stack":
            opts.append(f"times={rng.randint(1, 2)}")
        elif site.startswith("serve_") or site == "replay_submit":
            # bounded like execute_stack: an every-call admission/
            # execution/replay-submission fault starves the storm and
            # replay cases' retry loops
            opts.append(f"times={rng.randint(1, 3)}")
        elif rng.random() < 0.5:
            opts.append(f"times={rng.randint(1, 3)}")
        if rng.random() < 0.3:
            opts.append(f"prob={rng.choice((0.5, 0.75, 1.0))}")
        cond = f"@stack>={rng.randint(0, 2)}" if rng.random() < 0.3 else ""
        specs.append(f"{site}:{kind}{cond}," + ",".join(opts))
    return ";".join(specs)


def _serve_storm(entry: dict, seed: int) -> float:
    """Many concurrent clients through the serving plane.  Shed or
    failed requests are RESUBMITTED (bounded retries) — the resilience
    contract under test is that admission faults reject loudly and
    recover, never that work silently disappears — and the checksum
    over every request's C must match the clean run.  Every
    serve_shed/serve_degrade/serve_failed/serve_deadline_missed bus
    event must carry a request id (asserted here even without
    --events)."""
    import threading
    import time as _time

    import numpy as np

    from dbcsr_tpu import serve
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.obs import events as obs_events
    from dbcsr_tpu.ops.test_methods import checksum, make_random_matrix

    set_config(serve_coalesce=True, serve_window_ms=20.0)
    bs = entry["bs"]
    n_tenants = entry["serve_tenants"]
    n_req = entry["serve_requests"]
    eng = serve.ServeEngine(start=True)
    results: dict = {}
    failures: list = []
    sessions: list = []
    lock = threading.Lock()

    def client(i: int) -> None:
        try:
            sess = eng.open_session(f"chaos-tenant{i}")
            with lock:
                sessions.append(sess)
            for rep in range(n_req):
                a = make_random_matrix(
                    "A", bs, bs, dtype=entry["dtype"],
                    occupation=entry["occ"],
                    rng=np.random.default_rng(seed + 7 * rep))
                b = make_random_matrix(
                    "B", bs, bs, dtype=entry["dtype"],
                    occupation=entry["occ"],
                    rng=np.random.default_rng(seed + 7 * rep + 1))
                c = make_random_matrix(
                    "C", bs, bs, dtype=entry["dtype"], occupation=0.3,
                    rng=np.random.default_rng(seed + 7 * rep + 2))
                a.map_bin_data(lambda d: d * (1.0 + i))
                b.map_bin_data(lambda d: d * (1.0 + 0.5 * i))
                sess.put(f"A{rep}", a)
                sess.put(f"B{rep}", b)
                sess.put(f"C{rep}", c)
                for _attempt in range(60):
                    t = eng.submit(sess, a=f"A{rep}", b=f"B{rep}",
                                   c=f"C{rep}", alpha=1.0, beta=0.0)
                    if t.wait(timeout=120) and t.state == "done":
                        break
                    _time.sleep(0.02)  # shed/failed: retry
                else:
                    raise RuntimeError(
                        f"request never served after retries: {t.info()}")
                with lock:
                    results[(i, rep)] = checksum(c)
        except Exception as exc:
            with lock:
                failures.append(exc)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_tenants)]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
    finally:
        eng.shutdown()
        for s in sessions:
            s.close()
    if failures:
        raise failures[0]
    # correlation contract: no serving-plane rejection/degrade may be
    # anonymous on the bus
    if obs_events.enabled():
        for kind in ("serve_shed", "serve_degrade", "serve_failed",
                     "serve_deadline_missed"):
            for e in obs_events.records(kind=kind):
                if not e.get("request_id") and not e.get("request_ids"):
                    raise RuntimeError(
                        f"uncorrelated {kind} event on the bus: {e}")
    return float(sum(results[k] for k in sorted(results)))


def _usage_storm(entry: dict, seed: int) -> float:
    """The serve storm with the books audited: concurrent tenants,
    bounded retries, and — after every request lands — the tenant-cost
    conservation invariant asserted EXACTLY (`obs.attribution`).  All
    operands are uploaded BEFORE the attribution baseline is taken
    (client-side H2D outside serve billing windows is not serve cost),
    so the grand flops/bytes must equal the engine rollup bit-for-bit
    and per-tenant billings must sum to the grand totals, whatever the
    schedule shed, degraded or faulted."""
    import threading
    import time as _time

    import numpy as np

    from dbcsr_tpu import serve
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.obs import attribution, metrics
    from dbcsr_tpu.ops.test_methods import checksum, make_random_matrix

    set_config(serve_coalesce=True, serve_window_ms=20.0)
    bs = entry["bs"]
    n_tenants = entry["usage_tenants"]
    n_req = entry["usage_requests"]
    eng = serve.ServeEngine(start=True)
    sessions = []
    mats: dict = {}
    for i in range(n_tenants):
        sess = eng.open_session(f"usage-tenant{i}")
        sessions.append(sess)
        for rep in range(n_req):
            a = make_random_matrix(
                "A", bs, bs, dtype=entry["dtype"],
                occupation=entry["occ"],
                rng=np.random.default_rng(seed + 7 * rep))
            b = make_random_matrix(
                "B", bs, bs, dtype=entry["dtype"],
                occupation=entry["occ"],
                rng=np.random.default_rng(seed + 7 * rep + 1))
            c = make_random_matrix(
                "C", bs, bs, dtype=entry["dtype"], occupation=0.3,
                rng=np.random.default_rng(seed + 7 * rep + 2))
            a.map_bin_data(lambda d: d * (1.0 + i))
            b.map_bin_data(lambda d: d * (1.0 + 0.5 * i))
            sess.put(f"A{rep}", a)
            sess.put(f"B{rep}", b)
            sess.put(f"C{rep}", c)
            mats[(i, rep)] = c
    # baseline AFTER the uploads: from here on, every device-side
    # byte/flop the process spends happens inside a billing window
    metrics.reset()
    results: dict = {}
    failures: list = []
    lock = threading.Lock()

    def client(i: int) -> None:
        try:
            sess = sessions[i]
            for rep in range(n_req):
                for _attempt in range(60):
                    t = eng.submit(sess, a=f"A{rep}", b=f"B{rep}",
                                   c=f"C{rep}", alpha=1.0, beta=0.0)
                    if t.wait(timeout=120) and t.state == "done":
                        break
                    _time.sleep(0.02)  # shed/failed: retry
                else:
                    raise RuntimeError(
                        f"request never served after retries: {t.info()}")
        except Exception as exc:
            with lock:
                failures.append(exc)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_tenants)]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        if failures:
            raise failures[0]
        eng.shutdown()  # quiesce: no billing window left in flight
        # audit the books BEFORE touching any result matrix: a
        # checksum's D2H readback happens outside serve billing
        # windows and is not serve cost (same reason the baseline
        # follows the uploads)
        cons = attribution.conservation()
        for k, v in cons["tenant_sum"].items():
            if v != cons["grand"][k]:
                raise RuntimeError(
                    f"attribution conservation broken: "
                    f"tenant_sum[{k}]={v} != grand[{k}]="
                    f"{cons['grand'][k]} ({cons})")
        for k in ("flops", "bytes_moved"):
            if cons["grand"][k] != cons["rollup"][k]:
                raise RuntimeError(
                    f"attribution conservation broken: grand[{k}]="
                    f"{cons['grand'][k]} != rollup[{k}]="
                    f"{cons['rollup'][k]} ({cons})")
        if abs(cons["grand"]["device_ns"] / 1e9
               - cons["rollup"]["device_seconds"]) > 1e-6:
            raise RuntimeError(
                f"attribution device-seconds drifted past the "
                f"per-window quantization: {cons}")
        for key in sorted(mats):
            results[key] = checksum(mats[key])
    finally:
        eng.shutdown()
        for s in sessions:
            s.close()
    return float(sum(results[k] for k in sorted(results)))


def _tas_contract(entry: dict, seed: int) -> float:
    """The upper-layer pipelines under fire: a rank-3 contraction over
    the rectangular grid (chunked all-gather, `gather_chunk` edges)
    and a grouped-TAS multiply (staggered metronome, `tas_tick`
    edges), both with the pipeline forced on.  The checksum over both
    products must match the clean run whatever degrades."""
    import itertools

    import numpy as np

    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.ops.test_methods import checksum, make_random_matrix
    from dbcsr_tpu.parallel import make_grid
    from dbcsr_tpu.parallel.sparse_dist import (
        clear_mesh_plans, tas_grouped_multiply,
    )
    from dbcsr_tpu.tensor import create_tensor
    from dbcsr_tpu.tensor.contract import contract

    rng = np.random.default_rng(seed)
    bs = entry["bs"]
    prev = get_config().cannon_overlap
    set_config(cannon_overlap=entry["cannon_overlap"])
    try:
        # rank-3 x matrix over the rectangular (1, 2, 3) grid
        t3 = create_tensor("t3", [bs, bs, bs])
        for idx in itertools.product(*(range(len(bs)),) * 3):
            if rng.random() < entry["occ"]:
                t3.put_block(idx, rng.standard_normal(t3.block_shape(idx)))
        t3.finalize()
        m2 = create_tensor("m2", [bs, bs])
        for idx in itertools.product(*(range(len(bs)),) * 2):
            if rng.random() < 0.8:
                m2.put_block(idx, rng.standard_normal(m2.block_shape(idx)))
        m2.finalize()
        c3 = create_tensor("c3", [bs, bs, bs])
        c3.finalize()
        clear_mesh_plans()
        contract(1.0, t3, m2, 0.0, c3,
                 contract_a=(2,), notcontract_a=(0, 1),
                 contract_b=(0,), notcontract_b=(1,),
                 map_1=(0, 1), map_2=(2,),
                 mesh=make_grid(entry["contract_mesh"], layers=1))
        cs = float(np.sum(np.asarray(c3.to_dense())))
        # grouped-TAS metronome on the (2, 2, 2) world
        tall = bs * 2
        at = make_random_matrix("AT", tall, bs, dtype=entry["dtype"],
                                occupation=0.5, rng=rng)
        b2 = make_random_matrix("B2", bs, bs, dtype=entry["dtype"],
                                occupation=0.6, rng=rng)
        clear_mesh_plans()
        ct = tas_grouped_multiply(1.0, at, b2, 0.0, None,
                                  make_grid(entry["tas_mesh"]))
        return cs + checksum(ct)
    finally:
        set_config(cannon_overlap=prev)


def _sdc_chain(entry: dict, seed: int) -> float:
    """The layered finite-SDC defense on a McWeeny chain, pinned
    BITWISE.  Two paired legs run in a pristine inner fault context
    (the outer schedule is suspended by the nested ``inject_faults``
    and restored on exit):

    * leg A — ``DBCSR_TPU_ABFT=verify`` + ``execute_stack:flip``: the
      stack probe detects the finite corruption, the pristine
      same-driver retry recovers, and the purified result is
      bitwise-equal to the clean run.
    * leg B — ABFT off, flip again: the corruption slips past the
      (disarmed) probes into the iterate; the chain invariant rolls
      back to the checkpoint and recomputes — bitwise-equal again,
      and the rollback counter must have advanced.

    The returned checksum comes from a final leg under the OUTER
    schedule, so the case also participates in the ordinary chaos
    contract."""
    import numpy as np

    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.models.purify import make_test_density, mcweeny_purify
    from dbcsr_tpu.obs import metrics
    from dbcsr_tpu.ops.test_methods import to_dense
    from dbcsr_tpu.resilience import faults

    steps = int(entry["purify_steps"])

    def run():
        p = make_test_density(len(entry["bs"]), int(entry["bs"][0]),
                              occ=entry["occ"], seed=seed)
        out, _hist = mcweeny_purify(p, steps=steps)
        return np.asarray(to_dense(out))

    def rollbacks() -> float:
        c = metrics._counters.get("dbcsr_tpu_chain_rollback_total")
        return float(sum(c.values.values())) if c is not None else 0.0

    flip = f"execute_stack:flip,seed={seed % 997},times=1"
    prev_abft = get_config().abft
    with faults.inject_faults(""):  # pristine inner context
        try:
            set_config(abft="verify")
            ref = run()
            with faults.inject_faults(flip) as specs_a:
                out_a = run()
            if not specs_a[0].fired:
                raise RuntimeError("sdc_chain: flip spec never fired")
            if not (out_a == ref).all():
                raise RuntimeError(
                    "sdc_chain leg A: stack-ABFT recovery not "
                    "bitwise-equal to the clean run")
            set_config(abft="off")
            rb0 = rollbacks()
            with faults.inject_faults(flip):
                out_b = run()
            if rollbacks() <= rb0:
                raise RuntimeError(
                    "sdc_chain leg B: flip did not trigger a chain "
                    "rollback (invariant failed to catch finite SDC)")
            if not (out_b == ref).all():
                raise RuntimeError(
                    "sdc_chain leg B: chain-rollback recovery not "
                    "bitwise-equal to the clean run")
        finally:
            set_config(abft=prev_abft)
    # the paired legs' own fault_injected events are not part of the
    # OUTER schedule's correlation count — drop them before the final
    # leg so --events accounting stays exact
    from dbcsr_tpu.obs import events as obs_events

    if obs_events.enabled():
        obs_events.clear()
    # final leg under the outer schedule: the ordinary chaos contract
    return float(np.sum(run()))


def _delta_chain(entry: dict, seed: int) -> float:
    """The delta-aware incremental multiply under injected faults,
    pinned BITWISE.  Paired legs run in a pristine inner fault context
    (the outer schedule is suspended and restored on exit):

    * reference — ``incremental=full``: every product recomputed from
      scratch (the control semantics);
    * clean — ``incremental=auto``: the delta path must ENGAGE
      (reuse counters advance) and every iterate must be bitwise-equal
      to the reference;
    * faulted — ``incremental:flip`` then ``incremental:raise``: a
      fault mid-incremental-multiply forces the fallback full
      recompute (flip via the ABFT probe, raise via the splice abort),
      again bitwise-equal — a reused product never serves a stale or
      corrupted C.

    The returned checksum comes from a final leg under the OUTER
    schedule, so the case also participates in the ordinary chaos
    contract."""
    import numpy as np

    import dbcsr_tpu as dt
    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.mm import incremental as inc
    from dbcsr_tpu.ops.test_methods import make_random_matrix, to_dense
    from dbcsr_tpu.resilience import faults

    iters = int(entry["delta_iters"])
    bs = entry["bs"]
    bsz = int(bs[0])

    def run():
        rng = np.random.default_rng(seed)
        a = make_random_matrix("A", bs, bs, dtype=entry["dtype"],
                               occupation=entry["occ"], rng=rng)
        b = make_random_matrix("B", bs, bs, dtype=entry["dtype"],
                               occupation=entry["occ"], rng=rng)
        c = dt.create("C", bs, bs, dtype=entry["dtype"])
        rows, cols = a.entry_coords()
        sub = np.arange(max(1, len(rows) // 4))
        for _ in range(3):  # warm: plan + result caches prime
            dt.multiply("N", "N", 1.0, a, b, 0.0, c)
        outs = []
        for it in range(iters):
            r2 = np.random.default_rng(seed * 1000 + it)
            for i in sub:
                a.put_block(int(rows[i]), int(cols[i]),
                            r2.standard_normal((bsz, bsz)))
            a.finalize()
            dt.multiply("N", "N", 1.0, a, b, 0.0, c)
            outs.append(np.asarray(to_dense(c)))
        return outs

    prev_abft = get_config().abft
    prev_inc = get_config().incremental
    with faults.inject_faults(""):  # pristine inner context
        try:
            set_config(abft="verify", incremental="full")
            inc.reset()
            ref = run()
            set_config(incremental="auto")
            inc.reset()
            clean = run()
            if inc.stats_snapshot()["products"] < 1:
                raise RuntimeError(
                    "delta_chain: incremental plane never engaged")
            for i, (r, g) in enumerate(zip(ref, clean)):
                if not (r == g).all():
                    raise RuntimeError(
                        f"delta_chain iter {i}: incremental result not "
                        f"bitwise-equal to full recompute")
            for kind in ("flip", "raise"):
                inc.reset()
                spec = f"incremental:{kind},seed={seed % 997},times=1"
                with faults.inject_faults(spec) as specs:
                    faulted = run()
                if not specs[0].fired:
                    raise RuntimeError(
                        f"delta_chain: {kind} spec never fired")
                for i, (r, g) in enumerate(zip(ref, faulted)):
                    if not (r == g).all():
                        raise RuntimeError(
                            f"delta_chain iter {i}: {kind}-faulted run "
                            f"not bitwise-equal to the clean reference")
        finally:
            set_config(abft=prev_abft, incremental=prev_inc)
            inc.reset()
    # the paired legs' own fault_injected events are not part of the
    # OUTER schedule's correlation count
    from dbcsr_tpu.obs import events as obs_events

    if obs_events.enabled():
        obs_events.clear()
    # final leg under the outer schedule: the ordinary chaos contract
    return float(sum(float(np.sum(o)) for o in run()))


def _tune_storm(entry: dict, seed: int) -> float:
    """The online tuner promoting winners mid-traffic.  A temp params
    dir is seeded with a mistuned row for the workload's (4,4,4,f64)
    cell; a serve client streams requests while a tuner cycle runs on
    another thread.  Paired legs in a pristine inner fault context:

    * clean — the cycle must PROMOTE (the trial winner beats the
      mistuned row) and every request's checksum must equal the
      no-tuner reference (integer-valued operands: exact, so bitwise
      across whatever driver the promotion steers dispatch onto);
    * faulted — ``tune_trial:raise`` aborts the trial: the spec must
      fire, NO promotion may land, and the checksums must still match.

    The returned checksum comes from a final leg under the OUTER
    schedule (which may itself draw tune_trial), so the case also
    participates in the ordinary chaos contract."""
    import contextlib
    import tempfile
    import threading

    import numpy as np

    from dbcsr_tpu import serve
    from dbcsr_tpu.acc import params as params_mod
    from dbcsr_tpu.obs import metrics
    from dbcsr_tpu.ops.test_methods import checksum, make_random_matrix
    from dbcsr_tpu.resilience import faults
    from dbcsr_tpu.tune import service as tune_service
    from dbcsr_tpu.tune import store as tune_store

    # the tuner defers whenever admission is not OK — earlier corpus
    # cases legitimately leave DEGRADED residue (ABFT mismatch
    # counters, wedge-streak gauges), so the pinned promotion legs
    # start from a clean health slate (the resets are case-local:
    # every other case's assertions are delta- or bus-based)
    from dbcsr_tpu.obs import health as obs_health

    metrics.reset()
    obs_health.reset()

    bs = entry["bs"]
    n_req = int(entry["tune_requests"])
    cell = dict(m=int(bs[0]), n=int(bs[0]), k=int(bs[0]),
                dtype="float64", stack_size=512, driver="xla",
                observed_gflops=0.01, target_gflops=10.0,
                wasted_flop_seconds=1e3, flops=1e9,
                source="chaos", reason="seeded mistuned cell")

    def _promotions() -> float:
        c = metrics._counters.get("dbcsr_tpu_tune_promotions_total")
        return float(sum(c.values.values())) if c is not None else 0.0

    @contextlib.contextmanager
    def _temp_params():
        prev = os.environ.get("DBCSR_TPU_PARAMS_DIR")
        prev_knobs = {k: os.environ.get(k) for k in
                      ("DBCSR_TPU_TUNE_NREP", "DBCSR_TPU_TUNE_BUDGET_BYTES")}
        with tempfile.TemporaryDirectory() as td:
            os.environ["DBCSR_TPU_PARAMS_DIR"] = td
            os.environ["DBCSR_TPU_TUNE_NREP"] = "1"
            os.environ["DBCSR_TPU_TUNE_BUDGET_BYTES"] = str(1 << 20)
            params_mod.invalidate()
            params_mod.save_entry({
                "m": cell["m"], "n": cell["n"], "k": cell["k"],
                "dtype": "float64", "stack_size": 512,
                "driver": "xla_group", "r0": 4, "grouping": None,
                "gflops": 0.01, "env": "cpu"})
            try:
                yield td
            finally:
                for k, v in dict(DBCSR_TPU_PARAMS_DIR=prev,
                                 **prev_knobs).items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
                params_mod.invalidate()

    def _serve_run(tag: str, with_cycle: bool) -> float:
        svc = tune_service.TuneService(interval_s=3600)
        eng = serve.ServeEngine(start=True)
        sess = eng.open_session(f"chaos-tune-{tag}")
        cycle_out: dict = {}

        def _cycle():
            cycle_out.update(svc.cycle(cells=[dict(cell)]))

        tuner = threading.Thread(target=_cycle) if with_cycle else None
        total = 0.0
        try:
            if tuner is not None:
                tuner.start()
            for rep in range(n_req):
                rng = np.random.default_rng(seed + 31 * rep)
                a = make_random_matrix("A", bs, bs, dtype=entry["dtype"],
                                       occupation=entry["occ"], rng=rng)
                b = make_random_matrix("B", bs, bs, dtype=entry["dtype"],
                                       occupation=entry["occ"], rng=rng)
                c = make_random_matrix("C", bs, bs, dtype=entry["dtype"],
                                       occupation=0.3, rng=rng)
                # integer-valued operands: every driver's accumulation
                # is exact, so the checksum is driver-independent
                for mat in (a, b, c):
                    mat.map_bin_data(lambda d: np.trunc(d * 4.0))
                sess.put(f"A{rep}", a)
                sess.put(f"B{rep}", b)
                sess.put(f"C{rep}", c)
                for _attempt in range(60):
                    t = eng.submit(sess, a=f"A{rep}", b=f"B{rep}",
                                   c=f"C{rep}", alpha=1.0, beta=0.0)
                    if t.wait(timeout=120) and t.state == "done":
                        break
                    time.sleep(0.02)
                else:
                    raise RuntimeError(
                        f"tune_storm request never served: {t.info()}")
                total += checksum(c)
            if tuner is not None:
                tuner.join(timeout=600)
                if tuner.is_alive():
                    raise RuntimeError("tune_storm: tuner cycle hung")
        finally:
            eng.shutdown()
            sess.close()
        if with_cycle:
            _serve_run.last_cycle = dict(cycle_out)
        return total

    _serve_run.last_cycle = {}

    with faults.inject_faults(""):  # pristine inner context
        # reference: no tuner at all, mistuned table in force
        with _temp_params():
            ref = _serve_run("ref", with_cycle=False)
        # clean leg: the cycle must land a promotion mid-traffic and
        # the request results must be unchanged (bitwise: exact data)
        with _temp_params():
            p0 = _promotions()
            out = _serve_run("clean", with_cycle=True)
            if out != ref:
                raise RuntimeError(
                    f"tune_storm clean leg: checksum {out} != ref {ref} "
                    f"(promotion changed results, not just speed)")
            if _serve_run.last_cycle.get("outcome") != "promoted" \
                    or _promotions() != p0 + 1:
                raise RuntimeError(
                    "tune_storm clean leg: cycle did not promote "
                    f"({_serve_run.last_cycle})")
            if not tune_store.live_promotions():
                raise RuntimeError(
                    "tune_storm clean leg: promotion missing from the "
                    "ledger")
        # faulted leg: an injected trial fault must abort the trial
        # with NO promotion, results still equal
        with _temp_params():
            p0 = _promotions()
            with faults.inject_faults(
                    f"tune_trial:raise,seed={seed % 997},times=1") as sp:
                out = _serve_run("faulted", with_cycle=True)
            if not sp[0].fired:
                raise RuntimeError("tune_storm: tune_trial spec never "
                                   "fired")
            if _promotions() != p0 or tune_store.live_promotions():
                raise RuntimeError(
                    "tune_storm faulted leg: a promotion landed from a "
                    f"faulted trial ({_serve_run.last_cycle})")
            if out != ref:
                raise RuntimeError(
                    f"tune_storm faulted leg: checksum {out} != ref "
                    f"{ref}")
    from dbcsr_tpu.obs import events as obs_events

    if obs_events.enabled():
        obs_events.clear()  # inner legs' faults are not the outer
        #                     schedule's correlation count
    # final leg under the outer schedule: the ordinary chaos contract
    with _temp_params():
        return _serve_run("outer", with_cycle=True)


def _replay_storm(entry: dict, seed: int) -> float:
    """Record a small workload trace in-process, then replay it
    through the deterministic replay path (`serve.workload`) under the
    OUTER fault schedule.  Contract pinned here:

    * no request lost or duplicated — every stream entry lands exactly
      ONCE through bounded retries at `workload.replay_submit` (the
      ``replay_submit`` site fires there), cross-checked against the
      ``dbcsr_tpu_replay_requests_total`` ledger;
    * the faulted leg's per-request checksums equal the clean replay
      BITWISE (integer-valued operands: exact accumulation whatever
      driver or degraded path a fault forces);
    * a capacity certificate built while faults are active must carry
      ``degraded`` and `tools.loadtest.publish` must REFUSE it — the
      clean run publishes the same shape to prove the refusal is the
      degraded bit, not an accident."""
    import tempfile

    import numpy as np

    from dbcsr_tpu import serve
    from dbcsr_tpu.core.config import set_config
    from dbcsr_tpu.obs import events as obs_events
    from dbcsr_tpu.obs import metrics
    from dbcsr_tpu.ops.test_methods import checksum
    from dbcsr_tpu.resilience import faults
    from dbcsr_tpu.serve import workload

    # tools/ is on sys.path (the _hostdev insert); loadtest's import-
    # time TS-interval default must not leak into the rest of the suite
    _prev_ts = os.environ.get("DBCSR_TPU_TS_INTERVAL_S")
    import loadtest
    if _prev_ts is None:
        os.environ.pop("DBCSR_TPU_TS_INTERVAL_S", None)

    bs = entry["bs"]
    n_tenants = int(entry["replay_tenants"])
    n_req = int(entry["replay_requests"])
    set_config(serve_coalesce=True, serve_window_ms=5.0,
               serve_tenant_inflight=64)

    def _record() -> list:
        """A small live trace: each tenant submits ``n_req``
        multiplies drawn from 2 operand pairs (digest repeats worth
        replaying), recorded to a temp shard family."""
        base = os.path.join(tempfile.mkdtemp(prefix="chaos-replay-"),
                            "workload.jsonl")
        workload.enable_sink(base)
        eng = serve.ServeEngine(start=True)
        sessions, tickets = [], []
        try:
            for ti in range(n_tenants):
                sess = eng.open_session(f"replay-tenant{ti}")
                sessions.append(sess)
                for d in range(2):
                    s0 = seed + 97 * ti + 11 * d
                    sess.random(f"A{d}", bs, bs, dtype=entry["dtype"],
                                occupation=entry["occ"], seed=s0)
                    sess.random(f"B{d}", bs, bs, dtype=entry["dtype"],
                                occupation=entry["occ"], seed=s0 + 1)
                for i in range(n_req):
                    sess.create(f"C{i}", bs, bs, dtype=entry["dtype"])
                    tickets.append(eng.submit(
                        sess, a=f"A{i % 2}", b=f"B{i % 2}", c=f"C{i}",
                        alpha=1.0, beta=0.0))
            for t in tickets:
                if not (t.wait(timeout=120) and t.state == "done"):
                    raise RuntimeError(
                        f"replay_storm recording stalled: {t.info()}")
        finally:
            eng.shutdown()
            for s in sessions:
                s.close()
            workload.disable_sink()
        records = workload.read_trace(base)
        if len(records) != n_tenants * n_req:
            raise RuntimeError(
                f"replay_storm: recorded {len(records)} records, "
                f"expected {n_tenants * n_req}")
        return records

    def _done_total() -> float:
        return sum(v for labels, v in metrics.counter_items(
            "dbcsr_tpu_replay_requests_total")
            if labels.get("outcome") == "done")

    def _replay(tag: str, stream: list):
        """One serialized replay leg; returns ({entry_i: checksum},
        wall seconds).  Faulted submissions/executions are retried
        (bounded) — the contract is loud rejection and recovery, never
        silent loss."""
        eng = serve.ServeEngine(start=True)
        sessions: dict = {}
        cache: dict = {}
        checks: dict = {}
        d0 = _done_total()
        t0 = time.perf_counter()
        try:
            for ent in stream:
                sess = sessions.get(ent["tenant"])
                if sess is None:
                    sess = eng.open_session(ent["tenant"])
                    sessions[ent["tenant"]] = sess
                kwargs = dict(ent.get("params") or {})
                out_mat = None
                for k, spec in sorted((ent.get("operands") or {}).items()):
                    name = (f"{k}-{spec['digest'][:12]}"
                            if spec.get("role") != "out"
                            else f"{k}-{tag}-{ent['request_id']}")
                    fresh = (spec.get("role") == "out"
                             or (sess.tenant, spec["digest"]) not in cache)
                    m = workload.materialize(sess, name, spec, cache)
                    if fresh:
                        # integer-valued operands: every driver's
                        # accumulation is exact, so the checksum is
                        # bitwise whatever path a fault degrades onto
                        m.map_bin_data(lambda d: np.trunc(d * 4.0))
                    kwargs[k] = name
                    if spec.get("role") == "out":
                        out_mat = m
                for _attempt in range(60):
                    try:
                        t = workload.replay_submit(
                            eng, sess, ent, kwargs,
                            request_id=f"{tag}-{ent['request_id']}"
                                       f"a{_attempt}")
                    except Exception:
                        time.sleep(0.02)  # shed at submission: retry
                        continue
                    if t.wait(timeout=120) and t.state == "done":
                        break
                    time.sleep(0.02)  # shed/failed in-engine: retry
                else:
                    raise RuntimeError(
                        f"replay_storm {tag}: entry {ent['i']} never "
                        f"served after retries")
                checks[ent["i"]] = checksum(out_mat)
                workload.note_replay(ent["tenant"], "done")
        finally:
            eng.shutdown()
            for s in sessions.values():
                s.close()
        wall = time.perf_counter() - t0
        # loss/duplication audit: exactly one completion per stream
        # entry, and the replay ledger counter agrees
        if sorted(checks) != list(range(len(stream))):
            raise RuntimeError(
                f"replay_storm {tag}: {len(checks)}/{len(stream)} "
                f"entries landed exactly once")
        landed = _done_total() - d0
        if landed != len(stream):
            raise RuntimeError(
                f"replay_storm {tag}: replay ledger disagrees with "
                f"the stream ({landed} != {len(stream)})")
        return checks, wall

    # record + clean reference in a pristine inner fault context: the
    # outer schedule applies to the replayed leg, not the fixture
    with faults.inject_faults(""):
        records = _record()
        stream = workload.request_stream(records, seed=seed)
        ref, ref_wall = _replay("clean", stream)

    # certificate contract: under the outer schedule faults are active
    # -> degraded -> publish refuses; on the clean run it publishes
    cert = dict(
        loadtest._stamps(),
        kind="capacity_cert",
        workload_schema=workload.WORKLOAD_SCHEMA,
        metric=loadtest.CERT_METRIC,
        value=round(len(stream) / max(ref_wall, 1e-6), 3),
        unit="req/s/worker",
        certified_rate_x=1.0,
        p95_ms_at_knee=0.0,
        degraded=bool(faults.active()),
    )
    cpath = os.path.join(tempfile.mkdtemp(prefix="chaos-cert-"),
                         "CAPACITY_CERT.json")
    rc = loadtest.publish(cert, cpath)
    if cert["degraded"]:
        if rc != 3 or os.path.exists(cpath):
            raise RuntimeError(
                "replay_storm: a degraded certificate was published")
    elif rc != 0 or not os.path.exists(cpath):
        raise RuntimeError(
            f"replay_storm: clean certificate publish failed (rc={rc})")

    if obs_events.enabled():
        obs_events.clear()  # inner pristine legs are not the outer
        #                     schedule's correlation count
    # faulted leg under the OUTER schedule: the ordinary chaos
    # contract, pinned bitwise against the clean replay
    out, _wall = _replay("outer", stream)
    for i in sorted(ref):
        if out[i] != ref[i]:
            raise RuntimeError(
                f"replay_storm: entry {i} checksum {out[i]} != clean "
                f"{ref[i]} (must be bitwise)")
    # correlation: no replay-plane rejection may be anonymous
    if obs_events.enabled():
        for kind in ("serve_shed", "serve_degrade", "serve_failed",
                     "serve_deadline_missed"):
            for e in obs_events.records(kind=kind):
                if not e.get("request_id") and not e.get("request_ids"):
                    raise RuntimeError(
                        f"uncorrelated {kind} event on the bus: {e}")
    return float(sum(ref[k] for k in sorted(ref)))


def _fleet_storm(entry: dict, seed: int) -> float:
    """The multi-process fleet under fire (see the corpus comment).
    Three legs, all in inner fault contexts so the case is
    deterministic whatever the outer schedule drew:

    1. clean — ONE worker, no faults: the reference checksums;
    2. storm — N workers with ``fleet_route``/``fleet_handoff`` raise
       faults injected in the router process, the session's owning
       worker SIGKILLed mid-queue, its write-ahead journal failed over
       onto the surviving peer: every admitted request must reach
       exactly one terminal state fleet-wide (ledger audit), the
       liveness gauge and the advisory ``fleet`` health component
       must name the dead worker, and the failed-over results must be
       BITWISE equal to leg 1;
    3. rolling restart — more requests in flight, then every worker
       drained/replayed/restarted in turn: zero requests lost, audit
       still clean, results still bitwise."""
    import urllib.request

    import numpy as np

    from dbcsr_tpu.obs import events as obs_events
    from dbcsr_tpu.obs import health as obs_health
    from dbcsr_tpu.obs import metrics
    from dbcsr_tpu.resilience import faults
    from dbcsr_tpu.serve.fleet import Fleet
    from dbcsr_tpu.serve.router import SETTLED_STATES

    bs = entry["bs"]
    n_workers = int(entry["fleet_workers"])
    n_req = int(entry["fleet_requests"])
    dtype_name = np.dtype(entry["dtype"]).name
    cnames = [f"C{i}" for i in range(n_req)]
    rnames = [f"R{i}" for i in range(n_req)]

    def _checksums(url: str, sid: str, names) -> dict:
        out = {}
        for n in names:
            with urllib.request.urlopen(
                    f"{url}/serve/checksum?session={sid}&name={n}",
                    timeout=10) as resp:
                out[n] = json.loads(resp.read())["checksum"]
        return out

    def _stage(router, sid, outs):
        router.matrix(sid, name="A", row_blk=bs, dtype=dtype_name,
                      occupation=entry["occ"], seed=seed)
        router.matrix(sid, name="B", row_blk=bs, dtype=dtype_name,
                      occupation=entry["occ"], seed=seed + 1)
        for cn in outs:
            router.matrix(sid, name=cn, row_blk=bs, dtype=dtype_name,
                          kind="create")

    def _assert_exactly_once(router, rids):
        for rid in rids:
            row = router.ledger.get(rid)
            landings = row["landings"] if row else {}
            settled = [w for w, st in landings.items()
                       if st in SETTLED_STATES]
            if len(settled) != 1:
                raise RuntimeError(
                    f"fleet_storm: request {rid} settled on "
                    f"{settled or 'no worker'} (landings {landings}) "
                    f"— not exactly once")
        audit = router.audit()
        if audit["duplicated"] or audit["unresolved"]:
            raise RuntimeError(
                f"fleet_storm: ledger audit failed — duplicated="
                f"{audit['duplicated']} unresolved={audit['unresolved']}")

    # leg 1: clean single-worker reference (pristine fault context)
    with faults.inject_faults(""):
        with Fleet(n=1) as fl:
            router = fl.router()
            router.check()
            sid = router.open_session("fleet-t", session_id="fleet-s")
            _stage(router, sid, cnames + rnames)
            for i, cn in enumerate(cnames + rnames):
                info = router.submit(
                    sid, request_id=f"fs-{i}", op="multiply",
                    a="A", b="B", c=cn, wait=True, timeout_s=120.0)
                if info["state"] != "done":
                    raise RuntimeError(
                        f"fleet_storm clean leg stalled: {info}")
            ref = _checksums(fl.specs["w0"]["url"], sid,
                             cnames + rnames)

    # legs 2+3 under the deterministic fleet schedule: the first two
    # routed attempts and the first failover attempt fail loudly
    with faults.inject_faults(
            "fleet_route:raise,prob=1.0,times=2;"
            "fleet_handoff:raise,prob=1.0,times=1"):
        with Fleet(n=n_workers) as fl:
            router = fl.router()
            router.check()
            sid = router.open_session("fleet-t", session_id="fleet-s")
            _stage(router, sid, cnames + rnames)
            rids = []
            for i, cn in enumerate(cnames):
                info = router.submit(sid, request_id=f"fs-{i}",
                                     op="multiply", a="A", b="B", c=cn)
                rids.append(info["request_id"])
            # SIGKILL the owning worker mid-queue: the write-ahead
            # journal is now the only record of unfinished requests
            owner = router.sessions[sid]["worker"]
            fl.kill(owner)
            router.mark_down(owner)
            # degradation must be OBSERVABLE before it is repaired
            up = metrics.gauge("dbcsr_tpu_fleet_worker_up").value(
                worker=owner)
            if up != 0.0:
                raise RuntimeError(
                    f"fleet_storm: liveness gauge for dead {owner} "
                    f"reads {up}, want 0")
            fcomp = (obs_health.verdict().get("components") or {}).get(
                "fleet") or {}
            if fcomp.get("status") != "DEGRADED":
                raise RuntimeError(
                    f"fleet_storm: fleet health component is "
                    f"{fcomp.get('status')!r} with {owner} down, "
                    f"want DEGRADED")
            # failover: the injected fleet_handoff fault fails the
            # first attempt BEFORE any replay lands; bounded retry
            for _attempt in range(10):
                try:
                    moved = router.failover(owner)
                    break
                except Exception:
                    time.sleep(0.05)
            else:
                raise RuntimeError(
                    "fleet_storm: failover never succeeded")
            router.settle_replayed(moved["replayed"], moved["target"],
                                   timeout=120.0)
            _assert_exactly_once(router, rids)
            # bitwise results for every request the peer REPLAYED (a
            # request that finished on w0 in the instants before the
            # SIGKILL is settled by its journal tombstone instead —
            # its output died with the process, never silently wrong)
            replayed_c = [f"C{rid.split('-')[1]}"
                          for rid in moved["replayed"]]
            target_url = fl.specs[moved["target"]]["url"]
            out = _checksums(target_url, sid, replayed_c)
            for cn in replayed_c:
                if out[cn] != ref[cn]:
                    raise RuntimeError(
                        f"fleet_storm: {cn} checksum {out[cn]} != "
                        f"clean {ref[cn]} (must be bitwise)")

            # leg 3: rolling restart with work in flight — the dead
            # worker rejoins first so every drain has a surviving peer
            fl.respawn(owner)
            router.rejoin(owner)
            rrids = []
            for i, rn in enumerate(rnames):
                info = router.submit(
                    sid, request_id=f"fr-{i}", op="multiply",
                    a="A", b="B", c=rn)
                rrids.append(info["request_id"])
            fl.rolling_restart(router, timeout=120.0)
            # zero loss: every in-flight request settled exactly once
            # somewhere (done before its worker drained — reconciled
            # into the ledger at drain time — or replayed on the peer)
            _assert_exactly_once(router, rids + rrids)
            # the upgraded fleet still computes bitwise-identical
            # results: fresh requests through the restarted workers
            for i in range(n_req):
                router.matrix(sid, name=f"P{i}", row_blk=bs,
                              dtype=dtype_name, kind="create")
                info = router.submit(
                    sid, request_id=f"fp-{i}", op="multiply",
                    a="A", b="B", c=f"P{i}", wait=True,
                    timeout_s=120.0)
                if info["state"] != "done":
                    raise RuntimeError(
                        f"fleet_storm: post-restart submit stalled: "
                        f"{info}")
            sworker = router.sessions[sid]["worker"]
            out2 = _checksums(fl.specs[sworker]["url"], sid,
                              [f"P{i}" for i in range(n_req)])
            for i, rn in enumerate(rnames):
                if out2[f"P{i}"] != ref[rn]:
                    raise RuntimeError(
                        f"fleet_storm: post-restart P{i} checksum "
                        f"{out2[f'P{i}']} != clean {ref[rn]}")
    # the router-side story must be on the event bus, correlated
    if obs_events.enabled():
        kinds = {e.get("event") for e in obs_events.records()}
        for want in ("worker_down", "fleet_failover"):
            if want not in kinds:
                raise RuntimeError(
                    f"fleet_storm: no {want} event on the bus")
    return float(sum(ref[k] for k in sorted(ref)))


def _one_product(entry: dict, seed: int):
    import numpy as np

    from dbcsr_tpu.mm.multiply import multiply
    from dbcsr_tpu.ops.test_methods import checksum, make_random_matrix

    if entry.get("fleet_workers"):
        return _fleet_storm(entry, seed)
    if entry.get("replay_tenants"):
        return _replay_storm(entry, seed)
    if entry.get("tune_requests"):
        return _tune_storm(entry, seed)
    if entry.get("serve_tenants"):
        return _serve_storm(entry, seed)
    if entry.get("usage_tenants"):
        return _usage_storm(entry, seed)
    if entry.get("delta_iters"):
        return _delta_chain(entry, seed)
    if entry.get("purify_steps"):
        return _sdc_chain(entry, seed)
    if entry.get("contract_mesh"):
        return _tas_contract(entry, seed)
    if entry.get("mesh"):
        from dbcsr_tpu.core.config import set_config
        from dbcsr_tpu.parallel import make_grid, sparse_multiply_distributed
        from dbcsr_tpu.parallel.sparse_dist import clear_mesh_plans

        rng = np.random.default_rng(seed)
        bs = entry["bs"]
        a = make_random_matrix("A", bs, bs, dtype=entry["dtype"],
                               occupation=entry["occ"], rng=rng)
        b = make_random_matrix("B", bs, bs, dtype=entry["dtype"],
                               occupation=entry["occ"], rng=rng)
        prev = None
        if entry.get("cannon_overlap"):
            from dbcsr_tpu.core.config import get_config

            prev = get_config().cannon_overlap
            set_config(cannon_overlap=entry["cannon_overlap"])
        try:
            clear_mesh_plans()
            c = sparse_multiply_distributed(1.0, a, b, 0.0, None,
                                            make_grid(4))
        finally:
            if prev is not None:
                set_config(cannon_overlap=prev)
        return checksum(c)
    if entry.get("chain_steps"):
        from dbcsr_tpu.core import mempool
        from dbcsr_tpu.models.purify import make_test_density, mcweeny_step

        p = make_test_density(len(entry["bs"]), int(entry["bs"][0]),
                              occ=entry["occ"], seed=seed)
        with mempool.chain() as ch:
            cur = p
            for _ in range(int(entry["chain_steps"])):
                new = mcweeny_step(cur, filter_eps=1e-10)
                if cur is not p:
                    ch.retire(cur)
                cur = new
            ch.detach(cur)
        return checksum(cur)
    rng = np.random.default_rng(seed)
    bs = entry["bs"]
    dt = entry["dtype"]
    a = make_random_matrix("A", bs, bs, dtype=dt, occupation=entry["occ"],
                           rng=rng)
    b = make_random_matrix("B", bs, bs, dtype=dt, occupation=entry["occ"],
                           rng=rng)
    c = make_random_matrix("C", bs, bs, dtype=dt, occupation=0.3, rng=rng)
    multiply("N", "N", entry.get("alpha", 1.0), a, b,
             entry.get("beta", 0.0), c)
    return checksum(c)


def run_chaos(seed: int, rounds: int, verbose: bool = False,
              check_events: bool = False) -> dict:
    """Run ``rounds`` randomized schedules over the corpus; returns a
    result dict (also JSONL-printable).

    ``check_events`` additionally asserts the ops-plane correlation
    contract per faulted product: every fault the schedule actually
    fired must appear on the event bus (`dbcsr_tpu.obs.events`) as a
    ``fault_injected`` record carrying the multiply's ``product_id``
    (or, for serving-plane sites that fire before a product scope
    opens, the ``request_id``) — a fault that fires invisibly, or
    outside its correlation scope, is a failure even when the checksum
    survives."""
    import jax

    jax.config.update("jax_enable_x64", True)

    from dbcsr_tpu.core.config import get_config, set_config
    from dbcsr_tpu.resilience import breaker, faults

    import numpy as np

    # the whole suite runs with the ABFT probes armed: flip (and nan)
    # corruption at any corruptible target must be DETECTED and
    # recovered, extending the chaos contract from "crashes and NaNs
    # are invisible in the product" to "wrong-but-finite answers are
    # too" (docs/resilience.md § ABFT probe checksums)
    prev_abft = get_config().abft
    set_config(abft="verify")

    if check_events:
        from dbcsr_tpu.obs import events as obs_events

        # the assertion is meaningless with the bus off (an inherited
        # DBCSR_TPU_EVENTS=0 would fail every case vacuously)
        obs_events.set_enabled(True)

    rng = random.Random(seed)
    cases = corpus()
    refs = {}
    for name, entry in cases:
        refs[name] = _one_product(entry, seed=1234)

    def _tol(entry):
        return (1e-5 if np.dtype(entry["dtype"]) in (np.float32,
                                                     np.complex64)
                else 1e-11)

    failures = []
    schedules = []
    events_checked = 0
    for rnd in range(rounds):
        schedule = random_schedule(rng)
        schedules.append(schedule)
        for name, entry in cases:
            breaker.reset_board()
            if check_events:
                obs_events.clear()
            try:
                with faults.inject_faults(schedule) as installed:
                    cs = _one_product(entry, seed=1234)
            except Exception as exc:  # unrecovered failure
                failures.append({
                    "round": rnd, "case": name, "schedule": schedule,
                    "error": f"{type(exc).__name__}: {exc}",
                })
                continue
            if check_events:
                fired = sum(spec.fired for spec in installed)
                on_bus = obs_events.records(kind="fault_injected")
                # a fault is correlated when it carries a product id
                # (engine sites) OR a request id (serving-plane sites:
                # admission runs before any product scope opens)
                uncorrelated = [e for e in on_bus
                                if not e.get("product_id")
                                and not e.get("request_id")]
                events_checked += fired
                if len(on_bus) != fired or uncorrelated:
                    failures.append({
                        "round": rnd, "case": name, "schedule": schedule,
                        "events_error": (
                            f"{fired} faults fired, {len(on_bus)} on the "
                            f"bus, {len(uncorrelated)} without a "
                            f"product_id"),
                    })
                    continue
            ref = refs[name]
            rel = abs(cs - ref) / max(abs(ref), 1e-300)
            if rel > _tol(entry):
                failures.append({
                    "round": rnd, "case": name, "schedule": schedule,
                    "checksum": cs, "ref": ref, "rel_diff": rel,
                })
            elif verbose:
                print(f"  ok r{rnd} {name:>16} rel={rel:.1e} [{schedule}]")
    set_config(abft=prev_abft)
    return {
        "seed": seed,
        "rounds": rounds,
        "cases": len(cases),
        "runs": rounds * len(cases),
        "failures": failures,
        "schedules": schedules,
        "events_checked": events_checked if check_events else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=None,
                    help="schedule seed (default: clock; always logged)")
    ap.add_argument("--rounds", type=int, default=8,
                    help="randomized schedules per case (default 8)")
    ap.add_argument("--events", action="store_true",
                    help="also assert every injected fault is visible "
                         "on the event bus with a correlated product_id")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    seed = args.seed if args.seed is not None else int(time.time()) % 2**31
    print(f"chaos suite: seed={seed} rounds={args.rounds} "
          f"(replay: python tools/chaos_suite.py --seed {seed})")
    res = run_chaos(seed, args.rounds, verbose=args.verbose,
                    check_events=args.events)
    print(json.dumps({k: v for k, v in res.items() if k != "schedules"}))
    if res["failures"]:
        for f in res["failures"]:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    extra = (f", {res['events_checked']} faults correlated on the bus"
             if args.events else "")
    print(f"chaos suite PASSED: {res['runs']} faulted multiplies, "
          f"all checksums correct{extra}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
