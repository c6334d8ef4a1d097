"""chip_smoke.py — the quickest proof that the multiply path runs on the chip.

Drives the north-star deployment (BASELINE.json config 2: 10 000 x 10 000
BCSR, 23x23 blocks, occupancy 0.1; A, B and C resident in HBM) once
through the public API — `dt.init_lib`, `dt.make_random_matrix`,
`dt.multiply`, `dt.checksum`, `dt.to_dense` — in ONE process that owns
every chip of the host and starts no child that touches JAX:

  f64           unfiltered f64 product (the dense canvas route on a TPU)
  f64_filtered  the same product with filter_eps=1e-7 (forced onto the
                stack engine with on-device norm filtering); no block of
                these operands is below 1e-7, so C must equal f64's
  f32           unfiltered f32 product (whatever the format planner picks)
  f64_filtered_mixed  a filtered f64 product on atom blocks {5,13,23}
                cycled on m, n and k at occupancy 0.05 (the deployment of
                `mixed10k_filtered`, the same 10 000 rows): 54 (m,n,k)
                triples in 15 C bins through the stack engine on whatever
                driver `prepare_stack` gives each; held to NumPy on a row
                of every block size
  mesh4         the f64 product on the 2x2 grid `make_grid(4)` builds,
                serial and double-buffered Cannon; skipped, loudly, with
                fewer than four devices
  mesh4_filtered  the f64_filtered product on the same grid: the sparse
                mesh engine (per-product plan, sparse panels ring-shifted,
                stacks, collect, norm filter), serial and double-buffered
  sign_chain    three Newton-Schulz steps of `models.sign.sign_iteration`
                at 2 000 x 2 000 on the H of `h2o_ls_chain` (the cell's
                recipe, 38 neighbours a molecule): filtered products on
                the result of the last, the union add, the pool; checked
                against the benchmark's NumPy chain (flops, blocks,
                checksum)
  sign_chain_mesh4  the same three steps on the 2x2 grid
                (`sign_iteration(..., mesh=make_grid(4))`): every product
                the sparse mesh engine's, on bins its collect left on
                every device; skipped, loudly, with fewer than four
  tensor_3c     one batch of cubic-scaling RPA's chi(i tau) on a few waters
                (`benchmark/generators/rpa_chi.py`, the deployment of
                `rpa_h2o32`): two rank-3 x matrix contractions
                and one rank-3 x rank-3 contraction through
                `dbcsr_tpu.tensor` (restrict, remap, one TAS group each,
                f64 stacks forced, every span sliced where the device
                emulates f64, the deferred filter); checked
                against the generator's NumPy batch (flops, chi's
                pattern, every block)

Each leg runs one first call (set-up: compile + staging) and two fenced
repeats, requires bit-identical checksums across them, and is checked
against plain NumPy on sampled block rows (f64, f32, f64_filtered_mixed:
there a row of every block size), against the f64
leg's checksum (f64_filtered, mesh4, mesh4_filtered), against the NumPy
chain (sign_chain) or against the sign_chain leg (sign_chain_mesh4: flops,
blocks, checksum).  The engine's failover code is
safety code; the smoke FAILS when any of it fires.

It sets no platform: without a TPU it exits non-zero before any work.
The last stdout line of a passing run is one JSON object,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.

    python chip_smoke.py [--seed N] [--legs f64,mesh4_filtered]

`--legs` runs a subset (a four-chip call is charged four times: give it
the mesh legs and the f64 leg they are checked against).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

NORTH_STAR = {"n": 10000, "block": 23, "occupancy": 0.1}
MIXED = {"blocks": (5, 13, 23), "occupancy": 0.05}
SIGN_CHAIN = {"n": 2000, "steps": 3}
TENSOR_3C = {"molecules": 4, "batches": 2}
FILTER_EPS = 1e-7
N_SAMPLE_ROWS = 4
CHECKSUM_RTOL = 1e-9  # filtered / mesh legs vs the f64 leg


class SmokeFailure(AssertionError):
    """A leg computed a wrong answer, lost determinism, or ran on a
    failover path."""


def _block_sizes(n: int, block):
    from dbcsr_tpu.perf.driver import expand_block_sizes

    blocks = (block,) if isinstance(block, int) else block
    return expand_block_sizes(n, [(1, b) for b in blocks])


def make_operands(dtype, n: int, block, occupancy: float, seed: int):
    """A and B from ``seed`` (the same seed gives the same pattern and,
    up to the dtype's rounding, the same values).  ``block`` is one
    size, or several cycled on every dimension."""
    import numpy as np

    import dbcsr_tpu as dt

    sizes = _block_sizes(n, block)
    rng = np.random.default_rng(seed)
    a = dt.make_random_matrix("A", sizes, sizes, dtype=dtype,
                              occupation=occupancy, rng=rng)
    b = dt.make_random_matrix("B", sizes, sizes, dtype=dtype,
                              occupation=occupancy, rng=rng)
    return a, b


def _failover_state() -> dict:
    """Everything the engine bumps or sets when it carries on quietly
    after a failure: the failover/demotion counters and the session
    crosspack blacklist."""
    from dbcsr_tpu.acc import smm
    from dbcsr_tpu.obs import metrics

    counters = metrics.snapshot()["counters"]
    state = {
        name: sum(counters.get(name, {}).values())
        for name in ("dbcsr_tpu_driver_failures_total",
                     "dbcsr_tpu_driver_fallback_total",
                     "dbcsr_tpu_checksum_retry_total")
    }
    state["cross_disabled"] = sorted(map(str, smm._cross_disabled))
    return state


def _require_no_failover(leg: str, before: dict) -> None:
    after = _failover_state()
    if after != before:
        raise SmokeFailure(
            f"{leg}: the engine ran on a failover path "
            f"(before {before}, after {after})")


def _peak_bytes() -> list:
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def _rollup_delta(before: dict, after: dict) -> dict:
    """Stack drivers that ran between two `stats.driver_rollup()`
    snapshots, with their launch counts."""
    delta = {d: a["stacks"] - before.get(d, {}).get("stacks", 0)
             for d, a in sorted(after.items())}
    return {d: n for d, n in delta.items() if n}


def _timed_repeats(leg: str, run, nrep: int = 3):
    """``run()`` -> (c, flops): one first call timed as set-up, then
    ``nrep - 1`` steady repeats fenced like `run_perf`'s (one
    `fetch_fence` per bin); the LAST one also times
    `jax.block_until_ready` beside the fence.  Checksums must be
    bit-identical across all calls.  Returns (result row, last C)."""
    import jax

    import dbcsr_tpu as dt
    from dbcsr_tpu.core import stats
    from dbcsr_tpu.obs import flight
    from dbcsr_tpu.perf.driver import _force_completion

    before = _failover_state()
    rollup0 = stats.driver_rollup()
    times, checksums, c, flops = [], [], None, 0
    bur_s = fence_after_bur_s = None
    first_record = None
    for rep in range(nrep):
        c = None  # the previous repeat's C leaves HBM before the next
        t0 = time.perf_counter()
        c, flops = run()
        if rep == nrep - 1:
            jax.block_until_ready([b.data for b in c.bins])
            bur_s = time.perf_counter() - t0
        _force_completion(c)
        times.append(time.perf_counter() - t0)
        if rep == nrep - 1:
            fence_after_bur_s = times[-1] - bur_s
        if rep == 0:
            recs = flight.records()
            first_record = recs[-1] if recs else {}
        checksums.append(dt.checksum(c))
    if len(set(checksums)) != 1:
        raise SmokeFailure(
            f"{leg}: checksums differ across repeats: {checksums!r}")
    _require_no_failover(leg, before)
    drivers = {
        d: {"stacks": v["stacks"], "entries": v["entries"], "why": v["why"]}
        for d, v in sorted((first_record.get("drivers") or {}).items())
    }
    return {
        "leg": leg,
        "flops": int(flops),
        "setup_s": times[0],
        "steady_s": times[1:],
        "block_until_ready_s": bur_s,
        "fetch_fence_after_s": fence_after_bur_s,
        "checksum": checksums[0],
        "nblks": c.nblks,
        "algorithm": getattr(c, "_mm_algorithm", "?"),
        "format_reason": first_record.get("format_reason"),
        "dense_why": first_record.get("dense_why"),
        "drivers": drivers,
        "driver_launches": _rollup_delta(rollup0, stats.driver_rollup()),
        "peak_bytes_in_use": _peak_bytes(),
    }, c


def _report(tag: str, row: dict) -> None:
    print(f"{tag} " + json.dumps(row, default=str), flush=True)


def _sample_rows(row_sizes, seed: int):
    """A few block rows: the first, the (ragged) last, one of every
    other block size, and random ones."""
    import numpy as np

    nblk = len(row_sizes)
    rng = np.random.default_rng(seed + 1)
    picks = {0, nblk - 1}
    for size in sorted(set(row_sizes.tolist())
                       - {int(row_sizes[r]) for r in picks}):
        picks.add(int(rng.choice(np.nonzero(row_sizes == size)[0])))
    while len(picks) < min(N_SAMPLE_ROWS, nblk):
        picks.add(int(rng.integers(0, nblk)))
    return sorted(picks)


def _check_rows(leg: str, a, b, c, seed: int) -> dict:
    """C against plain NumPy on sampled block rows:
    ``to_dense(A)[rows] @ to_dense(B)`` on the host in f64, tolerance
    from `obs.costmodel.kernel_validation_tolerance` for a dot as deep
    as A's largest block, accumulated over every block column of A."""
    import numpy as np

    import dbcsr_tpu as dt
    from dbcsr_tpu.obs import costmodel

    off = a.row_blk_offsets
    block_rows = _sample_rows(a.row_blk_sizes, seed)
    rows = np.concatenate([
        np.arange(off[r], off[r + 1]) for r in block_rows
    ])
    ref = (dt.to_dense(a)[rows].astype(np.float64)
           @ dt.to_dense(b).astype(np.float64))
    got = dt.to_dense(c)[rows]
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        raise SmokeFailure(f"{leg}: C has shape {got.shape} (want "
                           f"{ref.shape}) or non-finite values")
    err = float(np.max(np.abs(got.astype(np.float64) - ref))
                / max(float(np.max(np.abs(ref))), 1.0))
    tol = costmodel.kernel_validation_tolerance(
        np.dtype(c.dtype).name, int(a.col_blk_sizes.max()), a.nblkcols)
    if not err <= tol:
        raise SmokeFailure(
            f"{leg}: relative error {err:.3e} > {tol:.3e} vs NumPy on "
            f"{len(rows)} sampled rows")
    return {"leg": leg, "check": "numpy_rows", "rows": int(len(rows)),
            "row_block_sizes": sorted({int(a.row_blk_sizes[r])
                                       for r in block_rows}),
            "rel_err": err, "tol": tol}


def _check_against(leg: str, res: dict, ref: dict) -> dict:
    """Checksum against another leg's.  Block counts are not compared:
    the dense route stores C's full pattern, explicit zero blocks
    included, the stack route only the blocks the product reaches."""
    rel = abs(res["checksum"] - ref["checksum"]) / abs(ref["checksum"])
    if not rel <= CHECKSUM_RTOL:
        raise SmokeFailure(
            f"{leg}: checksum {res['checksum']!r} vs {ref['leg']} "
            f"{ref['checksum']!r} (relative {rel:.3e} > "
            f"{CHECKSUM_RTOL:.0e})")
    return {"leg": leg, "check": f"checksum_vs_{ref['leg']}",
            "rel_diff": rel, "tol": CHECKSUM_RTOL}


def _single_chip_leg(leg, dtype, *, n, block, occupancy, seed,
                     filter_eps=None, check_rows=True, reference=None):
    import dbcsr_tpu as dt

    a, b = make_operands(dtype, n, block, occupancy, seed)

    def run():
        c = dt.create("C", a.row_blk_sizes, b.col_blk_sizes, dtype)
        flops = dt.multiply("N", "N", 1.0, a, b, 0.0, c,
                            filter_eps=filter_eps)
        return c, flops

    res, c = _timed_repeats(leg, run)
    _report("LEG", res)  # out before a check can fail
    if check_rows:
        _report("CHECK", _check_rows(leg, a, b, c, seed))
    if reference is not None:
        _report("CHECK", _check_against(leg, res, reference))
    return res  # only scalars outlive the leg: C leaves HBM with it


def leg_f64(**size):
    return _single_chip_leg("f64", "float64", **size)


def leg_f64_filtered(*, reference, **size):
    return _single_chip_leg("f64_filtered", "float64", **size,
                            filter_eps=FILTER_EPS, check_rows=False,
                            reference=reference)


def leg_f32(**size):
    return _single_chip_leg("f32", "float32", **size)


def leg_f64_filtered_mixed(*, n, block, occupancy, seed):
    """The filtered f64 product on atom blocks (`MIXED`, at the caller's
    ``n``; its ``block`` and ``occupancy`` are the uniform legs').  The
    tolerance is a dot of the largest block's depth."""
    return _single_chip_leg(
        "f64_filtered_mixed", "float64", n=n, block=MIXED["blocks"],
        occupancy=MIXED["occupancy"], seed=seed, filter_eps=FILTER_EPS)


def leg_mesh4(*, n, block, occupancy, seed, reference, filter_eps=None):
    """The f64 product through `sparse_multiply_distributed` on the 2x2
    grid, once per Cannon tick schedule; both must match the single-chip
    checksum and each other bit for bit.  With ``filter_eps`` (the
    `mesh4_filtered` leg) the filter forbids the dense Cannon and the
    sparse mesh engine runs."""
    import jax

    import dbcsr_tpu as dt
    from dbcsr_tpu.parallel import make_grid, sparse_multiply_distributed

    ndev = len(jax.devices())
    leg = "mesh4" if filter_eps is None else "mesh4_filtered"
    if ndev < 4:
        print(f"LEG {leg} skipped: {ndev} device(s)", flush=True)
        return None
    mesh = make_grid(4)
    a, b = make_operands("float64", n, block, occupancy, seed)
    prev = dt.get_config().cannon_overlap
    by_mode = {}
    try:
        for mode in ("serial", "double_buffer"):
            dt.set_config(cannon_overlap=mode)

            def run():
                c = sparse_multiply_distributed(1.0, a, b, 0.0, None, mesh,
                                                filter_eps=filter_eps)
                return c, getattr(c, "_last_flops", 0)

            res, _ = _timed_repeats(f"{leg}_{mode}", run)
            res["grid"] = dict(mesh.shape)
            _report("LEG", res)
            _report("CHECK", _check_against(res["leg"], res, reference))
            by_mode[mode] = res
    finally:
        dt.set_config(cannon_overlap=prev)
    if by_mode["serial"]["checksum"] != by_mode["double_buffer"]["checksum"]:
        raise SmokeFailure(
            f"{leg}: serial and double-buffered Cannon checksums differ: "
            f"{by_mode['serial']['checksum']!r} vs "
            f"{by_mode['double_buffer']['checksum']!r}")
    return by_mode


def leg_mesh4_filtered(**kw):
    return leg_mesh4(**kw, filter_eps=FILTER_EPS)


def _sign_chain_recipe():
    """The configuration `h2o_ls_chain` and the benchmark's own chain
    (`benchmark/generators/sign_chain.py`: NumPy, nothing of the
    program), loaded by path as the harness loads them."""
    import importlib.util

    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark")
    with open(os.path.join(here, "configs", "h2o_ls_chain.json")) as fh:
        cfg = json.load(fh)
    spec = importlib.util.spec_from_file_location(
        "_smoke_sign_chain", os.path.join(here, "generators", "sign_chain.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return cfg, ref


def _sign_chain_operand(n, block, seed):
    """(H as the benchmark's blocks, H staged, steps, filter_eps, the
    benchmark's chain module): the cell's H at 2 000 x 2 000 (smaller
    where the caller's ``n`` is), as many stored neighbours a molecule
    as the configuration has."""
    import numpy as np

    import dbcsr_tpu as dt

    cfg, ref = _sign_chain_recipe()
    sizes = np.asarray(_block_sizes(min(n, SIGN_CHAIN["n"]), block))
    neighbours = cfg["occupancy"]["a"] * len(_block_sizes(
        cfg["m"], cfg["blocks"]["m"][0][1]))
    recipe = {k: cfg["assumed"][k]["value"] for k in
              ("occupied_per_block", "coupling", "decay_length",
               "virtual_width")}
    h = ref.draw_hamiltonian(sizes, min(1.0, neighbours / len(sizes)),
                             cfg["pattern_seed"], seed, **recipe)
    mat = dt.create("H", sizes.astype(np.int32), sizes.astype(np.int32),
                    "float64")
    for rows, cols, data in h.by_shape():
        mat.put_blocks(rows, cols, data)
    return (h, mat.finalize(), SIGN_CHAIN["steps"],
            float(cfg["filter_eps"]), ref)


def leg_sign_chain(*, n, block, occupancy, seed):
    """Three steps of the sign chain on the cell's H.  ``occupancy`` is
    the product legs'; H's comes from the configuration."""
    from dbcsr_tpu.models.sign import sign_iteration

    h, mat, steps, eps, ref = _sign_chain_operand(n, block, seed)

    def run():
        x, history = sign_iteration(mat, steps=steps, filter_eps=eps,
                                    tol=0.0)
        assert len(history) == steps
        return x, x._last_flops

    res, _ = _timed_repeats("sign_chain", run)
    _report("LEG", res)
    want = ref.reference_chain(h, filter_eps=eps, tol=0.0, max_steps=steps)
    flops = sum(p["flops"] for p in want.products)
    checksum = float((want.x.data ** 2).sum())
    rel = abs(res["checksum"] - checksum) / checksum
    pruned = sum(p["pruned"] for p in want.products)
    dropped = sum(p["c_dropped"] for p in want.products)
    if (res["flops"] != flops or res["nblks"] != len(want.x.rows)
            or not rel <= CHECKSUM_RTOL):
        raise SmokeFailure(
            f"sign_chain: flops {res['flops']} (NumPy chain {flops}), "
            f"blocks {res['nblks']} ({len(want.x.rows)}), checksum "
            f"{res['checksum']!r} ({checksum!r}, relative {rel:.3e})")
    _report("CHECK", {"leg": "sign_chain", "check": "numpy_chain",
                      "steps": steps, "flops": flops, "rel_diff": rel,
                      "tol": CHECKSUM_RTOL, "candidates_pruned": pruned,
                      "blocks_dropped": dropped,
                      "history": want.history})
    return res


def leg_sign_chain_mesh4(*, n, block, occupancy, seed, reference):
    """The `sign_chain` leg's three steps on the 2x2 grid: every product
    through the sparse mesh engine on what the last one's collect left.
    Must return the one-chip chain's flops and blocks, and its checksum
    to `CHECKSUM_RTOL`."""
    import jax

    from dbcsr_tpu.models.sign import sign_iteration
    from dbcsr_tpu.parallel import make_grid

    ndev = len(jax.devices())
    if ndev < 4:
        print(f"LEG sign_chain_mesh4 skipped: {ndev} device(s)", flush=True)
        return None
    mesh = make_grid(4)
    _, mat, steps, eps, _ = _sign_chain_operand(n, block, seed)

    def run():
        x, history = sign_iteration(mat, steps=steps, filter_eps=eps,
                                    tol=0.0, mesh=mesh)
        assert len(history) == steps
        return x, x._last_flops

    res, _ = _timed_repeats("sign_chain_mesh4", run)
    res["grid"] = dict(mesh.shape)
    _report("LEG", res)
    if (res["flops"], res["nblks"]) != (reference["flops"],
                                        reference["nblks"]):
        raise SmokeFailure(
            f"sign_chain_mesh4: flops {res['flops']}, blocks "
            f"{res['nblks']}; the sign_chain leg {reference['flops']}, "
            f"{reference['nblks']}")
    _report("CHECK", _check_against("sign_chain_mesh4", res, reference))
    return res


def _rpa():
    """`benchmark/generators/rpa_chi.py` (the box, the tensors, the batch
    through `dbcsr_tpu.tensor` and its plain NumPy reference) and the
    configuration `rpa_h2o32`'s recipe, loaded by path."""
    import importlib.util
    import json

    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark")
    spec = importlib.util.spec_from_file_location(
        "_smoke_rpa_chi", os.path.join(here, "generators", "rpa_chi.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(here, "configs", "rpa_h2o32.json")) as fh:
        return mod, json.load(fh)


_TENSOR_COUNTERS = ("dbcsr_tpu_tensor_remap_blocks_total",
                    "dbcsr_tpu_tensor_remap_bytes_total",
                    "dbcsr_tpu_tas_groups_total",
                    "dbcsr_tpu_tensor_batches_total",
                    "dbcsr_tpu_stack_dot_total")


def _tensor_counters() -> dict:
    from dbcsr_tpu.obs import metrics

    return {name: {",".join(f"{k}={v}" for k, v in sorted(lab.items())): val
                   for lab, val in metrics.counter_items(name)}
            for name in _TENSOR_COUNTERS}


def leg_tensor_3c(*, n, block, occupancy, seed):
    """Batch 0 of `rpa_h2o32`'s chi(i tau) at `TENSOR_3C`'s
    molecules (2 where the caller's ``n`` is a few blocks): M^occ,
    M^virt and chi through `dbcsr_tpu.tensor.contract` inside
    `batched_contraction`, so `tas_multiply` and the f64 stack engine;
    held to the generator's NumPy batch (flops, chi's pattern, every
    block of chi).  A box of a few waters stores nearly every block, so
    the format planner would send each product to the dense route: the
    leg forces the stack engine (`mm_format="stack"`), whose tall (728
    rows) and deep (k = 169) spans are what a large box runs.  Where
    the device emulates f64 every span must take the sliced dot
    (`dbcsr_tpu_stack_dot_total{form="compiler"}` stands still).
    ``block`` and ``occupancy`` are the product legs'.  Prints the
    tensor counters that moved."""
    import numpy as np

    import dbcsr_tpu as dt
    from dbcsr_tpu.acc.smm import emulated_dtype_on_tpu

    molecules = TENSOR_3C["molecules"] if n >= 2000 else 2
    rpa, cfg = _rpa()
    dep = rpa.Deployment(dict(cfg["recipe"], batches=TENSOR_3C["batches"]),
                         molecules, cfg["pattern_seed"], seed)
    _report("OPERANDS", dict(dep.describe(), leg="tensor_3c"))
    last = {}

    def run():
        last["chi"], flops = dep.run_batch(0)
        return last["chi"].matrix, flops

    prev = dt.get_config().mm_format
    dt.set_config(mm_format="stack")
    try:
        counters0 = _tensor_counters()
        res, _ = _timed_repeats("tensor_3c", run)
        counters1 = _tensor_counters()
    finally:
        dt.set_config(mm_format=prev)
    res["counters"] = {
        name: {lab: v - counters0[name].get(lab, 0.0)
               for lab, v in by.items() if v != counters0[name].get(lab)}
        for name, by in counters1.items()}
    _report("LEG", res)
    ref = dep.reference(0)
    want = sum(info["flops"] for info in ref.infos)
    check = dep.check(0, last["chi"], ref=ref)
    compiler_form = sum(
        v for lab, v in res["counters"]["dbcsr_tpu_stack_dot_total"].items()
        if "form=compiler" in lab)
    if emulated_dtype_on_tpu(np.float64) and compiler_form:
        raise SmokeFailure(
            f"tensor_3c: {compiler_form} spans took the compiler's f64 "
            "dot, not the sliced one")
    if res["flops"] != want or not check["ok"]:
        raise SmokeFailure(
            f"tensor_3c: flops {res['flops']} (NumPy batch {want}), "
            f"check {check}")
    _report("CHECK", dict(check, leg="tensor_3c", check="numpy_batch",
                          flops=want))
    return res


LEGS = ("f64", "f64_filtered", "f32", "f64_filtered_mixed", "mesh4",
        "mesh4_filtered", "sign_chain", "sign_chain_mesh4", "tensor_3c")


def run_legs(*, n, block, occupancy, seed, mesh=True, legs=LEGS) -> dict:
    """The ``legs`` at one size, under the no-quiet-failover contract.
    A failed leg does not stop the later ones (a chip run should say
    everything that is wrong); any failure raises at the end."""
    import traceback

    import dbcsr_tpu as dt

    dt.init_lib()
    # repeats must run the ENGINE: an unchanged beta==0 product would
    # otherwise be served from the incremental plane's cache (the same
    # guard `perf.driver.run_perf` applies)
    prev_inc = dt.get_config().incremental
    dt.set_config(incremental="off")
    size = dict(n=n, block=block, occupancy=occupancy, seed=seed)
    before = _failover_state()
    out, failures = {}, []

    def attempt(leg, fn, **kw):
        if leg not in legs:
            return
        if "reference" in kw and kw["reference"] is None:
            failures.append(f"{leg}: not run, the leg it is checked "
                            "against gave no reference")
            return
        try:
            out[leg] = fn(**size, **kw)
            return
        except SmokeFailure as exc:
            why = str(exc)
        except Exception as exc:  # a crashed leg is a failed leg
            traceback.print_exc()
            why = f"{leg}: {type(exc).__name__}: {exc}"
        failures.append(why)
        print(f"LEG {leg} failed: {why}", flush=True)

    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            attempt("f64", leg_f64)
            attempt("f64_filtered", leg_f64_filtered,
                    reference=out.get("f64"))
            attempt("f32", leg_f32)
            attempt("f64_filtered_mixed", leg_f64_filtered_mixed)
            if mesh:
                attempt("mesh4", leg_mesh4, reference=out.get("f64"))
                attempt("mesh4_filtered", leg_mesh4_filtered,
                        reference=out.get("f64"))
            attempt("sign_chain", leg_sign_chain)
            if mesh:
                attempt("sign_chain_mesh4", leg_sign_chain_mesh4,
                        reference=out.get("sign_chain"))
            attempt("tensor_3c", leg_tensor_3c)
    finally:
        dt.set_config(incremental=prev_inc)
    here = os.path.dirname(os.path.abspath(__file__))
    failures += [
        f"RuntimeWarning from {w.filename}:{w.lineno}: {w.message}"
        for w in caught
        if issubclass(w.category, RuntimeWarning)
        and os.path.abspath(w.filename).startswith(here)
    ]
    try:
        _require_no_failover("exit", before)
    except SmokeFailure as exc:
        failures.append(str(exc))
    if failures:
        raise SmokeFailure("\n".join(failures))
    return out


def _versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def _environment(device: dict) -> dict:
    """What the run depends on besides the code: versions, the compile
    cache, the native index library, and the tables the device kind
    selects."""
    import jax

    from dbcsr_tpu import native
    from dbcsr_tpu.acc import params
    from dbcsr_tpu.obs import costmodel

    lib = native.get_lib()
    costmodel.peaks_for(device["kind"])  # an unmatched TPU kind raises
    return {
        "versions": _versions(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "native_index_library": (
            f"built from dbcsr_tpu/native/*.cpp ({os.path.basename(native._SO)})"
            if lib is not None else "NumPy fallback"),
        "peaks_row": costmodel.peaks_key(device["kind"]),
        "params_file": os.path.basename(params.params_path()),
        "params_file_exists": os.path.exists(params.params_path()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=12341313)
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma-separated subset of %(default)s")
    args = ap.parse_args(argv)
    legs = tuple(args.legs.split(","))
    if not set(legs) <= set(LEGS):
        ap.error(f"--legs: unknown leg in {legs}; have {LEGS}")

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}",
              file=sys.stderr)
        return 2
    import dbcsr_tpu  # noqa: F401 — absent program: fail before any output

    t0 = time.perf_counter()
    _report("DEVICE", device)
    _report("ENV", _environment(device))
    try:
        run_legs(**NORTH_STAR, seed=args.seed, legs=legs)
    except SmokeFailure as exc:
        for line in str(exc).splitlines():
            print(f"FAILED {line}", flush=True)
        return 1
    print(f"TOTAL {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
